"""A decoder-hybrid-decoder: state-space layers, differential attention
and the two memories a cross-decoder reads.

The architecture (Ren et al. 2025, arXiv:2507.06607) runs a
SELF-decoder over the first half of its layers, ``Mamba`` alternating
with ``DifferentialAttention`` inside a sliding window, closes it with
one ``Mamba`` whose scan output is kept as the memory ``M`` and one
full-attention layer whose keys and values are kept, and then a
CROSS-decoder over the second half: ``GatedMemoryUnit`` layers that gate
``M`` by the current stream, alternating with ``DifferentialAttention``
layers that project a query only and read the kept K/V.  Every mixer is
followed by a ``GatedFeedForward``; both sit under a LayerNorm pre-norm
and a residual sum (``HybridDecoderLayer``).

``decoder_hybrid_decoder`` builds the functional ``Model`` whose output
is each sequence's next-token loss: the kept K/V and the memory are
edges of its graph, from the layer that writes them to every layer that
reads them, so their cotangents are sums over the readers by
construction.  The head is tied to the embedding: ``Embedding(...,
tie_head=True)`` hands its table on as an edge, and ``NextTokenLoss``
forms the logits from it a chunk of rows at a time.

Precision: matrix products take the compute dtype and accumulate in
float32; the residual stream, the LayerNorm statistics, ``dt``, the
scan, the softmax, the sub-norm and the loss are float32.
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops.dtypes import get_policy
from analytics_zoo_tpu.pipeline.api.keras.engine import Layer, Params
from analytics_zoo_tpu.pipeline.api.keras.layers.attention import (
    _flash_route, _mm)
from analytics_zoo_tpu.pipeline.api.keras.layers.normalization import (
    rms_norm)

F32 = jnp.float32


def layer_norm(x, gamma, beta, epsilon: float):
    """LayerNorm with float32 statistics -> the compute dtype."""
    xf = x.astype(F32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + epsilon) * gamma + beta
    return y.astype(get_policy().compute_dtype)


def gated_feed_forward(x, gate_up_kernel, down_kernel):
    """``[g, u] = x W1``; ``(u * silu(g)) W2`` in float32: the products
    in the compute dtype, the gate in float32."""
    compute = get_policy().compute_dtype
    gu = _mm(x, gate_up_kernel).astype(compute)
    g, u = jnp.split(gu, 2, axis=-1)
    act = (u.astype(F32) * jax.nn.silu(g.astype(F32))).astype(compute)
    return _mm(act, down_kernel)


class GatedFeedForward(Layer):
    """``[g, u] = x W1``; ``y = (u * silu(g)) W2``; no bias."""

    def __init__(self, intermediate_size: int, **kwargs):
        super().__init__(**kwargs)
        self.intermediate_size = int(intermediate_size)

    def build(self, rng, input_shape) -> Params:
        d, ff = input_shape[-1], self.intermediate_size
        params: Params = {}
        self.add_weight(params, rng, "gate_up_kernel", (d, 2 * ff),
                        init="normal")
        self.add_weight(params, rng, "down_kernel", (ff, d), init="normal")
        return params

    def call(self, params, x, training=False, rng=None):
        return gated_feed_forward(x, params["gate_up_kernel"],
                                  params["down_kernel"]).astype(x.dtype)


class Mamba(Layer):
    """The selective state-space mixer: ``[x, z] = u W_in``; ``x =
    silu(causal depthwise conv(x) + b)``; ``[dt_r, B, C] = x W_x``;
    ``dt = softplus(dt_r W_dt + b_dt)``; ``A = -exp(A_log)``; ``y =
    selective_scan(x, dt, A, B, C) + D x``; out ``= (y * silu(z))
    W_out``.  ``emit_memory``: outputs ``[out, y]``, the scan's output
    before the gate, for the layers that read it as their memory."""

    def __init__(self, d_inner: int, d_state: int = 16, d_conv: int = 4,
                 dt_rank: Optional[int] = None, emit_memory: bool = False,
                 **kwargs):
        super().__init__(**kwargs)
        self.d_inner, self.d_state = int(d_inner), int(d_state)
        self.d_conv = int(d_conv)
        self.dt_rank = None if dt_rank is None else int(dt_rank)
        self.emit_memory = bool(emit_memory)

    def build(self, rng, input_shape) -> Params:
        d, c, n = input_shape[-1], self.d_inner, self.d_state
        if self.dt_rank is None:
            self.dt_rank = math.ceil(d / 16)
        r = self.dt_rank
        params: Params = {}
        self.add_weight(params, rng, "in_kernel", (d, 2 * c), init="normal")
        self.add_weight(params, rng, "conv_kernel", (self.d_conv, c),
                        init="normal")
        self.add_weight(params, rng, "conv_bias", (c,), init="zero")
        self.add_weight(params, rng, "x_kernel", (c, r + 2 * n),
                        init="normal")
        self.add_weight(params, rng, "dt_kernel", (r, c), init="normal")
        # dt starts log-uniform in [1e-3, 1e-1]; A at -(1..N) a channel
        self.add_weight(
            params, rng, "dt_bias", (c,),
            init=lambda key, shape, dtype: _inverse_softplus(jnp.exp(
                jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                   math.log(1e-1)))))
        self.add_weight(
            params, rng, "a_log", (c, n),
            init=lambda key, shape, dtype: jnp.broadcast_to(
                jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)), shape))
        self.add_weight(params, rng, "d", (c,), init="one")
        self.add_weight(params, rng, "out_kernel", (c, d), init="normal")
        return params

    def compute_output_shape(self, input_shape):
        if self.emit_memory:
            return [tuple(input_shape),
                    tuple(input_shape[:-1]) + (self.d_inner,)]
        return tuple(input_shape)

    def call(self, params, u, training=False, rng=None):
        from analytics_zoo_tpu.ops.selective_scan import selective_scan
        compute = get_policy().compute_dtype
        t, n, r = u.shape[1], self.d_state, self.dt_rank
        xz = _mm(u, params["in_kernel"]).astype(compute)
        x, z = jnp.split(xz, 2, axis=-1)
        # tap k of the causal convolution reads position t - (K - 1) + k
        x = x.astype(F32)
        pad = jnp.pad(x, ((0, 0), (self.d_conv - 1, 0), (0, 0)))
        x = sum(pad[:, k:k + t] * params["conv_kernel"][k]
                for k in range(self.d_conv)) + params["conv_bias"]
        x = jax.nn.silu(x)
        proj = _mm(x, params["x_kernel"])
        dt_r, b, c = jnp.split(proj, [r, r + n], axis=-1)
        dt = jax.nn.softplus(
            jnp.matmul(dt_r, params["dt_kernel"],
                       precision=jax.lax.Precision.HIGHEST)
            + params["dt_bias"])
        y, _ = selective_scan(x, dt, -jnp.exp(params["a_log"]), b, c)
        y = y + params["d"] * x
        out = _mm((y * jax.nn.silu(z.astype(F32))).astype(compute),
                  params["out_kernel"]).astype(u.dtype)
        return [out, y.astype(compute)] if self.emit_memory else out


def _inverse_softplus(y):
    return y + jnp.log(-jnp.expm1(-y))


class GatedMemoryUnit(Layer):
    """Inputs ``[x, memory]``: ``out = (silu(x W1) * memory) W2``, the
    memory another layer's (its scan output), no bias."""

    def build(self, rng, input_shape) -> Params:
        d, c = input_shape[0][-1], input_shape[1][-1]
        params: Params = {}
        self.add_weight(params, rng, "in_kernel", (d, c), init="normal")
        self.add_weight(params, rng, "out_kernel", (c, d), init="normal")
        return params

    def compute_output_shape(self, input_shape):
        return tuple(input_shape[0])

    def call(self, params, inputs, training=False, rng=None):
        x, memory = inputs
        compute = get_policy().compute_dtype
        gate = jax.nn.silu(_mm(x, params["in_kernel"]))
        return _mm((gate * memory.astype(F32)).astype(compute),
                   params["out_kernel"]).astype(x.dtype)


def _lambda_vector(key, shape, dtype):
    return 0.1 * jax.random.normal(key, shape, dtype)


def differential_lambda_init(layer_index: int) -> float:
    """``lam0`` of the layer at (published) depth ``layer_index``."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer_index)


class DifferentialAttention(Layer):
    """Differential attention over heads in pairs.  Query heads ``2j``
    and ``2j + 1`` are ``q1_j`` and ``q2_j``, K/V heads ``2i`` and ``2i +
    1`` are ``k1_i, k2_i`` and ``v1_i, v2_i``, pair ``j`` reads K/V pair
    ``j // (pairs / K/V pairs)``.  With ``V_i = [v1_i, v2_i]``::

        a1 = softmax(q1 k1^T / sqrt(D) + mask) V
        a2 = softmax(q2 k2^T / sqrt(D) + mask) V
        lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0
        out_j = RMSNorm(a1 - lam a2) * (1 - lam0)

    (one gain of ``2 D``), the pairs side by side, times ``W_o``; biases
    on both projections; ``lam0 = differential_lambda_init(layer_index)``.

    ``mask``: ``"causal"`` or ``ops.pallas_attention.sliding_window(W)``.
    ``emit_kv``: outputs ``[out, k, v]``, this layer's keys and values as
    the projection wrote them.  ``cross``: inputs ``[x, k, v]``; the
    layer projects a query only and reads the K/V it is handed (what
    comes back to them is this reader's share of their cotangent).

    On one device the flash kernels form the two maps of every pair
    (``flash_attention_token_major(..., differential=True)``: no second
    copy of V, no repeated K/V); elsewhere dense attention does."""

    def __init__(self, n_head: int, n_kv_head: int, head_dim: int,
                 layer_index: int, mask="causal", cross: bool = False,
                 emit_kv: bool = False, norm_epsilon: float = 1e-5,
                 **kwargs):
        super().__init__(**kwargs)
        if n_head % 2 or n_kv_head % 2 or (n_head // 2) % (n_kv_head // 2):
            raise ValueError(
                f"{n_head} heads on {n_kv_head} K/V heads do not pair up")
        self.n_head, self.n_kv_head = int(n_head), int(n_kv_head)
        self.head_dim = int(head_dim)
        self.lambda_init = differential_lambda_init(int(layer_index))
        self.mask, self.cross, self.emit_kv = mask, bool(cross), bool(emit_kv)
        self.norm_epsilon = float(norm_epsilon)

    def build(self, rng, input_shape) -> Params:
        d = (input_shape[0] if self.cross else input_shape)[-1]
        hd, kvd = (self.n_head * self.head_dim,
                   self.n_kv_head * self.head_dim)
        width = hd if self.cross else hd + 2 * kvd
        params: Params = {}
        self.add_weight(params, rng, "in_kernel", (d, width), init="normal")
        self.add_weight(params, rng, "in_bias", (width,), init="zero")
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            self.add_weight(params, rng, name, (self.head_dim,),
                            init=_lambda_vector)
        self.add_weight(params, rng, "subln_gamma", (2 * self.head_dim,),
                        init="one")
        self.add_weight(params, rng, "out_kernel", (hd, d), init="normal")
        self.add_weight(params, rng, "out_bias", (d,), init="zero")
        return params

    def compute_output_shape(self, input_shape):
        shape = tuple(input_shape[0] if self.cross else input_shape)
        if self.emit_kv:
            kv = shape[:-1] + (self.n_kv_head * self.head_dim,)
            return [shape, kv, kv]
        return shape

    def _maps(self, q, k, v, qkv):
        """Every head's map over its pair's V, (B, T, H, 2 D)."""
        from analytics_zoo_tpu.ops import fused
        from analytics_zoo_tpu.ops.pallas_attention import (
            SlidingWindowMask, allowed_pairs, flash_attention_token_major)
        b, t = q.shape[:2]
        h, h_kv, d = self.n_head, self.n_kv_head, self.head_dim
        windowed = isinstance(self.mask, SlidingWindowMask)
        kind = "flash_attention_window" if windowed \
            else "flash_attention_differential"
        on_kernels = _flash_route(t, h, h_kv, d, differential=True)
        fused.count_build(kind, "pallas" if on_kernels else "lax")
        if on_kernels:
            # a window's band is narrow: smaller tiles walk less of what
            # lies outside it
            block = 256 if windowed or t % 1024 else 512
            ops = (qkv,) if qkv is not None else (q, k, v)
            out = flash_attention_token_major(
                *ops, n_head=h, n_kv_head=h_kv, differential=True,
                causal=self.mask == "causal", block_q=block, block_k=block,
                mask=self.mask if windowed else None)
            return out.reshape(b, t, h, 2 * d)
        group = (h // 2) // (h_kv // 2)
        q = q.reshape(b, t, h // 2, 2, d)
        k = jnp.repeat(k.reshape(b, t, h_kv // 2, 2, d), group, axis=2)
        v = jnp.repeat(v.reshape(b, t, h_kv // 2, 2 * d), group, axis=2)
        logits = jnp.einsum("bqjrd,bkjrd->bjrqk", q, k,
                            preferred_element_type=F32) / math.sqrt(d)
        logits = jnp.where(jnp.asarray(allowed_pairs(self.mask, t)),
                           logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        out = jnp.einsum("bjrqk,bkje->bqjre", probs, v,
                         preferred_element_type=F32)
        return out.reshape(b, t, h, 2 * d).astype(q.dtype)

    def call(self, params, inputs, training=False, rng=None):
        compute = get_policy().compute_dtype
        h, d = self.n_head, self.head_dim
        hd, kvd = h * d, self.n_kv_head * d
        x = inputs[0] if self.cross else inputs
        b, t = x.shape[:2]
        proj = (_mm(x, params["in_kernel"])
                + params["in_bias"]).astype(compute)
        if self.cross:
            q, (k, v), qkv = proj, inputs[1:], None
        else:
            q, k, v = jnp.split(proj, [hd, hd + kvd], axis=-1)
            qkv = proj
        a = self._maps(q, k, v, qkv).astype(F32)
        a = a.reshape(b, t, h // 2, 2, 2 * d)
        lam0 = self.lambda_init
        lam = (jnp.exp(jnp.sum(params["lambda_q1"] * params["lambda_k1"]))
               - jnp.exp(jnp.sum(params["lambda_q2"] * params["lambda_k2"]))
               + lam0).astype(F32)
        diff = rms_norm(a[..., 0, :] - lam * a[..., 1, :],
                        params["subln_gamma"], self.norm_epsilon)
        diff = (diff * (1.0 - lam0)).astype(compute).reshape(b, t, hd)
        out = (_mm(diff, params["out_kernel"])
               + params["out_bias"]).astype(x.dtype)
        return [out, k, v] if self.emit_kv else out


class KeepsKernelResults(Layer):
    """A layer that may run its body under ``jax.checkpoint`` with the
    policy that keeps what the body's Pallas kernels wrote, by name
    (``KEPT_RESULTS`` of ``ops/pallas_attention.py`` and
    ``ops/selective_scan.py``), and publishes the bytes so kept in the
    gauge ``train_recompute_kept_bytes{name}``, over every recomputed
    layer traced."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        # this layer's share of train_recompute_kept_bytes, by name
        self._kept_bytes: dict = {}

    def _recomputed(self, body, *args):
        from analytics_zoo_tpu.ops import (
            fused, pallas_attention, selective_scan)
        keep = jax.checkpoint_policies.save_only_these_names(
            *pallas_attention.KEPT_RESULTS, *selective_scan.KEPT_RESULTS)
        with fused.recording_kept_results() as kept:
            out = jax.checkpoint(body, policy=keep)(*args)
        self._gauge_kept(kept)
        return out

    def _gauge_kept(self, kept: dict) -> None:
        """Moves the gauge by what this trace of the layer keeps more
        (or less) than its last: tracing a layer again adds nothing."""
        from analytics_zoo_tpu.observability import get_registry
        gauge = get_registry().gauge(
            "train_recompute_kept_bytes",
            "bytes of kernel results that recomputed layers keep for the "
            "backward pass", labels=("name",))
        for name in kept.keys() | self._kept_bytes.keys():
            gauge.labels(name).inc(
                kept.get(name, 0) - self._kept_bytes.get(name, 0))
        self._kept_bytes = kept


class HybridDecoderLayer(KeepsKernelResults):
    """One decoder layer: ``h = h + mixer(LN1(h))``, then ``h = h +
    ffn(LN2(h))``, LayerNorm with weight and bias.  Inputs ``h`` or ``[h,
    *what the mixer reads besides]``; outputs ``h`` or ``[h, *what the
    mixer emits besides]``.  The layer holds the weights of its parts
    under ``ln1_*``, ``mixer_*``, ``ln2_*``, ``ffn_*``.

    ``recompute``: the layer's internals are not kept for the backward
    pass but computed again in it (``jax.checkpoint`` round the layer),
    so that a deep model holds one layer's internals at a time beside
    every layer's input.  EXCEPT what its Pallas kernels wrote, which
    the checkpoint's policy keeps by name (``KEPT_RESULTS`` of
    ``ops/pallas_attention.py`` and ``ops/selective_scan.py``): a kept
    byte of attention's output saves five times the device time a kept
    byte of the MLP's would (docs/hybrid-decoder-layers.md), and the
    forward kernels then run once a step.  The gauge
    ``train_recompute_kept_bytes{name}`` holds the bytes so kept, over
    every recomputed layer traced."""

    def __init__(self, mixer: Layer, ffn: Layer, epsilon: float = 1e-5,
                 recompute: bool = False, **kwargs):
        super().__init__(**kwargs)
        self.mixer, self.ffn = mixer, ffn
        self.epsilon, self.recompute = float(epsilon), bool(recompute)

    @staticmethod
    def _stream(input_shape):
        many = isinstance(input_shape, list)
        return (input_shape[0], input_shape[1:]) if many \
            else (input_shape, [])

    def build(self, rng, input_shape) -> Params:
        h, extra = self._stream(input_shape)
        params: Params = {}
        parts = (("mixer", self.mixer, [h, *extra] if extra else h),
                 ("ffn", self.ffn, h))
        for i, (prefix, part, shape) in enumerate(parts):
            self.add_weight(params, rng, f"ln{i + 1}_gamma", (h[-1],),
                            init="one")
            self.add_weight(params, rng, f"ln{i + 1}_beta", (h[-1],),
                            init="zero")
            sub = part.init(jax.random.fold_in(rng, i), shape)["params"]
            params.update({f"{prefix}_{k}": v for k, v in sub.items()})
        return params

    def compute_output_shape(self, input_shape):
        h, extra = self._stream(input_shape)
        out = self.mixer.compute_output_shape([h, *extra] if extra else h)
        return [tuple(h), *out[1:]] if isinstance(out, list) else tuple(h)

    def _body(self, params, h, *extra):
        def part(prefix):
            return {k[len(prefix) + 1:]: v for k, v in params.items()
                    if k.startswith(prefix + "_")}

        a = layer_norm(h, params["ln1_gamma"], params["ln1_beta"],
                       self.epsilon)
        mixed = self.mixer.call(part("mixer"), [a, *extra] if extra else a)
        mixed, emitted = (mixed[0], mixed[1:]) \
            if isinstance(mixed, (list, tuple)) else (mixed, [])
        h = h + mixed.astype(h.dtype)
        m = layer_norm(h, params["ln2_gamma"], params["ln2_beta"],
                       self.epsilon)
        h = h + self.ffn.call(part("ffn"), m).astype(h.dtype)
        return (h, *emitted)

    def call(self, params, inputs, training=False, rng=None):
        args = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        out = self._recomputed(self._body, params, *args) \
            if self.recompute else self._body(params, *args)
        return list(out) if len(out) > 1 else out[0]


class NextTokenLoss(Layer):
    """Inputs ``[h (B, T, D), ids (B, T), table (V, D)]`` -> (B,): each
    sequence's mean over its ``T - 1`` predicted positions of the
    cross-entropy of ``h_t table^T`` against ``ids_{t+1}``, float32.
    ``table`` is the embedding's own (``Embedding(..., tie_head=True)``),
    so the head is tied: one leaf, two uses.  Over a slice of the
    vocabulary that starts at id ``vocab_first``, logit ``j`` is id
    ``vocab_first + j``.

    ``head_units``: an UNTIED head: the layer holds its own ``kernel``
    (D, head_units), takes ``[h, ids]`` and forms the logits as ``h_t
    kernel``.

    ``chunk_rows``: the logits are formed that many positions at a time
    and formed again in the backward pass, so that one chunk's (rows, V)
    float32 logits exist at a time, not the sequence's."""

    def __init__(self, vocab_first: int = 0, chunk_rows: int = 0,
                 head_units: int = 0, **kwargs):
        super().__init__(**kwargs)
        self.vocab_first, self.chunk_rows = int(vocab_first), int(chunk_rows)
        self.head_units = int(head_units)

    def build(self, rng, input_shape) -> Params:
        params: Params = {}
        if self.head_units:
            self.add_weight(params, rng, "kernel",
                            (input_shape[0][-1], self.head_units),
                            init="normal")
        return params

    def compute_output_shape(self, input_shape):
        return (input_shape[0][0],)

    def call(self, params, inputs, training=False, rng=None):
        if self.head_units:
            (h, ids), table, over = inputs, params["kernel"], 0
        else:
            (h, ids, table), over = inputs, 1
        b, t, d = h.shape
        targets = jnp.roll(ids.astype(jnp.int32), -1, axis=1) \
            - self.vocab_first
        weights = (jnp.arange(t) < t - 1).astype(F32) / (t - 1)
        rows = self.chunk_rows if 0 < self.chunk_rows < t \
            and t % self.chunk_rows == 0 else t

        def chunk(args):
            h_c, targets_c = args                   # (B, rows, D), (B, rows)
            logits = jax.lax.dot_general(
                get_policy().cast_compute(h_c),
                get_policy().cast_compute(table),
                (((2,), (over,)), ((), ())), preferred_element_type=F32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, targets_c[..., None],
                                         axis=-1)[..., 0]
            return lse - picked

        def by_chunk(v):
            return jnp.moveaxis(
                v.reshape(b, t // rows, rows, *v.shape[2:]), 1, 0)

        nll = jax.lax.map(jax.checkpoint(chunk),
                          (by_chunk(h), by_chunk(targets)))
        nll = jnp.moveaxis(nll, 0, 1).reshape(b, t)
        return jnp.sum(nll * weights, axis=-1)


def hybrid_layer_kind(index: int, num_layers: int) -> str:
    """What the layer at (0-based) depth ``index`` of ``num_layers`` is:
    the self-decoder is the first half and one pair more, ``mamba``
    (even) and ``window_attention`` (odd) alternating, its last pair
    ``mamba_memory`` and ``full_attention``; the cross-decoder after it
    alternates ``memory_unit`` (even) and ``cross_attention`` (odd)."""
    half = num_layers // 2
    if index % 2 == 0:
        return ("mamba" if index < half else
                "mamba_memory" if index == half else "memory_unit")
    return ("window_attention" if index < half else
            "full_attention" if index == half + 1 else "cross_attention")


def decoder_hybrid_decoder(*, seq_len: int, vocab_size: int,
                           hidden_size: int, intermediate_size: int,
                           n_head: int, n_kv_head: int, head_dim: int,
                           num_layers: int,
                           layer_ids: Optional[Sequence[int]] = None,
                           d_inner: int, d_state: int = 16, d_conv: int = 4,
                           dt_rank: Optional[int] = None,
                           sliding_window: int = 512,
                           norm_epsilon: float = 1e-5, vocab_held=None,
                           recompute: bool = False, loss_chunk_rows: int = 0,
                           extra_inputs: int = 0):
    """The decoder-hybrid-decoder of the module's docstring as a graph
    ``Model`` whose output is each sequence's next-token loss; train it
    under ``lambda y_true, y_pred: jnp.mean(y_pred)``.

    ``layer_ids``: the depths, out of ``num_layers``, of the layers to
    build (default: all).  A layer is what its depth makes it
    (``hybrid_layer_kind``) and its ``lam0`` is its depth's; a
    cross-decoder layer needs the layer that writes what it reads.
    ``vocab_held=(first, count)``: this chip's slice of a
    vocabulary-parallel embedding and tied head (ids, logits and loss
    over the slice).  ``recompute`` and ``loss_chunk_rows``:
    ``HybridDecoderLayer``'s and ``NextTokenLoss``'s.  ``extra_inputs``
    further model inputs are taken and not used."""
    from analytics_zoo_tpu.pipeline.api.keras.engine import Input
    from analytics_zoo_tpu.pipeline.api.keras.layers.embedding import (
        Embedding)
    from analytics_zoo_tpu.pipeline.api.keras.layers.normalization import (
        LayerNorm)
    from analytics_zoo_tpu.pipeline.api.keras.topology import Model
    from analytics_zoo_tpu.ops.pallas_attention import (
        sliding_window as window_mask)

    first, _ = vocab_held or (0, vocab_size)
    ids = Input(shape=(seq_len,))
    unused = [Input(shape=(seq_len,)) for _ in range(extra_inputs)]
    h, table = Embedding(vocab_size, hidden_size, init="normal",
                         vocab_held=vocab_held, tie_head=True)(ids)
    memory = keys = values = None

    def attention(index, **kwargs):
        return DifferentialAttention(n_head, n_kv_head, head_dim, index,
                                     norm_epsilon=norm_epsilon, **kwargs)

    for index in (range(num_layers) if layer_ids is None else layer_ids):
        kind = hybrid_layer_kind(index, num_layers)
        reads: List = []
        if kind in ("mamba", "mamba_memory"):
            mixer = Mamba(d_inner, d_state, d_conv, dt_rank,
                          emit_memory=kind == "mamba_memory")
        elif kind == "window_attention":
            mixer = attention(index, mask=window_mask(sliding_window))
        elif kind == "full_attention":
            mixer = attention(index, emit_kv=True)
        elif kind == "memory_unit":
            mixer, reads = GatedMemoryUnit(), [memory]
        else:
            mixer, reads = attention(index, cross=True), [keys, values]
        if any(r is None for r in reads):
            raise ValueError(
                f"layer {index} ({kind}) reads what no layer before it "
                f"among {layer_ids} writes")
        out = HybridDecoderLayer(
            mixer, GatedFeedForward(intermediate_size), norm_epsilon,
            recompute=recompute)([h, *reads] if reads else h)
        if kind == "mamba_memory":
            h, memory = out
        elif kind == "full_attention":
            h, keys, values = out
        else:
            h = out
    loss = NextTokenLoss(first, loss_chunk_rows)(
        [LayerNorm(norm_epsilon)(h), ids, table])
    return Model([ids, *unused], loss)
