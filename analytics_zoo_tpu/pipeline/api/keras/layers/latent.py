"""A latent-attention decoder with sigmoid-routed and shared experts
(the DeepSeek-V3 family's layer: Liu et al. 2024, arXiv:2412.19437).

``LatentAttention`` is multi-head latent attention on the TRAINING
path: keys and values come out of one low-rank latent a position, and a
head's logit is the sum of a product with its own ``k_nope`` and a
product with ONE rotary key shared by all heads.  ``LatentDecoderLayer``
is the pre-norm block round it (RMSNorm, residual sums) with a dense
gated MLP or a ``DroplessMoE`` as its feed-forward, recomputed in the
backward pass if asked, with its kernels' results kept.
``latent_moe_decoder`` builds the functional ``Model`` whose output is
each sequence's next-token loss over an untied, vocabulary-sliced head.

Column order of the projections (a permutation of the published
matrices' columns, to be applied where a checkpoint is laid into the
program; random weights need none):

* ``q_kernel`` (D, H (n + r)): every head's ``q_nope`` (n wide), head by
  head, THEN every head's rotary part (r wide), head by head — the
  published ``q_proj`` has ``[q_nope_h | q_pe_h]`` a head;
* ``kv_a_kernel`` (D, L + r): the latent, then the shared rotary key
  (as published);
* ``kv_b_kernel`` (L, H (n + v)): every head's ``k_nope``, THEN every
  head's ``v`` — the published ``kv_b_proj`` has ``[k_nope_h | v_h]`` a
  head;
* within each rotary part the columns are in ROTATE-HALF order: column
  ``i < r / 2`` is the published (interleaved) column ``2 i`` and column
  ``r / 2 + i`` the published ``2 i + 1``, so that the published
  rotation of the pairs ``(2 i, 2 i + 1)`` is
  ``ops.attention.rotary_embedding``'s of ``(i, r / 2 + i)``.  A dot
  product does not see a permutation applied to both of its sides.

So laid out, the kernels (``ops/pallas_latent_attention.py``) read
``q_nope`` out of the query projection's result and ``k_nope`` and ``v``
out of the up-projection's where they lie; only the rotary parts, which
the rotation writes anyway, are arrays of their own.

Precision: matrix products take the compute dtype and accumulate in
float32; the residual stream, the norms, the rotary angles, the router,
the softmax and the loss are float32.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops.dtypes import get_policy
from analytics_zoo_tpu.pipeline.api.keras.engine import Layer, Params, State
from analytics_zoo_tpu.pipeline.api.keras.layers.attention import _mesh, _mm
from analytics_zoo_tpu.pipeline.api.keras.layers.normalization import (
    rms_norm)
from analytics_zoo_tpu.pipeline.api.keras.layers.ssm import (
    GatedFeedForward, KeepsKernelResults)


def _latent_route(t: int, n_head: int, nope: int, rope: int, v: int) -> bool:
    """Whether latent attention over ``t`` positions goes to the
    kernels: ``layers.attention._flash_route``'s conditions (one device,
    256-position tiles, the suite's one capability probe) and heads of
    the sizes the kernels take."""
    from analytics_zoo_tpu.ops import fused
    from analytics_zoo_tpu.ops.pallas_latent_attention import kernel_fits
    return bool(fused.pallas_supported()
                and math.prod(_mesh().shape.values()) == 1
                and t % 256 == 0 and kernel_fits(n_head, nope, rope, v))


class LatentAttention(Layer):
    """Causal multi-head latent attention over ``x`` (B, T, D), no query
    latent, no bias::

        q = x Wq                      -> H heads of [q_nope (n) | q_pe (r)]
        [c | k_pe] = x Wkva           (L | r);  c = RMSNorm(c)
        [k_nope_h | v_h] = c Wkvb     (H heads of n | v)
        q_pe_h, k_pe = rotary(...)    at positions 0 .. T-1, base theta
        s_h = (q_nope_h k_nope_h^T + q_pe_h k_pe^T) / sqrt(n + r)
        y = [softmax(s_h + causal) v_h]_h Wo

    (the module's docstring has the columns' order).  On one device the
    latent flash kernels form the logit as that sum: no (T, H, n + r)
    key and no copy of ``k_pe`` a head exists; elsewhere dense attention
    does."""

    def __init__(self, n_head: int, kv_lora_rank: int, qk_nope_head_dim: int,
                 qk_rope_head_dim: int, v_head_dim: int,
                 rope_theta: float = 10000.0, norm_epsilon: float = 1e-6,
                 **kwargs):
        super().__init__(**kwargs)
        self.n_head, self.rank = int(n_head), int(kv_lora_rank)
        self.nope, self.rope = int(qk_nope_head_dim), int(qk_rope_head_dim)
        self.v_dim = int(v_head_dim)
        self.rope_theta = float(rope_theta)
        self.norm_epsilon = float(norm_epsilon)

    def build(self, rng, input_shape) -> Params:
        d, h = input_shape[-1], self.n_head
        params: Params = {}
        self.add_weight(params, rng, "q_kernel",
                        (d, h * (self.nope + self.rope)), init="normal")
        self.add_weight(params, rng, "kv_a_kernel",
                        (d, self.rank + self.rope), init="normal")
        self.add_weight(params, rng, "kv_a_norm", (self.rank,), init="one")
        self.add_weight(params, rng, "kv_b_kernel",
                        (self.rank, h * (self.nope + self.v_dim)),
                        init="normal")
        self.add_weight(params, rng, "o_kernel", (h * self.v_dim, d),
                        init="normal")
        return params

    def call(self, params, x, training=False, rng=None):
        from analytics_zoo_tpu.ops import fused
        from analytics_zoo_tpu.ops import pallas_latent_attention as latent
        from analytics_zoo_tpu.ops.attention import rotary_embedding
        compute = get_policy().compute_dtype
        b, t, _ = x.shape
        h, n, r = self.n_head, self.nope, self.rope
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

        def rotated(pe, heads):
            return rotary_embedding(
                pe.reshape(b, t, heads, r), positions,
                self.rope_theta).astype(compute).reshape(b, t, heads * r)

        q = _mm(x, params["q_kernel"]).astype(compute)
        down = _mm(x, params["kv_a_kernel"])                 # float32
        c = rms_norm(down[..., :self.rank], params["kv_a_norm"],
                     self.norm_epsilon)
        kv = _mm(c, params["kv_b_kernel"]).astype(compute)
        q_pe, k_pe = rotated(q[..., h * n:], h), rotated(
            down[..., self.rank:], 1)

        on_kernels = _latent_route(t, h, n, r, self.v_dim)
        fused.count_build("flash_attention_latent",
                          "pallas" if on_kernels else "lax")
        if on_kernels:
            block = 512 if t % 1024 == 0 else 256
            ctx = latent.latent_flash_attention(
                q, q_pe, kv, k_pe, n_head=h, causal=True, block_q=block,
                block_k=block)
        else:
            ctx = latent.latent_attention_dense(
                q, q_pe, kv, k_pe, n_head=h, nope_dim=n, v_dim=self.v_dim,
                causal=True)
        return _mm(ctx, params["o_kernel"]).astype(x.dtype)


class LatentDecoderLayer(KeepsKernelResults):
    """One decoder layer: ``h = h + attention(RMSNorm(h))``, then ``h = h
    + ffn(RMSNorm(h))``.  ``ffn``: a ``GatedFeedForward`` (a dense layer)
    or a ``DroplessMoE`` (a sparse one, whose state — ``rows_routed``,
    ``selection_bias`` — is this layer's, so that
    ``observability.moe_stats`` finds it).  The layer holds the weights
    of its parts under ``ln1_gamma``, ``attn_*``, ``ln2_gamma``,
    ``ffn_*``.

    ``recompute``: the layer's internals are computed again in the
    backward pass, EXCEPT what its flash kernels wrote, which the policy
    keeps by name (``KeepsKernelResults``)."""

    def __init__(self, attention: Layer, ffn: Layer, epsilon: float = 1e-6,
                 recompute: bool = False, **kwargs):
        super().__init__(**kwargs)
        self.attention, self.ffn = attention, ffn
        self.epsilon, self.recompute = float(epsilon), bool(recompute)

    def build(self, rng, input_shape) -> Params:
        params: Params = {}
        for i, (prefix, part) in enumerate((("attn", self.attention),
                                            ("ffn", self.ffn))):
            self.add_weight(params, rng, f"ln{i + 1}_gamma",
                            (input_shape[-1],), init="one")
            sub = part.init(jax.random.fold_in(rng, i),
                            input_shape)["params"]
            params.update({f"{prefix}_{k}": v for k, v in sub.items()})
        return params

    def init_state(self, input_shape) -> State:
        return self.ffn.init_state(input_shape)

    def _body(self, params, state, h):
        def part(prefix):
            return {k[len(prefix) + 1:]: v for k, v in params.items()
                    if k.startswith(prefix + "_")}

        # the norms' results stay float32: the products cast their
        # operands themselves and the router reads float32
        a = rms_norm(h, params["ln1_gamma"], self.epsilon)
        h = h + self.attention.call(part("attn"), a).astype(h.dtype)
        m = rms_norm(h, params["ln2_gamma"], self.epsilon)
        y, state = self.ffn.apply(part("ffn"), m, state=state)
        y = y[0] if isinstance(y, (list, tuple)) else y      # [y, aux]
        return h + y.astype(h.dtype), state

    def apply(self, params, x, state: Optional[State] = None,
              training=False, rng=None):
        # a dense layer's empty state comes back as it went in
        if self.recompute:
            return self._recomputed(self._body, params, state, x)
        return self._body(params, state, x)

    def call(self, params, x, training=False, rng=None):
        return self.apply(params, x)[0]


def latent_moe_decoder(*, seq_len: int, vocab_size: int, hidden_size: int,
                       num_layers: int, n_head: int, kv_lora_rank: int,
                       qk_nope_head_dim: int, qk_rope_head_dim: int,
                       v_head_dim: int, intermediate_size: int,
                       first_dense_layers: int = 1, num_experts: int,
                       top_k: int, expert_hidden: int, shared_hidden: int = 0,
                       routed_scaling_factor: float = 1.0,
                       norm_topk_prob: bool = True, experts_held=None,
                       vocab_held=None, rope_theta: float = 10000.0,
                       norm_epsilon: float = 1e-6, recompute: bool = False,
                       loss_chunk_rows: int = 0, extra_inputs: int = 0):
    """A pre-norm decoder of ``LatentDecoderLayer``s as a graph ``Model``
    whose output is each sequence's next-token loss; train it under
    ``lambda y_true, y_pred: jnp.mean(y_pred)``.

    The first ``first_dense_layers`` layers have a dense gated MLP of
    ``intermediate_size``; the others a ``DroplessMoE`` with sigmoid
    scores, a selection bias (state), ``top_k`` of ``num_experts`` routed
    experts of ``expert_hidden`` scaled by ``routed_scaling_factor``,
    and shared experts of ``shared_hidden`` in all.  ``experts_held``
    and ``vocab_held=(first, count)`` give this chip's slice of the
    experts and of a vocabulary-parallel embedding and UNTIED head (ids,
    logits and loss over the slice).  ``recompute`` and
    ``loss_chunk_rows``: ``LatentDecoderLayer``'s and ``NextTokenLoss``'s.
    ``extra_inputs`` further model inputs are taken and not used."""
    from analytics_zoo_tpu.pipeline.api.keras.engine import Input
    from analytics_zoo_tpu.pipeline.api.keras.layers.embedding import (
        Embedding)
    from analytics_zoo_tpu.pipeline.api.keras.layers.moe import DroplessMoE
    from analytics_zoo_tpu.pipeline.api.keras.layers.normalization import (
        RMSNorm)
    from analytics_zoo_tpu.pipeline.api.keras.layers.ssm import NextTokenLoss
    from analytics_zoo_tpu.pipeline.api.keras.topology import Model

    first, count = vocab_held or (0, vocab_size)
    ids = Input(shape=(seq_len,))
    unused = [Input(shape=(seq_len,)) for _ in range(extra_inputs)]
    h = Embedding(vocab_size, hidden_size, init="normal",
                  vocab_held=vocab_held)(ids)
    for index in range(num_layers):
        attention = LatentAttention(
            n_head, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
            v_head_dim, rope_theta=rope_theta, norm_epsilon=norm_epsilon)
        if index < first_dense_layers:
            ffn: Layer = GatedFeedForward(intermediate_size)
        else:
            ffn = DroplessMoE(
                num_experts, expert_hidden, top_k=top_k,
                norm_topk_prob=norm_topk_prob, experts_held=experts_held,
                init="normal", scoring="sigmoid",
                routed_scaling_factor=routed_scaling_factor,
                shared_hidden=shared_hidden)
        h = LatentDecoderLayer(attention, ffn, norm_epsilon,
                               recompute=recompute)(h)
    loss = NextTokenLoss(first, loss_chunk_rows, head_units=count)(
        [RMSNorm(norm_epsilon)(h), ids])
    return Model([ids, *unused], loss)
