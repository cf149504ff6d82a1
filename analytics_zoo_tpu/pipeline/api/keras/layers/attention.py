"""Attention layers + BERT.

Reference: zoo/pipeline/api/keras/layers/BERT.scala:66 (embeddings +
N transformer blocks + pooler) and pyzoo
zoo/pipeline/api/keras/layers/self_attention.py (TransformerLayer).

TPU design: QKV is one fused matmul; heads live in a reshaped axis (no
per-head loops), and on the flash path they are not split at all: the
kernels take the projection's (B, T, 3·H·D) result and pick a head by
a block of its last dimension.  With a populated ``seq`` mesh axis the layer routes
through ring attention (sequence parallelism over ICI, ppermute ring) —
the long-context capability the reference lacks (SURVEY.md §5).  With a
populated ``model`` axis, QKV/out projections shard Megatron-style
(column then row parallel).
"""

from __future__ import annotations

import math
from typing import List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.ops import activations as acts
from analytics_zoo_tpu.ops.attention import scaled_dot_product_attention
from analytics_zoo_tpu.ops.dtypes import get_policy
from analytics_zoo_tpu.pipeline.api.keras.engine import (
    Input, Layer, Params, fold_name,
)
from analytics_zoo_tpu.pipeline.api.keras.layers.core import Dense, Dropout
from analytics_zoo_tpu.pipeline.api.keras.layers.embedding import Embedding
from analytics_zoo_tpu.pipeline.api.keras.layers.normalization import (
    LayerNorm, rms_norm,
)
from analytics_zoo_tpu.pipeline.api.keras.topology import Model
from analytics_zoo_tpu.parallel.mesh import (
    DATA_AXIS, FSDP_AXIS, MODEL_AXIS, SEQ_AXIS,
)


def _mm(x, w):
    policy = get_policy()
    return jax.lax.dot_general(
        policy.cast_compute(x), policy.cast_compute(w),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _mesh():
    from analytics_zoo_tpu.common.zoo_context import get_zoo_context
    return get_zoo_context().mesh


def _flash_route(t: int, n_head: int, n_kv_head: int, head_dim: int,
                 differential: bool = False):
    """How many heads share a lane tile if self-attention over ``t``
    positions goes to the flash kernels, 0 if it does not:
    ``pallas_call`` is not GSPMD-partitionable, so only on a trivial
    (single-device) mesh; the tiles are 256 positions long; and a head
    has to be a block of the projection's last dimension, a lane
    multiple or one of ``128 // head_dim`` that fill 128 lanes
    (``ops/pallas_attention._heads_per_tile``).  Every operand enters
    the kernels tile by tile, so no length is too long.  Availability
    comes from the kernel suite's ONE capability probe
    (ops/fused.pallas_supported — does this backend compile Pallas?),
    not from a backend-name string match."""
    from analytics_zoo_tpu.ops import fused
    from analytics_zoo_tpu.ops.pallas_attention import _heads_per_tile
    if not (fused.pallas_supported()
            and math.prod(_mesh().shape.values()) == 1 and t % 256 == 0):
        return 0
    return _heads_per_tile(n_head, n_kv_head, head_dim, differential)


def _count_flash(heads_per_tile: int) -> None:
    """Which form of attention a traced program got: the kernels
    (and, beside it, whether heads share a tile) or the lax path."""
    from analytics_zoo_tpu.ops import fused
    fused.count_build("flash_attention",
                      "pallas" if heads_per_tile else "lax")
    if heads_per_tile > 1:
        fused.count_build("flash_attention_packed", "pallas")


class MultiHeadSelfAttention(Layer):
    """Self-attention over (B, T, D); optional (B, T) 0/1 mask as a
    second input.  ``sequence_parallel``/``tensor_parallel``: "auto"
    routes by whether the mesh axis is populated."""

    def __init__(self, hidden_size: int, n_head: int,
                 attn_dropout: float = 0.0, causal: bool = False,
                 sequence_parallel: str = "auto",
                 tensor_parallel: str = "auto", **kwargs):
        super().__init__(**kwargs)
        assert hidden_size % n_head == 0
        self.hidden_size = int(hidden_size)
        self.n_head = int(n_head)
        self.head_dim = self.hidden_size // self.n_head
        self.attn_dropout = float(attn_dropout)
        self.causal = causal
        self.sequence_parallel = sequence_parallel
        self.tensor_parallel = tensor_parallel

    def _use_sp(self):
        return (self.sequence_parallel == "auto" and
                _mesh().shape[SEQ_AXIS] > 1) or \
            self.sequence_parallel is True

    def _use_tp(self):
        return (self.tensor_parallel == "auto" and
                _mesh().shape[MODEL_AXIS] > 1) or \
            self.tensor_parallel is True

    def build(self, rng, input_shape) -> Params:
        if isinstance(input_shape, list):
            input_shape = input_shape[0]
        d = input_shape[-1]
        params: Params = {}
        self.add_weight(params, rng, "qkv_kernel",
                        (d, 3 * self.hidden_size))
        self.add_weight(params, rng, "qkv_bias", (3 * self.hidden_size,),
                        init="zero")
        self.add_weight(params, rng, "out_kernel",
                        (self.hidden_size, d))
        self.add_weight(params, rng, "out_bias", (d,), init="zero")
        if self._use_tp():
            self.param_pspecs["qkv_kernel"] = P(None, MODEL_AXIS)
            self.param_pspecs["qkv_bias"] = P(MODEL_AXIS)
            self.param_pspecs["out_kernel"] = P(MODEL_AXIS, None)
            self.param_pspecs["out_bias"] = P()
        return params

    def call(self, params, inputs, training=False, rng=None):
        if isinstance(inputs, (list, tuple)):
            x, mask = inputs[0], inputs[1]
        else:
            x, mask = inputs, None
        b, t, _ = x.shape
        qkv = _mm(x, params["qkv_kernel"]) + params["qkv_bias"]

        use_sp = self._use_sp() and mask is None
        per_tile = 0 if use_sp or mask is not None else _flash_route(
            t, self.n_head, self.n_head, self.head_dim)
        if per_tile:
            from analytics_zoo_tpu.ops.pallas_attention import (
                flash_attention_token_major)
            _count_flash(per_tile)
            # the kernels read q, k and v out of the projection's
            # result where it lies, and write ctx as the output
            # projection reads it
            ctx = flash_attention_token_major(
                qkv, n_head=self.n_head, causal=self.causal)
        else:
            qkv = qkv.reshape(b, t, 3, self.n_head, self.head_dim)
            q, k, v = (jnp.moveaxis(qkv[:, :, i], 1, 2) for i in range(3))
            if use_sp:
                from analytics_zoo_tpu.parallel.ring_attention import (
                    ring_attention)
                mesh = _mesh()
                spec = NamedSharding(
                    mesh, P((DATA_AXIS, FSDP_AXIS), None, SEQ_AXIS, None))
                q = jax.lax.with_sharding_constraint(q, spec)
                k = jax.lax.with_sharding_constraint(k, spec)
                v = jax.lax.with_sharding_constraint(v, spec)
                ctx = ring_attention(q, k, v, mesh, causal=self.causal)
            else:
                attn_mask = None
                if mask is not None:
                    attn_mask = mask[:, None, None, :]   # (B,1,1,Tk)
                _count_flash(0)
                ctx = scaled_dot_product_attention(
                    q, k, v, mask=attn_mask, causal=self.causal)
            ctx = jnp.moveaxis(ctx, 1, 2).reshape(b, t, self.hidden_size)

        if training and self.attn_dropout > 0:
            if rng is None:
                raise ValueError(f"{self.name} needs rng when training")
            keep = 1.0 - self.attn_dropout
            ctx = ctx * jax.random.bernoulli(
                rng, keep, ctx.shape) / keep

        return (_mm(ctx, params["out_kernel"]) +
                params["out_bias"]).astype(x.dtype)

    def compute_output_shape(self, input_shape):
        if isinstance(input_shape, list):
            return tuple(input_shape[0])
        return tuple(input_shape)


class GroupedQueryAttention(Layer):
    """Self-attention with fewer key/value heads than query heads, as
    the current open decoders have it: separate q/k/v/o projections
    without bias, an optional RMS norm over each head of q and k (own
    gains), rotary positions (rotate-half form), and query head ``i``
    reading K/V head ``i // (n_head / n_kv_head)``.

    Inputs ``[x, positions]``: ``x`` (B, T, D) and integer position ids
    (B, T).  ``mask``: ``None`` (every query reads every key),
    ``"causal"``, or ``ops.pallas_attention.block_diffusion(L, B)``
    over ``T = 2 L`` positions (the noisy copy, then the clean one).

    Routing is ``MultiHeadSelfAttention``'s: the flash kernels on one
    device (heads are blocks of the projections' last dimension, K/V
    heads are indexed, never repeated, and tiles the mask rules out are
    skipped), dense attention under an explicit mask elsewhere."""

    def __init__(self, n_head: int, n_kv_head: int, head_dim: int,
                 rope_theta: float = 10000.0, qk_norm: bool = True,
                 norm_epsilon: float = 1e-6, mask=None, **kwargs):
        super().__init__(**kwargs)
        if n_head % n_kv_head:
            raise ValueError(
                f"{n_kv_head} K/V heads do not divide {n_head} heads")
        self.n_head, self.n_kv_head = int(n_head), int(n_kv_head)
        self.head_dim = int(head_dim)
        self.rope_theta = float(rope_theta)
        self.qk_norm = bool(qk_norm)
        self.norm_epsilon = float(norm_epsilon)
        self.mask = mask

    def build(self, rng, input_shape) -> Params:
        d = input_shape[0][-1]
        params: Params = {}
        self.add_weight(params, rng, "q_kernel",
                        (d, self.n_head * self.head_dim))
        self.add_weight(params, rng, "k_kernel",
                        (d, self.n_kv_head * self.head_dim))
        self.add_weight(params, rng, "v_kernel",
                        (d, self.n_kv_head * self.head_dim))
        self.add_weight(params, rng, "o_kernel",
                        (self.n_head * self.head_dim, d))
        if self.qk_norm:
            self.add_weight(params, rng, "q_norm", (self.head_dim,),
                            init="one")
            self.add_weight(params, rng, "k_norm", (self.head_dim,),
                            init="one")
        return params

    def call(self, params, inputs, training=False, rng=None):
        from analytics_zoo_tpu.ops.attention import rotary_embedding
        from analytics_zoo_tpu.ops.pallas_attention import (
            allowed_pairs, flash_attention_token_major)
        x, positions = inputs
        b, t, _ = x.shape
        compute = get_policy().compute_dtype

        def heads(kernel, n, gain):
            # the MXU accumulates in float32; the activation is kept in
            # the compute dtype (a float32 q at 8,192 positions is
            # 128 MB a layer, held for the backward pass)
            y = _mm(x, params[kernel]).astype(compute)
            y = y.reshape(b, t, n, self.head_dim)
            if self.qk_norm and gain is not None:
                y = rms_norm(y, params[gain], self.norm_epsilon)
            return y

        q = rotary_embedding(heads("q_kernel", self.n_head, "q_norm"),
                             positions, self.rope_theta)
        k = rotary_embedding(heads("k_kernel", self.n_kv_head, "k_norm"),
                             positions, self.rope_theta)
        v = heads("v_kernel", self.n_kv_head, None)

        causal = self.mask == "causal"
        per_tile = _flash_route(t, self.n_head, self.n_kv_head,
                                self.head_dim)
        _count_flash(per_tile)
        if per_tile:
            block = 512 if t % 1024 == 0 else 256
            ctx = flash_attention_token_major(
                *(a.reshape(b, t, -1) for a in (q, k, v)),
                n_head=self.n_head, causal=causal, block_q=block,
                block_k=block, mask=None if causal else self.mask)
        else:
            group = self.n_head // self.n_kv_head
            q, k, v = (jnp.moveaxis(a, 1, 2) for a in (q, k, v))
            ctx = scaled_dot_product_attention(
                q, jnp.repeat(k, group, axis=1),
                jnp.repeat(v, group, axis=1),
                mask=None if self.mask is None else jnp.asarray(
                    allowed_pairs(self.mask, t)))
            ctx = jnp.moveaxis(ctx, 1, 2).reshape(
                b, t, self.n_head * self.head_dim)
        return _mm(ctx, params["o_kernel"]).astype(x.dtype)

    def compute_output_shape(self, input_shape):
        return tuple(input_shape[0])


class PositionwiseFeedForward(Layer):
    """Transformer FFN: up-proj (column-TP) → gelu → down-proj (row-TP)."""

    def __init__(self, hidden_size: int, intermediate_size: int,
                 activation="gelu", tensor_parallel: str = "auto",
                 **kwargs):
        super().__init__(**kwargs)
        self.hidden_size = int(hidden_size)
        self.intermediate_size = int(intermediate_size)
        self.activation = acts.get(activation)
        self.tensor_parallel = tensor_parallel

    def _use_tp(self):
        return (self.tensor_parallel == "auto" and
                _mesh().shape[MODEL_AXIS] > 1) or \
            self.tensor_parallel is True

    def build(self, rng, input_shape) -> Params:
        d = input_shape[-1]
        params: Params = {}
        self.add_weight(params, rng, "up_kernel",
                        (d, self.intermediate_size))
        self.add_weight(params, rng, "up_bias",
                        (self.intermediate_size,), init="zero")
        self.add_weight(params, rng, "down_kernel",
                        (self.intermediate_size, self.hidden_size))
        self.add_weight(params, rng, "down_bias",
                        (self.hidden_size,), init="zero")
        if self._use_tp():
            self.param_pspecs["up_kernel"] = P(None, MODEL_AXIS)
            self.param_pspecs["up_bias"] = P(MODEL_AXIS)
            self.param_pspecs["down_kernel"] = P(MODEL_AXIS, None)
            self.param_pspecs["down_bias"] = P()
        return params

    def call(self, params, x, training=False, rng=None):
        up = _mm(x, params["up_kernel"])
        if self.activation is acts.gelu:
            # fused bias→GeLU epilogue (ops/fused.py) — the FFN tail
            # without an HBM round trip of the intermediate; the lax
            # form is exactly gelu(up + bias)
            from analytics_zoo_tpu.ops import fused
            if fused.fused_enabled():
                h = fused.bias_gelu(up, params["up_bias"])
            else:
                h = acts.gelu(up + params["up_bias"])
        else:
            h = up + params["up_bias"]
            if self.activation is not None:   # get()->None = identity
                h = self.activation(h)
        return (_mm(h, params["down_kernel"]) +
                params["down_bias"]).astype(x.dtype)


def transformer_block(x, mask, hidden_size: int, n_head: int,
                      intermediate_size: int, dropout: float = 0.1,
                      causal: bool = False, activation="gelu",
                      ln_eps: float = 1e-5,
                      hidden_dropout: Optional[float] = None):
    """Post-LN transformer encoder block (BERT-style).

    ``dropout`` is the attention-probs dropout; ``hidden_dropout``
    (default: same value) applies to the attention output and FFN
    output, matching the published recipe's separate
    attention_probs_dropout_prob / hidden_dropout_prob knobs."""
    if hidden_dropout is None:
        hidden_dropout = dropout
    attn_in = [x, mask] if mask is not None else x
    a = MultiHeadSelfAttention(hidden_size, n_head,
                               attn_dropout=dropout,
                               causal=causal)(attn_in)
    a = Dropout(hidden_dropout)(a)
    from analytics_zoo_tpu.pipeline.api.keras.layers.merge import Merge
    x = Merge(mode="sum")([x, a])
    x = LayerNorm(epsilon=ln_eps)(x)
    f = PositionwiseFeedForward(hidden_size, intermediate_size,
                                activation=activation)(x)
    f = Dropout(hidden_dropout)(f)
    x = Merge(mode="sum")([x, f])
    return LayerNorm(epsilon=ln_eps)(x)


class BERT:
    """BERT encoder (BERT.scala:66 surface): builds a graph Model with
    inputs [token_ids, token_type_ids, position_ids, attention_mask] and
    outputs [sequence_output, pooled_output]."""

    def __init__(self, vocab: int = 40990, hidden_size: int = 768,
                 n_block: int = 12, n_head: int = 12,
                 seq_len: int = 512, intermediate_size: int = 3072,
                 max_position_len: int = 512, type_vocab_size: int = 2,
                 hidden_drop: float = 0.1, attn_drop: float = 0.1,
                 hidden_act: str = "gelu", ln_eps: float = 1e-12):
        # hidden_act/ln_eps defaults follow the published BERT recipe
        # (tanh-approx gelu is "gelu_new"; checkpoints trained with the
        # erf gelu import with hidden_act="gelu_erf")
        self.cfg = dict(vocab=vocab, hidden_size=hidden_size,
                        n_block=n_block, n_head=n_head, seq_len=seq_len,
                        intermediate_size=intermediate_size,
                        max_position_len=max_position_len,
                        type_vocab_size=type_vocab_size,
                        hidden_drop=hidden_drop, attn_drop=attn_drop,
                        hidden_act=hidden_act, ln_eps=ln_eps)

    def build(self) -> Model:
        c = self.cfg
        ids = Input(shape=(c["seq_len"],))
        seg = Input(shape=(c["seq_len"],))
        pos = Input(shape=(c["seq_len"],))
        mask = Input(shape=(c["seq_len"],))

        from analytics_zoo_tpu.pipeline.api.keras.layers.merge import Merge
        tok_e = Embedding(c["vocab"], c["hidden_size"],
                          init="normal")(ids)
        seg_e = Embedding(c["type_vocab_size"], c["hidden_size"],
                          init="normal")(seg)
        pos_e = Embedding(c["max_position_len"], c["hidden_size"],
                          init="normal")(pos)
        x = Merge(mode="sum")([tok_e, seg_e, pos_e])
        x = LayerNorm(epsilon=c["ln_eps"])(x)
        x = Dropout(c["hidden_drop"])(x)
        for _ in range(c["n_block"]):
            x = transformer_block(x, mask, c["hidden_size"], c["n_head"],
                                  c["intermediate_size"],
                                  dropout=c["attn_drop"],
                                  hidden_dropout=c["hidden_drop"],
                                  activation=c["hidden_act"],
                                  ln_eps=c["ln_eps"])
        seq_output = x
        from analytics_zoo_tpu.pipeline.api.keras.layers.core import Lambda
        first_tok = Lambda(lambda t: t[:, 0],
                           output_shape=(c["hidden_size"],))(x)
        pooled = Dense(c["hidden_size"], activation="tanh")(first_tok)
        return Model([ids, seg, pos, mask], [seq_output, pooled])


class TransformerLayer:
    """GPT-style decoder stack (pyzoo self_attention.py TransformerLayer
    :46): inputs [token_ids, position_ids], outputs [last block states,
    pooled first-token output].  ``bidirectional=False`` applies the
    causal mask (the reference's tril mask constant).

    As in the reference's default embedding, tokens and positions share
    ONE ``vocab``-row table: position ids are offset ids in
    ``[vocab - seq_len, vocab)`` (vocab = n_tokens + n_position_slots),
    and both lookups go through the same Embedding instance."""

    def __init__(self, n_block: int = 12, hidden_drop: float = 0.1,
                 attn_drop: float = 0.1, n_head: int = 12,
                 bidirectional: bool = False,
                 vocab: int = 40990, seq_len: int = 77,
                 hidden_size: int = 768, intermediate_size: int = 0):
        self.cfg = dict(n_block=n_block, hidden_drop=hidden_drop,
                        attn_drop=attn_drop, n_head=n_head,
                        bidirectional=bidirectional, vocab=vocab,
                        seq_len=seq_len, hidden_size=hidden_size,
                        intermediate_size=intermediate_size or
                        4 * hidden_size)

    @classmethod
    def init_with_default_embedding(cls, vocab: int = 40990,
                                    seq_len: int = 77, n_block: int = 12,
                                    hidden_drop: float = 0.1,
                                    attn_drop: float = 0.1,
                                    n_head: int = 12,
                                    bidirectional: bool = False,
                                    hidden_size: int = 768):
        return cls(n_block=n_block, hidden_drop=hidden_drop,
                   attn_drop=attn_drop, n_head=n_head,
                   bidirectional=bidirectional, vocab=vocab,
                   seq_len=seq_len, hidden_size=hidden_size)

    def build(self) -> Model:
        c = self.cfg
        ids = Input(shape=(c["seq_len"],))
        pos = Input(shape=(c["seq_len"],))
        from analytics_zoo_tpu.pipeline.api.keras.layers.merge import Merge
        shared = Embedding(c["vocab"], c["hidden_size"], init="normal")
        tok_e = shared(ids)
        pos_e = shared(pos)
        x = Merge(mode="sum")([tok_e, pos_e])
        x = Dropout(c["hidden_drop"])(x)
        for _ in range(c["n_block"]):
            x = transformer_block(x, None, c["hidden_size"], c["n_head"],
                                  c["intermediate_size"],
                                  dropout=c["attn_drop"],
                                  hidden_dropout=c["hidden_drop"],
                                  causal=not c["bidirectional"])
        from analytics_zoo_tpu.pipeline.api.keras.layers.core import Lambda
        first_tok = Lambda(lambda t: t[:, 0],
                           output_shape=(c["hidden_size"],))(x)
        pooled = Dense(c["hidden_size"], activation="tanh")(first_tok)
        return Model([ids, pos], [x, pooled])
