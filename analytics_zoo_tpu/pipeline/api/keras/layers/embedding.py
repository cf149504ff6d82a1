"""Embedding layers (ref: keras/layers/Embedding.scala,
SparseEmbedding.scala).

TPU note: embedding lookup is a gather from an HBM-resident table; for
model-parallel runs the table rows can be sharded on the ``model`` axis
and XLA turns the gather into an all-to-all — no custom code needed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops.dtypes import get_policy
from analytics_zoo_tpu.pipeline.api.keras.engine import Layer, Params


class Embedding(Layer):
    """Integer ids (B, T) -> vectors (B, T, D)."""

    def __init__(self, input_dim: int, output_dim: int, init="uniform",
                 W_regularizer=None, mask_zero: bool = False,
                 parallel_mode: str = None, vocab_held=None,
                 tie_head: bool = False, multiplier: float = 1.0,
                 **kwargs):
        """parallel_mode: None | "dim" — "dim" shards the embedding dim
        over the ``model`` axis (the gather stays local; downstream TP
        layers consume the sharded activations directly).

        vocab_held: ``(first, count)`` — this chip's slice of a
        vocabulary-parallel table of ``input_dim`` rows: the layer
        holds rows ``first .. first + count - 1`` only and gives zeros
        for every other id (what the other ranks would add is theirs to
        compute; no exchange is built here).  Default: the whole
        table.

        tie_head: the layer has a second output, the table itself
        (count, D), for a head that forms its logits from it
        (``layers.ssm.NextTokenLoss``): one leaf with two uses, whose
        gradient is the sum of both.

        multiplier: the vectors come out times this factor (Granite's
        ``embedding_multiplier``); the table handed to a tied head is
        the table itself."""
        super().__init__(**kwargs)
        self.vocab_first, self.vocab_count = (
            (0, int(input_dim)) if vocab_held is None
            else (int(vocab_held[0]), int(vocab_held[1])))
        if not 0 <= self.vocab_first \
                <= self.vocab_first + self.vocab_count <= int(input_dim):
            raise ValueError(
                f"vocab_held {vocab_held} outside 0..{input_dim}")
        self.input_dim = int(input_dim)
        self.output_dim = int(output_dim)
        self.kernel_init = init
        self.mask_zero = mask_zero
        self.W_regularizer = W_regularizer
        if parallel_mode not in (None, "dim"):
            raise ValueError("parallel_mode must be None|dim")
        self.parallel_mode = parallel_mode
        self.tie_head = bool(tie_head)
        self.multiplier = float(multiplier)

    def build(self, rng, input_shape) -> Params:
        from jax.sharding import PartitionSpec as P
        from analytics_zoo_tpu.parallel.mesh import MODEL_AXIS
        params: Params = {}
        self.add_weight(params, rng, "embeddings",
                        (self.vocab_count, self.output_dim),
                        init=self.kernel_init,
                        regularizer=self.W_regularizer)
        if self.parallel_mode == "dim":
            self.param_pspecs["embeddings"] = P(None, MODEL_AXIS)
        return params

    def call(self, params, x, training=False, rng=None):
        ids = x.astype(jnp.int32)
        if self.vocab_count != self.input_dim:
            local = ids - self.vocab_first
            held = (local >= 0) & (local < self.vocab_count)
            out = jnp.take(params["embeddings"],
                           jnp.where(held, local, 0), axis=0)
            out = jnp.where(held[..., None], out, 0)
        else:
            out = jnp.take(params["embeddings"], ids, axis=0)
        if self.mask_zero:
            out = out * (ids != 0)[..., None].astype(out.dtype)
        if self.multiplier != 1.0:
            out = out * self.multiplier
        return [out, params["embeddings"]] if self.tie_head else out

    def compute_output_shape(self, input_shape):
        out = tuple(input_shape) + (self.output_dim,)
        if self.tie_head:
            return [out, (self.vocab_count, self.output_dim)]
        return out


class WordEmbedding(Embedding):
    """Embedding initialised from pretrained vectors, optionally frozen
    (ref: keras/layers/WordEmbedding.scala — GloVe loading)."""

    def __init__(self, embedding_matrix, trainable: bool = False, **kwargs):
        import numpy as np
        mat = np.asarray(embedding_matrix)
        super().__init__(mat.shape[0], mat.shape[1], **kwargs)
        self._pretrained = mat
        self.trainable = trainable

    def build(self, rng, input_shape) -> Params:
        return {"embeddings": jnp.asarray(
            self._pretrained, get_policy().param_dtype)}

    def call(self, params, x, training=False, rng=None):
        emb = params["embeddings"]
        if not self.trainable:
            emb = jax.lax.stop_gradient(emb)
        return jnp.take(emb, x.astype(jnp.int32), axis=0)


class SparseEmbedding(Layer):
    """Combiner embedding over variable-length id lists
    (SparseEmbedding.scala, BigDL LookupTableSparse).  TPU-native shape
    contract: ids are a dense (B, T) int array padded with -1; the
    combiner ("sum" | "mean" | "sqrtn") reduces the valid rows to
    (B, D).  The reference's SparseTensor input becomes this static
    padded-dense form — dynamic shapes would block XLA tiling."""

    def __init__(self, input_dim: int, output_dim: int,
                 combiner: str = "sum", max_norm: float = -1.0,
                 init="uniform", W_regularizer=None, **kwargs):
        super().__init__(**kwargs)
        if combiner not in ("sum", "mean", "sqrtn"):
            raise ValueError("combiner must be sum|mean|sqrtn")
        self.input_dim = int(input_dim)
        self.output_dim = int(output_dim)
        self.combiner = combiner
        self.max_norm = float(max_norm)
        self.kernel_init = init
        self.W_regularizer = W_regularizer

    def build(self, rng, input_shape) -> Params:
        params: Params = {}
        self.add_weight(params, rng, "embeddings",
                        (self.input_dim, self.output_dim),
                        init=self.kernel_init,
                        regularizer=self.W_regularizer)
        return params

    def call(self, params, x, training=False, rng=None):
        ids = x.astype(jnp.int32)
        valid = (ids >= 0)
        rows = jnp.take(params["embeddings"], jnp.maximum(ids, 0), axis=0)
        if self.max_norm > 0:
            # per looked-up row (TF embedding_lookup semantics) — never
            # renormalise the whole table on the hot path
            norms = jnp.linalg.norm(rows, axis=-1, keepdims=True)
            rows = rows * jnp.minimum(1.0, self.max_norm /
                                      jnp.maximum(norms, 1e-12))
        rows = rows * valid[..., None].astype(rows.dtype)
        out = jnp.sum(rows, axis=-2)
        count = jnp.maximum(jnp.sum(valid, axis=-1, keepdims=True), 1)
        if self.combiner == "mean":
            out = out / count
        elif self.combiner == "sqrtn":
            out = out / jnp.sqrt(count.astype(out.dtype))
        return out

    def compute_output_shape(self, input_shape):
        return tuple(input_shape[:-1]) + (self.output_dim,)
