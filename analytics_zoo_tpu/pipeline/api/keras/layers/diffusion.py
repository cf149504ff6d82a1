"""Block-diffusion fine-tuning: the noise a training step applies to a
sequence, and the loss it is trained under.

A block-diffusion language model generates a block of ``block`` tokens
at a time by denoising it, given the clean blocks before it.  Training
runs every sequence through the layers twice over, a noisy copy ``xt``
beside the clean one ``x0``, under the mask
``ops.pallas_attention.block_diffusion`` (``GroupedQueryAttention``
takes it), and trains the logits at the masked positions of the noisy
copy to predict those positions (no shift).

The target is an input of the model, so the model's output can be the
loss itself, one value a sequence, trained under a criterion that is its
mean: ``model.compile(optimizer, lambda y_true, y_pred:
jnp.mean(y_pred))`` (``objectives.get`` takes a callable; ``y_true`` is
ignored).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.pipeline.api.keras.engine import Layer


def block_diffusion_noise(x0, u, s, block: int, mask_id: int, t_min: float):
    """The rule, from draws in [0, 1): ``x0`` (B, L) ids, ``u`` (B, L)
    one draw a position, ``s`` (B, L / block) one draw a block.  Block
    ``b`` is noised at level ``t_b = t_min + (1 - t_min) s_b``; position
    ``i`` of it is masked iff ``u_i < t_b``.  Returns ``(xt, masked,
    t)``, ``t`` the level of each position's block, (B, L)."""
    t = jnp.repeat(t_min + (1.0 - t_min) * s, block, axis=1)
    masked = u < t
    return jnp.where(masked, mask_id, x0), masked, t


class BlockDiffusionNoise(Layer):
    """Builds a training step's inputs from a batch of sequences.

    Outputs ``[tokens, positions, targets, weights]``: ``tokens``
    (B, 2 L) the noisy copy followed by the clean one, ``positions``
    (B, 2 L) the position ids ``[0..L-1 ; 0..L-1]``, ``targets`` (B, L)
    the clean ids and ``weights`` (B, L) float32 the loss weight of each
    position: ``1 / (t L)`` where it is masked (the linear schedule),
    else 0.

    ``draws="rng"`` (a user's job): the input is the ids (B, L) and the
    noise comes from the layer's ``rng``, fresh every step (the clean
    copy passes unnoised when not training).  ``draws="record"`` (a data
    set that keeps each sequence with the noise drawn for it, so that a
    step is a function of its rows alone): the input is (B, 2 L + L /
    block) integers, the ids, then one integer a position and one a
    block, each read as ``integer / draw_range``."""

    def __init__(self, seq_len: int, block: int, mask_id: int,
                 t_min: float = 1e-3, draws: str = "rng",
                 draw_range: int = 0, **kwargs):
        super().__init__(**kwargs)
        if seq_len % block:
            raise ValueError(f"block {block} must divide {seq_len}")
        if draws not in ("rng", "record") or \
                (draws == "record" and draw_range <= 0):
            raise ValueError("draws is 'rng', or 'record' with a "
                             "positive draw_range")
        self.seq_len, self.block = int(seq_len), int(block)
        self.mask_id, self.t_min = int(mask_id), float(t_min)
        self.draws, self.draw_range = draws, int(draw_range)

    def record_len(self) -> int:
        L = self.seq_len
        return 2 * L + L // self.block if self.draws == "record" else L

    def compute_output_shape(self, input_shape):
        b, L = input_shape[0], self.seq_len
        return [(b, 2 * L), (b, 2 * L), (b, L), (b, L)]

    def call(self, params, x, training=False, rng=None):
        L, nb = self.seq_len, self.seq_len // self.block
        if x.shape[1] != self.record_len():
            raise ValueError(
                f"{self.name} takes rows of {self.record_len()}, "
                f"got {x.shape[1]}")
        x = x.astype(jnp.int32)
        x0 = x[:, :L]
        if self.draws == "record":
            # a multiplication by a constant rounds the same wherever it
            # is compiled (a division need not)
            scale = jnp.float32(1.0 / self.draw_range)
            u = x[:, L:2 * L].astype(jnp.float32) * scale
            s = x[:, 2 * L:].astype(jnp.float32) * scale
        elif training:
            if rng is None:
                raise ValueError(f"{self.name} needs rng when training")
            ku, ks = jax.random.split(rng)
            u = jax.random.uniform(ku, x0.shape, jnp.float32)
            s = jax.random.uniform(ks, (x0.shape[0], nb), jnp.float32)
        else:
            u = jnp.ones(x0.shape, jnp.float32)
            s = jnp.zeros((x0.shape[0], nb), jnp.float32)
        xt, masked, t = block_diffusion_noise(
            x0, u, s, self.block, self.mask_id, self.t_min)
        positions = jnp.broadcast_to(
            jnp.tile(jnp.arange(L, dtype=jnp.int32), 2), (x.shape[0], 2 * L))
        weights = jnp.where(masked, 1.0 / (t * L), 0.0)
        return [jnp.concatenate([xt, x0], axis=1), positions, x0, weights]


class BlockDiffusionLoss(Layer):
    """The loss head: inputs ``[logits (B, L, V), targets (B, L),
    weights (B, L), aux_1 (B,), ...]`` -> (B,): each sequence's
    ``sum_i weights_i * CE(logits_i, targets_i)`` in float32, plus
    ``aux_coef`` times the sum of the auxiliary terms (the expert
    layers' load-balance terms).  Over a slice of the vocabulary that
    starts at id ``vocab_first``, logit ``j`` is id ``vocab_first + j``."""

    def __init__(self, aux_coef: float = 0.0, vocab_first: int = 0,
                 **kwargs):
        super().__init__(**kwargs)
        self.aux_coef = float(aux_coef)
        self.vocab_first = int(vocab_first)

    def compute_output_shape(self, input_shape):
        return (input_shape[0][0],)

    def call(self, params, inputs, training=False, rng=None):
        logits, targets, weights, *aux = inputs
        lsm = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(
            lsm, (targets.astype(jnp.int32) - self.vocab_first)[..., None],
            axis=-1)[..., 0]
        loss = -jnp.sum(weights * ll, axis=-1)
        if aux:
            loss = loss + self.aux_coef * sum(aux)
        return loss


def block_diffusion_decoder(*, seq_len: int, block: int, vocab_size: int,
                            hidden_size: int, num_layers: int,
                            n_head: int, n_kv_head: int, head_dim: int,
                            num_experts: int, top_k: int,
                            expert_hidden: int, experts_held=None,
                            vocab_held=None, rope_theta: float = 1e6,
                            norm_epsilon: float = 1e-6,
                            norm_topk_prob: bool = True,
                            t_min: float = 1e-3, aux_coef: float = 1e-3,
                            draws: str = "rng", draw_range: int = 0,
                            extra_inputs: int = 0):
    """A pre-norm decoder of ``GroupedQueryAttention`` and
    ``DroplessMoE`` blocks under the block-diffusion objective, as a
    graph ``Model`` whose output is each sequence's loss (see the
    module's docstring for the criterion).

    The LAST id of the vocabulary held is the mask id; data ids lie
    below it.  ``vocab_held=(first, count)`` and ``experts_held`` give
    this chip's slice of a vocabulary-parallel embedding and head and of
    the experts (a sliced vocabulary is a smaller vocabulary: logits and
    loss are over the slice).  ``extra_inputs`` further model inputs are
    taken and not used (a data set whose records carry more arrays than
    the ids)."""
    from analytics_zoo_tpu.pipeline.api.keras.engine import Input
    from analytics_zoo_tpu.pipeline.api.keras.layers.attention import (
        GroupedQueryAttention)
    from analytics_zoo_tpu.pipeline.api.keras.layers.core import (
        Dense, Lambda)
    from analytics_zoo_tpu.pipeline.api.keras.layers.embedding import (
        Embedding)
    from analytics_zoo_tpu.pipeline.api.keras.layers.merge import Merge
    from analytics_zoo_tpu.pipeline.api.keras.layers.moe import DroplessMoE
    from analytics_zoo_tpu.pipeline.api.keras.layers.normalization import (
        RMSNorm)
    from analytics_zoo_tpu.pipeline.api.keras.topology import Model
    from analytics_zoo_tpu.ops.pallas_attention import block_diffusion

    first, count = vocab_held or (0, vocab_size)
    noise = BlockDiffusionNoise(seq_len, block, mask_id=first + count - 1,
                                t_min=t_min, draws=draws,
                                draw_range=draw_range)
    rows = Input(shape=(noise.record_len(),))
    unused = [Input(shape=(noise.record_len(),))
              for _ in range(extra_inputs)]
    tokens, positions, targets, weights = noise(rows)
    h = Embedding(vocab_size, hidden_size, init="normal",
                  vocab_held=vocab_held)(tokens)
    mask, aux = block_diffusion(seq_len, block), []
    for _ in range(num_layers):
        a = GroupedQueryAttention(
            n_head, n_kv_head, head_dim, rope_theta=rope_theta,
            norm_epsilon=norm_epsilon, mask=mask)(
                [RMSNorm(norm_epsilon)(h), positions])
        h = Merge(mode="sum")([h, a])
        y, term = DroplessMoE(
            num_experts, expert_hidden, top_k=top_k,
            norm_topk_prob=norm_topk_prob, experts_held=experts_held)(
                RMSNorm(norm_epsilon)(h))
        h = Merge(mode="sum")([h, y])
        aux.append(term)
    # logits only where the loss reads them: the noisy half
    noisy = Lambda(lambda t: t[:, :seq_len],
                   output_shape=(seq_len, hidden_size))(h)
    logits = Dense(count, bias=False)(RMSNorm(norm_epsilon)(noisy))
    loss = BlockDiffusionLoss(aux_coef, vocab_first=first)(
        [logits, targets, weights, *aux])
    return Model([rows, *unused], loss)
