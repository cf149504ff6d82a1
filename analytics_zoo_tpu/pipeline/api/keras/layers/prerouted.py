"""A decoder whose expert router is placed before attention, over
layers that mix windowed rotary attention with global attention that
has no position encoding (the SmallThinker family's layer: arXiv
2507.20984).

``PreRoutedDecoderLayer`` is one pre-norm block: the router reads the
layer's normed ATTENTION input, so the experts a token goes to are
known before attention runs (a deployment can fetch them meanwhile),
and the expert layer then works on its own normed input under that
routing::

    a = RMSNorm(h; g1)
    (gates, experts) = route(a)          softmax over top_k of a Wr
    h = h + attention(a)
    m = RMSNorm(h; g2)
    h = h + sum_{e in held} gate_e (relu(m Wg_e) * (m Wu_e)) Wd_e

``prerouted_moe_decoder`` builds the functional ``Model`` whose output
is each sequence's next-token loss over an untied, vocabulary-sliced
head.  A layer's attention follows the published layouts: where
``sliding_window_layout[l]`` is set, causal inside a band of
``sliding_window`` keys, else causal over all keys; where
``rope_layout[l]`` is set, rotary positions, else none.

Precision: matrix products take the compute dtype and accumulate in
float32; the router (its product at the highest precision), the
residual stream, the norms, the softmax and the loss are float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.pipeline.api.keras.layers.ssm import (
    PreNormDecoderLayer)


class PreRoutedDecoderLayer(PreNormDecoderLayer):
    """The module's block: ``PreNormDecoderLayer`` round a
    ``GroupedQueryAttention`` (read with the positions ``0 .. T - 1``)
    and a ``DroplessMoE`` routed from the attention's normed input, its
    router's product in float32 at the highest precision (a rounding
    that swaps the ``top_k``-th and the next expert changes which rows
    exist).  The router is ``ffn_router``."""

    def _attention_inputs(self, a):
        b, t, _ = a.shape
        return [a, jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))]

    def _attention_input_shape(self, input_shape):
        return [input_shape, tuple(input_shape[:-1])]

    def _routing(self, ffn_params, a) -> dict:
        from analytics_zoo_tpu.ops.fused import count_build
        count_build("moe_router", "attention_input")
        return {"routing": self.ffn.route(
            ffn_params["router"], a, precision=jax.lax.Precision.HIGHEST)}


def prerouted_moe_decoder(*, seq_len: int, vocab_size: int,
                          hidden_size: int, num_layers: int, n_head: int,
                          n_kv_head: int, head_dim: int, num_experts: int,
                          top_k: int, expert_hidden: int,
                          sliding_window: int, sliding_window_layout,
                          rope_layout, rope_theta: float,
                          norm_topk_prob: bool = True, experts_held=None,
                          vocab_held=None, norm_epsilon: float = 1e-6,
                          recompute: bool = False, loss_chunk_rows: int = 0,
                          extra_inputs: int = 0):
    """A pre-norm decoder of ``num_layers`` ``PreRoutedDecoderLayer``s
    (the published layers ``0 .. num_layers - 1``: layer ``l``'s kind is
    ``sliding_window_layout[l]`` and ``rope_layout[l]``) as a graph
    ``Model`` whose output is each sequence's next-token loss; train it
    under ``lambda y_true, y_pred: jnp.mean(y_pred)``.

    Grouped-query attention of ``n_head`` heads on ``n_kv_head`` K/V
    heads of ``head_dim``, no bias, no q/k norm; a softmax router over
    ``num_experts`` (float32 at the highest precision) sending
    ``top_k`` a token to ReGLU experts of ``expert_hidden``; no shared
    expert, no auxiliary loss.
    ``experts_held`` and ``vocab_held=(first, count)`` give this chip's
    slice of the experts and of a vocabulary-parallel embedding and
    UNTIED head.  ``recompute`` and ``loss_chunk_rows``:
    ``PreNormDecoderLayer``'s and ``NextTokenLoss``'s.  ``extra_inputs``
    further model inputs are taken and not used."""
    from analytics_zoo_tpu.ops.pallas_attention import (
        sliding_window as window_mask)
    from analytics_zoo_tpu.pipeline.api.keras.engine import Input
    from analytics_zoo_tpu.pipeline.api.keras.layers.attention import (
        GroupedQueryAttention)
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
        attention = GroupedQueryAttention(
            n_head, n_kv_head, head_dim,
            rope_theta=rope_theta if rope_layout[index] else None,
            qk_norm=False, norm_epsilon=norm_epsilon,
            mask=window_mask(sliding_window)
            if sliding_window_layout[index] else "causal")
        ffn = DroplessMoE(
            num_experts, expert_hidden, top_k=top_k,
            norm_topk_prob=norm_topk_prob, experts_held=experts_held,
            init="normal", activation="relu")
        h = PreRoutedDecoderLayer(attention, ffn, norm_epsilon,
                                  recompute=recompute)(h)
    loss = NextTokenLoss(first, loss_chunk_rows, head_units=count)(
        [RMSNorm(norm_epsilon)(h), ids])
    return Model([ids, *unused], loss)
