"""The ``phi-4-mini-flash-reasoning`` configuration as the program
builds it: ``layers.ssm.decoder_hybrid_decoder`` (a vocabulary-sliced
``Embedding`` that hands its table on, six ``HybridDecoderLayer``s at
the published depths the file lists, each a ``Mamba``, a
``DifferentialAttention`` or a ``GatedMemoryUnit`` under a
``GatedFeedForward``, recomputed in the backward pass, a ``LayerNorm``,
and ``NextTokenLoss`` over the tied table by chunks of rows), compiled
with Adam under the warm-up and a criterion that is the mean of the
model's output.

A record is one int32 row of ``seq_len`` ids below 25,008, one document.
The generator's second array (its position ids) is taken by the model
and not used: the model has no positional encoding."""

from __future__ import annotations

from typing import Dict


def build(cfg: Dict):
    import jax.numpy as jnp
    from analytics_zoo_tpu.pipeline.api.keras.layers.ssm import (
        decoder_hybrid_decoder)
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import (
        Adam, fixed, warmup_then)
    mamba, recompute = cfg["mamba"], cfg["recompute"]
    model = decoder_hybrid_decoder(
        seq_len=cfg["seq_len"], vocab_size=cfg["vocab_size_published"],
        vocab_held=tuple(cfg["vocab_held"]),
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_layers=cfg["num_hidden_layers_published"],
        layer_ids=tuple(cfg["layer_ids_published"]),
        d_inner=mamba["d_inner"], d_state=mamba["d_state"],
        d_conv=mamba["d_conv"], dt_rank=mamba["dt_rank"],
        sliding_window=cfg["sliding_window"],
        norm_epsilon=cfg["layer_norm_eps"],
        recompute=recompute["decoder_layers"],
        loss_chunk_rows=recompute["loss_chunk_rows"], extra_inputs=1)
    opt, sched = cfg["optimizer"], cfg["optimizer"]["schedule"]
    schedule = warmup_then(sched["base"], sched["warmup_iterations"],
                           fixed(sched["base"]))
    model.compile(Adam(lr=opt["learning_rate"], beta_1=opt["beta_1"],
                       beta_2=opt["beta_2"], epsilon=opt["epsilon"],
                       schedule=schedule),
                  lambda y_true, y_pred: jnp.mean(y_pred))
    return model


def input_spec(cfg: Dict) -> Dict:
    return {"kind": "tokens", "seq_len": cfg["seq_len"],
            "vocab": cfg["vocab_size"], "classes": 1}
