"""The ``smallthinker-21ba3b-instruct`` configuration as the program
builds it: ``layers.prerouted.prerouted_moe_decoder`` (a
vocabulary-sliced ``Embedding``, four ``PreRoutedDecoderLayer``s — a
softmax router over 64 experts reading the normed attention input,
``GroupedQueryAttention`` of 28 heads on 4 (global with no position
encoding in layer 0, a 4,096-key window with rotary in layers 1-3), and
a ``DroplessMoE`` of 16 of the 64 ReGLU experts under that routing —
recomputed in the backward pass with the flash kernels' results kept,
an ``RMSNorm``, and ``NextTokenLoss`` over its own untied head by chunks
of rows), compiled with Adam under the warm-up and a criterion that is
the mean of the model's output.

A record is one int32 row of ``seq_len`` ids below 37,984, one document.
The generator's second array (its position ids) is taken by the model
and not used: the layers count positions themselves."""

from __future__ import annotations

from typing import Dict


def build(cfg: Dict):
    import jax.numpy as jnp
    from analytics_zoo_tpu.pipeline.api.keras.layers.prerouted import (
        prerouted_moe_decoder)
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import (
        Adam, fixed, warmup_then)
    if (cfg["moe_primary_router_apply_softmax"], cfg["rope_scaling"],
            cfg["tie_word_embeddings"]) != (True, None, False):
        raise ValueError(
            "built for a softmax router, no rope scaling and an untied head")
    recompute = cfg["recompute"]
    model = prerouted_moe_decoder(
        seq_len=cfg["seq_len"], vocab_size=cfg["vocab_size_published"],
        vocab_held=tuple(cfg["vocab_held"]),
        hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_experts=cfg["moe_num_primary_experts_published"],
        experts_held=tuple(cfg["experts_held"]),
        top_k=cfg["moe_num_active_primary_experts"],
        expert_hidden=cfg["moe_ffn_hidden_size"],
        sliding_window=cfg["sliding_window_size"],
        sliding_window_layout=cfg["sliding_window_layout"],
        rope_layout=cfg["rope_layout"], rope_theta=cfg["rope_theta"],
        norm_topk_prob=cfg["norm_topk_prob"],
        norm_epsilon=cfg["rms_norm_eps"],
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
