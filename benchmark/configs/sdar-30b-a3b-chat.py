"""The ``sdar-30b-a3b-chat`` configuration as the program builds it:
``layers.diffusion.block_diffusion_decoder`` (``BlockDiffusionNoise``
from the record's own draws, a vocabulary-sliced ``Embedding``, pre-norm
blocks of ``GroupedQueryAttention`` under the block-diffusion mask and
``DroplessMoE`` holding 16 of the 128 experts, ``RMSNorm``, the untied
head over the slice, ``BlockDiffusionLoss``), compiled with Adam under
the warm-up and a criterion that is the mean of the model's output.

A record is one int32 row of ``2 L + L / B`` ids below 18,991: the
sequence, then one draw a position and one a block (each read as
``id / 18991``), so that a step is a function of the seed's weights and
rows alone and the plain reference can follow it.  The generator's
second array (its position ids) is taken by the model and not used."""

from __future__ import annotations

from typing import Dict


def record_len(cfg: Dict) -> int:
    return 2 * cfg["seq_len"] + cfg["seq_len"] // cfg["block_length"]


def build(cfg: Dict):
    import jax.numpy as jnp
    from analytics_zoo_tpu.pipeline.api.keras.layers.diffusion import (
        block_diffusion_decoder)
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import (
        Adam, fixed, warmup_then)
    model = block_diffusion_decoder(
        seq_len=cfg["seq_len"], block=cfg["block_length"],
        vocab_size=cfg["vocab_size_published"],
        vocab_held=tuple(cfg["vocab_held"]),
        hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_experts=cfg["num_experts_published"],
        experts_held=tuple(cfg["experts_held"]),
        top_k=cfg["num_experts_per_tok"],
        expert_hidden=cfg["moe_intermediate_size"],
        rope_theta=cfg["rope_theta"], norm_epsilon=cfg["rms_norm_eps"],
        norm_topk_prob=cfg["norm_topk_prob"], t_min=cfg["t_min"],
        aux_coef=cfg["router_aux_loss_coef"], draws="record",
        draw_range=cfg["vocab_size"] - 1, extra_inputs=1)
    opt, sched = cfg["optimizer"], cfg["optimizer"]["schedule"]
    schedule = warmup_then(sched["base"], sched["warmup_iterations"],
                           fixed(sched["base"]))
    model.compile(Adam(lr=opt["learning_rate"], beta_1=opt["beta_1"],
                       beta_2=opt["beta_2"], epsilon=opt["epsilon"],
                       schedule=schedule),
                  lambda y_true, y_pred: jnp.mean(y_pred))
    return model


def input_spec(cfg: Dict) -> Dict:
    return {"kind": "tokens", "seq_len": record_len(cfg),
            "vocab": cfg["vocab_size"] - 1, "classes": 1}
