"""The ``kanana-2-30b-a3b-instruct-2601`` configuration as the program
builds it: ``layers.latent.latent_moe_decoder`` (a vocabulary-sliced
``Embedding``, six ``LatentDecoderLayer``s — ``LatentAttention`` under
an RMSNorm pre-norm, a ``GatedFeedForward`` in the first and a
``DroplessMoE`` with sigmoid scores, a selection bias, 16 of the 128
routed experts and the shared experts in the others — recomputed in the
backward pass with the flash kernels' results kept, an ``RMSNorm``, and
``NextTokenLoss`` over its own untied head by chunks of rows), compiled
with Adam under the warm-up and a criterion that is the mean of the
model's output.

A record is one int32 row of ``seq_len`` ids below 16,032, one document.
The generator's second array (its position ids) is taken by the model
and not used: the layers count positions themselves.

The selection bias is non-trained state, which the harness leaves as the
model made it: ``build`` sets it to the reference's own draw
(``reference.selection_bias``: from the configuration's seed, not from
``--seed``)."""

from __future__ import annotations

from typing import Dict


def build(cfg: Dict):
    import jax.numpy as jnp
    from analytics_zoo_tpu.pipeline.api.keras.layers.latent import (
        latent_moe_decoder)
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import (
        Adam, fixed, warmup_then)
    if (cfg["n_group"], cfg["topk_group"], cfg["moe_layer_freq"],
            cfg["q_lora_rank"], cfg["rope_scaling"]) != (1, 1, 1, None, None):
        raise ValueError(
            "built for one expert group, every later layer sparse, no "
            "query latent and no rope scaling")
    recompute = cfg["recompute"]
    model = latent_moe_decoder(
        seq_len=cfg["seq_len"], vocab_size=cfg["vocab_size_published"],
        vocab_held=tuple(cfg["vocab_held"]),
        hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        intermediate_size=cfg["intermediate_size"],
        first_dense_layers=cfg["first_k_dense_replace"],
        num_experts=cfg["n_routed_experts_published"],
        experts_held=tuple(cfg["experts_held"]),
        top_k=cfg["num_experts_per_tok"],
        expert_hidden=cfg["moe_intermediate_size"],
        shared_hidden=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"],
        rope_theta=cfg["rope_theta"], norm_epsilon=cfg["rms_norm_eps"],
        recompute=recompute["decoder_layers"],
        loss_chunk_rows=recompute["loss_chunk_rows"], extra_inputs=1)
    opt, sched = cfg["optimizer"], cfg["optimizer"]["schedule"]
    schedule = warmup_then(sched["base"], sched["warmup_iterations"],
                           fixed(sched["base"]))
    model.compile(Adam(lr=opt["learning_rate"], beta_1=opt["beta_1"],
                       beta_2=opt["beta_2"], epsilon=opt["epsilon"],
                       schedule=schedule),
                  lambda y_true, y_pred: jnp.mean(y_pred))
    variables = model.get_variables()
    state = dict(variables["state"])
    from benchmark import harness
    drawn = harness.load_module("reference", cfg["name"]).selection_bias(cfg)
    sparse = [name for name in state if "selection_bias" in state[name]]
    # both in depth order: the layers as created, the draws as l<depth>
    for name, bias in zip(sparse, drawn.values(), strict=True):
        state[name] = {**state[name], "selection_bias": bias}
    model.set_variables({"params": variables["params"], "state": state})
    return model


def input_spec(cfg: Dict) -> Dict:
    return {"kind": "tokens", "seq_len": cfg["seq_len"],
            "vocab": cfg["vocab_size"], "classes": 1}
