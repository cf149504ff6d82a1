"""The ``granite-4.0-h-micro`` configuration as the program builds it:
``layers.ssm.mamba2_hybrid_decoder`` (a vocabulary-sliced ``Embedding``
tied to the head, its vectors times ``embedding_multiplier``; ten
``PreNormDecoderLayer``s after ``layer_types[:10]`` — a ``Mamba2`` mixer
of 64 heads of 64 on one group of 128 states, chunks of 256, or, at index
5, ``GroupedQueryAttention`` of 32 heads on 8 of 64 with no position
encoding and ``attention_multiplier`` as the softmax scale — each with a
``GatedFeedForward`` of 8,192 and both branches times
``residual_multiplier``, recomputed in the backward pass with the
kernels' results kept; an ``RMSNorm``; and ``NextTokenLoss`` over the
tied table by chunks of rows, its logits divided by
``logits_scaling``), compiled with Adam under the warm-up and a
criterion that is the mean of the model's output.

A record is one int32 row of ``seq_len`` ids below 12,544, one document.
The generator's second array (its position ids) is taken by the model
and not used: no layer encodes positions."""

from __future__ import annotations

from typing import Dict


def build(cfg: Dict):
    import jax.numpy as jnp
    from analytics_zoo_tpu.pipeline.api.keras.layers.ssm import (
        mamba2_hybrid_decoder)
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import (
        Adam, fixed, warmup_then)
    if (cfg["position_embedding_type"], cfg["tie_word_embeddings"],
            cfg["attention_bias"], cfg["mamba_proj_bias"],
            cfg["mamba_conv_bias"], cfg["hidden_act"]) != (
                "nope", True, False, False, True, "silu"):
        raise ValueError(
            "built for no position encoding, a tied head, no projection "
            "bias, a convolution bias and silu")
    recompute = cfg["recompute"]
    inner = cfg["mamba_expand"] * cfg["hidden_size"]
    if inner != cfg["mamba_n_heads"] * cfg["mamba_d_head"]:
        raise ValueError("mamba_expand x hidden_size is not the heads' width")
    model = mamba2_hybrid_decoder(
        seq_len=cfg["seq_len"], vocab_size=cfg["vocab_size_published"],
        vocab_held=tuple(cfg["vocab_held"]),
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["shared_intermediate_size"],
        layer_types=cfg["layer_types"][:cfg["num_hidden_layers"]],
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"],
        mamba_heads=cfg["mamba_n_heads"], mamba_head_dim=cfg["mamba_d_head"],
        d_state=cfg["mamba_d_state"], n_groups=cfg["mamba_n_groups"],
        d_conv=cfg["mamba_d_conv"], chunk=cfg["mamba_chunk_size"],
        attention_scale=cfg["attention_multiplier"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=cfg["logits_scaling"],
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
