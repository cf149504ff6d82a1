"""The ``openai-gpt`` configuration as the program builds it: the same
``Embedding`` and ``transformer_block`` calls that the repo's
``TransformerLayer`` makes (one table for tokens and positions, causal
blocks with no mask input, so each reaches ``flash_attention``), then
the paper's classifier: a ``Dense`` on the LAST token's final state.
``TransformerLayer``'s own ``pooled`` output is the first token, which
under the causal mask has seen one token only."""

from __future__ import annotations

from typing import Dict


def build(cfg: Dict):
    from analytics_zoo_tpu.pipeline.api.keras import Input, Model
    from analytics_zoo_tpu.pipeline.api.keras.layers import (
        Dense, Dropout, Embedding, Lambda, Merge)
    from analytics_zoo_tpu.pipeline.api.keras.layers.attention import (
        transformer_block)
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import (
        Adam, fixed, warmup_then)
    seq, width = cfg["n_positions"], cfg["n_embd"]
    ids, pos = Input(shape=(seq,)), Input(shape=(seq,))
    shared = Embedding(cfg["vocab_size"] + cfg["n_positions"], width,
                       init="normal")
    x = Merge(mode="sum")([shared(ids), shared(pos)])
    x = Dropout(cfg["embd_pdrop"])(x)
    for _ in range(cfg["n_layer"]):
        x = transformer_block(
            x, None, width, cfg["n_head"], cfg["n_inner"],
            dropout=cfg["attn_pdrop"], hidden_dropout=cfg["resid_pdrop"],
            causal=True, activation=cfg["afn"],
            ln_eps=cfg["layer_norm_epsilon"])
    last = Lambda(lambda t: t[:, -1], output_shape=(width,))(x)
    model = Model([ids, pos], Dense(cfg["num_classes"])(last))
    opt, sched = cfg["optimizer"], cfg["optimizer"]["schedule"]
    schedule = warmup_then(sched["base"], sched["warmup_iterations"],
                           fixed(sched["base"]))
    model.compile(Adam(lr=opt["learning_rate"], beta_1=opt["beta_1"],
                       beta_2=opt["beta_2"], epsilon=opt["epsilon"],
                       schedule=schedule), cfg["loss"])
    return model


def input_spec(cfg: Dict) -> Dict:
    return {"kind": "tokens", "seq_len": cfg["n_positions"],
            "vocab": cfg["vocab_size"], "classes": cfg["num_classes"]}
