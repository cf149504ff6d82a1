"""FLOPs and bytes the ``smallthinker-21ba3b-instruct`` configuration
requires, from shapes and from the rows the router really sent to the
held experts.

A multiply-add is two operations.  One record is one sequence of T
tokens.  Per layer: the four projections of grouped-query attention
(q 28 heads, k and v 4 heads of 128, the output) and the router at every
position; attention's two products at 128 over the pairs the layer's
mask allows, for each of the 28 query heads (``T (T + 1) / 2`` under the
causal mask of a global layer; under the 4,096-key window of the others
``W (W + 1) / 2 + (T - W) W``); three products for every row routed to
an expert held here.  The untied head reads the T - 1 positions that
predict.  Backward is twice forward; what the recomputed layers, flash
attention and the expert layer compute a second time is not counted,
the embedding is a gather."""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from . import common


def _sizes(cfg: Dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["seq_len"])


def layer_pairs(cfg: Dict, layer: int) -> float:
    """(query, key) pairs of one sequence that layer ``layer`` (its
    published depth) reads: causal, or causal inside the window."""
    t = float(cfg["seq_len"])
    if not cfg["sliding_window_layout"][layer]:
        return t * (t + 1) / 2
    w = float(min(cfg["sliding_window_size"], cfg["seq_len"]))
    return w * (w + 1) / 2 + (t - w) * w


def allowed_pairs(cfg: Dict) -> float:
    """Pairs of one sequence over all the layers held."""
    return sum(layer_pairs(cfg, l) for l in range(cfg["num_hidden_layers"]))


def expected_expert_rows(cfg: Dict) -> float:
    """Rows a record routes to the held experts over all layers if the
    router were even: ``T x top_k x held / published`` a layer."""
    return (float(cfg["seq_len"]) * cfg["moe_num_active_primary_experts"]
            * cfg["experts_held"][1]
            / cfg["moe_num_primary_experts_published"]
            * cfg["num_hidden_layers"])


def forward_flops_per_record(cfg: Dict,
                             expert_rows: Optional[float] = None) -> float:
    """``expert_rows``: rows routed to the held experts, all layers
    together (the program's ``moe_rows_routed_total{held="1"}`` a
    record); default the even split."""
    d, h, kv, hd, t = _sizes(cfg)
    if expert_rows is None:
        expert_rows = expected_expert_rows(cfg)
    projections = 2.0 * (2 * d * h * hd + 2 * d * kv * hd) \
        + 2.0 * d * cfg["moe_num_primary_experts_published"]
    maps = 2.0 * allowed_pairs(cfg) * h * 2 * hd
    experts = expert_rows * 3 * 2.0 * d * cfg["moe_ffn_hidden_size"]
    head = 2.0 * (t - 1) * d * cfg["vocab_held"][1]
    return cfg["num_hidden_layers"] * projections * t + maps + experts \
        + head


def train_flops_per_record(cfg: Dict,
                           expert_rows: Optional[float] = None) -> float:
    return 3.0 * forward_flops_per_record(cfg, expert_rows)


def param_shapes(cfg: Dict) -> List[Tuple[int, ...]]:
    d, h, kv, hd, _ = _sizes(cfg)
    e, ids = cfg["experts_held"][1], cfg["vocab_held"][1]
    f = cfg["moe_ffn_hidden_size"]
    shapes: List[Tuple[int, ...]] = [(ids, d)]
    for _ in range(cfg["num_hidden_layers"]):
        shapes += [(d,), (d, h * hd), (d, kv * hd), (d, kv * hd),
                   (h * hd, d), (d,),
                   (d, cfg["moe_num_primary_experts_published"]),
                   (e, d, f), (e, d, f), (e, f, d)]
    shapes += [(d,), (d, ids)]
    return shapes


def optimizer_kernel_bytes_per_step(cfg: Dict) -> float:
    return common.optimizer_bytes(
        cfg["optimizer"]["kind"],
        common.kernel_leaf_elements(param_shapes(cfg)))


def attention_per_step(cfg: Dict) -> Tuple[float, float]:
    """(FLOPs, bytes) that attention under the layers' masks requires in
    one training step over all layers.  Forward: QK^T and PV at 128 over
    the allowed pairs of every query head.  Backward: four products of
    the same size (dV, dP, dQ, dK).  Bytes: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv; K and
    V have 4 heads (nothing is repeated)."""
    _, h, kv, hd, t = _sizes(cfg)
    b, layers = cfg["batch_size"], cfg["num_hidden_layers"]
    flops = 6.0 * 2.0 * b * allowed_pairs(cfg) * h * hd
    item = cfg["attention_io_itemsize"]
    q_tensor = float(t) * b * h * hd * item
    kv_tensor = float(t) * b * kv * hd * item
    return flops, layers * 6.0 * (q_tensor + kv_tensor)


def grouped_matmul_per_step(cfg: Dict,
                            expert_rows: float) -> Tuple[float, float]:
    """(FLOPs, bytes) the ReGLU experts' products require in one training
    step for ``expert_rows`` rows routed to the held experts (all layers
    together): gate, up and down, forward and the two backward products
    of each.  Bytes: each pass reads (or, for the weights' gradient,
    writes) every held expert's float32 matrix once, and reads and
    writes each row's operands once in the compute dtype."""
    d, f = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    flops = 3.0 * 3 * 2.0 * expert_rows * d * f
    weights = (3.0 * 3 * cfg["num_hidden_layers"] * cfg["experts_held"][1]
               * d * f * 4.0)
    rows = 3.0 * 3 * expert_rows * (d + f) * cfg["attention_io_itemsize"]
    return flops, weights + rows
