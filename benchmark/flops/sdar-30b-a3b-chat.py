"""FLOPs and bytes the ``sdar-30b-a3b-chat`` configuration requires,
from shapes and from the rows the router really sent to the held
experts.

A multiply-add is two operations.  One record is one sequence of L
tokens, run through the layers as 2 L positions (the noisy copy and the
clean one).  Per layer: the four attention projections and the router
over 2 L positions; attention's two products over the ``L^2 + L B``
(query, key) pairs the block-diffusion mask allows, for each of the
query heads; three products (gate, up, down) for every row routed to an
expert held here.  The head reads the L noisy positions only.  Backward
is twice forward; what flash attention and the expert layer recompute
in their backward passes is not counted, the embedding is a gather."""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from . import common


def _sizes(cfg: Dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["seq_len"])


def allowed_pairs(cfg: Dict) -> float:
    """(query, key) pairs a sequence's mask allows: a quarter of the
    dense ``(2 L)^2``, and L B more."""
    L, B = cfg["seq_len"], cfg["block_length"]
    return float(L) * L + float(L) * B


def expected_expert_rows(cfg: Dict) -> float:
    """Rows a record routes to the held experts over all layers if the
    router were even: ``2 L x top_k x held / published`` a layer."""
    return (2.0 * cfg["seq_len"] * cfg["num_experts_per_tok"]
            * cfg["experts_held"][1] / cfg["num_experts_published"]
            * cfg["num_hidden_layers"])


def forward_flops_per_record(cfg: Dict,
                             expert_rows: Optional[float] = None) -> float:
    """``expert_rows``: rows routed to the held experts, all layers
    together (the program's ``moe_rows_routed_total{held="1"}`` a
    record); default the even split."""
    d, h, hkv, hd, f, L = _sizes(cfg)
    if expert_rows is None:
        expert_rows = expected_expert_rows(cfg)
    positions = 2.0 * L
    dense = 2.0 * d * (2 * h * hd + 2 * hkv * hd) \
        + 2.0 * d * cfg["num_experts_published"]
    attention = 2.0 * 2.0 * allowed_pairs(cfg) * h * hd
    experts = expert_rows * 3 * 2.0 * d * f
    head = 2.0 * L * d * cfg["vocab_held"][1]
    return cfg["num_hidden_layers"] * (dense * positions + attention) \
        + experts + head


def train_flops_per_record(cfg: Dict,
                           expert_rows: Optional[float] = None) -> float:
    return 3.0 * forward_flops_per_record(cfg, expert_rows)


def param_shapes(cfg: Dict) -> List[Tuple[int, ...]]:
    d, h, hkv, hd, f, _ = _sizes(cfg)
    e, v = cfg["experts_held"][1], cfg["vocab_held"][1]
    shapes: List[Tuple[int, ...]] = [(v, d)]
    for _ in range(cfg["num_hidden_layers"]):
        shapes += [(d,), (d, h * hd), (d, hkv * hd), (d, hkv * hd),
                   (h * hd, d), (hd,), (hd,), (d,),
                   (d, cfg["num_experts_published"]),
                   (e, d, f), (e, d, f), (e, f, d)]
    shapes += [(d,), (d, v)]
    return shapes


def optimizer_kernel_bytes_per_step(cfg: Dict) -> float:
    return common.optimizer_bytes(
        cfg["optimizer"]["kind"],
        common.kernel_leaf_elements(param_shapes(cfg)))


def attention_per_step(cfg: Dict) -> Tuple[float, float]:
    """(FLOPs, bytes) that attention under the block-diffusion mask
    requires in one training step over all layers.  Forward: QK^T and PV
    over the allowed pairs of every query head.  Backward: four products
    of the same size (dV, dP, dQ, dK).  Bytes: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv; K and
    V have ``num_key_value_heads`` heads (nothing is repeated)."""
    _, h, hkv, hd, _, L = _sizes(cfg)
    b, layers = cfg["batch_size"], cfg["num_hidden_layers"]
    one = 2.0 * b * allowed_pairs(cfg) * h * hd       # one product
    item = cfg["attention_io_itemsize"]
    q_tensor = 2.0 * L * b * h * hd * item
    kv_tensor = 2.0 * L * b * hkv * hd * item
    return layers * 6.0 * one, layers * 6.0 * (q_tensor + kv_tensor)


def grouped_matmul_per_step(cfg: Dict,
                            expert_rows: float) -> Tuple[float, float]:
    """(FLOPs, bytes) the expert products require in one training step
    for ``expert_rows`` rows routed to the held experts (all layers
    together): gate, up and down, forward and the two backward products
    of each.  Bytes: each pass reads (or, for the weights' gradient,
    writes) every held expert's float32 matrix once, and reads and
    writes each row's operands once in the compute dtype."""
    d, _, _, _, f, _ = _sizes(cfg)
    flops = 3.0 * 3 * 2.0 * expert_rows * d * f
    weights = 3.0 * 3 * cfg["num_hidden_layers"] * cfg["experts_held"][1] \
        * d * f * 4.0
    rows = 3.0 * 3 * expert_rows * (d + f) * cfg["attention_io_itemsize"]
    return flops, weights + rows
