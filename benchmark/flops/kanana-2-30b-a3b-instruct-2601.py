"""FLOPs and bytes the ``kanana-2-30b-a3b-instruct-2601`` configuration
requires, from shapes and from the rows the router really sent to the
held experts.

A multiply-add is two operations.  One record is one sequence of T
tokens.  Per layer: the latent attention's five products (query,
down-projection, up-projection, output; the norm of the latent is no
product) at every position; attention's two products over the ``T (T +
1) / 2`` causal pairs of each of the 32 heads, QK^T at 192 (128 of the
head's own key and 64 of the shared rotary key) and PV at 128; then
either the dense gated MLP (layer 0) or the router, the shared experts
and three products for every row routed to an expert held here.  The
untied head reads the T - 1 positions that predict.  Backward is twice
forward; what the recomputed layers, flash attention and the expert
layer compute a second time is not counted, the embedding is a gather."""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from . import common


def _sizes(cfg: Dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["seq_len"])


def _layers(cfg: Dict) -> Tuple[int, int]:
    """(dense layers, sparse layers)."""
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def causal_pairs(cfg: Dict) -> float:
    """(query, key) pairs of one sequence under the causal mask."""
    t = float(cfg["seq_len"])
    return t * (t + 1) / 2


def expected_expert_rows(cfg: Dict) -> float:
    """Rows a record routes to the held experts over all sparse layers
    if the router were even: ``T x top_k x held / published`` a layer."""
    return (float(cfg["seq_len"]) * cfg["num_experts_per_tok"]
            * cfg["experts_held"][1] / cfg["n_routed_experts_published"]
            * _layers(cfg)[1])


def forward_flops_per_record(cfg: Dict,
                             expert_rows: Optional[float] = None) -> float:
    """``expert_rows``: rows routed to the held experts, all layers
    together (the program's ``moe_rows_routed_total{held="1"}`` a
    record); default the even split."""
    d, h, n, r, v, rank, t = _sizes(cfg)
    if expert_rows is None:
        expert_rows = expected_expert_rows(cfg)
    dense_layers, sparse_layers = _layers(cfg)
    f = cfg["moe_intermediate_size"]
    attention_products = 2.0 * (d * h * (n + r) + d * (rank + r)
                                + rank * h * (n + v) + h * v * d)
    maps = 2.0 * causal_pairs(cfg) * h * (n + r + v)
    mlp = 2.0 * 3 * d * cfg["intermediate_size"]
    sparse = 2.0 * d * cfg["n_routed_experts_published"] \
        + 2.0 * 3 * d * cfg["n_shared_experts"] * f
    experts = expert_rows * 3 * 2.0 * d * f
    head = 2.0 * (t - 1) * d * cfg["vocab_held"][1]
    return cfg["num_hidden_layers"] * (attention_products * t + maps) \
        + dense_layers * mlp * t + sparse_layers * sparse * t \
        + experts + head


def train_flops_per_record(cfg: Dict,
                           expert_rows: Optional[float] = None) -> float:
    return 3.0 * forward_flops_per_record(cfg, expert_rows)


def param_shapes(cfg: Dict) -> List[Tuple[int, ...]]:
    d, h, n, r, v, rank, _ = _sizes(cfg)
    e, ids = cfg["experts_held"][1], cfg["vocab_held"][1]
    f = cfg["moe_intermediate_size"]
    shared = cfg["n_shared_experts"] * f
    shapes: List[Tuple[int, ...]] = [(ids, d)]
    for layer in range(cfg["num_hidden_layers"]):
        shapes += [(d,), (d, h * (n + r)), (d, rank + r), (rank,),
                   (rank, h * (n + v)), (h * v, d), (d,)]
        if layer >= cfg["first_k_dense_replace"]:
            shapes += [(d, cfg["n_routed_experts_published"]),
                       (e, d, f), (e, d, f), (e, f, d),
                       (d, 2 * shared), (shared, d)]
        else:
            shapes += [(d, 2 * cfg["intermediate_size"]),
                       (cfg["intermediate_size"], d)]
    shapes += [(d,), (d, ids)]
    return shapes


def optimizer_kernel_bytes_per_step(cfg: Dict) -> float:
    return common.optimizer_bytes(
        cfg["optimizer"]["kind"],
        common.kernel_leaf_elements(param_shapes(cfg)))


def attention_per_step(cfg: Dict) -> Tuple[float, float]:
    """(FLOPs, bytes) that latent attention requires in one training
    step over all layers.  Forward: QK^T at 192 and PV at 128 over the
    causal pairs of every head.  Backward: dP and dV at 128, dQ and dK
    at 192.  Bytes: forward reads q_nope, q_pe, k_nope, v and the ONE
    k_pe and writes o; backward reads those, o and do and writes the
    five gradients (dk_pe once, summed over the heads)."""
    _, h, n, r, v, _, t = _sizes(cfg)
    b, layers = cfg["batch_size"], cfg["num_hidden_layers"]
    flops = 2.0 * b * causal_pairs(cfg) * h * 3 * (n + r + v)
    item = cfg["attention_io_itemsize"]
    per_head = float(t) * b * h * item
    q_side, k_side = per_head * (n + r), per_head * (n + v) + t * b * r * item
    out = per_head * v
    return (layers * flops,
            layers * ((q_side + k_side + out)
                      + (q_side + k_side + 2 * out) + (q_side + k_side)))


def grouped_matmul_per_step(cfg: Dict,
                            expert_rows: float) -> Tuple[float, float]:
    """(FLOPs, bytes) the routed experts' products require in one
    training step for ``expert_rows`` rows routed to the held experts
    (all layers together): gate, up and down, forward and the two
    backward products of each.  Bytes: each pass reads (or, for the
    weights' gradient, writes) every held expert's float32 matrix once,
    and reads and writes each row's operands once in the compute
    dtype."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = 3.0 * 3 * 2.0 * expert_rows * d * f
    weights = 3.0 * 3 * _layers(cfg)[1] * cfg["experts_held"][1] * d * f * 4.0
    rows = 3.0 * 3 * expert_rows * (d + f) * cfg["attention_io_itemsize"]
    return flops, weights + rows
