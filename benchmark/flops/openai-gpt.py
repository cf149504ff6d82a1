"""FLOPs and bytes the ``openai-gpt`` configuration requires, from
shapes.

A multiply-add is two operations.  Per token and block: the QKV, output
and two feed-forward products, and causal attention's two products over
the (T + 1) / 2 keys a token sees on average.  The embedding is a
gather and the classifier reads one token a sequence: both left out.
Backward is twice forward; what flash attention recomputes in its
backward pass is not counted."""

from __future__ import annotations

from typing import Dict, List, Tuple

from . import common


def forward_flops_per_record(cfg: Dict) -> float:
    d, ffn, t = cfg["n_embd"], cfg["n_inner"], cfg["n_positions"]
    dense = 2.0 * d * 3 * d + 2.0 * d * d + 2.0 * 2 * d * ffn
    attn = 2.0 * 2 * d * (t + 1) / 2.0
    return cfg["n_layer"] * (dense + attn) * t \
        + 2.0 * d * cfg["num_classes"]


def train_flops_per_record(cfg: Dict) -> float:
    return 3.0 * forward_flops_per_record(cfg)


def param_shapes(cfg: Dict) -> List[Tuple[int, ...]]:
    d, ffn = cfg["n_embd"], cfg["n_inner"]
    shapes: List[Tuple[int, ...]] = [(cfg["vocab_size"] + cfg["n_positions"], d)]
    for _ in range(cfg["n_layer"]):
        shapes += [(d, 3 * d), (3 * d,), (d, d), (d,), (d,), (d,),
                   (d, ffn), (ffn,), (ffn, d), (d,), (d,), (d,)]
    shapes += [(d, cfg["num_classes"]), (cfg["num_classes"],)]
    return shapes


def optimizer_kernel_bytes_per_step(cfg: Dict) -> float:
    return common.optimizer_bytes(
        cfg["optimizer"]["kind"],
        common.kernel_leaf_elements(param_shapes(cfg)))


def attention_per_step(cfg: Dict) -> Tuple[float, float]:
    """(FLOPs, bytes) that causal attention requires in one training
    step, forward and backward, over all blocks.  Forward: QK^T and PV
    over the causal half.  Backward: four products of the same size
    (dV, dP, dQ, dK).  Bytes: forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv, at the width
    the program hands the kernel."""
    d, t, b = cfg["n_embd"], cfg["n_positions"], cfg["batch_size"]
    one = 2.0 * b * t * d * (t + 1) / 2.0        # one causal product
    flops = cfg["n_layer"] * 6.0 * one
    tensor = float(b * t * d * cfg["attention_io_itemsize"])
    return flops, cfg["n_layer"] * 12.0 * tensor
