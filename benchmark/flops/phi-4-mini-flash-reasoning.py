"""FLOPs and bytes the ``phi-4-mini-flash-reasoning`` configuration
requires, from shapes.

A multiply-add is two operations.  One record is one sequence of T
positions through the layers the file lists.  Per layer: the gated MLP's
two products; a Mamba layer's four projections, its convolution and the
scan's seven operations a (position, channel, state); a differential
attention layer's projections and its two maps over the (query, key)
pairs its mask allows, QK^T at the head's 64 and PV at the pair's 128,
for each of the query heads; a gated memory unit's two products.  The
tied head reads every position.  Backward is twice forward; what the
layers, flash attention, the scan and the loss recompute in their
backward passes is not counted, the embedding is a gather."""

from __future__ import annotations

from typing import Dict, List, Tuple

from . import common

SCAN_OPS = 7.0   # exp(dt A): 2; s = decay s + dt x B: 3; y += s C: 2


def _sizes(cfg: Dict):
    m = cfg["mamba"]
    return (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], m["d_inner"], m["d_state"], m["d_conv"],
            m["dt_rank"], cfg["seq_len"])


def layer_kinds(cfg: Dict) -> List[str]:
    """``mamba`` | ``window_attention`` | ``full_attention`` |
    ``cross_attention`` | ``memory_unit`` of each layer held."""
    half = cfg["num_hidden_layers_published"] // 2
    kinds = []
    for l in cfg["layer_ids_published"]:
        if l % 2 == 0:
            kinds.append("mamba" if l <= half else "memory_unit")
        else:
            kinds.append("window_attention" if l < half else
                         "full_attention" if l == half + 1
                         else "cross_attention")
    return kinds


def allowed_pairs(cfg: Dict, kind: str) -> float:
    """(query, key) pairs a sequence's mask allows."""
    t, w = cfg["seq_len"], min(cfg["sliding_window"], cfg["seq_len"])
    if kind == "window_attention":
        return w * (w + 1) / 2.0 + float(t - w) * w
    return t * (t + 1) / 2.0


def attention_maps_flops(cfg: Dict, kind: str) -> float:
    """One forward pass of a layer's two maps, every query head."""
    _, _, h, _, hd, *_ = _sizes(cfg)
    return 2.0 * allowed_pairs(cfg, kind) * h * (hd + 2 * hd)


def forward_flops_per_record(cfg: Dict) -> float:
    d, ff, h, hkv, hd, c, n, kc, r, t = _sizes(cfg)
    total = 0.0
    for kind in layer_kinds(cfg):
        total += 2.0 * t * d * 2 * ff + 2.0 * t * ff * d
        if kind == "mamba":
            total += 2.0 * t * (d * 2 * c + c * (r + 2 * n) + r * c + c * d)
            total += 2.0 * t * c * kc + SCAN_OPS * t * c * n
        elif kind == "memory_unit":
            total += 2.0 * t * 2 * d * c
        else:
            width = h * hd if kind == "cross_attention" \
                else (h + 2 * hkv) * hd
            total += 2.0 * t * d * (width + h * hd)
            total += attention_maps_flops(cfg, kind)
    return total + 2.0 * t * d * cfg["vocab_held"][1]


def train_flops_per_record(cfg: Dict) -> float:
    return 3.0 * forward_flops_per_record(cfg)


def param_shapes(cfg: Dict) -> List[Tuple[int, ...]]:
    d, ff, h, hkv, hd, c, n, kc, r, _ = _sizes(cfg)
    shapes: List[Tuple[int, ...]] = [(cfg["vocab_held"][1], d)]
    for kind in layer_kinds(cfg):
        shapes += [(d,), (d,)]
        if kind == "mamba":
            shapes += [(d, 2 * c), (kc, c), (c,), (c, r + 2 * n), (r, c),
                       (c,), (c, n), (c,), (c, d)]
        elif kind == "memory_unit":
            shapes += [(d, c), (c, d)]
        else:
            width = h * hd if kind == "cross_attention" \
                else (h + 2 * hkv) * hd
            shapes += [(d, width), (width,), (hd,), (hd,), (hd,), (hd,),
                       (2 * hd,), (h * hd, d), (d,)]
        shapes += [(d,), (d,), (d, 2 * ff), (ff, d)]
    return shapes + [(d,), (d,)]


def optimizer_kernel_bytes_per_step(cfg: Dict) -> float:
    return common.optimizer_bytes(
        cfg["optimizer"]["kind"],
        common.kernel_leaf_elements(param_shapes(cfg)))


def attention_per_step(cfg: Dict) -> Tuple[float, float]:
    """(FLOPs, bytes) the three attention layers' maps require in one
    training step.  Forward: QK^T and PV over the allowed pairs of every
    query head; backward: four products of the same sizes (dV, dP at the
    pair's 128; dQ, dK at the head's 64).  Bytes: forward reads q, k, v
    and writes the two maps of every pair (twice q's width); backward
    reads q, k, v, the maps and their cotangent and writes dq, dk, dv;
    K and V have ``num_key_value_heads`` heads (nothing is repeated, V
    is not copied for the second map)."""
    _, _, h, hkv, hd, *_, t = _sizes(cfg)
    b, item = cfg["batch_size"], cfg["attention_io_itemsize"]
    flops = nbytes = 0.0
    for kind in layer_kinds(cfg):
        if not kind.endswith("attention"):
            continue
        flops += 3.0 * b * attention_maps_flops(cfg, kind)
        q, kv, maps = (t * b * w * hd * item for w in (h, hkv, 2 * h))
        nbytes += 3.0 * q + 6.0 * kv + 3.0 * maps
    return flops, nbytes


def selective_scan_per_step(cfg: Dict) -> Tuple[float, float]:
    """(operations, bytes) the scans require in one training step:
    ``SCAN_OPS`` a (position, channel, state) forward and twice that
    backward, on the vector unit (there is no matrix form), held against
    the chip's bf16 matrix peak for want of a published vector one.
    Bytes: forward reads x, dt, B, C and writes y; backward reads x, dt,
    B, C, dy and writes dx, ddt, dB, dC."""
    *_, c, n, _, _, t = _sizes(cfg)
    b, item = cfg["batch_size"], cfg["scan_io_itemsize"]
    layers = sum(k == "mamba" for k in layer_kinds(cfg))
    ops = layers * 3.0 * SCAN_OPS * b * t * c * n
    nbytes = layers * b * t * item * (8.0 * c + 8.0 * n)
    return ops, nbytes
