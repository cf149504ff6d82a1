"""FLOPs and bytes the ``granite-4.0-h-micro`` configuration requires,
from shapes.

A multiply-add is two operations.  One record is one sequence of T
positions through the layers held (``layer_types[:num_hidden_layers]``).
Per layer: the gated MLP's two products; a Mamba-2 layer's two
projections and the scan's chunked products (``scan_flops_per_position``:
``C B^T`` a group and the map times ``x`` a head over the causal half of
each chunk's (position, position) pairs, ``C S`` and the state's update a
head); the attention layer's four projections and its two maps over the
causal (query, key) pairs of every query head.  The tied head reads every
position.  Backward is twice forward; what the recomputed layers, the
kernels and the loss compute again is not counted, nor the convolution,
the gates and the norms (vector work), and the embedding is a gather."""

from __future__ import annotations

from typing import Dict, List, Tuple


def _sizes(cfg: Dict):
    return (cfg["hidden_size"], cfg["shared_intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
            cfg["mamba_n_groups"], cfg["seq_len"])


def layer_kinds(cfg: Dict) -> List[str]:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def scan_flops_per_position(cfg: Dict) -> float:
    """One forward pass of one Mamba-2 layer's chunked scan, a position:
    over a chunk of L, ``C B^T`` (N a pair) for each group and the map
    times ``x`` (P a pair) for each head over the ``L (L + 1) / 2`` causal
    pairs, ``C S`` and ``B^T x`` (``N P`` a position) for each head."""
    *_, heads, p, n, groups, _ = _sizes(cfg)
    length = cfg["mamba_chunk_size"]
    causal = (length + 1) / 2.0          # pairs a position, on average
    return 2.0 * causal * (groups * n + heads * p) + 2.0 * 2 * heads * n * p


def forward_flops_per_record(cfg: Dict) -> float:
    d, ff, h, hkv, heads, p, n, groups, t = _sizes(cfg)
    hd = d // h
    inner = heads * p
    total = 0.0
    for kind in layer_kinds(cfg):
        total += 2.0 * t * (d * 2 * ff + ff * d)
        if kind == "mamba":
            total += 2.0 * t * (d * (2 * inner + 2 * groups * n + heads)
                                + inner * d)
            total += t * scan_flops_per_position(cfg)
        else:
            total += 2.0 * t * d * (2 * h * hd + 2 * hkv * hd)
            total += 2.0 * (t * (t + 1) / 2.0) * h * 2 * hd
    return total + 2.0 * t * d * cfg["vocab_held"][1]


def train_flops_per_record(cfg: Dict) -> float:
    return 3.0 * forward_flops_per_record(cfg)


def param_shapes(cfg: Dict) -> List[Tuple[int, ...]]:
    d, ff, h, hkv, heads, p, n, groups, _ = _sizes(cfg)
    hd, inner = d // h, heads * p
    conv = inner + 2 * groups * n
    shapes: List[Tuple[int, ...]] = [(cfg["vocab_held"][1], d)]
    for kind in layer_kinds(cfg):
        shapes.append((d,))
        if kind == "mamba":
            shapes += [(d, inner + conv + heads), (cfg["mamba_d_conv"], conv),
                       (conv,), (heads,), (heads,), (heads,), (inner,),
                       (inner, d)]
        else:
            shapes += [(d, h * hd), (d, hkv * hd), (d, hkv * hd),
                       (h * hd, d)]
        shapes += [(d,), (d, 2 * ff), (ff, d)]
    return shapes + [(d,)]


def ssd_scan_per_step(cfg: Dict) -> Tuple[float, float]:
    """(FLOPs, bytes) the Mamba-2 layers' scans require in one training
    step: ``scan_flops_per_position`` forward and twice that backward.
    Bytes: x, B, C and y and their cotangents once each at the scan's
    input width, dt and its cotangent in float32, and the float32 states
    at the chunks' starts, written forward and read backward."""
    *_, heads, p, n, groups, t = _sizes(cfg)
    b, item = cfg["batch_size"], cfg["scan_io_itemsize"]
    layers = sum(k == "mamba" for k in layer_kinds(cfg))
    flops = layers * 3.0 * b * t * scan_flops_per_position(cfg)
    per_position = 2.0 * item * (2 * heads * p + 2 * groups * n) \
        + 2.0 * 4 * heads
    starts = t / cfg["mamba_chunk_size"] * heads * n * p * 4
    nbytes = layers * b * (t * per_position + 2.0 * starts)
    return flops, nbytes
