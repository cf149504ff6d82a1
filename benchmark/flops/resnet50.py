"""FLOPs and bytes the ``resnet50`` configuration requires, from shapes.

A multiply-add is two operations.  Forward counts the convolutions and
the classifier (BatchNorm, ReLU, pooling and the loss are under 1% and
left out); backward is twice forward (a gradient for the input and one
for the weights of every product), so a training record costs three
forward passes.  ResNet-50 at 224: 4.09 GMACs forward, 24.5 GFLOP a
training image."""

from __future__ import annotations

from typing import Dict, List, Tuple

from . import common

EXPANSION = 4


def _convs(cfg: Dict) -> List[Tuple[int, int, int, int, int]]:
    """(output side, kernel, in, out, count) of every convolution."""
    side = cfg["image_size"] // 2
    out = [(side, 7, cfg["image_channels"], cfg["stem_width"], 1)]
    side //= 2                       # the stem's pool
    in_ch = cfg["stem_width"]
    for s, (n, w) in enumerate(zip(cfg["stage_blocks"], cfg["stage_widths"])):
        for b in range(n):
            stride = 2 if (s > 0 and b == 0) else 1
            out.append((side, 1, in_ch, w, 1))
            side_out = side // stride
            out.append((side_out, 3, w, w, 1))
            out.append((side_out, 1, w, EXPANSION * w, 1))
            if b == 0:
                out.append((side_out, 1, in_ch, EXPANSION * w, 1))
            in_ch, side = EXPANSION * w, side_out
    return out


def forward_flops_per_record(cfg: Dict) -> float:
    total = sum(2.0 * side * side * k * k * cin * cout * n
                for side, k, cin, cout, n in _convs(cfg))
    total += 2.0 * cfg["stage_widths"][-1] * EXPANSION * cfg["num_classes"]
    return total


def train_flops_per_record(cfg: Dict) -> float:
    return 3.0 * forward_flops_per_record(cfg)


def param_shapes(cfg: Dict) -> List[Tuple[int, ...]]:
    shapes: List[Tuple[int, ...]] = []
    for side, k, cin, cout, _ in _convs(cfg):
        shapes += [(k, k, cin, cout), (cout,), (cout,)]
    feat = cfg["stage_widths"][-1] * EXPANSION
    shapes += [(feat, cfg["num_classes"]), (cfg["num_classes"],)]
    return shapes


def optimizer_kernel_bytes_per_step(cfg: Dict) -> float:
    return common.optimizer_bytes(
        cfg["optimizer"]["kind"],
        common.kernel_leaf_elements(param_shapes(cfg)))
