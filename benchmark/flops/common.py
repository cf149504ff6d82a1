"""Operations and bytes that the algorithms require, from shapes alone
(nothing from the compiler's cost analysis, which counts a scan body
once and changes with the implementation)."""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

# the program's fused update takes a float32 leaf whose size is a
# multiple of one (8, 128) tile and at least 1024 (ops/fused._leaf_rows);
# the rest goes through XLA's own elementwise fusion
TILE = 8 * 128


def kernel_leaf_elements(shapes: Iterable[Tuple[int, ...]]) -> int:
    total = 0
    for shape in shapes:
        n = 1
        for d in shape:
            n *= int(d)
        if n >= 1024 and n % TILE == 0:
            total += n
    return total


def optimizer_bytes(kind: str, elements: int) -> float:
    """Bytes one update must move for ``elements`` float32 parameters:
    SGD with momentum reads p, g, v and writes p, v; Adam reads p, g, m,
    v and writes p, m, v."""
    passes = {"sgd": 5, "adam": 7}[kind]
    return 4.0 * passes * elements


def roofline_seconds(flops: float, nbytes: float, peaks: Dict) -> Tuple[float, str]:
    """The least time the chip could take, and which bound gives it."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
