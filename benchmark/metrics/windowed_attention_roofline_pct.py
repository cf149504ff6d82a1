"""Flash attention's share of its roofline in the windowed cell: the
FLOPs and bytes that attention over the pairs the layers' masks allow
requires (``flops.attention_per_step``: QK^T and PV at 128 for 28 query
heads, q / k / v / o / do and the three gradients once each at 4 K/V
heads) over the own time of the ``flash_attention_*`` events."""

from benchmark.metrics._sparse import roofline_pct
from benchmark.metrics.windowed_attention_ms_per_step import KERNELS


def read(run):
    return roofline_pct(run, KERNELS,
                        run["flops"].attention_per_step(run["cfg"]))
