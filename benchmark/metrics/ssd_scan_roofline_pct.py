"""The Mamba-2 scan's share of its roofline: the FLOPs and bytes the
chunked scan requires (``flops.ssd_scan_per_step``: its products, x, dt,
B, C, y and their cotangents once each, the states at the chunks'
starts written and read once; nothing recomputed), the larger of the two
bounds, over the own time of the ``ssd_scan_*`` events."""

from benchmark.metrics._sparse import roofline_pct


def read(run):
    return roofline_pct(run, ("ssd_scan_",),
                        run["flops"].ssd_scan_per_step(run["cfg"]))
