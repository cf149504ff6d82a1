"""The selective scan's share of its roofline: the operations and bytes
the recurrence requires (``flops.selective_scan_per_step``: nothing
recomputed) over the own time of the ``selective_scan_*`` events.  The
bound is the memory one (its operations are few beside the matrix
peak); the vector unit, which has no published peak, sets the pace."""

from benchmark.metrics._sparse import roofline_pct


def read(run):
    return roofline_pct(run, ("selective_scan_",),
                        run["flops"].selective_scan_per_step(run["cfg"]))
