"""The grouped matrix products' share of their roofline: the FLOPs and
bytes the rows really routed to the held experts require
(``flops.grouped_matmul_per_step``: three products forward, six
backward, nothing recomputed) over the own time of the
``grouped_matmul_*`` events."""

from benchmark.metrics._sparse import roofline_pct, routed_rows


def read(run):
    rows = routed_rows(run)
    if rows is None or not run["steps"]:
        return None
    return roofline_pct(
        run, ("grouped_matmul_",),
        run["flops"].grouped_matmul_per_step(run["cfg"],
                                             rows / run["steps"]))
