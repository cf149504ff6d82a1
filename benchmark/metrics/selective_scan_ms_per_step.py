"""Device milliseconds a step inside the selective scan's two kernels
(``selective_scan_fwd``, ``selective_scan_bwd``), all state-space layers
together."""

from benchmark.metrics._program import kernel_ms_per_step


def read(run):
    return kernel_ms_per_step(run, ("selective_scan_",))
