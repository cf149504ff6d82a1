"""Device milliseconds a step inside the Mamba-2 scan's two kernels
(``ssd_scan_fwd``, ``ssd_scan_bwd``), all Mamba-2 layers together."""

from benchmark.metrics._program import kernel_ms_per_step


def read(run):
    return kernel_ms_per_step(run, ("ssd_scan_",))
