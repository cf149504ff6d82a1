"""Device milliseconds a step inside the fused optimizer kernels
(``fused_sgd``, ``fused_adam``), all leaves together."""

from benchmark.metrics._program import kernel_ms_per_step


def read(run):
    return kernel_ms_per_step(run, ("fused_sgd", "fused_adam"))
