"""Device milliseconds a step inside the grouped matrix products
(``grouped_matmul_fwd``, ``grouped_matmul_dlhs``,
``grouped_matmul_drhs``), the backward pass's recomputed forward
products included."""

from benchmark.metrics._program import kernel_ms_per_step


def read(run):
    return kernel_ms_per_step(run, ("grouped_matmul_",))
