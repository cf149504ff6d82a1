"""Device milliseconds a step inside the routed experts' grouped matrix
products in the latent cell (``grouped_matmul_ms_per_step``'s reading,
declared for this cell)."""

from benchmark.metrics.grouped_matmul_ms_per_step import read  # noqa: F401
