"""Host milliseconds a step inside the call that dispatches the
windowed cell's train program (``host_dispatch_ms_per_step``'s reading,
declared for this cell)."""

from benchmark.metrics.host_dispatch_ms_per_step import read  # noqa: F401
