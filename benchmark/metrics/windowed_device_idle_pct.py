"""Share of the windowed cell's traced window in which no operation ran
on the device (``device_idle_pct``'s reading, declared for this
cell)."""

from benchmark.metrics.device_idle_pct import read  # noqa: F401
