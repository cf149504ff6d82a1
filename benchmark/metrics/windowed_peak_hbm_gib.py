"""Peak device memory after the windowed cell's window
(``peak_hbm_gib``'s reading, declared for this cell)."""

from benchmark.metrics.peak_hbm_gib import read  # noqa: F401
