"""The whole step's share of the chip's bf16 peak in the Mamba-2 cell:
the FLOPs the model requires for a record (``flops/<config>.py``: the
products, the scan's chunked products over the causal half of each
chunk, attention's two maps over the causal pairs, the tied head over
the slice; nothing recomputed) times the records a second of the traced
window, over the published peak."""

from benchmark.metrics.hybrid_step_mfu import read  # noqa: F401
