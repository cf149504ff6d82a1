"""The whole step's share of the chip's bf16 peak in the latent cell:
the FLOPs the model requires for a record (``flops/<config>.py``: the
products, the routed experts' from the rows the router really sent to
the held experts, attention's from the causal pairs at 192 and 128,
nothing recomputed) times the records a second of the traced window,
over the published peak."""

from benchmark.metrics.sparse_step_mfu import read  # noqa: F401
