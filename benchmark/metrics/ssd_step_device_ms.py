"""Device milliseconds a training step of the Mamba-2 cell took: the
union of the device's operation intervals in the traced window, per
step (``step_device_ms``'s reading, declared for this cell)."""

from benchmark.metrics.step_device_ms import read  # noqa: F401
