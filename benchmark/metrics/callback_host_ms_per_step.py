"""Host milliseconds a step inside the bodies of the step's host
callbacks (``callback_finite_check``, ``callback_grad_norm``) on the
runtime's callback thread: to set against
``finite_check_device_ms_per_step``, the device's wait for them."""

from benchmark.metrics._program import span_ms_per_step


def read(run):
    return span_ms_per_step(run, "span_seconds_total", prefix="callback_")
