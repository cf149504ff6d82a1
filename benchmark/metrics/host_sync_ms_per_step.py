"""Host milliseconds a step blocked on the device: every ``float(loss)``
(``train_loss_sync``) and the sampled ``block_until_ready`` of
``observability.device_time_every`` (``train_device_sync``)."""

from benchmark.metrics._program import span_ms_per_step


def read(run):
    return span_ms_per_step(run, "span_seconds_total",
                            ("train_loss_sync", "train_device_sync"))
