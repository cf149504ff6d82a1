"""Host milliseconds a step inside the call that dispatches the train
program: the self time of ``train_epoch_scan``, ``train_dispatch`` and
``train_step`` (the sampled ``train_device_sync`` nested in
``train_step`` is a child, so it is left out)."""

from benchmark.metrics._program import span_ms_per_step


def read(run):
    return span_ms_per_step(
        run, "span_self_seconds_total",
        ("train_epoch_scan", "train_dispatch", "train_step"))
