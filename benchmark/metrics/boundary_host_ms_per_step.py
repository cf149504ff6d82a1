"""Host milliseconds a step between dispatches: the self time of
``train_boundary`` (heartbeat, watchdog, collective accounting,
triggers, the epoch's end) and ``train_permute`` (the epoch's
permutation on the scan path).  The loss reads, checkpoints and
validation nested in a boundary are spans of their own."""

from benchmark.metrics._program import span_ms_per_step


def read(run):
    return span_ms_per_step(run, "span_self_seconds_total",
                            ("train_boundary", "train_permute"))
