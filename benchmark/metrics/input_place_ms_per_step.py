"""Prefetch-thread milliseconds a step inside ``put_fn(batch)``, the
host-to-device placement (``data_place``)."""

from benchmark.metrics._program import span_ms_per_step


def read(run):
    return span_ms_per_step(run, "span_seconds_total", ("data_place",))
