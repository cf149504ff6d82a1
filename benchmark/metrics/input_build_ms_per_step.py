"""Worker milliseconds a step building host batches (``data_build``:
gather and stages), summed over the pool's threads: busy time, so over
``workers`` threads it is their occupancy, not a wait."""

from benchmark.metrics._program import span_ms_per_step


def read(run):
    return span_ms_per_step(run, "span_seconds_total", ("data_build",))
