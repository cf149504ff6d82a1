"""Rate at which ``Estimator.train`` hands the trained state back to the
host: growth of ``host_fetch_bytes_total{site="train_return"}`` (every
``path``: the bytes the return's ``fetch_global`` fetched) over growth
of ``span_seconds_total{name="train_return"}`` between the snapshots,
in GB/s.  A checkpoint's fetch counts under its own ``site`` and is
left out.  The return is the window's last part and leaves the device
idle (``train_return_pct_of_window`` is its share); this says how fast
the copy ran.  A program without the counter or the span reads
``None``."""

from benchmark.harness import counter_delta


def read(run):
    fetched = sum(v for k, v in counter_delta(
        run["after"], run["before"], "host_fetch_bytes_total").items()
        if 'site="train_return"' in k)
    seconds = counter_delta(run["after"], run["before"],
                            "span_seconds_total").get('{name="train_return"}')
    if not fetched or not seconds:
        return None
    return fetched / seconds / 1e9
