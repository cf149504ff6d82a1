"""What the metrics of PR 25 share: the program's span counters between
the window's two registry snapshots, and the named Pallas kernels in the
device trace.  A program without the counters or the names (the parent
of PR 25) reads ``None`` everywhere, never an error."""

from benchmark.harness import counter_delta
from benchmark.trace_reduce import op_kind


def span_ms_per_step(run, counter, names=(), prefix=None):
    """Milliseconds a step of ``counter`` (``span_seconds_total`` or
    ``span_self_seconds_total``) for the spans in ``names`` or whose
    name starts with ``prefix``; ``None`` when none of them moved."""
    if not run["steps"]:
        return None
    wanted = {'{name="%s"}' % n for n in names}
    moved = [v for label, v in counter_delta(
        run["after"], run["before"], counter).items()
        if label in wanted or (prefix is not None
                               and label.startswith('{name="' + prefix))]
    if not moved:
        return None
    return 1e3 * sum(moved) / run["steps"]


def kernel_ms_per_step(run, names):
    """Device milliseconds a step inside the Pallas kernels whose name
    (``name=`` of their ``pallas_call``) is in ``names``: the own time of
    the ``tpu_custom_call`` events that carry it, as instruction name or
    in ``op_name``.  The copies XLA schedules around a kernel are other
    events and are not counted."""
    if not run["steps"]:
        return None
    seconds = sum(s for event, s in run["trace"]["by_name"].items()
                  if op_kind(event) == "tpu_custom_call"
                  and any(n in event for n in names))
    return 1e3 * seconds / run["steps"] if seconds else None
