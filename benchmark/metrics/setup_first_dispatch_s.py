"""Seconds of set-up inside the first dispatch of the train program
(``startup_first_dispatch``) and the warm-start before it
(``aot_warm_start``, the per-step engine): the envelope that holds the
train program's trace, lowering, compile or cache read, its cost
analysis and the enqueue.  The envelope less (``setup_trace_lower_s`` +
``compile_s``) is what else a first dispatch costs."""

from benchmark.metrics._startup import span_seconds_at_open


def read(run):
    return span_seconds_at_open(
        run, ("startup_first_dispatch", "aot_warm_start"),
        needs="startup_first_dispatch")
