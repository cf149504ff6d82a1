"""Seconds of ``setup_s`` the main thread spent under NO span of the
program: ``run["setup_s"]`` less the sum of ``span_self_seconds_total``
over every main-thread span name as the window opens (the self times
of one thread's spans partition the time it spent under any of them).
What is left is imports, the harness's rows, the reference's weights,
``to_program``, whatever the program does outside a span, and the
boundary at which the window opens, still open when the snapshot is
taken (in a traced run the profiler starts inside it).  The trigger's
host copies of the state run inside the FIRST ``train_boundary``,
which has ended by then (every cell warms up past it), and count
there, not here."""

from analytics_zoo_tpu.observability.tracing import TRAIN_TIMELINE_SPANS

from benchmark.metrics._startup import span_seconds_at_open


def read(run):
    under_spans = span_seconds_at_open(
        run, TRAIN_TIMELINE_SPANS["main"], needs="train_startup",
        counter="span_self_seconds_total")
    return None if under_spans is None else run["setup_s"] - under_spans
