"""Profiling / tracing utilities.

Reference posture (SURVEY.md §5): coarse ``Utils.timeIt`` wall timing
around session runs + per-iteration phase metrics in the driver log.
TPU version: the same cheap step timers, plus first-class
``jax.profiler`` trace capture viewable in TensorBoard / Perfetto.

All interval math uses ``time.perf_counter`` (monotonic): wall-clock
(NTP) adjustments must never yield negative or garbage durations.
``time_it`` is kept API-compatible and is BACKED by the observability
tracer (observability/): it records a span, so existing callers show up
on the timeline and in the span counters of ``/metrics`` for free.
"""

from __future__ import annotations

import contextlib
import logging
import time

import jax

from analytics_zoo_tpu.observability import get_tracer

log = logging.getLogger("analytics_zoo_tpu.profiling")


class _TimedBlock:
    """Handle yielded by :func:`time_it`; register the block's output
    with ``set`` so the timer can block on it before reading the clock."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = None

    def set(self, value):
        self.value = value
        return value


@contextlib.contextmanager
def time_it(name: str, sync: bool = False):
    """Wall-time a block (the Utils.timeIt role).  With ``sync=True``,
    call ``handle.set(out)`` inside the block and the timer blocks on
    that jax value so async device work is included::

        with time_it("fwd", sync=True) as tb:
            tb.set(model.apply(params, x))
    """
    handle = _TimedBlock()
    with get_tracer().span(name):
        t0 = time.perf_counter()
        yield handle
        if sync and handle.value is not None:
            jax.block_until_ready(handle.value)
        log.info("%s took %.3fs", name, time.perf_counter() - t0)


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a jax.profiler trace for the enclosed block."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
