"""Span tracer: nested, thread-safe ``span("name")`` context managers
exported as Chrome-trace-format JSON (load in Perfetto / chrome://tracing).

The reference's time visibility was coarse driver-side ``Utils.timeIt``
log lines; ``jax.profiler`` covers the device side but not host
orchestration (batch assembly, checkpoint IO, Redis round trips).  Spans
fill that gap: a bounded in-memory ring of complete ("ph":"X") events,
cheap enough to leave on in production (two perf_counter reads, a
deque append and three counter increments per span).

Each event keeps its ``parent`` (the enclosing span's name on that
thread).  At a span's end the tracer also counts, by span name, into
the shared registry: ``span_seconds_total``, ``span_self_seconds_total``
(the duration less what the spans nested in it on the same thread
cover) and ``spans_total`` — the reading a benchmark bounds with two
registry snapshots.

Interval math uses ``time.perf_counter`` (monotonic); the wall-clock
epoch is recorded once so exported timestamps still line up with log
timestamps.

``span(..., jax_annotation=True)`` additionally brackets the block with
``jax.profiler.TraceAnnotation`` carrying the span's ``args``, so the
same name lands on the host planes of a captured device profile, on the
profiler's clock.  Without a profiler session that is a flag test.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from analytics_zoo_tpu.observability.metrics import get_registry

try:    # once, at module load: a span must not pay an import
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except Exception:   # noqa: BLE001 — profiler unavailable: spans still record
    _TraceAnnotation = None


# The training timeline's spans, by the thread that records them: the
# names a captured profile is read by (dev/trace-summary) and the span
# counters are labelled with.  docs/observability.md says what each is
# around.
TRAIN_TIMELINE_SPANS = {
    "main": ("train_epoch_scan", "train_dispatch", "train_step",
             "train_permute", "train_loss_sync", "train_device_sync",
             "train_boundary", "data_wait", "checkpoint_save",
             "checkpoint_restore", "eval", "aot_warm_start",
             "moe_stats_read",
             # the job's two edges (docs/observability.md, "The
             # start-up timeline"): once a model or a train()
             "startup_init_variables", "train_startup",
             "startup_place_state", "startup_loader",
             "startup_first_dispatch", "startup_cost_analysis",
             "train_return"),
    "prefetch": ("data_assemble", "data_place"),
    "worker": ("data_build",),
    "callback": ("callback_grad_norm",),
}


class _Span:
    """One open span: a frame on its thread's stack, which collects
    the seconds of the spans nested in it."""

    __slots__ = ("_tracer", "_name", "_args", "_annotation", "_frame",
                 "_parent", "_start")

    def __init__(self, tracer, name, annotate, args):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._annotation = _TraceAnnotation(name, **args) \
            if annotate and _TraceAnnotation is not None else None

    def __enter__(self):
        stack = self._tracer._stack()
        self._parent = stack[-1][0] if stack else None
        self._frame = [self._name, 0.0]   # name, children's seconds
        stack.append(self._frame)
        self._start = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__enter__()
        return self._tracer

    def __exit__(self, exc_type, exc, tb):
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        dur = time.perf_counter() - self._start
        tracer = self._tracer
        stack = tracer._stack()
        stack.pop()
        if stack:
            stack[-1][1] += dur
        tracer._record(self._name, self._start, dur, self._parent,
                       self._args)
        tracer._count(self._name, dur, max(dur - self._frame[1], 0.0))
        return False


class Tracer:
    """Collects complete-span events into a bounded ring buffer.

    Nesting is tracked per-thread (a thread-local span stack) so
    concurrent serving/prefetch threads trace independently; Perfetto
    renders nesting from timestamp containment per tid, which the
    stack discipline guarantees.
    """

    def __init__(self, max_events: int = 200_000):
        self._events: deque = deque(maxlen=max_events)
        self._local = threading.local()
        self._lock = threading.Lock()
        # perf_counter origin pinned to a wall-clock instant so exported
        # ts values are "us since tracer start" and displayable
        self._t0 = time.perf_counter()
        self._wall0 = time.time()
        self.enabled = True
        self._disabled_span = contextlib.nullcontext(self)
        # the three span counters' children by span name, for the
        # registry they were made in (tests swap the registry)
        self._registry = None
        self._series: Dict[str, tuple] = {}

    # ------------------------------------------------------------- spans
    def _stack(self) -> List[list]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def span(self, name: str, jax_annotation: bool = False, **args):
        """Time a block as one trace event.  ``args`` become the
        event's Chrome-trace ``args`` dict (values must be
        JSON-serializable) and, with ``jax_annotation``, the
        annotation's.  The context manager yields the tracer."""
        if not self.enabled:
            return self._disabled_span
        return _Span(self, name, jax_annotation, args)

    def _record(self, name: str, start_perf: float, duration_s: float,
                parent: Optional[str], args: Dict) -> None:
        event = {
            "name": name, "ph": "X",
            "ts": (start_perf - self._t0) * 1e6,
            "dur": duration_s * 1e6,
            "pid": os.getpid(), "tid": threading.get_ident(),
            "parent": parent,
        }
        if args:
            event["args"] = args
        # the ring lock pairs with events()/clear(): appends must not
        # rely on the GIL for exclusion (free-threaded builds)
        with self._lock:
            self._events.append(event)

    def _count(self, name: str, duration_s: float, self_s: float) -> None:
        """The window-bounded reading: seconds, self seconds and count
        by span name.  Never raises (spans run on the runtime's
        callback thread too)."""
        try:
            reg = get_registry()
            if reg is not self._registry:
                self._registry, self._series = reg, {}
            series = self._series.get(name)
            if series is None:
                series = self._series[name] = (
                    reg.counter(
                        "span_seconds_total",
                        "seconds inside spans, by span name",
                        labels=("name",)).labels(name),
                    reg.counter(
                        "span_self_seconds_total",
                        "span seconds less what the spans nested in "
                        "them on the same thread cover",
                        labels=("name",)).labels(name),
                    reg.counter(
                        "spans_total", "spans ended, by span name",
                        labels=("name",)).labels(name))
            series[0].inc(duration_s)
            series[1].inc(self_s)
            series[2].inc()
        except Exception:   # noqa: BLE001 — never breaks the traced code
            pass

    def complete(self, name: str, start_perf: float, duration_s: float,
                 **args) -> None:
        """Record a complete span from explicit timing (non-lexical
        scopes — e.g. an epoch whose end is reached via several code
        paths).  ``start_perf`` is a ``time.perf_counter()`` reading.
        Not on the stack: it has no parent and no self time, and is
        not counted."""
        if not self.enabled:
            return
        self._record(name, start_perf, duration_s, None, args)

    def instant(self, name: str, **args) -> None:
        """A zero-duration marker event (``ph: "i"``)."""
        if not self.enabled:
            return
        with self._lock:
            self._events.append({
                "name": name, "ph": "i", "s": "t",
                "ts": (time.perf_counter() - self._t0) * 1e6,
                "pid": os.getpid(), "tid": threading.get_ident(),
                **({"args": args} if args else {}),
            })

    def flow_start(self, name: str, flow_id: str, **args) -> None:
        """Open a flow (``ph: "s"``) — a causal arrow OUT of the
        enclosing slice on this thread.  Pair with :meth:`flow_end`
        under the same ``flow_id`` on the receiving thread and
        Perfetto draws the arrow across the two lanes (e.g. a serving
        request handed from its transport thread to the batcher's
        executor thread).  ``cat`` is mandatory on flow events."""
        self._flow(name, flow_id, "s", args)

    def flow_end(self, name: str, flow_id: str, **args) -> None:
        """Close a flow (``ph: "f"`` with ``bp: "e"`` — bind to the
        ENCLOSING slice, the post-Chrome-M47 convention Perfetto
        expects)."""
        self._flow(name, flow_id, "f", args)

    def _flow(self, name: str, flow_id: str, ph: str, args) -> None:
        if not self.enabled:
            return
        ev = {
            "name": name, "ph": ph, "cat": "flow",
            "id": str(flow_id),
            "ts": (time.perf_counter() - self._t0) * 1e6,
            "pid": os.getpid(), "tid": threading.get_ident(),
        }
        if ph == "f":
            ev["bp"] = "e"
        if args:
            ev["args"] = dict(args)
        with self._lock:
            self._events.append(ev)

    def current_span(self) -> Optional[str]:
        stack = self._stack()
        return stack[-1][0] if stack else None

    def depth(self) -> int:
        return len(self._stack())

    # ------------------------------------------------------------ export
    def events(self) -> List[Dict]:
        with self._lock:
            return list(self._events)

    def events_since(self, start_perf: float) -> List[Dict]:
        """The calling thread's complete spans that started at or
        after ``start_perf``, a ``time.perf_counter()`` reading."""
        ts = (start_perf - self._t0) * 1e6
        tid = threading.get_ident()
        return [e for e in self.events() if e["ph"] == "X"
                and e["tid"] == tid and e["ts"] >= ts]

    def chrome_trace(self) -> Dict:
        """The Chrome trace 'JSON Object Format': Perfetto and
        chrome://tracing both load it directly."""
        return {
            "traceEvents": self.events(),
            "displayTimeUnit": "ms",
            "otherData": {
                "wall_time_origin": self._wall0,
                "producer": "analytics_zoo_tpu.observability",
            },
        }

    def export_chrome_trace(self, path: str) -> str:
        """Write the trace JSON; returns the path (``.json`` — open in
        https://ui.perfetto.dev or chrome://tracing)."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    # --------------------------------------------------- jax profiler tie
    @contextlib.contextmanager
    def jax_trace(self, log_dir: str, name: str = "jax_profile"):
        """Bracket a block with BOTH a span and a ``jax.profiler``
        trace capture: the span records where the capture sits in host
        time; the profile holds the device timeline (view either in
        Perfetto)."""
        import jax
        with self.span(name, log_dir=log_dir):
            jax.profiler.start_trace(log_dir)
            try:
                yield
            finally:
                jax.profiler.stop_trace()


_global_tracer: Optional[Tracer] = None
_tracer_lock = threading.Lock()


def get_tracer() -> Tracer:
    global _global_tracer
    if _global_tracer is None:
        with _tracer_lock:
            if _global_tracer is None:
                max_events = 200_000
                try:
                    from analytics_zoo_tpu.common.config import get_config
                    max_events = int(get_config().get(
                        "observability.trace_events", 200_000))
                except Exception:
                    pass
                _global_tracer = Tracer(max_events=max_events)
    return _global_tracer


def reset_tracer() -> None:
    """Drop the process-wide tracer (test helper)."""
    global _global_tracer
    with _tracer_lock:
        _global_tracer = None


def iteration_args(first: Optional[int], offset: int = 0) -> Dict:
    """The ``iteration`` argument of a hot-path span: the training
    step the work belongs to, ``first + offset``; nothing where the
    caller knows no step (an eval pass, a bare loader)."""
    return {} if first is None else {"iteration": int(first) + offset}


def span(name: str, **kwargs):
    """Module-level convenience: ``with span("train_step"): ...`` on
    the process-wide tracer."""
    return get_tracer().span(name, **kwargs)
