"""Training-health diagnostics: compile/recompile tracking, XLA
cost-analysis FLOPs, live MFU, and step-time attribution instruments.

PR 1 gave the platform raw instruments (registry, spans, telemetry);
this module *interprets* the signals the way BigDL's driver-side
Metrics table + Spark UI did for the reference: it answers "is the
step slow because of recompilation, input starvation, or the device?"
and "what fraction of peak FLOPs are we getting?".

Three pieces:

* :class:`CompileMonitor` — wraps jitted functions, counts
  compilations (new entries in jit's own cache) and compile seconds
  per function, detects recompilation *churn* after a configurable
  warmup with a loud structured warning naming the offending
  signature, and pulls ``jax.stages`` cost analysis (FLOPs / bytes
  accessed) into gauges so the trainer can publish a live MFU
  estimate.
* :func:`step_attribution_histogram` — the shared
  ``train_step_time_seconds{component}`` family decomposing each
  wall-clock step into ``data_wait`` (host batch wait), and
  ``host_dispatch`` / ``device`` (dispatch wall vs the sampled
  dispatch→``block_until_ready`` bracket).
* ``jax.monitoring`` listeners accumulating the runtime's own
  ``backend_compile`` durations — the ground-truth compile clock that
  first-call walls (which include the first execution) only bound —
  and the persistent compilation cache's hits and misses.

Everything here must degrade to "fewer gauges", never to an exception
on a hot path.
"""

from __future__ import annotations

import collections
import logging
import threading
import time
from typing import Any, Dict, Optional, Tuple

import numpy as np

from analytics_zoo_tpu.observability.metrics import (
    MetricsRegistry, get_registry)
from analytics_zoo_tpu.observability.tracing import get_tracer

log = logging.getLogger("analytics_zoo_tpu.observability")

# Components of one wall-clock training step (the attribution table in
# docs/observability.md "Diagnosing a slow or sick run").
STEP_COMPONENTS = ("data_wait", "host_dispatch", "device")


def step_attribution_histogram(registry: Optional[MetricsRegistry] = None):
    """The shared step-time attribution family; every producer
    (trainer prefetch, DeviceLoader, dispatch bracket) observes into
    the same histogram so ``/metrics`` shows the breakdown directly."""
    reg = registry if registry is not None else get_registry()
    return reg.histogram(
        "train_step_time_seconds",
        "wall-clock step decomposition: data_wait = host wait for the "
        "next device batch; host_dispatch = python + dispatch wall; "
        "device = dispatch->block_until_ready bracket (sampled every "
        "observability.device_time_every steps)",
        labels=("component",))


def _short_signature(sig: Tuple, limit: int = 400) -> str:
    s = repr(sig)
    return s if len(s) <= limit else s[:limit] + "..."


def abstract_signature(args: Tuple) -> Tuple:
    """Shapes and dtypes of a call's arguments, for the text of the
    compile log and the churn warning (jit's cache also keys on
    sharding, weak type and static values: this is not its key).  No
    device sync, no tracing; a donated, deleted array still has
    both."""
    leaves = []
    for a in _tree_leaves(args):
        if a is None:
            leaves.append(None)
        elif hasattr(a, "shape") and hasattr(a, "dtype"):
            leaves.append((tuple(a.shape), str(a.dtype)))
        else:
            # python scalars are weak-typed: the VALUE does not retrace
            # but the TYPE does
            leaves.append(type(a).__name__)
    return tuple(leaves)


def _tree_leaves(tree):
    import jax
    return jax.tree_util.tree_leaves(
        tree, is_leaf=lambda v: v is None)


# ----------------------------------------------------------- monitoring
_listener_lock = threading.Lock()
_listener_installed = False

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class _CompileSeries:
    """The compile clocks' counters, made once a registry as
    ``Tracer._count`` makes the span counters (tests swap the registry):
    a compile fires three duration events and a cache answer, and a
    jitted ``jnp`` function traced inside a train program fires one
    each."""

    def __init__(self):
        self._registry = None

    def get(self) -> "_CompileSeries":
        reg = get_registry()
        if reg is not self._registry:
            self._make(reg)
        return self

    def _make(self, reg) -> None:
        self.backend_compiles = reg.counter(
            "jax_backend_compiles_total",
            "XLA backend compilations (jax.monitoring)")
        self.backend_seconds = reg.counter(
            "jax_backend_compile_seconds_total",
            "seconds inside XLA backend_compile (jax.monitoring); a "
            "persistent-cache hit's read from disk counts here too")
        self.trace_seconds = reg.counter(
            "jax_trace_seconds_total",
            "seconds tracing a jitted function's Python body to a "
            "jaxpr, less the traces nested in it: the series sum to "
            "the time spent tracing", labels=("fn",))
        self.traces = reg.counter(
            "jax_traces_total",
            "traces of a jitted function's Python body (a trace that "
            "jit's own cache answers is none)", labels=("fn",))
        self.lower_seconds = reg.counter(
            "jax_lower_seconds_total",
            "seconds lowering a traced program to an MLIR module, "
            "less the traces nested in it", labels=("fn",))
        self.cache_load_seconds = reg.counter(
            "compile_cache_load_seconds_total",
            "seconds reading executables from JAX's persistent "
            "compilation cache: the part of jax_backend_compile_"
            "seconds_total that is no compile")
        self.cache_hits = reg.counter(
            "compile_cache_hits_total",
            "compiles answered by JAX's persistent compilation "
            "cache (an executable read from disk)")
        self.cache_misses = reg.counter(
            "compile_cache_misses_total",
            "compiles JAX's persistent compilation cache could "
            "not answer and has written (full XLA compile paid; "
            "one shorter than jax_persistent_cache_min_compile_"
            "time_secs counts as neither)")
        self._registry = reg


_series = _CompileSeries()
_stage_local = threading.local()


def _stage_self_seconds(duration: float) -> float:
    """``duration`` of the trace or lowering that has just ended on
    this thread, less the stages that ran inside it.  JAX reports a
    stage when it ENDS, so the nested ones have been reported already:
    they are the kept stages that started no earlier than this one.
    What is left of every stage adds up to the wall time the thread
    spent tracing and lowering, which the raw durations overstate (a
    train program's trace holds the trace of every jitted function and
    kernel called in it)."""
    kept = _stage_local.__dict__.setdefault(
        "kept", collections.deque(maxlen=4096))
    start = time.time() - duration   # JAX's own clock for these events
    nested = 0.0
    while kept and kept[-1][0] >= start - 1e-4:
        nested += kept.pop()[1]
    kept.append((start, duration))
    return max(duration - nested, 0.0)


def _fn_label(fun_name: Optional[str]) -> str:
    """JAX names a trace by the Python function and a lowering by the
    module (``jit(<function>)``, ``jit_<function>`` in other
    releases): one label for both."""
    if not fun_name:
        return "?"
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name[4:] if fun_name.startswith("jit_") else fun_name


def _backend_compile_listener(event: str, duration: float,
                              fun_name: Optional[str] = None,
                              **_kw) -> None:
    """jax.monitoring duration listener: accumulate the runtime's own
    compile clocks by stage (trace, lowering, backend compile, the
    persistent cache's read).  Never raises (it runs inside jax
    internals)."""
    try:
        if event == _TRACE_EVENT:
            series, fn = _series.get(), _fn_label(fun_name)
            series.traces.labels(fn).inc()
            series.trace_seconds.labels(fn).inc(
                _stage_self_seconds(float(duration)))
        elif event == _LOWER_EVENT:
            _series.get().lower_seconds.labels(_fn_label(fun_name)).inc(
                _stage_self_seconds(float(duration)))
        elif event == _BACKEND_EVENT:
            series = _series.get()
            series.backend_compiles.inc()
            series.backend_seconds.inc(float(duration))
        elif event == _CACHE_LOAD_EVENT:
            _series.get().cache_load_seconds.inc(float(duration))
    except Exception:
        pass


def _persistent_cache_listener(event: str, **_kw) -> None:
    """jax.monitoring event listener: what JAX's persistent compilation
    cache answered.  Never raises (it runs inside jax internals)."""
    try:
        if event == "/jax/compilation_cache/cache_hits":
            _series.get().cache_hits.inc()
        elif event == "/jax/compilation_cache/cache_misses":
            _series.get().cache_misses.inc()
    except Exception:
        pass


def install_compile_listener() -> bool:
    """Register the ``jax.monitoring`` compile listeners once per
    process; returns whether the hooks are active."""
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return True
        try:
            import jax.monitoring
            jax.monitoring.register_event_duration_secs_listener(
                _backend_compile_listener)
            jax.monitoring.register_event_listener(
                _persistent_cache_listener)
            _listener_installed = True
        except Exception:
            return False
    return True


# -------------------------------------------------------- CompileMonitor
class _MonitoredJit:
    """A jitted callable wrapped with compile tracking.

    A compile is a new entry in jit's own cache (``_cache_size``, the
    one JAX-private name this module takes on): whatever jit keys on —
    shape, dtype, sharding, weak type, a static value — is counted,
    and a steady dispatch pays two integer reads.  Warmup/churn state
    lives on the WRAPPER (one per built program), so a freshly built
    trainer starts a fresh warmup; the metrics it feeds aggregate per
    function *name* in the shared registry.  Unknown attributes
    (``lower``, ``warm``, ...) forward to the underlying jitted
    function.
    """

    def __init__(self, monitor: "CompileMonitor", name: str, fn):
        self._monitor = monitor
        self._name = name
        self._fn = fn
        self._cache_size = fn._cache_size
        self._calls = 0

    def __call__(self, *args):
        before = self._cache_size()
        t0 = time.perf_counter()
        out = self._fn(*args)
        entries = self._cache_size()
        if entries > before:
            mon = self._monitor
            mon._record_compile(
                self._name, abstract_signature(args),
                time.perf_counter() - t0,
                calls_before=self._calls,
                warmed_up=self._calls >= mon.warmup_calls,
                n_signatures=entries)
            mon._maybe_cost_analysis(self._name, self._fn, args)
        self._calls += 1
        return out

    def __getattr__(self, item):
        return getattr(self._fn, item)


class CompileMonitor:
    """Per-function compile accounting over the shared registry.

    ``wrap(name, jitted)`` returns a transparent callable; each call
    that adds an entry to jit's cache counts as a compilation.
    Entries appearing after ``warmup_calls`` calls are *recompilation
    churn* — the classic silent TPU perf killer (a shape, dtype or
    sharding drifting per step recompiles every step) — and emit one
    loud structured warning each, naming the offending arguments'
    shapes and dtypes.

    First-call wall time is recorded as ``jax_compile_seconds_total``
    (an upper bound: it includes the first execution); the
    ``jax.monitoring`` listener records the runtime's own
    ``backend_compile`` seconds alongside.
    """

    def __init__(self, warmup_calls: Optional[int] = None,
                 cost_analysis: Optional[bool] = None,
                 registry: Optional[MetricsRegistry] = None):
        if warmup_calls is None or cost_analysis is None:
            try:
                from analytics_zoo_tpu.common.config import get_config
                cfg = get_config()
                if warmup_calls is None:
                    warmup_calls = int(cfg.get(
                        "observability.compile_warmup_calls", 3))
                if cost_analysis is None:
                    cost_analysis = bool(cfg.get(
                        "observability.cost_analysis", True))
            except Exception:
                warmup_calls = 3 if warmup_calls is None else warmup_calls
                cost_analysis = True if cost_analysis is None \
                    else cost_analysis
        self.warmup_calls = int(warmup_calls)
        self.cost_analysis = bool(cost_analysis)
        self._registry = registry
        self._lock = threading.Lock()
        # per-name aggregates (across wrapper instances)
        self._stats: Dict[str, Dict[str, float]] = {}
        install_compile_listener()

    def _reg(self) -> MetricsRegistry:
        # lazy: survives reset_registry() between tests/runs
        return self._registry if self._registry is not None \
            else get_registry()

    # ------------------------------------------------------------- wrap
    def wrap(self, name: str, jitted) -> _MonitoredJit:
        return _MonitoredJit(self, name, jitted)

    def _state(self, name: str) -> Dict[str, float]:
        st = self._stats.get(name)
        if st is None:
            st = self._stats.setdefault(name, {
                "compiles": 0, "recompiles_after_warmup": 0,
                "compile_seconds": 0.0, "flops": None, "bytes": None,
            })
        return st

    def _record_compile(self, name: str, key, wall_s: float,
                        calls_before: int, warmed_up: bool,
                        n_signatures: int) -> None:
        reg = self._reg()
        with self._lock:
            st = self._state(name)
            st["compiles"] += 1
            st["compile_seconds"] += wall_s
            if warmed_up:
                st["recompiles_after_warmup"] += 1
        reg.counter(
            "jax_compiles_total",
            "jit compilations observed per monitored function (new "
            "entries in jit's cache)", labels=("fn",)).labels(name).inc()
        reg.counter(
            "jax_compile_seconds_total",
            "first-call wall seconds per new signature (upper bound "
            "on compile time; includes the first execution)",
            labels=("fn",)).labels(name).inc(wall_s)
        if warmed_up:
            reg.counter(
                "jax_recompiles_total",
                "compilations AFTER the warmup — recompilation churn",
                labels=("fn",)).labels(name).inc()
            log.warning(
                "recompilation churn: %r compiled signature #%d on "
                "call %d (after its %d-call warmup), %.2fs — a shape, "
                "dtype or sharding is drifting between steps; the "
                "offending call's shapes and dtypes: %s",
                name, n_signatures, calls_before + 1,
                self.warmup_calls, wall_s, _short_signature(key))
        else:
            log.info("compiled %r signature #%d in %.2fs (call %d)",
                     name, n_signatures, wall_s, calls_before + 1)

    # ---------------------------------------------------- cost analysis
    def _maybe_cost_analysis(self, name: str, fn, args) -> None:
        """FLOPs / bytes of the just-compiled program into gauges.

        Prefers ``Lowered.cost_analysis()`` (pure HLO analysis — no
        second backend compile); falls back to compiling the lowered
        program (``jax.stages.Compiled.cost_analysis()``), which recent
        runtimes dedupe via the compilation cache.  Lowering uses
        ShapeDtypeStructs of the arguments' shapes and dtypes, which a
        donated, deleted buffer still has."""
        if not self.cost_analysis:
            return
        with get_tracer().span("startup_cost_analysis", fn=name):
            self._cost_analysis(name, fn, args)

    def _cost_analysis(self, name: str, fn, args) -> None:
        try:
            import jax

            def sds(a):
                if a is None:
                    return None
                if hasattr(a, "shape") and hasattr(a, "dtype"):
                    return jax.ShapeDtypeStruct(
                        tuple(a.shape), np.dtype(a.dtype))
                return a   # python scalar: pass through
            shaped = jax.tree_util.tree_map(
                sds, args, is_leaf=lambda v: v is None)
            lowered = fn.lower(*shaped)
            try:
                cost = lowered.cost_analysis()
            except Exception:
                cost = lowered.compile().cost_analysis()
            if isinstance(cost, list):
                cost = cost[0] if cost else {}
            flops = float(cost.get("flops", 0.0)) or None
            hbm = float(cost.get("bytes accessed", 0.0)) or None
        except Exception:
            return
        reg = self._reg()
        with self._lock:
            st = self._state(name)
            st["flops"], st["bytes"] = flops, hbm
        if flops is not None:
            reg.gauge(
                "train_step_flops",
                "XLA cost-analysis FLOPs of the compiled program "
                "(scan bodies counted once)", labels=("fn",)
            ).labels(name).set(flops)
        if hbm is not None:
            reg.gauge(
                "train_step_hbm_bytes",
                "XLA cost-analysis bytes accessed of the compiled "
                "program", labels=("fn",)).labels(name).set(hbm)

    # ------------------------------------------------------------ reads
    def flops(self, name: str) -> Optional[float]:
        with self._lock:
            st = self._stats.get(name)
            return st["flops"] if st else None

    def stats(self, name: Optional[str] = None) -> Dict[str, Any]:
        """Per-function aggregates (or all of them) — what
        ``scripts/check_determinism.py`` asserts on."""
        with self._lock:
            if name is not None:
                return dict(self._stats.get(name, {}))
            return {k: dict(v) for k, v in self._stats.items()}


_global_monitor: Optional[CompileMonitor] = None
_monitor_lock = threading.Lock()


def get_compile_monitor() -> CompileMonitor:
    """The process-wide monitor the training engines wrap through."""
    global _global_monitor
    if _global_monitor is None:
        with _monitor_lock:
            if _global_monitor is None:
                _global_monitor = CompileMonitor()
    return _global_monitor


def reset_compile_monitor() -> None:
    """Drop the process-wide monitor (test helper)."""
    global _global_monitor
    with _monitor_lock:
        _global_monitor = None


# ----------------------------------------------------------------- MFU
def publish_mfu(fn_name: str, device_step_s: float,
                registry: Optional[MetricsRegistry] = None
                ) -> Optional[float]:
    """Set the live ``train_mfu`` gauge from the monitored function's
    cost-analysis FLOPs and a sampled device step time.

    The denominator is the chip's bf16 peak (``benchmarks.PEAK_FLOPS``
    by device kind) or the ``observability.peak_flops`` override —
    required on backends whose peak is unknown (CPU).  Returns the MFU
    or None when it cannot be computed (the gauge then keeps its last
    value; it exists at 0 from registration)."""
    reg = registry if registry is not None else get_registry()
    gauge = reg.gauge(
        "train_mfu",
        "model FLOPs utilisation: cost-analysis FLOPs / sampled device "
        "step time / chip peak (observability.peak_flops overrides the "
        "denominator)")
    try:
        flops = get_compile_monitor().flops(fn_name)
        if not flops or device_step_s <= 0:
            return None
        peak = None
        try:
            from analytics_zoo_tpu.common.config import get_config
            peak = float(get_config().get(
                "observability.peak_flops", 0.0)) or None
        except Exception:
            peak = None
        import jax
        from analytics_zoo_tpu.benchmarks import mfu_estimate
        mfu = mfu_estimate(flops, device_step_s, jax.devices()[0],
                           peak=peak)
        if mfu is not None:
            gauge.set(mfu)
        return mfu
    except Exception:
        return None
