"""Device telemetry: sample accelerator memory + runtime cache state
into registry gauges.

Answers "where does the memory go" — the half of the ROADMAP north-star
the step timers can't see.  On TPU, ``device.memory_stats()`` exposes
``bytes_in_use`` / ``peak_bytes_in_use`` / ``bytes_limit``; the CPU
backend returns ``None`` (every field is gated, never assumed).  Live
jax.Array census and jit-cache size come from public jax APIs where
they exist, skipped where they don't — telemetry must degrade to
"fewer gauges", never to an exception on a hot path.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Dict, Optional

from analytics_zoo_tpu.observability.metrics import (
    MetricsRegistry, get_registry)

log = logging.getLogger("analytics_zoo_tpu.observability")

# memory_stats keys worth exporting, mapped to gauge names
_MEM_KEYS = {
    "bytes_in_use": "device_bytes_in_use",
    "peak_bytes_in_use": "device_peak_bytes_in_use",
    "bytes_limit": "device_bytes_limit",
    "largest_free_block_bytes": "device_largest_free_block_bytes",
    "pool_bytes": "device_pool_bytes",
    "num_allocs": "device_num_allocs",
}


def _jit_cache_size() -> Optional[int]:
    """Compiled-executable cache entries, via whichever internal cache
    this jax version exposes; None when none are reachable."""
    try:
        from jax._src import pjit as _pjit
        for attr in ("_cpp_pjit_cache_fun_only",
                     "_cpp_pjit_cache_explicit_attributes",
                     "_pjit_lower_cached"):
            cache = getattr(_pjit, attr, None)
            if cache is None:
                continue
            if hasattr(cache, "cache_info"):
                return int(cache.cache_info().currsize)
            if hasattr(cache, "size"):
                return int(cache.size())
    except Exception:
        pass
    return None


# devices that have successfully reported memory_stats at least once
# in this process — a later failed poll on one of these marks its
# gauges STALE instead of silently freezing them (some backends drop
# memory_stats mid-run).
# Guarded: the TelemetrySampler thread and direct callers (estimator
# per-epoch sampling, tests) may run a pass concurrently.
_reported_devices: set = set()
_reported_lock = threading.Lock()


def sample_device_telemetry(registry: Optional[MetricsRegistry] = None
                            ) -> Dict[str, float]:
    """One sampling pass: set the gauges and return what was sampled
    (a plain dict, handy for logging/tests).  Never raises — a backend
    where ``memory_stats()`` becomes unavailable mid-run degrades to
    stale-marked gauges (``device_telemetry_stale{device}=1`` while
    the last good values stay exported) rather than an exception
    escaping the sampler thread."""
    reg = registry if registry is not None else get_registry()
    sampled: Dict[str, float] = {}
    try:
        import jax
        devices = jax.local_devices()
    except Exception:
        return sampled

    for dev in devices:
        stats = None
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        label = str(getattr(dev, "id", dev))
        with _reported_lock:
            reported_before = label in _reported_devices
            if stats:
                _reported_devices.add(label)
        if not stats:
            if reported_before:
                # the device USED to report: keep the last-good gauge
                # values (scrapes still see them) but flag staleness
                # so dashboards/alerts don't trust a frozen number
                reg.gauge(
                    "device_telemetry_stale",
                    "1 when the device stopped reporting memory_stats "
                    "mid-run (its device_* gauges hold last-good "
                    "values)", labels=("device",)).labels(label).set(1)
                sampled[f"device_telemetry_stale{{{label}}}"] = 1.0
            continue
        if reported_before:
            reg.gauge(
                "device_telemetry_stale",
                "1 when the device stopped reporting memory_stats "
                "mid-run (its device_* gauges hold last-good values)",
                labels=("device",)).labels(label).set(0)
        for key, gname in _MEM_KEYS.items():
            if key in stats:
                reg.gauge(
                    gname, f"device memory_stats()[{key!r}]",
                    labels=("device",)).labels(label).set(stats[key])
                sampled[f"{gname}{{{label}}}"] = float(stats[key])

    # host-side census of live jax.Arrays (count + bytes): catches
    # leaked epoch caches / unreleased checkpoints even on backends
    # with no memory_stats
    try:
        import jax
        arrays = jax.live_arrays()
        nbytes = 0
        for a in arrays:
            try:
                nbytes += a.nbytes
            except Exception:
                continue
        reg.gauge("jax_live_arrays",
                  "live jax.Array objects in this process"
                  ).set(len(arrays))
        reg.gauge("jax_live_array_bytes",
                  "total bytes of live jax.Arrays (logical, pre-"
                  "sharding)").set(nbytes)
        sampled["jax_live_arrays"] = float(len(arrays))
        sampled["jax_live_array_bytes"] = float(nbytes)
    except Exception:
        pass

    size = _jit_cache_size()
    if size is not None:
        reg.gauge("jax_jit_cache_entries",
                  "compiled executables in the pjit cache").set(size)
        sampled["jax_jit_cache_entries"] = float(size)
    return sampled


class TelemetrySampler:
    """Background sampler: calls :func:`sample_device_telemetry` every
    ``interval_s`` until stopped.  Daemon thread, safe to abandon."""

    def __init__(self, interval_s: float = 10.0,
                 registry: Optional[MetricsRegistry] = None):
        self.interval_s = float(interval_s)
        self.registry = registry
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "TelemetrySampler":
        if self._thread is not None:
            return self
        self._stop.clear()   # restartable after stop()
        self._thread = threading.Thread(
            target=self._run, daemon=True,
            name="zoo-telemetry-sampler")
        self._thread.start()
        return self

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                sample_device_telemetry(self.registry)
            except Exception:
                log.exception("telemetry sample failed")
            self._stop.wait(self.interval_s)

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
