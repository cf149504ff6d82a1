"""Content-addressed persistent executable cache.

BENCH_r05 measured ResNet-50 spending ~141s compiling for ~2.3s of
timed compute per repeat, and every serving replica spawn, elastic
mesh re-formation (PR 6), and bench round pays the same cold-start
tax again.  The reference platform amortizes setup across a
long-lived Spark cluster (BigDL, arXiv:1804.05839); here a compiled
XLA executable becomes a *cached, shippable artifact* instead of a
per-process toll.

Layout: one file per entry, ``<cache_dir>/<key>.zooexec``, where
``key`` is a content digest over

* the lowered StableHLO text (subsumes the jaxpr, baked static-arg
  values, sharding annotations and mesh partitioning),
* the abstract call signature (shapes / dtypes / shardings / pytree
  structure — the same information CompileMonitor and COMPILE003 key
  recompiles on),
* backend platform + device kind + device/process counts (mesh
  geometry beyond what the HLO encodes),
* XLA_FLAGS, and the donation/static-argnum spec.

jax/jaxlib/backend *versions* deliberately live in the entry's META,
not the key: a version bump finds the old entry, evicts it LOUDLY
(``compile_cache_errors_total{kind="stale"}``), and recompiles —
rather than silently stranding unreachable files until the LRU sweep.

Durability contract:

* writes are atomic (same-directory temp file + ``os.replace``), so
  two processes racing on one key — the compile-farm case — can never
  tear an entry; last writer wins with identical content;
* loads are corruption-safe: any unreadable/undeserializable/stale
  entry is a MISS plus a loud counter and eviction, never a crash;
* the directory honors a size cap with LRU eviction
  (``compile.cache_max_mb``, ``compile_cache_evictions_total``).

Compile-farm mode: when no explicit cache dir is configured but the
process runs inside a launcher ``run_dir`` (the PR 4 env contract),
the cache lands in ``<run_dir>/compile-cache`` and only host 0
persists entries — workers deserialize host-0's executables instead
of recompiling.
"""

from __future__ import annotations

import hashlib
import logging
import os
import pickle
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

log = logging.getLogger("analytics_zoo_tpu.compile")

#: explicit cache-dir override (takes precedence over config); the
#: same variable bench.py --compile-cache and the Jenkins test lanes
#: export
ENV_CACHE_DIR = "ZOO_TPU_COMPILE_CACHE"

ENTRY_SUFFIX = ".zooexec"


def _counter(name: str, doc: str, labels=()):
    from analytics_zoo_tpu.observability import get_registry
    return get_registry().counter(name, doc, labels=labels)


def _count_error(kind: str) -> None:
    """Loud-counter contract: every bad/stale/unwritable entry is
    visible on /metrics, never silently absorbed."""
    try:
        _counter(
            "compile_cache_errors_total",
            "executable-cache entries rejected or failed, by kind "
            "(corrupt/stale/io/serialize/call)",
            labels=("kind",)).labels(kind).inc()
    except Exception:   # noqa: BLE001 — metrics never block the cache
        pass


def backend_signature() -> str:
    """Platform + device kind + device/process counts — the part of
    the mesh geometry the HLO text alone does not pin down."""
    import jax
    dev = jax.devices()[0]
    return "|".join((
        getattr(dev, "platform", "?"),
        str(getattr(dev, "device_kind", "?")),
        str(jax.device_count()),
        str(jax.process_count()),
    ))


def runtime_versions() -> Dict[str, str]:
    """The version triple checked (loudly) at LOAD time — an entry
    serialized by a different jax/jaxlib/backend build is evicted, not
    trusted."""
    import jax
    import jaxlib
    try:
        backend = jax.devices()[0].client.platform_version
    except Exception:   # noqa: BLE001 — version probe must not raise
        backend = "?"
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "backend": str(backend)}


def cache_key(hlo_digest: str, signature_repr: str,
              donate_repr: str = "()", static_repr: str = "()",
              backend_sig: Optional[str] = None,
              xla_flags: Optional[str] = None) -> str:
    """Content digest of everything that determines the executable.

    Shape/dtype/static-arg/sharding changes land in ``hlo_digest`` and
    ``signature_repr``; mesh changes land in both the HLO partitioning
    and ``backend_sig``; donation is keyed explicitly because aliasing
    must match the caller's buffer expectations even where a backend
    elides it from the IR text.
    """
    if backend_sig is None:
        backend_sig = backend_signature()
    if xla_flags is None:
        xla_flags = os.environ.get("XLA_FLAGS", "")
    material = "\x1f".join((hlo_digest, signature_repr, donate_repr,
                            static_repr, backend_sig, xla_flags))
    return hashlib.sha256(material.encode()).hexdigest()


def _process_id() -> int:
    """Worker index for the farm write policy: the launcher env
    contract first (works before/without jax.distributed), the live
    jax process index second."""
    raw = os.environ.get("ZOO_TPU_PROCESS_ID")
    if raw is not None:
        try:
            return int(raw)
        except ValueError:
            pass
    try:
        import jax
        return jax.process_index()
    except Exception:   # noqa: BLE001
        return 0


def resolve_cache_dir() -> Optional[Tuple[str, bool]]:
    """``(cache_dir, farm_mode)`` or None when caching is off.

    Precedence: ``ZOO_TPU_COMPILE_CACHE`` env > ``compile.cache_dir``
    config > (``compile.farm``) the launcher run-dir slot
    ``<ZOO_TPU_RUN_DIR>/compile-cache``.
    """
    env = os.environ.get(ENV_CACHE_DIR, "").strip()
    if env:
        return env, False
    from analytics_zoo_tpu.common.config import get_config
    cfg = get_config()
    explicit = str(cfg.get("compile.cache_dir") or "").strip()
    if explicit:
        return explicit, False
    if bool(cfg.get("compile.farm", True)):
        run_dir = os.environ.get("ZOO_TPU_RUN_DIR", "").strip()
        if run_dir:
            return os.path.join(run_dir, "compile-cache"), True
    return None


class _StaleEntry(RuntimeError):
    pass


def _device_ids(compiled) -> Optional[List[int]]:
    """The executable's device assignment, in order, as device ids
    (None for a program with no array in or out)."""
    import jax
    shardings = jax.tree_util.tree_leaves(
        (compiled.input_shardings, compiled.output_shardings))
    if not shardings:
        return None
    return [d.id for d in shardings[0]._device_assignment]


def _devices_by_id(ids: Optional[List[int]]):
    """``execution_devices`` for ``deserialize_and_load``: since jaxlib
    0.9 it defaults to ALL of the backend's devices, which a program
    compiled for fewer refuses at its first call."""
    if ids is None:
        return None
    import jax
    by_id = {d.id: d for d in jax.devices()}
    return [by_id[i] for i in ids]


class ExecutableCache:
    """On-disk executable store with atomic writes, corruption-safe
    loads, and an LRU size cap.  One instance per directory per
    process (see :func:`get_cache`); safe under concurrent processes
    because every mutation is a whole-file rename or unlink."""

    def __init__(self, cache_dir: str, max_mb: Optional[float] = None,
                 write_enabled: bool = True):
        self.dir = os.path.abspath(cache_dir)
        os.makedirs(self.dir, exist_ok=True)
        if max_mb is None:
            try:
                from analytics_zoo_tpu.common.config import get_config
                max_mb = float(get_config().get(
                    "compile.cache_max_mb", 2048))
            except Exception:   # noqa: BLE001
                max_mb = 2048.0
        self.max_bytes = int(max_mb * (1 << 20)) if max_mb > 0 else 0
        self.write_enabled = bool(write_enabled)
        self._lock = threading.Lock()

    # --------------------------------------------------------------- paths
    def path_for(self, key: str) -> str:
        return os.path.join(self.dir, key + ENTRY_SUFFIX)

    def entries(self) -> List[str]:
        try:
            return sorted(f for f in os.listdir(self.dir)
                          if f.endswith(ENTRY_SUFFIX))
        except OSError:
            return []

    # ---------------------------------------------------------------- load
    def load(self, key: str):
        """Deserialize the entry for ``key`` into a live
        ``jax.stages.Compiled``, or None (miss).  A present-but-bad
        entry — torn write, hand-edit, version skew — is EVICTED with
        a loud counter and becomes a miss; it can never crash the
        caller or poison a training step."""
        path = self.path_for(key)
        if not os.path.exists(path):
            return None
        try:
            with open(path, "rb") as f:
                doc = pickle.load(f)
            meta = doc["meta"]
            current = runtime_versions()
            if meta.get("versions") != current:
                raise _StaleEntry(
                    f"entry built by {meta.get('versions')}, running "
                    f"{current}")
            from jax.experimental import serialize_executable as se
            exe = se.deserialize_and_load(
                *doc["payload"],
                execution_devices=_devices_by_id(meta.get("device_ids")))
        except _StaleEntry as e:
            # read-only processes (farm workers, cache_write=false)
            # must never mutate the shared directory: a worker on a
            # skewed jax build unlinking host-0's valid entry would
            # cold-start every SAME-version peer on the fleet.  For
            # them a stale entry is just a miss; the writer evicts.
            log.warning(
                "compile cache: %s VERSION-STALE entry %s (%s); "
                "treating as a miss",
                "evicting" if self.write_enabled else "ignoring",
                os.path.basename(path), e)
            _count_error("stale")
            if self.write_enabled:
                self._evict_file(path)
            return None
        except Exception:   # noqa: BLE001 — corrupt-entry contract
            log.warning(
                "compile cache: %s unreadable/corrupt entry %s; "
                "treating as a miss",
                "evicting" if self.write_enabled else "ignoring",
                os.path.basename(path), exc_info=True)
            _count_error("corrupt")
            if self.write_enabled:
                self._evict_file(path)
            return None
        if self.write_enabled:
            try:
                os.utime(path, None)   # LRU recency on hit
            except OSError:
                pass
        return exe

    # --------------------------------------------------------------- store
    def store(self, key: str, compiled, key_hint: str = "") -> bool:
        """Serialize + persist atomically (write-then-rename): a
        concurrent writer on the same key — two farm hosts, two bench
        children — cannot tear the entry; both produce identical
        content and the last rename wins.  Returns whether the entry
        landed.  Backends that cannot serialize executables degrade to
        False with a loud counter (the in-memory AOT executable still
        serves this process)."""
        if not self.write_enabled:
            return False
        try:
            from jax.experimental import serialize_executable as se
            payload = se.serialize(compiled)
            blob = pickle.dumps({
                "meta": {
                    "versions": runtime_versions(),
                    "key_hint": key_hint,
                    "created_unix": round(time.time(), 1),
                    "device_ids": _device_ids(compiled),
                },
                "payload": payload,
            })
        except Exception:   # noqa: BLE001 — non-serializing backend
            log.warning(
                "compile cache: backend cannot serialize executable "
                "for %r; entry not persisted (in-memory AOT still "
                "active)", key_hint or key, exc_info=True)
            _count_error("serialize")
            return False
        path = self.path_for(key)
        try:
            fd, tmp = tempfile.mkstemp(
                dir=self.dir, prefix=".tmp-" + key[:16] + "-")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                os.replace(tmp, path)   # atomic on one filesystem
            except BaseException:
                try:
                    os.remove(tmp)
                except OSError:
                    pass
                raise
        except Exception:   # noqa: BLE001 — full disk, permissions...
            log.warning("compile cache: could not persist entry %s",
                        os.path.basename(path), exc_info=True)
            _count_error("io")
            return False
        try:
            _counter("compile_cache_writes_total",
                     "executable-cache entries persisted").inc()
        except Exception:   # noqa: BLE001
            pass
        self._enforce_cap()
        return True

    # ------------------------------------------------------------ eviction
    def _evict_file(self, path: str) -> None:
        try:
            os.remove(path)
        except OSError:
            pass

    def _enforce_cap(self) -> None:
        """LRU sweep: drop oldest-by-mtime entries until the directory
        fits ``compile.cache_max_mb``.  mtime is bumped on every hit,
        so recency ordering is true LRU across processes sharing the
        directory."""
        if self.max_bytes <= 0:
            return
        with self._lock:
            try:
                stats = []
                for name in self.entries():
                    p = os.path.join(self.dir, name)
                    try:
                        st = os.stat(p)
                    except OSError:
                        continue
                    stats.append((st.st_mtime, st.st_size, p))
                total = sum(s[1] for s in stats)
                if total <= self.max_bytes:
                    return
                stats.sort()   # oldest first
                evicted = 0
                for mtime, size, p in stats:
                    if total <= self.max_bytes:
                        break
                    self._evict_file(p)
                    total -= size
                    evicted += 1
                if evicted:
                    log.info(
                        "compile cache: LRU-evicted %d entr%s to fit "
                        "the %.0f MB cap (%s)", evicted,
                        "y" if evicted == 1 else "ies",
                        self.max_bytes / (1 << 20), self.dir)
                    try:
                        _counter(
                            "compile_cache_evictions_total",
                            "executable-cache entries LRU-evicted to "
                            "honor compile.cache_max_mb").inc(evicted)
                    except Exception:   # noqa: BLE001
                        pass
            except Exception:   # noqa: BLE001 — the sweep is advisory
                log.debug("compile cache: LRU sweep failed",
                          exc_info=True)


# ------------------------------------------------------------- singleton
_caches: Dict[str, ExecutableCache] = {}
_caches_lock = threading.Lock()


def get_cache() -> Optional[ExecutableCache]:
    """The process cache for the currently-resolved directory, or None
    when AOT caching is off (no dir configured, or ``compile.aot``
    false).  Farm mode (run-dir-derived dir) enables writes on host 0
    only; everyone reads."""
    try:
        from analytics_zoo_tpu.common.config import get_config
        cfg = get_config()
        if not bool(cfg.get("compile.aot", True)):
            return None
        resolved = resolve_cache_dir()
        if resolved is None:
            return None
        cache_dir, farm = resolved
        cache_dir = os.path.abspath(cache_dir)
        with _caches_lock:
            cache = _caches.get(cache_dir)
            if cache is None:
                write = bool(cfg.get("compile.cache_write", True)) and \
                    (not farm or _process_id() == 0)
                cache = ExecutableCache(cache_dir, write_enabled=write)
                _caches[cache_dir] = cache
        return cache
    except Exception:   # noqa: BLE001 — cache resolution must never
        log.debug("compile cache resolution failed", exc_info=True)
        return None     # break a training/serving path


def reset_cache_state() -> None:
    """Drop the per-directory cache singletons (test helper — config
    or write-policy changes take effect on the next resolve)."""
    with _caches_lock:
        _caches.clear()
