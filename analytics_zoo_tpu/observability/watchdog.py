"""Training-health watchdog: non-finite loss/grad detection, loss
divergence/plateau detection over a sliding window, and a stall
heartbeat — with a configurable policy.

The reference surfaced run health through driver logs and validation
summaries; a silently-NaN'd run was only visible when someone read the
loss curve.  Here the health signals are *first-class*: the jitted
train step folds a ``jnp.isfinite`` reduction over loss+grads into its
program and returns the flag beside the loss, which the driver loop
reads where it already blocks on the device; the driver loop also
feeds observed losses and heartbeats;
a background thread flags stalls when no step completes within a
deadline.  The policy decides what an unhealthy signal does:

* ``warn`` — structured log + metrics, training continues;
* ``checkpoint_and_halt`` — the Estimator snapshots through its
  checkpoint machinery and raises :class:`TrainingHalted` (which the
  failure-retry loop deliberately does NOT absorb — retrying a NaN'd
  step would replay the same poison).

Plateau and stall are *advisory* (always warn-only): halting a run for
a plateau would turn early stopping into a crash; a truly stalled loop
cannot run the halting code anyway, so the heartbeat thread's loud log
line and health gauge are the honest best-effort.

Metrics: ``train_nonfinite_total{source}``,
``watchdog_events_total{kind}``, ``train_health_status``
(0 healthy / 1 warned / 2 halt-pending).
"""

from __future__ import annotations

import logging
import math
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from analytics_zoo_tpu.observability.metrics import (
    MetricsRegistry, get_registry)

log = logging.getLogger("analytics_zoo_tpu.observability")

HEALTHY, WARNED, HALT_PENDING = 0, 1, 2


class TrainingHalted(RuntimeError):
    """Raised by the ``checkpoint_and_halt`` policy after the halt
    snapshot is written.  Carries ``issue`` (the triggering event
    dict) so callers can render the reason without parsing the
    message."""

    def __init__(self, message: str, issue: Optional[Dict] = None):
        super().__init__(message)
        self.issue = issue or {}


class TrainingWatchdog:
    """Aggregates health signals from three producers — the in-jit
    finite check's drained flags, the driver loop
    (``beat``/``observe_loss``), and the stall monitor thread — into a
    queue of *issues* the driver polls between steps.

    ``clock`` is injectable for tests (defaults to
    ``time.monotonic``); all interval math uses it.
    """

    HALTING_KINDS = ("nonfinite", "divergence")

    def __init__(self, policy: Optional[str] = None,
                 window: Optional[int] = None,
                 min_delta: Optional[float] = None,
                 divergence: Optional[float] = None,
                 stall_timeout_s: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic,
                 registry: Optional[MetricsRegistry] = None):
        from analytics_zoo_tpu.common.config import get_config
        cfg = get_config()
        self.policy = str(policy if policy is not None else cfg.get(
            "observability.watchdog_policy", "warn"))
        if self.policy not in ("warn", "checkpoint_and_halt"):
            raise ValueError(
                f"watchdog policy {self.policy!r}: expected 'warn' or "
                "'checkpoint_and_halt'")
        self.window = int(window if window is not None else cfg.get(
            "observability.watchdog_window", 50))
        self.min_delta = float(
            min_delta if min_delta is not None
            else cfg.get("observability.watchdog_min_delta", 1e-4))
        self.divergence = float(
            divergence if divergence is not None
            else cfg.get("observability.watchdog_divergence", 10.0))
        self.stall_timeout_s = float(
            stall_timeout_s if stall_timeout_s is not None
            else cfg.get("observability.watchdog_stall_s", 0.0))
        self._clock = clock
        self._registry = registry
        self._lock = threading.Lock()
        self._issues: List[Dict] = []
        self._best = math.inf
        self._since_improve = 0
        self._observed = 0
        self._nonfinite_seen = 0
        self._diverged_fired = False
        self._stall_fired = False
        self._drift_fired: set = set()   # series currently in episode
        self._last_beat = clock()
        self._halted = False
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._set_status(HEALTHY)

    def _reg(self) -> MetricsRegistry:
        return self._registry if self._registry is not None \
            else get_registry()

    def _set_status(self, value: int) -> None:
        try:
            self._reg().gauge(
                "train_health_status",
                "watchdog verdict: 0 healthy, 1 warned, 2 halt "
                "pending/halted").set(value)
        except Exception:
            pass

    def _push(self, kind: str, **detail) -> None:
        issue = {"kind": kind, **detail}
        with self._lock:
            self._issues.append(issue)
        try:
            self._reg().counter(
                "watchdog_events_total",
                "training-health events by kind",
                labels=("kind",)).labels(kind).inc()
        except Exception:
            pass
        # every watchdog episode is a flight event: the single
        # chokepoint all detectors (nonfinite/divergence/plateau/
        # stall/drift) funnel through
        try:
            from analytics_zoo_tpu.observability.flightrec import \
                record_event
            record_event("watchdog.episode", issue=kind, **detail)
        except Exception:   # noqa: BLE001 — forensics never halts health
            pass

    # ------------------------------------------------------- producers
    def beat(self) -> None:
        """A train step completed — feeds the stall deadline.  A beat
        after a flagged stall ends that episode and re-arms the
        detector for the next one."""
        with self._lock:     # vs the heartbeat thread's check_stall
            self._last_beat = self._clock()
            self._stall_fired = False

    def record_nonfinite(self, source: str = "step") -> None:
        """A non-finite loss/grad was detected (a drained in-jit flag
        or a driver-side isfinite check).  The counter counts every
        occurrence; the ISSUE (and its warning log) is throttled —
        under the warn policy a permanently-NaN run would otherwise
        log once per step."""
        try:
            self._reg().counter(
                "train_nonfinite_total",
                "steps whose loss or gradients were non-finite",
                labels=("source",)).labels(source).inc()
        except Exception:
            pass
        with self._lock:
            self._nonfinite_seen += 1
            n = self._nonfinite_seen
        if n == 1 or n % 100 == 0:
            self._push("nonfinite", source=source, occurrences=n)

    def observe_loss(self, loss: float) -> None:
        """Feed a host-synced loss sample (logging crossings / epoch
        ends — never forces an extra device sync)."""
        try:
            loss = float(loss)
        except (TypeError, ValueError):
            return
        if not math.isfinite(loss):
            self.record_nonfinite("loss_sample")
            return
        self._observed += 1
        scale = max(abs(self._best), 1.0)
        if not math.isfinite(self._best) \
                or loss < self._best - self.min_delta * scale:
            # first finite sample seeds best (inf arithmetic would
            # otherwise NaN the comparison and freeze it forever)
            self._best = loss
            self._since_improve = 0
            self._diverged_fired = False
            return
        self._since_improve += 1
        if (not self._diverged_fired
                and math.isfinite(self._best)
                and loss - self._best > self.divergence * scale):
            self._diverged_fired = True   # once until a new best
            self._push("divergence", loss=loss, best=self._best,
                       factor=self.divergence)
        if self.window > 0 and self._since_improve >= self.window:
            self._since_improve = 0       # re-arm: one event per window
            self._push("plateau", best=self._best, window=self.window,
                       min_delta=self.min_delta)

    def observe_drift(self, series: str, score: float) -> None:
        """Advisory drift signal from ``observability/drift.py``: a
        normalized score (1.0 = at the detector's z-threshold) for a
        watched metric series.  Like plateau/stall, drift never halts
        — a distribution shift is a reason to LOOK at a run, not to
        kill it — but it rides the same issue queue and
        ``watchdog_events_total{kind="drift"}`` counter so the driver
        loop and obs_report surface it next to loss-health events.
        One event per episode: re-arms when the series drops back
        under threshold."""
        try:
            score = float(score)
        except (TypeError, ValueError):
            return
        if score < 1.0:
            self._drift_fired.discard(series)
            return
        if series in self._drift_fired:
            return
        self._drift_fired.add(series)
        self._push("drift", series=series, score=round(score, 3))

    # ---------------------------------------------------- stall monitor
    def check_stall(self) -> bool:
        """One stall check against the injectable clock (the heartbeat
        thread calls this; tests call it directly with a fake clock)."""
        if self.stall_timeout_s <= 0:
            return False
        # guard and flag-set under one lock so a beat() landing between
        # them can't be stomped by a stale stall verdict; _push takes
        # the same (non-reentrant) lock, so it runs after release
        with self._lock:
            if self._stall_fired:
                return False
            idle = self._clock() - self._last_beat
            if idle <= self.stall_timeout_s:
                return False
            self._stall_fired = True      # once per stall episode
        self._push("stall", idle_s=round(idle, 1),
                   deadline_s=self.stall_timeout_s)
        log.error(
            "training stall: no step completed in %.0fs (deadline "
            "%.0fs) — the loop may be hung in dispatch, a collective, "
            "or the input pipeline", idle, self.stall_timeout_s)
        self._set_status(HALT_PENDING if self._halted else WARNED)
        return True

    def start_stall_monitor(self) -> "TrainingWatchdog":
        """Daemon heartbeat thread; no-op when the deadline is 0."""
        if self.stall_timeout_s <= 0 or self._thread is not None:
            return self
        # arm the deadline NOW: time between construction and start
        # (checkpoint restore, cache placement) is setup, not a stall
        self.beat()
        self._stop.clear()

        def run():
            while not self._stop.is_set():
                try:
                    self.check_stall()
                except Exception:
                    log.exception("stall check failed")
                self._stop.wait(min(self.stall_timeout_s / 4.0, 10.0))

        self._thread = threading.Thread(
            target=run, daemon=True, name="zoo-train-watchdog")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    # -------------------------------------------------------- consumer
    def poll(self) -> Optional[Dict]:
        """Drain the next pending issue (driver loop, between steps).

        Every issue is logged with structure; the return value is the
        first HALTING-ELIGIBLE issue when the policy is
        ``checkpoint_and_halt`` (the caller then snapshots and raises
        :class:`TrainingHalted`), else None."""
        halting = None
        while True:
            with self._lock:
                issue = self._issues.pop(0) if self._issues else None
            if issue is None:
                break
            log.warning("training-health event: %s", issue)
            self._set_status(WARNED)
            if (halting is None
                    and self.policy == "checkpoint_and_halt"
                    and issue["kind"] in self.HALTING_KINDS):
                halting = issue
        if halting is not None:
            self._halted = True
            self._set_status(HALT_PENDING)
        return halting

    def halted(self) -> bool:
        return self._halted


# -------------------------------------------------- process-wide hookup
_active_watchdog: Optional[TrainingWatchdog] = None
_active_lock = threading.Lock()


def set_active_watchdog(wd: Optional[TrainingWatchdog]
                        ) -> Optional[TrainingWatchdog]:
    """Install the watchdog the drained finite flags report to;
    returns the previous one (restore it in a ``finally``)."""
    global _active_watchdog
    with _active_lock:
        prev = _active_watchdog
        _active_watchdog = wd
    return prev


def get_active_watchdog() -> Optional[TrainingWatchdog]:
    return _active_watchdog


def fold_finiteness_check(loss, grads):
    """IN-JIT: fold an ``isfinite(loss + Σ grads)`` reduction into the
    traced step (NaN/Inf propagate through the sums — one add-reduce
    per grad leaf) and return the flag, a bool scalar the step hands
    back beside its loss.  The single implementation both engines'
    step builders call, so the detection logic cannot diverge between
    them; the host reads the flag through :class:`PendingFiniteFlags`."""
    import jax
    import jax.numpy as jnp
    total = loss.astype(jnp.float32)
    for g in jax.tree_util.tree_leaves(grads):
        total = total + jnp.sum(g).astype(jnp.float32)
    return jnp.isfinite(total)


def record_finite_checks(steps: int, nonfinite: int) -> None:
    """The ONE host function every read flag goes through: ``steps``
    train steps were checked, ``nonfinite`` of them had a non-finite
    loss or gradient.  Each of those is reported once, to the active
    watchdog or the bare counter.  A registry that is down never
    stops training."""
    try:
        get_registry().counter(
            "train_finite_checked_steps_total",
            "train steps whose in-jit finite flag the host has read"
        ).inc(steps)
    except Exception:
        pass
    wd = get_active_watchdog()
    for _ in range(nonfinite):
        if wd is not None:
            wd.record_nonfinite("step")
        else:
            try:
                get_registry().counter(
                    "train_nonfinite_total",
                    "steps whose loss or gradients were non-finite",
                    labels=("source",)).labels("step").inc()
            except Exception:
                pass


class PendingFiniteFlags:
    """The finite checks of dispatched train steps, kept as device
    values until the host reads them.  A step program hands back its
    flag (bool: was the step finite), a scan program the count of its
    non-finite steps; neither is read at dispatch.  ``drain`` reads
    all that is pending — free right after the caller has blocked on
    the newest dispatch, since older flags are ready by program
    order — and reports through :func:`record_finite_checks`.  A
    caller that never drains is bounded: past ``MAX_PENDING`` the
    oldest flag, long since computed, is read at ``keep``."""

    MAX_PENDING = 64

    def __init__(self):
        self._pending: deque = deque()

    def keep(self, value, steps: int = 1) -> None:
        """Hold one dispatch's check (``None``: the check is off)."""
        if value is None:
            return
        self._pending.append((value, steps))
        if len(self._pending) > self.MAX_PENDING:
            self._read([self._pending.popleft()])

    def drain(self) -> None:
        if self._pending:
            pending, self._pending = self._pending, deque()
            self._read(pending)

    @staticmethod
    def _read(pending) -> None:
        import jax
        # plain host reads: nothing compiles, nothing is dispatched.
        # All copies are started before the first is waited for (a
        # scalar's round trip is 0.45 ms on the v5e: sixteen one by
        # one read 7.2 ms, PERF.md PR 26)
        for value, _ in pending:
            try:
                value.copy_to_host_async()
            except Exception:   # noqa: BLE001 — numpy value, or below
                pass
        checked = nonfinite = 0
        for value, steps in pending:
            try:
                value = jax.device_get(value)
            except Exception:
                # the dispatch itself failed (it surfaced at the
                # caller's own sync): no step ran, nothing to check
                log.debug("finite flag unreadable; its dispatch "
                          "failed", exc_info=True)
                continue
            checked += steps
            nonfinite += int(value) if value.dtype != bool \
                else int(not value)
        if checked:
            record_finite_checks(checked, nonfinite)
