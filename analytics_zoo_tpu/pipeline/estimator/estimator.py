"""Estimator — the uniform train/evaluate facade.

Reference: ``Estimator`` (zoo/pipeline/estimator/Estimator.scala:65,
train :118-155, evaluate :163) over InternalDistriOptimizer, with
trigger-driven checkpoint/validation wiring and the failure-retry loop
(Topology.scala:1179-1261): on an exception mid-training it restores the
latest checkpoint (model + optim state + epoch counters) and resumes,
with a bounded retry budget.

TPU version drives the jitted DistributedTrainer step from a host loop:
epochs → (optionally disk slices) → batches; triggers fire on the same
TrainingState snapshots; checkpoints capture params/opt/state/driver
counters in one payload so resume is exact.
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from analytics_zoo_tpu.common.config import get_config
from analytics_zoo_tpu.observability import (
    EPOCH_BUCKETS, flush_worker_observability, get_registry,
    get_tracer, sample_device_telemetry)
from analytics_zoo_tpu.observability.flightrec import record_event
from analytics_zoo_tpu.observability.moe_stats import MoeStatsReader
from analytics_zoo_tpu.observability.watchdog import (
    TrainingHalted, TrainingWatchdog, set_active_watchdog)
from analytics_zoo_tpu.parallel import mesh as mesh_lib
from analytics_zoo_tpu.common.triggers import (
    EveryEpoch, MaxEpoch, TrainingState, Trigger)
from analytics_zoo_tpu.parallel.trainer import ClipSpec, DistributedTrainer
from analytics_zoo_tpu.resilience import (
    DegradedTraining, HostHeartbeat, RecoveryAction, RecoveryPolicy,
    RetryBudget)
from analytics_zoo_tpu.utils.serialization import Checkpoint
from analytics_zoo_tpu.utils.summary import TrainSummary, ValidationSummary

log = logging.getLogger("analytics_zoo_tpu.estimator")


def _train_metrics():
    """Shared-registry instruments for the training loop (get-or-create
    — cheap to call per train())."""
    reg = get_registry()
    return {
        "epoch_seconds": reg.histogram(
            "train_epoch_seconds", "wall time per completed epoch",
            labels=("engine",), buckets=EPOCH_BUCKETS),
        "samples": reg.counter(
            "train_samples_total", "training samples consumed"),
        "throughput": reg.gauge(
            "train_throughput_samples_per_sec",
            "most recent epoch's training throughput"),
        "loss": reg.gauge("train_loss", "most recent sampled loss"),
        "eval_seconds": reg.histogram(
            "train_eval_seconds", "wall time per validation pass"),
        "ckpt_save": reg.counter(
            "checkpoint_save_total", "checkpoint snapshots written"),
        "ckpt_restore": reg.counter(
            "checkpoint_restore_total",
            "checkpoint restores (resume + failure recovery)"),
        "retries": reg.counter(
            "train_retry_total",
            "training-step failures absorbed by the retry loop"),
        # resilience plane: every mid-training failure by taxonomy
        # class, and every recovery action the policy engine took
        # (resilience/policy.py) — degrade/raise outcomes included, so
        # failures == recoveries + raises always balances
        "failures": reg.counter(
            "train_failures_total",
            "mid-training failures by classified cause",
            labels=("class",)),
        "recoveries": reg.counter(
            "train_recovery_total",
            "recovery actions taken by the failure policy engine",
            labels=("action",)),
        # same family the per-step path (trainer.py) counts into
        "steps": reg.counter(
            "train_steps_total", "train steps dispatched",
            labels=("path",)),
    }


def _tree_bytes(tree) -> int:
    return sum(getattr(a, "nbytes", 0)
               for a in jax.tree_util.tree_leaves(tree))


_NOT_FIRST = contextlib.nullcontext()


class _StartupTimeline:
    """One ``train()``'s start-up on the tracer
    (docs/observability.md, "The start-up timeline"): the umbrella
    span ``train_startup`` from the call's first line to just before
    its first dispatch, whose SELF time is what the phases nested in it
    do not name; ``startup_first_dispatch`` round that dispatch (trace,
    lowering, compile or cache read, enqueue); and, at the first host
    read that proves a step has finished, the gauge
    ``train_time_to_first_step_seconds`` with the operator's log
    line."""

    def __init__(self):
        self._t0 = time.perf_counter()
        self._tracer = get_tracer()
        self._umbrella = self._tracer.span("train_startup")
        self._umbrella.__enter__()
        self._reported = False

    def close(self) -> None:
        """End the umbrella, once: before the first dispatch, or when
        ``train()`` is over however it ended (the span does not stay
        on the thread's stack)."""
        if self._umbrella is not None:
            self._umbrella.__exit__(None, None, None)
            self._umbrella = None

    def first_dispatch(self):
        """The context round a dispatch of a train program: a span if
        it is this ``train()``'s first, nothing after it."""
        if self._umbrella is None:
            return _NOT_FIRST
        self.close()
        return self._tracer.span("startup_first_dispatch")

    def first_step_done(self) -> None:
        """Called wherever the host has just blocked on a dispatch's
        result; acts once."""
        if self._reported:
            return
        self._reported = True
        total = time.perf_counter() - self._t0
        get_registry().gauge(
            "train_time_to_first_step_seconds",
            "the newest train() call, from its entry to the first host "
            "read of a finished step's result").set(total)
        # self seconds by span name on this thread since the entry:
        # they partition the time spent under any span (a span still
        # open, as the boundary this may be called in, has none yet)
        own: Dict[str, float] = {}
        for ev in self._tracer.events_since(self._t0):
            dur = ev["dur"] / 1e6
            own[ev["name"]] = own.get(ev["name"], 0.0) + dur
            if ev["parent"] is not None:
                own[ev["parent"]] = own.get(ev["parent"], 0.0) - dur
        log.info("time to first step %.2f s: %s", total, ", ".join(
            "%s %.2f" % pair for pair in
            sorted(own.items(), key=lambda kv: -kv[1])
            if pair[1] >= 0.005))


class _UnrecoverableTraining(RuntimeError):
    """Training state was lost (donated to a failed dispatch) with no
    checkpoint to restore — the retry loop must not spin on it."""


def eval_batches(data_set, batch_size: int):
    """Ordered, masked eval batches from either data layer: a
    ``FeatureSet`` (zero-padded tail + mask) or a ``DataPipeline``
    built with ``remainder="pad"`` (which yields the identical
    ``(x, y, mask)`` shape).  The shared entry for evaluate() and the
    in-training validation pass."""
    from analytics_zoo_tpu.data import DataPipeline
    if isinstance(data_set, DataPipeline):
        if data_set.sampler.remainder != "pad":
            raise ValueError(
                "evaluation needs every sample exactly once: build the "
                "validation DataPipeline with remainder='pad' (and "
                "shuffle=False) so the tail batch is masked, not "
                "dropped")
        return _read_once(data_set)
    return data_set.epoch_batches(0, batch_size, train=False)


def _read_once(pipeline):
    """Epoch 0's batches; the builds the read carried into epoch 1 are
    dropped, since the next evaluation reads epoch 0 again."""
    try:
        for _step, batch in pipeline.iter_epoch(0):
            yield batch
    finally:
        pipeline.drop_stream()


def predict_in_batches(run_batch, x, batch_size: int):
    """Fixed-shape batched prediction: zero-pad the tail batch so one
    compiled program serves every batch, slice the padding back off,
    and concatenate on host.  Shared by Estimator and LocalEstimator."""
    import math
    leaves = jax.tree_util.tree_leaves(x)
    n = len(leaves[0]) if leaves else 0
    if n == 0:
        raise ValueError("predict called with an empty input")
    # Pipelined fetch: a device_get per batch would sync every batch,
    # serializing the loop; keeping ALL
    # results on device until the end risks HBM exhaustion for large
    # outputs. A sliding window keeps `window` batches in flight —
    # dispatch runs ahead while older results stream to host.
    window = 8
    outs, in_flight = [], []
    for b in range(math.ceil(n / batch_size)):
        lo, hi = b * batch_size, min((b + 1) * batch_size, n)
        xb = jax.tree_util.tree_map(lambda a: a[lo:hi], x)
        real = hi - lo
        if real < batch_size:   # pad to keep one compiled shape
            from analytics_zoo_tpu.feature.feature_set import pad_rows
            xb = pad_rows(xb, batch_size - real)
        out = run_batch(xb)
        in_flight.append(jax.tree_util.tree_map(lambda o: o[:real], out))
        if len(in_flight) >= window:
            outs.append(jax.device_get(in_flight.pop(0)))
    outs.extend(jax.device_get(in_flight))
    return jax.tree_util.tree_map(
        lambda *parts: np.concatenate(parts), *outs)


class Estimator:
    def __init__(self, model, optim_method=None,
                 optim_methods: Optional[Dict] = None,
                 model_dir: Optional[str] = None, mesh=None):
        from analytics_zoo_tpu.pipeline.api.keras import optimizers as opt
        self.model = model
        self.optim_method = opt.get(optim_method) \
            if optim_method is not None else None
        self.optim_groups = optim_methods
        self.model_dir = model_dir
        # explicit device mesh (default: the live context mesh) —
        # elastic recovery rebinds this to the re-formed surviving
        # topology so evaluate/predict after a recovered train() run
        # on the topology that actually exists
        self._mesh = mesh
        self._clip: Optional[ClipSpec] = None
        self._train_summary = None
        self._val_summary = None
        self.variables = None
        self.history: List[Dict] = []
        self.train_state = TrainingState()

    # ------------------------------------------------------------- settings
    def set_constant_gradient_clipping(self, min_value, max_value):
        self._clip = ClipSpec("const", float(min_value), float(max_value))

    def set_l2_norm_gradient_clipping(self, clip_norm):
        self._clip = ClipSpec("l2norm", float(clip_norm))

    def clear_gradient_clipping(self):
        self._clip = None

    def set_tensorboard(self, log_dir: str, app_name: str):
        self._train_summary = TrainSummary(log_dir, app_name)
        self._val_summary = ValidationSummary(log_dir, app_name)

    # ------------------------------------------------------------- training
    def train(self, train_set, criterion, end_trigger: Optional[Trigger] = None,
              checkpoint_trigger: Optional[Trigger] = None,
              validation_set=None, validation_method=None,
              batch_size: int = 32, rng=None):
        # the start-up timeline (docs/observability.md): opened here,
        # ended before the first dispatch or, whatever way train() is
        # left, by the finally at its end.  In this frame and not in a
        # wrapper round it: with one (a decorator calling the body by
        # *args) the train program's first lowering read 8.0 s where
        # it reads 4.8 (PERF.md Findings, PR 35)
        startup = _StartupTimeline()
        try:
            tracer = get_tracer()
            from analytics_zoo_tpu.data import DataPipeline, DeviceLoader
            from analytics_zoo_tpu.feature.feature_set import FeatureSet
            assert self.optim_method or self.optim_groups, \
                "Estimator needs an optim_method to train"
            from analytics_zoo_tpu.pipeline.api.keras import objectives
            criterion = objectives.get(criterion)
            end_trigger = end_trigger or MaxEpoch(1)
            checkpoint_trigger = checkpoint_trigger or EveryEpoch()
            rng = rng if rng is not None else jax.random.PRNGKey(
                int(get_config().get("data.shuffle_seed")))

            is_pipeline = isinstance(train_set, DataPipeline)
            if is_pipeline:
                # the pipeline owns its batch geometry (it is part of the
                # checkpointed stream identity) — the argument is ignored
                batch_size = train_set.batch_size
            trainer = DistributedTrainer(
                self.model, criterion, optim_method=self.optim_method,
                mesh=self._mesh, clip=self._clip,
                optim_groups=self.optim_groups)
            # The global batch must tile the data-parallel mesh (the analogue
            # of BigDL's batchSize % totalCores == 0 requirement).
            mesh_lib.local_batch_size(trainer.mesh, batch_size)
            if not is_pipeline and \
                    getattr(train_set, "size", batch_size) < batch_size:
                raise ValueError(
                    f"batch_size {batch_size} exceeds dataset size "
                    f"{train_set.size}: no full training batch can be formed "
                    "(training drops the remainder batch)")

            # --- init / restore -------------------------------------------------
            if self.variables is None:
                self.variables = self.model.get_variables()
            with tracer.span("startup_place_state",
                             bytes=_tree_bytes(self.variables)):
                params = trainer.place_params(self.variables["params"])
                state = trainer.replicate(self.variables["state"])
                opt_state = trainer.init_opt_state(params)
            trainer.after_device_sync = startup.first_step_done

            ckpt = Checkpoint(self.model_dir) if self.model_dir else None
            ts = self.train_state
            met = _train_metrics()

            # training-health watchdog: collects the in-jit finite check's
            # flags (trainer._step_core; drained where this loop already
            # blocks on the device), the losses observed at sync points,
            # and the stall heartbeat; health_check() runs between steps
            # and applies the policy.  (Installed as the ACTIVE
            # watchdog just before the training loop — see below — so a
            # failure in restore/cache setup can't leak the thread.)
            watchdog = TrainingWatchdog()
            # worker liveness heartbeat (launcher run-dir contract,
            # resilience/detector.py): a throttled file write so the
            # launcher's check_health can tell a slow worker from one
            # wedged in a dead collective.  None outside a run dir.
            heartbeat = HostHeartbeat.from_env()

            def beat():
                watchdog.beat()
                if heartbeat is not None:
                    heartbeat.beat(ts.iteration)
            # dedupe loss observations by iteration: several sync points
            # (logging crossings, dispatch branches, epoch end) may hold
            # the same already-synced loss — observing it once per
            # iteration keeps the plateau window meaning what the config
            # says
            last_observed_iter = [-1]

            def observe_loss_once(value):
                if ts.iteration != last_observed_iter[0]:
                    last_observed_iter[0] = ts.iteration
                    watchdog.observe_loss(value)

            def health_check():
                issue = watchdog.poll()
                if issue is None:
                    return
                # checkpoint_and_halt: snapshot through the normal
                # checkpoint machinery, but into <model_dir>/halt/ — the
                # halt-time state may itself be poisoned (NaN params), and
                # a poisoned snapshot.N.ckpt at the HIGHEST step would
                # shadow the last good periodic snapshot on the next
                # restore_latest.  Then stop in a way the retry loop will
                # NOT absorb: retrying a NaN'd step replays the same
                # poison.
                log.error("watchdog halting training: %s", issue)
                if ckpt is not None:
                    halt_dir = os.path.join(self.model_dir, "halt")
                    save_snapshot(target=Checkpoint(halt_dir))
                    log.error(
                        "halt-time state snapshotted to %s (iteration %d); "
                        "resume from model_dir restores the last GOOD "
                        "periodic snapshot", halt_dir, ts.iteration)
                raise TrainingHalted(
                    f"training halted by watchdog policy "
                    f"'checkpoint_and_halt' at iteration {ts.iteration}: "
                    f"{issue}", issue=issue)

            def restore_snapshot(like):
                """ckpt.restore_latest with a span + restore counter (all
                restore sites — resume, HBM-cache recovery, retry loop —
                go through here so the counter is a complete record).  When
                training from a DataPipeline, ``like`` carries a ``data``
                slot; a LEGACY checkpoint (saved before the pipeline layer
                existed) lacks it, so retry without — the position then
                stays wherever the pipeline is, matching the old
                replay-the-epoch semantics."""
                if ckpt is None:
                    return None
                with tracer.span("checkpoint_restore", jax_annotation=True):
                    try:
                        restored = ckpt.restore_latest(like)
                    except (ValueError, KeyError):
                        if "data" not in like:
                            raise
                        like = {k: v for k, v in like.items() if k != "data"}
                        restored = ckpt.restore_latest(like)
                        if restored is not None:
                            log.warning(
                                "checkpoint has no data-pipeline state "
                                "(pre-pipeline snapshot); restored model "
                                "state only — the epoch's batches replay "
                                "from the pipeline's current position")
                if restored is not None:
                    met["ckpt_restore"].inc()
                return restored

            def snapshot_like():
                """The restore target, built from the CURRENT device trees
                (late-bound locals)."""
                like = {"params": params, "state": state,
                        "opt_state": opt_state, "epoch": 0, "iteration": 0}
                if is_pipeline:
                    like["data"] = train_set.state_dict()
                return like

            def restore_data_state(restored) -> None:
                """Seek the pipeline to the checkpointed position so the
                resumed run consumes the exact next batch (no replayed or
                skipped samples)."""
                if is_pipeline and restored is not None \
                        and restored.get("data") is not None:
                    train_set.load_state_dict(restored["data"])

            if ckpt is not None:
                restored = restore_snapshot(snapshot_like())
                if restored is not None:
                    with tracer.span("startup_place_state",
                                     bytes=_tree_bytes(restored["params"])
                                     + _tree_bytes(restored["state"])
                                     + _tree_bytes(restored["opt_state"])):
                        params = trainer.place_params(restored["params"])
                        state = trainer.replicate(restored["state"])
                        opt_state = trainer.place_like(
                            restored["opt_state"], opt_state)
                    ts.epoch = int(restored["epoch"])
                    ts.iteration = int(restored["iteration"])
                    restore_data_state(restored)
                    log.info("resumed from checkpoint at epoch %d iter %d",
                             ts.epoch, ts.iteration)

            # iteration count at entry to THIS call — "no step committed
            # yet" for the HBM-cache recovery below means no step beyond
            # this point, not zero lifetime iterations (a second train()
            # call starts with the previous call's counter)
            start_iteration = ts.iteration
            # the pipeline position at entry: the rebuild-from-entry-copy
            # recovery path must rewind the stream too, or the batches a
            # doomed dispatch consumed would be silently skipped
            entry_data_state = train_set.state_dict() if is_pipeline else None

            eval_runner = None
            if validation_set is not None and validation_method:
                eval_runner = trainer.make_eval_runner(validation_method)

            # failure policy engine (resilience/policy.py): the reference's
            # time-windowed retry budget (bigdl.failure.retryTimes /
            # retryTimeInterval, Topology.scala:1179-1261) is the
            # TRANSIENT branch; classified lost-host failures re-form the
            # mesh instead, poisoned state always raises.  RetryBudget
            # runs on the monotonic clock: a wall-clock (NTP) adjustment
            # must not reset or starve the budget.
            cfg = get_config()
            policy = RecoveryPolicy(
                RetryBudget(int(cfg.get("train.retry_times")),
                            float(cfg.get("train.retry_interval_s"))),
                elastic=bool(cfg.get("train.elastic", True)),
                max_reformations=int(
                    cfg.get("train.max_mesh_reformations", 2)))

            # --- epoch loop -----------------------------------------------------
            def save_snapshot(target=None):
                # fetch_global is a COLLECTIVE (cross-process allgather for
                # non-addressable shards) — every process must run it; only
                # the coordinator writes the file, like the reference's
                # driver-side snapshot (Topology.scala:1293). Restore assumes
                # model_dir is on a filesystem all hosts can read.
                # ``target`` overrides the destination Checkpoint (the
                # watchdog's halt snapshot goes to model_dir/halt/).
                with tracer.span("checkpoint_save", jax_annotation=True,
                                 iteration=ts.iteration):
                    # one fetch: every leaf's copy in flight at once
                    host_params, host_state, host_opt = \
                        mesh_lib.fetch_global((params, state, opt_state),
                                              site="checkpoint_save")
                    payload = {"params": host_params, "state": host_state,
                               "opt_state": host_opt,
                               "epoch": ts.epoch, "iteration": ts.iteration}
                    if is_pipeline:
                        # the pipeline position points at the NEXT batch to
                        # deliver (committed per consumed batch), so this
                        # snapshot resumes mid-epoch exactly
                        payload["data"] = train_set.state_dict()
                    if jax.process_index() == 0:
                        (ckpt if target is None else target).save(
                            payload, step=ts.iteration)
                        # counted only where the file is actually written,
                        # so per-host scrapes reflect per-host truth
                        met["ckpt_save"].inc()

            # Chunked dispatch (train.steps_per_dispatch): fuse k steps into
            # one lax.scan dispatch — per-step host/dispatch overhead drops
            # ~k-fold while HBM holds only k x batch rows.  Only when semantics are provably
            # unchanged: epoch-scoped triggers (iteration-level triggers
            # must fire mid-epoch at exact steps), a single slice, and the
            # EXACT FeatureSet class (subclasses may override epoch_batches
            # with streaming/failure semantics that chunking would bypass).
            device_loader = DeviceLoader(train_set, put_fn=trainer.put_batch) \
                if is_pipeline else None

            chunk_steps = int(get_config().get("train.steps_per_dispatch"))
            use_chunks = (chunk_steps > 1
                          and getattr(train_set, "num_slices", 1) == 1
                          and type(train_set) is FeatureSet
                          and isinstance(end_trigger, MaxEpoch)
                          and isinstance(checkpoint_trigger, EveryEpoch))
            chunk_fns: Dict[int, object] = {}

            # HBM epoch cache (train.hbm_cache_mb): under the same
            # semantics-preserving conditions as chunking, if the WHOLE
            # epoch (source + one permuted copy) fits the budget, place it
            # on device ONCE and reshuffle it on-device each epoch with the
            # FeatureSet's own deterministic permutation — zero per-epoch
            # H2D, one dispatch per epoch. This is the device tier of the
            # reference's cache hierarchy (FeatureSet.scala:585-662) made
            # automatic. Single-process only: multi-host placement treats
            # host arrays as per-process shards, which put_epoch_source
            # does not model.
            hbm_src = None
            hbm_mb = float(get_config().get("train.hbm_cache_mb"))
            if use_chunks and hbm_mb > 0 and jax.process_count() == 1:
                nbytes = _tree_bytes((train_set.x, train_set.y))
                if 2 * nbytes <= hbm_mb * (1 << 20):
                    # size guard at entry ensures nb_epoch >= 1
                    nb_epoch = train_set.size // batch_size
                    epoch_rows = nb_epoch * batch_size
                    try:
                        hbm_src = trainer.put_epoch_source(train_set.x,
                                                           train_set.y)
                        hbm_permute = trainer.permute_rows_fn()
                        hbm_scan = trainer.epoch_scan_fn(nb_epoch,
                                                         batch_size)
                    except Exception:
                        # the budget gate can't see free HBM — if the
                        # placement itself OOMs, train chunked instead
                        hbm_src = None
                        log.warning(
                            "HBM epoch cache placement failed; falling "
                            "back to chunked dispatch", exc_info=True)
                    else:
                        log.info(
                            "HBM epoch cache active: %.1f MB on device, "
                            "%d steps/epoch in one dispatch, on-device "
                            "reshuffle", nbytes / (1 << 20), nb_epoch)
            hbm_train_bytes = 2 * nbytes if hbm_src is not None else 0

            # Eval-batch HBM cache: eval iterates the SAME epoch-0 batches
            # every time (ordered, no shuffle), so when they fit the budget
            # ALONGSIDE the train cache they are placed on device once and
            # reused — validation stops re-uploading its dataset every
            # epoch. Single-process only (same reason as the train cache);
            # `None` in the holder = stream from host.
            eval_cache_holder = [None]
            if (eval_runner is not None and hbm_mb > 0
                    and jax.process_count() == 1
                    and type(validation_set) is FeatureSet):
                # exact-class check like the train cache: subclasses may
                # override epoch_batches with per-call semantics (fresh
                # augmentation, changing source) that freezing would break
                val_bytes = _tree_bytes((validation_set.x, validation_set.y))
                if val_bytes + hbm_train_bytes <= hbm_mb * (1 << 20):
                    try:
                        eval_cache_holder[0] = [
                            trainer.put_batch(b) for b in
                            validation_set.epoch_batches(
                                0, batch_size, train=False)]
                        log.info("eval-batch HBM cache active: %.1f MB "
                                 "on device", val_bytes / (1 << 20))
                    except Exception:
                        eval_cache_holder[0] = None
                        log.warning("eval-batch HBM cache placement "
                                    "failed; streaming per epoch",
                                    exc_info=True)

            def run_eval(params, state):
                """Eval with the cached device batches when available; on
                a dispatch failure (e.g. OOM from the added resident HBM)
                release the cache and retry streaming from host."""
                t0 = time.perf_counter()
                try:
                    with tracer.span("eval", jax_annotation=True,
                                     iteration=ts.iteration):
                        if eval_cache_holder[0] is not None:
                            try:
                                return eval_runner(params, state,
                                                   eval_cache_holder[0])
                            except Exception:
                                eval_cache_holder[0] = None
                                log.warning(
                                    "eval failed with cached batches; "
                                    "released the cache, retrying streamed",
                                    exc_info=True)
                        return eval_runner(
                            params, state,
                            eval_batches(validation_set, batch_size))
                finally:
                    met["eval_seconds"].observe(time.perf_counter() - t0)

            def sync_loss(loss, it0) -> float:
                """Every host read of a loss: the host blocks here until
                the dispatch that produced it has run.  ``loss`` is always
                the newest dispatch's, so every pending finite flag is
                ready by program order: they are read here, at no wait."""
                with tracer.span("train_loss_sync", jax_annotation=True,
                                 iteration=it0):
                    value = float(loss)
                startup.first_step_done()
                trainer.drain_finite()
                # the same dispatch produced ``state``: the expert layers'
                # counts are ready too, and are read at no wait
                moe_stats.read(state, it0)
                return value

            def log_loss_crossing(loss, k):
                """Sync + log when the iteration counter crosses a
                20-multiple (same cadence as the per-step path, without a
                device sync per dispatch)."""
                if (ts.iteration // 20) != ((ts.iteration - k) // 20):
                    ts.last_loss = sync_loss(loss, ts.iteration - k)
                    met["loss"].set(ts.last_loss)
                    # already-synced loss → watchdog divergence/plateau/
                    # NaN detection at zero extra device cost
                    observe_loss_once(ts.last_loss)
                    if self._train_summary is not None:
                        self._train_summary.add_scalar(
                            "Loss", ts.last_loss, ts.iteration)

            def boundary(it0, loss, k, fused=False, epoch_loss=False) -> bool:
                """The host's work between two dispatches, as ONE span
                (``it0``: the first step of the dispatch just made, ``k``
                its steps; ``fused``: a scan dispatch, whose collectives
                are accounted here; ``epoch_loss``: ``ts.last_loss`` was
                read at this dispatch's end).  Returns whether the end
                trigger fired."""
                with tracer.span("train_boundary", jax_annotation=True,
                                 iteration=it0):
                    if fused:
                        trainer.account_collectives(params, k)
                    log_loss_crossing(loss, k)
                    beat()
                    if epoch_loss:
                        observe_loss_once(ts.last_loss)
                    # iteration-level triggers fire mid-epoch; EveryEpoch
                    # (all the scan engines admit) answers False here
                    save = ckpt is not None and checkpoint_trigger(ts)
                    if save:
                        # the snapshot blocks on every dispatched step
                        # anyway: read their flags first, so that a
                        # non-finite step halts instead of being saved as
                        # the newest good snapshot
                        trainer.drain_finite()
                    health_check()
                    if save:
                        save_snapshot()
                    return bool(end_trigger(ts))

            # Warm-start (docs/aot-compile.md): compile the per-step train
            # program, or read it from JAX's persistent compilation cache,
            # under its own span (aot_warm_start) before the first
            # dispatched step, which then finds the executable in jit's
            # cache.  Per-step/pipeline paths only: the fused paths (hbm
            # scan, chunked) compile on first dispatch.  The peeked batch
            # is NOT consumed: the pipeline position only commits per
            # batch the DeviceLoader delivers, and epoch_batches is a
            # fresh generator every epoch.
            if hbm_src is None and not use_chunks and \
                    getattr(train_set, "num_slices", 1) == 1:
                warm_batch = None
                try:
                    # one batch built on this thread (the pipeline's
                    # workers start with the first epoch)
                    with tracer.span("startup_loader"):
                        if is_pipeline:
                            warm_batch = train_set.batch_at(
                                train_set.epoch, train_set.step)
                        elif type(train_set) is FeatureSet:
                            # exact-class guard, same as the HBM/eval
                            # caches: subclasses may have per-call
                            # epoch_batches semantics (fresh augmentation,
                            # a consuming source) that an extra peek would
                            # disturb
                            warm_batch = next(iter(train_set.epoch_batches(
                                ts.epoch, batch_size, train=True)))
                except StopIteration:
                    warm_batch = None
                except Exception:   # noqa: BLE001 — warm is best-effort
                    log.debug("could not peek a warm-start batch",
                              exc_info=True)
                if warm_batch is not None:
                    trainer.warm_start(params, opt_state, state,
                                       warm_batch, rng)

            # the expert layers' routed-row counts, from here on (after any
            # restore); a model without such layers makes this a no-op
            moe_stats = MoeStatsReader(self.model, state)
            stop = False
            # install the watchdog only now: the finally below is the ONLY
            # teardown, so nothing may fail between install and the try
            prev_watchdog = set_active_watchdog(watchdog)
            watchdog.start_stall_monitor()
            try:
                while not stop and not end_trigger(ts):
                    # monotonic clock for the epoch interval: wall-clock
                    # adjustments must not produce negative/garbage durations
                    epoch_start = time.perf_counter()
                    epoch_it0 = ts.iteration
                    seen = 0
                    loss = None
                    num_slices = getattr(train_set, "num_slices", 1)
                    try:
                        if is_pipeline:
                            # resumable engine: the DeviceLoader pulls host
                            # batches ahead (worker pool + double buffer)
                            # and commits the pipeline position per batch
                            # consumed, so any checkpoint below captures
                            # the exact next batch
                            for batch in device_loader.epoch(
                                    iteration=ts.iteration):
                                with startup.first_dispatch():
                                    params, opt_state, state, loss = \
                                        trainer.train_step_at(
                                            params, opt_state, state, batch,
                                            rng, np.int32(ts.iteration))
                                ts.iteration += 1
                                seen += batch_size
                                if boundary(ts.iteration - 1, loss, 1):
                                    stop = True
                                    break
                        elif hbm_src is not None:
                            try:
                                xs, ys = hbm_src
                                if train_set.shuffle:
                                    with tracer.span("train_permute",
                                                     jax_annotation=True,
                                                     iteration=ts.iteration):
                                        perm = train_set._epoch_perm(
                                            ts.epoch)[:epoch_rows].astype(
                                                np.int32)
                                        xe, ye = hbm_permute(xs, ys, perm)
                                else:
                                    # unshuffled: the scan slices the source
                                    # in order; no gather, no second copy
                                    xe, ye = xs, ys
                                with startup.first_dispatch(), \
                                        tracer.span("train_epoch_scan",
                                                    jax_annotation=True,
                                                    iteration=ts.iteration,
                                                    steps=nb_epoch,
                                                    path="epoch_scan"):
                                    params, opt_state, state, loss = hbm_scan(
                                        params, opt_state, state, xe, ye, rng,
                                        np.int32(ts.iteration))
                                # JAX dispatch is async: an execution-time
                                # failure (OOM) would otherwise surface at a
                                # LATER sync point (a 20-crossing float, eval,
                                # or next epoch's permute) — outside this
                                # recovery scope, after the iteration counter
                                # had committed for an epoch that never ran.
                                # Force it to surface HERE with a host read of
                                # the epoch's loss output (a D2H read cannot
                                # return before the program completes). One
                                # scalar read per epoch on a
                                # one-dispatch-per-epoch path.
                                ts.last_loss = sync_loss(loss, ts.iteration)
                                # drop the permuted copy eagerly: holding it
                                # across epochs would put THREE epoch-sized
                                # buffers live at the next permute (source +
                                # old + new) — the budget gate accounts for two
                                del xe, ye
                            except Exception:
                                # The budget gate knows the dataset size, not
                                # free HBM: a model whose params/activations
                                # nearly fill the device can OOM here. The
                                # epoch is ONE dispatch, so no step committed —
                                # but params/opt_state/state were DONATED to
                                # the failed dispatch and may be deleted, so
                                # recovery must re-place them (never continue
                                # with the old references). Release every
                                # epoch-sized device buffer first: the chunked
                                # retry below must not inherit the memory
                                # pressure that caused the failure.
                                hbm_src = xs = ys = xe = ye = None  # noqa: F841
                                eval_cache_holder[0] = None
                                restored = restore_snapshot(
                                    {"params": params, "state": state,
                                     "opt_state": opt_state, "epoch": 0,
                                     "iteration": 0})
                                if restored is not None:
                                    log.warning(
                                        "HBM epoch cache failed (likely OOM); "
                                        "restored checkpoint, falling back to "
                                        "chunked dispatch", exc_info=True)
                                    params = trainer.place_params(
                                        restored["params"])
                                    state = trainer.replicate(restored["state"])
                                    opt_state = trainer.init_opt_state(params)
                                    opt_state = trainer.place_like(
                                        restored["opt_state"], opt_state)
                                    ts.epoch = int(restored["epoch"])
                                    ts.iteration = int(restored["iteration"])
                                    continue
                                if ts.iteration == start_iteration:
                                    # nothing learned THIS call: rebuild from
                                    # the entry-time host copy, retry chunked
                                    log.warning(
                                        "HBM epoch cache failed (likely OOM) "
                                        "before any step; falling back to "
                                        "chunked dispatch", exc_info=True)
                                    params = trainer.place_params(
                                        self.variables["params"])
                                    state = trainer.replicate(
                                        self.variables["state"])
                                    opt_state = trainer.init_opt_state(params)
                                    continue
                                # steps committed, no snapshot to restore:
                                # the donated training state is unrecoverable
                                # (near-unreachable: EveryEpoch + model_dir
                                # snapshots every completed epoch)
                                raise _UnrecoverableTraining(
                                    f"HBM epoch cache failed at iteration "
                                    f"{ts.iteration} with no checkpoint to "
                                    "restore; set model_dir or "
                                    "train.hbm_cache_mb=0")
                            ts.iteration += nb_epoch
                            seen += epoch_rows
                            met["steps"].labels("epoch_scan").inc(nb_epoch)
                            if boundary(ts.iteration - nb_epoch, loss,
                                        nb_epoch, fused=True,
                                        epoch_loss=True):
                                stop = True
                        elif use_chunks:
                            global_rows = mesh_lib.global_batch_rows(
                                trainer.mesh, batch_size)
                            gen = ((x, y) for x, y, _ in train_set.epoch_chunks(
                                ts.epoch, batch_size, chunk_steps))
                            for placed in trainer.prefetch(
                                    gen, iteration=ts.iteration,
                                    stride=chunk_steps):
                                xc, yc = placed
                                # chunk length from the placed arrays (single
                                # source of truth is epoch_chunks' row count)
                                k = jax.tree_util.tree_leaves(xc)[0].shape[0] \
                                    // global_rows
                                fn = chunk_fns.get(k)
                                if fn is None:
                                    fn = trainer.epoch_scan_fn(
                                        k, batch_size, path="chunked")
                                    chunk_fns[k] = fn
                                # same rng stream as per-step dispatch: the fn
                                # folds rng by (start_step + i) internally
                                with startup.first_dispatch(), \
                                        tracer.span("train_dispatch",
                                                    jax_annotation=True,
                                                    iteration=ts.iteration,
                                                    steps=k, path="chunked"):
                                    params, opt_state, state, loss = fn(
                                        params, opt_state, state, xc, yc, rng,
                                        np.int32(ts.iteration))
                                ts.iteration += k
                                seen += k * batch_size
                                met["steps"].labels("chunked").inc(k)
                                if boundary(ts.iteration - k, loss, k,
                                            fused=True):
                                    stop = True
                                    break
                        else:
                            for sl in range(num_slices):
                                ts.slice_index = sl
                                if num_slices > 1:
                                    batches = train_set.slice_batches(
                                        ts.epoch, sl, batch_size)
                                else:
                                    batches = train_set.epoch_batches(
                                        ts.epoch, batch_size, train=True)
                                for batch in trainer.prefetch(
                                        batches, iteration=ts.iteration):
                                    # rng folded IN-JIT by the step index: no
                                    # extra fold_in dispatch per step
                                    with startup.first_dispatch():
                                        params, opt_state, state, loss = \
                                            trainer.train_step_at(
                                                params, opt_state, state,
                                                batch, rng,
                                                np.int32(ts.iteration))
                                    ts.iteration += 1
                                    seen += batch_size
                                    # avoid a device sync per step: loss is
                                    # fetched only at logging points;
                                    # iteration-level triggers (MaxIteration,
                                    # SeveralIteration) fire mid-epoch
                                    if boundary(ts.iteration - 1, loss, 1):
                                        stop = True
                                        break
                                if stop:
                                    break
                    except (_UnrecoverableTraining, TrainingHalted):
                        # a watchdog halt is deliberate: retrying would
                        # replay the same poisoned step.  Listed BEFORE the
                        # policy engine so no classifier bug can ever
                        # absorb them.
                        raise
                    except Exception as exc:   # noqa: BLE001 — policy engine, ref :1179-1261
                        decision = policy.decide(
                            exc, have_checkpoint=ckpt is not None)
                        met["failures"].labels(
                            decision.failure_class.value).inc()
                        record_event(
                            "train.failure",
                            classification=decision.failure_class.value,
                            action=decision.action.name.lower(),
                            iteration=ts.iteration,
                            cause=f"{type(exc).__name__}: {exc}"[:200])
                        if decision.action is RecoveryAction.RAISE:
                            log.error(
                                "training failure classified %s is not "
                                "recoverable here: %s",
                                decision.failure_class.value, decision.reason)
                            raise
                        if decision.action is RecoveryAction.DEGRADE:
                            met["recoveries"].labels("degrade").inc()
                            self._raise_degraded(
                                exc, decision, ckpt,
                                train_set if is_pipeline else None)
                        reformed = False
                        if decision.action is RecoveryAction.REFORM_MESH:
                            from analytics_zoo_tpu.resilience import (
                                recovery as recovery_lib)
                            try:
                                with tracer.span("elastic_recovery",
                                                 iteration=ts.iteration):
                                    survivors = recovery_lib.surviving_devices(
                                        exc)
                                    new_mesh = recovery_lib.reform_mesh(
                                        survivors, batch_size=batch_size)
                            except recovery_lib.NoViableTopology as nv:
                                met["recoveries"].labels("degrade").inc()
                                self._raise_degraded(
                                    exc, decision, ckpt,
                                    train_set if is_pipeline else None,
                                    detail=str(nv))
                            log.exception(
                                "lost-host failure at iteration %d; mesh "
                                "re-formed on %d surviving device(s) — "
                                "restoring the latest snapshot onto the "
                                "new topology", ts.iteration,
                                new_mesh.devices.size)
                            old_mesh = getattr(trainer, "mesh",
                                               None) or self._mesh
                            old_devices = int(getattr(
                                getattr(old_mesh, "devices", None),
                                "size", 0) or 0)
                            record_event(
                                "mesh.reform",
                                old_devices=old_devices,
                                new_devices=int(new_mesh.devices.size),
                                iteration=ts.iteration)
                            # rebuild every mesh-bound engine artifact: the
                            # old trainer's jitted programs, shardings and
                            # placed batches all name dead devices
                            trainer = DistributedTrainer(
                                self.model, criterion,
                                optim_method=self.optim_method,
                                mesh=new_mesh, clip=self._clip,
                                optim_groups=self.optim_groups)
                            self._mesh = new_mesh
                            self._placed_infer = None
                            if is_pipeline:
                                device_loader = DeviceLoader(
                                    train_set, put_fn=trainer.put_batch)
                            if eval_runner is not None:
                                eval_runner = trainer.make_eval_runner(
                                    validation_method)
                            chunk_fns.clear()
                            hbm_src = None
                            eval_cache_holder[0] = None
                            # detach the rng key from the lost topology
                            rng = np.asarray(rng)  # zoolint: disable=SYNC002 — recovery path, not per-step
                            reformed = True
                            met["recoveries"].labels("reform_mesh").inc()
                        else:   # RETRY — the reference's restore-and-replay
                            # counted only when the failure IS absorbed —
                            # re-raised terminal failures are not "retries"
                            met["retries"].inc()
                            met["recoveries"].labels("retry").inc()
                            record_event(
                                "train.retry",
                                classification=decision.failure_class.value,
                                retries_left=policy.budget.remaining,
                                iteration=ts.iteration)
                            log.exception(
                                "training step failed (%s); restoring "
                                "latest checkpoint (%d retries left)",
                                decision.failure_class.value,
                                policy.budget.remaining)
                        restored = restore_snapshot(snapshot_like())
                        if restored is not None:
                            params = trainer.place_params(restored["params"])
                            state = trainer.replicate(restored["state"])
                            if reformed:
                                # the held opt_state leaves carry the OLD
                                # mesh's shardings — re-derive them on the
                                # new topology before placing the restored
                                # host arrays
                                opt_state = trainer.init_opt_state(params)
                            opt_state = trainer.place_like(restored["opt_state"], opt_state)
                            ts.epoch = int(restored["epoch"])
                            ts.iteration = int(restored["iteration"])
                            restore_data_state(restored)
                        elif reformed:
                            if ts.iteration != start_iteration:
                                # steps committed on the lost topology and
                                # no snapshot to recover them from
                                raise _UnrecoverableTraining(
                                    f"mesh re-formed at iteration "
                                    f"{ts.iteration} but no snapshot exists "
                                    "to restore the training state lost "
                                    "with the old topology; set model_dir "
                                    "or checkpoint more often") from exc
                            # nothing learned THIS call: rebuild from the
                            # entry-time host copy and rewind the stream
                            params = trainer.place_params(
                                self.variables["params"])
                            state = trainer.replicate(self.variables["state"])
                            opt_state = trainer.init_opt_state(params)
                            if is_pipeline and entry_data_state is not None:
                                train_set.load_state_dict(entry_data_state)
                        continue

                    # the epoch's end is boundary work too: one span, so
                    # that telemetry, flush, validation and snapshot show
                    # on the timeline (the nested eval and checkpoint_save
                    # spans keep their own time)
                    with tracer.span("train_boundary", jax_annotation=True,
                                     iteration=epoch_it0):
                        if loss is not None:
                            ts.last_loss = sync_loss(loss, epoch_it0)
                            observe_loss_once(ts.last_loss)
                            health_check()
                        if stop:
                            break
                        ts.epoch += 1
                        ts.slice_index = 0
                        ts.epoch_finished = True
                        wall = time.perf_counter() - epoch_start
                        throughput = seen / max(wall, 1e-9)
                        tracer.complete("epoch", epoch_start, wall, epoch=ts.epoch,
                                        samples=seen)
                        met["epoch_seconds"].labels("distributed").observe(wall)
                        met["samples"].inc(seen)
                        met["throughput"].set(throughput)
                        met["loss"].set(ts.last_loss)
                        sample_device_telemetry()
                        # multi-host runs: land this epoch's snapshot in the
                        # worker's run-dir slot, so offline cluster aggregation
                        # (obs_report --merge-hosts) sees fresh numbers even if
                        # the worker later dies without its atexit flush
                        flush_worker_observability()
                        record = {"epoch": ts.epoch, "loss": ts.last_loss,
                                  "throughput": throughput, "wall_s": wall}
                        if self._train_summary is not None:
                            self._train_summary.add_scalar(
                                "Throughput", throughput, ts.iteration)

                        if eval_runner is not None:
                            scores = run_eval(params, state)
                            record["val"] = scores
                            ts.last_score = next(iter(scores.values()), None)
                            if self._val_summary is not None:
                                for k, v in scores.items():
                                    self._val_summary.add_scalar(
                                        k, v, ts.iteration)
                            log.info("epoch %d loss %.4f val %s (%.1f samples/s)",
                                     ts.epoch, ts.last_loss, scores, throughput)
                        else:
                            log.info("epoch %d loss %.4f (%.1f samples/s)",
                                     ts.epoch, ts.last_loss, throughput)
                        self.history.append(record)

                        if ckpt is not None and checkpoint_trigger(ts):
                            save_snapshot()
                    ts.epoch_finished = False
                # every step's flag has been read before train returns
                # (an epoch's end reads them; a recovery that ran into
                # the end trigger has not)
                trainer.drain_finite()
                moe_stats.read(state, ts.iteration)
                health_check()
            finally:
                watchdog.stop()
                set_active_watchdog(prev_watchdog)
                if is_pipeline:
                    # the workers may be building the next epoch: it
                    # will not run, so free them before train_return
                    train_set.drop_stream()
                # summaries hold open file handles (JSONL + tfevents):
                # close them whether training completed or raised.
                # _ScalarWriter reopens on the next add_scalar, so a
                # later train() on this estimator still records.
                for s in (self._train_summary, self._val_summary):
                    if s is not None:
                        s.close()

            # the job's other edge: the trained state comes back to the host
            with tracer.span("train_return", jax_annotation=True,
                             bytes=_tree_bytes((params, state))):
                self.variables = mesh_lib.fetch_global(
                    {"params": params, "state": state}, site="train_return")
                self.model.set_variables(self.variables)
            return self
        finally:
            startup.close()

    # ----------------------------------------------------------- resilience
    def _raise_degraded(self, exc, decision, ckpt,
                        pipeline=None, detail: Optional[str] = None):
        """Checkpoint-and-queue: end the run DEGRADED instead of
        hanging or dying empty.  The structured record (the thing
        bench/CI surface instead of an rc=124 timeout) points at the
        last good snapshot + data position, so a later run — or a
        queue consumer watching ``degraded.json`` — resumes exactly
        where capacity ran out.  Never returns: raises
        :class:`DegradedTraining` carrying the record."""
        ts = self.train_state
        snapshot = ckpt.latest_path() if ckpt is not None else None
        result = {
            "status": "degraded",
            "failure_class": decision.failure_class.value,
            "reason": detail or decision.reason,
            "cause": f"{type(exc).__name__}: {exc}",
            "epoch": ts.epoch,
            "iteration": ts.iteration,
            "checkpoint_dir": self.model_dir,
            "snapshot": snapshot,
            "data_position": (
                {"epoch": pipeline.epoch, "step": pipeline.step}
                if pipeline is not None else None),
            "recorded_unix": round(time.time(), 1),
        }
        if self.model_dir:
            try:
                with open(os.path.join(self.model_dir,
                                       "degraded.json"), "w") as f:
                    json.dump(result, f, indent=2)
            except OSError:
                log.exception("could not write degraded.json")
        try:
            get_registry().counter(
                "train_degraded_total",
                "training runs that ended degraded "
                "(checkpoint-and-queue)").inc()
        except Exception:   # noqa: BLE001 — metrics never block the exit
            pass
        record_event(
            "train.degraded",
            failure_class=decision.failure_class.value,
            reason=str(detail or decision.reason)[:200],
            epoch=ts.epoch, iteration=ts.iteration,
            snapshot=snapshot or "")
        log.error("training DEGRADED (checkpoint-and-queue): %s", result)
        raise DegradedTraining(
            "no viable topology to continue training; run queued at "
            f"snapshot {snapshot!r} — resume from model_dir "
            f"{self.model_dir!r} when capacity returns", result=result
        ) from exc

    # ------------------------------------------------------------ inference
    def _infer_trainer(self) -> DistributedTrainer:
        """Cached trainer for evaluate/predict so the jitted programs
        compile once per Estimator, not once per call.  Invalidated
        when elastic recovery re-formed the mesh mid-train: the cached
        programs would target lost devices."""
        cached = getattr(self, "_cached_infer_trainer", None)
        if cached is None or (self._mesh is not None
                              and cached.mesh is not self._mesh):
            self._cached_infer_trainer = DistributedTrainer(
                self.model, None, mesh=self._mesh)
            self._cached_eval_runners = {}
        return self._cached_infer_trainer

    def _infer_placed(self, trainer):
        """Device-resident (params, state) for evaluate/predict,
        cached across calls: re-uploading the weight tree per call
        would put a whole-model H2D copy into every inference call.

        Invalidation keys on the identity of every leaf, so any path
        that swaps arrays — set_variables, set_weights, per-layer
        weight grafts — invalidates; the cache pins the keyed LEAF
        OBJECTS themselves (not just the enclosing dict, which
        set_weights mutates in place) so a freed leaf's id can't be
        reused by a new array and fake a hit.  Only mutating a numpy
        leaf's BUFFER in place would go stale, and no framework path
        does that."""
        variables = self.model.get_variables()
        leaves = jax.tree_util.tree_leaves(variables)
        key = (id(variables),) + tuple(id(l) for l in leaves)
        cached = getattr(self, "_placed_infer", None)
        if cached is not None and cached[0] == key:
            return cached[1], cached[2]
        params = trainer.place_params(variables["params"])
        state = trainer.replicate(variables["state"])
        # leaves pinned alongside: their ids stay unique while cached
        self._placed_infer = (key, params, state, leaves)
        return params, state

    def evaluate(self, data_set, criterion=None, validation_method=None,
                 batch_size: int = 32) -> Dict[str, float]:
        from analytics_zoo_tpu.pipeline.api.keras import metrics as met
        methods = list(validation_method or [])
        if criterion is not None:
            methods = [met.Loss(criterion)] + methods
        trainer = self._infer_trainer()
        params, state = self._infer_placed(trainer)
        key = tuple(id(m) for m in methods)
        runner = self._cached_eval_runners.get(key)
        if runner is None:
            runner = trainer.make_eval_runner(methods)
            self._cached_eval_runners[key] = runner
        return runner(params, state, eval_batches(data_set, batch_size))

    # -------------------------------------------------------------- predict
    def predict(self, x, batch_size: int = 256):
        trainer = self._infer_trainer()
        params, state = self._infer_placed(trainer)
        fn = trainer.predict_fn()
        nproc = jax.process_count()

        def run(xb):
            out = fn(params, state, trainer.put_batch(xb))
            if nproc > 1:
                # the global batch concatenates per-host slices in
                # process order — slice this host's own rows back out.
                # (Every host must predict the same number of rows so
                # the SPMD programs stay in step.)
                pid = jax.process_index()
                bs = len(jax.tree_util.tree_leaves(xb)[0])
                out = jax.tree_util.tree_map(
                    lambda o: o[pid * bs:(pid + 1) * bs], out)
            return out

        return predict_in_batches(run, x, batch_size)
