"""The distributed training engine.

Reference: ``InternalDistriOptimizer`` (Topology.scala:1069-1598) — per
iteration it launches a Spark job that runs forward/backward on every
executor's model replicas, then syncs gradients through a partitioned
allreduce over the Spark BlockManager, applies the OptimMethod per
parameter chunk, and broadcasts updated weights back.

TPU redesign: the *entire* iteration is ONE jit-compiled XLA program
over the device mesh.  The batch is sharded on the ``data`` axis;
params/optimizer state are replicated (or fsdp-sharded); XLA inserts the
gradient all-reduce over ICI automatically from the sharding contract —
there is no hand-written communication.  Buffer donation makes the
update in-place in HBM.

Supports the reference's optimizer features: constant / L2-norm gradient
clipping (Topology.scala setConstantGradientClipping etc.), multiple
optim methods over disjoint parameter groups (Topology.scala:1130-1151),
and bf16 gradient sync (the analogue of BigDL's compressed FP16
parameter exchange).
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from analytics_zoo_tpu.common.config import get_config
from analytics_zoo_tpu.compile import engine_jit
from analytics_zoo_tpu.observability import get_registry, get_tracer
from analytics_zoo_tpu.observability.diagnostics import (
    get_compile_monitor, publish_mfu, step_attribution_histogram)
from analytics_zoo_tpu.observability.watchdog import (
    PendingFiniteFlags, fold_finiteness_check)
from analytics_zoo_tpu.parallel import mesh as mesh_lib
from analytics_zoo_tpu.resilience.chaos import (
    SITE_TRAINER_DISPATCH, active_chaos)


def _record_grad_norm(gnorm) -> None:
    """Host callback target: surface the in-jit global grad norm as a
    gauge (debug.callback delivers a host copy after the step runs).
    Runs on the runtime's callback thread: never raises."""
    try:
        with get_tracer().span("callback_grad_norm",
                               jax_annotation=True):
            get_registry().gauge(
                "train_grad_norm",
                "global L2 gradient norm (observability.grad_norm=true)"
            ).set(float(gnorm))
    except Exception:
        pass


class _ScanDispatch:
    """What ``epoch_scan_fn`` hands out: the compiled scan, called as
    before for ``(params, opt_state, state, mean_loss)``.  The
    program's fifth output (its count of non-finite steps) stays
    behind as a pending device value.  Other attributes (``lower``,
    ``warm``, ...) are the jitted function's, whose program has all
    five outputs."""

    def __init__(self, jitted, finite_flags, steps: int):
        self._jitted = jitted
        self._finite_flags = finite_flags
        self._steps = steps

    def __call__(self, *args):
        *out, nonfinite = self._jitted(*args)
        self._finite_flags.keep(nonfinite, self._steps)
        return tuple(out)

    def __getattr__(self, item):
        return getattr(self._jitted, item)


@dataclasses.dataclass
class ClipSpec:
    kind: str          # "const" | "l2norm"
    a: float = 0.0
    b: float = 0.0


def _apply_clipping(grads, clip: Optional[ClipSpec]):
    if clip is None:
        return grads
    if clip.kind == "const":
        return jax.tree_util.tree_map(
            lambda g: jnp.clip(g, clip.a, clip.b), grads)
    if clip.kind == "l2norm":
        gnorm = optax.global_norm(grads)
        scale = jnp.minimum(1.0, clip.a / (gnorm + 1e-12))
        return jax.tree_util.tree_map(lambda g: g * scale, grads)
    raise ValueError(clip.kind)


def mask_frozen_params(model, params, new_params):
    """Keep frozen layers' params bit-identical through an optimizer
    update (transfer learning): restoring the old leaves masks weight
    decay too, which plain gradient zeroing would not."""
    frozen = (model.frozen_layer_names()
              if hasattr(model, "frozen_layer_names") else set())
    if not frozen:
        return new_params
    return {k: (params[k] if k in frozen else v)
            for k, v in new_params.items()}


def _group_params(params, groups: Dict[str, Sequence[str]]):
    """Split a top-level params dict into named disjoint groups.

    ``groups`` maps group name -> list of top-level layer names; one
    group may be "*" (the rest).  Mirrors the reference's
    multi-optimMethod parameter splits (Topology.scala:1130-1151).
    """
    assigned = set()
    for names in groups.values():
        if names != "*":
            assigned.update(names)
    out = {}
    for gname, names in groups.items():
        if names == "*":
            out[gname] = [k for k in params if k not in assigned]
        else:
            out[gname] = list(names)
    return out


class DistributedTrainer:
    """Builds and runs the jitted train/eval/predict steps."""

    def __init__(self, model, loss_fn: Callable, optim_method=None,
                 mesh=None, clip: Optional[ClipSpec] = None,
                 optim_groups: Optional[Dict[str, Tuple[Any, Sequence[str]]]]
                 = None):
        from analytics_zoo_tpu.common.zoo_context import get_zoo_context
        self.model = model
        self.loss_fn = loss_fn
        self.optim = optim_method
        self.mesh = mesh if mesh is not None else get_zoo_context().mesh
        self.clip = clip
        self.optim_groups = optim_groups  # {name: (OptimMethod, layer_names)}
        cfg = get_config()
        self.donate = bool(cfg.get("train.donate"))
        self.remat = bool(cfg.get("train.remat"))
        self.grad_sync_dtype = str(cfg.get("train.grad_sync_dtype"))
        # fused optimizer update (ops/fused.py): clip + moment update +
        # param apply in ONE pass per leaf instead of the optax
        # global_norm → update → apply_updates triple traversal (three
        # full HBM sweeps of params+grads).  None = unsupported
        # (optimizer groups, exotic transform, or train.fused_optimizer
        # off) — the optax path below stays the source of truth.
        self._fused_update = None
        if (bool(cfg.get("train.fused_optimizer", True))
                and not self.optim_groups and self.optim is not None):
            from analytics_zoo_tpu.ops.fused import build_fused_update
            self._fused_update = build_fused_update(self.optim,
                                                    self.clip)
        self._train_step = None
        self._train_step_at = None
        self._eval_step = None
        self._predict_step = None
        self._permute_rows = None
        self._rep = mesh_lib.replicated(self.mesh)
        self._param_shardings = None
        # observability: shared-registry instruments for the hot path.
        # Per-step latency here is HOST dispatch-to-dispatch wall time —
        # device work is async, but donation + the dispatch queue make
        # it converge to device step time in steady state.
        reg = get_registry()
        self._m_step_latency = reg.histogram(
            "train_step_latency_seconds",
            "host wall time per dispatched train step (dispatch-to-"
            "dispatch; device work is async)", labels=("path",))
        self._m_steps = reg.counter(
            "train_steps_total", "train steps dispatched",
            labels=("path",))
        # counted in the Python body of each train program, so once a
        # TRACE of it and never at run time: a first call, a warm-start
        # and a cost analysis whose signature jit has not traced yet
        self._m_program_traces = reg.counter(
            "train_program_traces_total",
            "traces of a train program's Python body, by the engine "
            "it is the program of", labels=("path",))
        self._m_prefetch_depth = reg.gauge(
            "train_prefetch_queue_depth",
            "device-placed batches waiting in the prefetch queue")
        # grad-norm gauge costs an in-jit norm + host callback per step:
        # opt-in via config (observability.grad_norm)
        self._obs_grad_norm = bool(cfg.get("observability.grad_norm"))
        # training-health diagnostics: in-jit finite check (watchdog
        # NaN detector), sampled device-step bracket, compile monitor
        self._obs_check_finite = bool(
            cfg.get("observability.check_finite"))
        # the fifth output of every compiled train program, stripped
        # where the jitted call is wrapped and held until drain_finite
        self._finite_flags = PendingFiniteFlags()
        self._traced_finite = None   # see _step_core
        self._obs_device_every = int(
            cfg.get("observability.device_time_every") or 0)
        self._monitor = get_compile_monitor()
        self._m_step_time = step_attribution_histogram(reg)
        # cross-host skew instrumentation: at every sampled device
        # step on a multi-process run, time an explicit cluster
        # barrier — the wait is (max_host_step − my_step), so the
        # straggler reads ~0 while every other host reads the skew.
        # The aggregator's straggler report consumes this together
        # with per-host train_step_latency_seconds.
        self._obs_barrier_probe = bool(
            cfg.get("observability.barrier_probe", True))
        self._barrier_supported: Optional[bool] = None
        self._m_barrier_wait = reg.histogram(
            "train_barrier_wait_seconds",
            "sampled cross-host barrier wait after a train step "
            "(multi-host only): ~0 on the straggler, ~skew on the "
            "fastest host")
        # collective accounting: per-step psum/all-gather bytes implied
        # by the sharding contract (observability/collectives.py),
        # estimated once per params signature then counted per dispatch
        self._obs_collectives = bool(
            cfg.get("observability.collectives", True))
        self._collective_bytes = None
        self._m_device_step = reg.gauge(
            "train_device_step_seconds",
            "sampled dispatch->block_until_ready wall of one train "
            "step (observability.device_time_every)")
        # registered here so a scrape shows the gauge (at 0) even
        # before the first computable sample — see publish_mfu
        reg.gauge(
            "train_mfu",
            "model FLOPs utilisation: cost-analysis FLOPs / sampled "
            "device step time / chip peak (observability.peak_flops "
            "overrides the denominator)")
        self._dispatch_count = 0
        # called ONCE, after the next sampled device sync: the
        # per-step path's first proof that a step has finished
        # (Estimator.train's start-up timeline sets it)
        self.after_device_sync: Optional[Callable[[], None]] = None

    # ------------------------------------------------------------ sharding
    def param_shardings(self, params):
        """TP/FSDP/replicated sharding pytree for the model's params."""
        if self._param_shardings is None:
            from analytics_zoo_tpu.parallel.sharding import (
                collect_param_shardings)
            self._param_shardings = collect_param_shardings(
                self.model, params, self.mesh)
        return self._param_shardings

    def place_params(self, params):
        """Copy params onto the mesh per their TP/FSDP shardings.

        Multi-host: every process holds the full host copy (identical
        init / restored checkpoint), so each contributes its
        addressable shards via ``make_array_from_process_local_data``.
        """
        sh = self.param_shardings(params)
        if jax.process_count() > 1:
            return jax.tree_util.tree_map(
                lambda a, s: jax.make_array_from_process_local_data(
                    s, np.asarray(a), np.shape(a)), params, sh)
        return jax.tree_util.tree_map(
            lambda a, s: jax.device_put(jnp.array(a, copy=True), s),
            params, sh)

    def place_like(self, host_tree, like_tree):
        """Place host arrays with the shardings of a live device tree
        (checkpoint restore of sharded optimizer state)."""
        if jax.process_count() > 1:
            return jax.tree_util.tree_map(
                lambda a, ref: jax.make_array_from_process_local_data(
                    ref.sharding, np.asarray(a), np.shape(a)),
                host_tree, like_tree)
        return jax.tree_util.tree_map(
            lambda a, ref: jax.device_put(jnp.array(a, copy=True),
                                          ref.sharding),
            host_tree, like_tree)

    # ----------------------------------------------------------- optimizer
    def init_opt_state(self, params):
        """Jitted so optimizer-state leaves inherit the param shardings
        (GSPMD propagation) — sharded optimizer update, ZeRO-style."""
        def init(p):
            if self.optim_groups:
                groups = _group_params(
                    p, {k: v[1] for k, v in self.optim_groups.items()})
                return {
                    g: self.optim_groups[g][0].init(
                        {k: p[k] for k in names})
                    for g, names in groups.items()
                }
            return self.optim.init(p)

        out = engine_jit(init, key_hint="init_opt_state")(params)
        if jax.process_count() > 1:
            # multi-host jit outputs are already global arrays
            return out
        # leaves unrelated to any param (e.g. the step counter) may land
        # on a single device — normalize them onto the mesh.  On a
        # one-device mesh EVERY leaf comes back with a
        # SingleDeviceSharding, while the step hands its outputs back
        # with the mesh's NamedSharding: left alone, the second step
        # sees another input sharding and compiles the whole program
        # again (45 s for ResNet-50 on the v5e).
        mesh_devices = set(np.asarray(self.mesh.devices).flat)

        def fix(leaf):
            if isinstance(leaf, jax.Array) and (
                    set(leaf.sharding.device_set) != mesh_devices
                    or (leaf.sharding.is_fully_replicated
                        and not isinstance(leaf.sharding,
                                           jax.sharding.NamedSharding))):
                return jax.device_put(leaf, self._rep)
            return leaf

        return jax.tree_util.tree_map(fix, out)

    @property
    def fused_optimizer_active(self) -> bool:
        """Whether steps run the single-pass fused update
        (ops/fused.py) instead of the optax triple traversal."""
        return self._fused_update is not None

    def _optimizer_update(self, grads, opt_state, params):
        if self.optim_groups:
            groups = _group_params(
                params, {k: v[1] for k, v in self.optim_groups.items()})
            new_params = dict(params)
            new_state = {}
            for g, names in groups.items():
                method = self.optim_groups[g][0]
                sub_p = {k: params[k] for k in names}
                sub_g = {k: grads[k] for k in names}
                updates, new_state[g] = method.update(
                    sub_g, opt_state[g], sub_p)
                upd = optax.apply_updates(sub_p, updates)
                new_params.update(upd)
            return new_params, new_state
        updates, new_state = self.optim.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_state

    # ---------------------------------------------------------- train step
    def _step_core(self, params, opt_state, state, batch, rng):
        """One forward+backward+update — traced into both the per-step
        jit and the whole-epoch scan, both through ``_step_checked``.
        Returns ``(params, opt_state, state, loss)``: the benchmark's
        tests replace this method and hold it to these four, so the
        step's finite flag leaves by ``self._traced_finite`` instead
        (a bool scalar of the trace in progress; ``None`` with
        ``observability.check_finite`` off), which ``_step_checked``
        takes right after the call.

        Mixed precision is OP-LEVEL: the matmul/conv kernels cast their
        operands per ``dtype.compute`` (ops/dtypes.py policy), so bf16
        MXU compute with f32 master weights needs no whole-tree casting
        here."""
        model, loss_fn, clip = self.model, self.loss_fn, self.clip
        x, y = batch

        def objective(p):
            out, new_state = model.apply(p, x, state=state,
                                         training=True, rng=rng)
            loss = loss_fn(y, out)
            reg = model.regularization_loss(p)
            return loss + reg, (new_state, loss)

        if self.remat:
            # recompute the forward during the backward instead of
            # storing activations (train.remat) — see config.py
            objective = jax.checkpoint(objective)
        # the three scopes are names only (op_name metadata): a trace's
        # reduction finds a phase whatever implements it
        with jax.named_scope("forward_loss"):
            grads, (new_state, loss) = jax.grad(
                objective, has_aux=True)(params)
        if self._obs_grad_norm:
            # surfaces the norm on host after each step without
            # changing the step's signature; opt-in because the
            # callback costs a host round trip per step
            jax.debug.callback(_record_grad_norm,
                               optax.global_norm(grads))
        if self._obs_check_finite:
            # watchdog NaN/Inf detector, folded into the step's
            # program.  The flag is a VALUE the program returns, not a
            # host callback: the program keeps pjit's C++ dispatch
            # path and can be stored in the persistent caches, and the
            # device never waits inside the step for the host.  The
            # driver reads it where it already blocks (drain_finite).
            with jax.named_scope("finite_check"):
                self._traced_finite = fold_finiteness_check(loss, grads)
        if self.grad_sync_dtype == "bfloat16":
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.bfloat16).astype(jnp.float32),
                grads)
        with jax.named_scope("optimizer_update"):
            if self._fused_update is not None:
                # single-pass clip+moments+apply (ops/fused.py),
                # numerically the optax triple pass below — proven by
                # tests/test_fused_kernels.py
                new_params, new_opt_state = self._fused_update(
                    grads, opt_state, params)
            else:
                grads = _apply_clipping(grads, clip)
                new_params, new_opt_state = self._optimizer_update(
                    grads, opt_state, params)
        new_params = mask_frozen_params(model, params, new_params)
        return new_params, new_opt_state, new_state, loss

    def _step_checked(self, params, opt_state, state, batch, rng):
        """``_step_core`` with the finite flag it folded as a fifth
        value beside ``loss``: what every compiled train program
        traces."""
        self._traced_finite = None
        out = self._step_core(params, opt_state, state, batch, rng)
        finite, self._traced_finite = self._traced_finite, None
        return (*out, finite)

    def _build_train_step(self, fold_rng: bool = False):
        """One source of truth for the train-step jit spec; with
        ``fold_rng`` the program takes (.., rng, step) and derives the
        per-step rng in-jit."""
        donate = (0, 1, 2) if self.donate else ()
        traces = self._m_program_traces.labels("per_step")
        # named as their key_hint: jax.monitoring reports a program's
        # trace and lowering under its function's name
        if fold_rng:
            def train_step_at(p, o, s, b, r, i):
                traces.inc()
                return self._step_checked(
                    p, o, s, b, jax.random.fold_in(r, i))
            fn = train_step_at
        else:
            def train_step(p, o, s, b, r):
                traces.inc()
                return self._step_checked(p, o, s, b, r)
            fn = train_step
        jitted = engine_jit(
            fn,
            out_shardings=(self._param_shardings, None, self._rep,
                           self._rep, self._rep),
            donate_argnums=donate, key_hint=fn.__name__)
        # compile/recompile accounting + cost-analysis FLOPs for the
        # live MFU gauge (diagnostics.CompileMonitor)
        return self._monitor.wrap("train_step", jitted)

    def _dispatch_instrumented(self, fn, *args, iteration=None):
        """One step dispatch wrapped in a train_step span + the
        per-step latency histogram and step counter.  ``iteration`` is
        the training step this dispatch runs, for the timeline; a
        caller that keeps no count gets this trainer's dispatch index.

        Step-time attribution: every dispatch observes its host wall
        (``host_dispatch``); every N-th dispatch additionally brackets
        dispatch→``block_until_ready`` (``device``) — one device sync
        on the sampled step only — and refreshes the live MFU gauge
        from the CompileMonitor's cost-analysis FLOPs."""
        chaos = active_chaos()
        if chaos is not None:
            # fault-injection site, keyed on this trainer's 0-based
            # dispatch index and tripped BEFORE the dispatch: a fault
            # at step k leaves exactly k committed steps and donates
            # no buffer to a doomed dispatch (resilience/chaos.py)
            chaos.trip(SITE_TRAINER_DISPATCH, self._dispatch_count)
        if iteration is None:
            iteration = self._dispatch_count
        self._dispatch_count += 1
        sample_device = (self._obs_device_every > 0 and
                         self._dispatch_count % self._obs_device_every
                         == 0)
        if self._collective_bytes is None and args:
            self._collective_bytes = self._estimate_collectives(args[0])
        tracer = get_tracer()
        with tracer.span("train_step", jax_annotation=True,
                         iteration=iteration, steps=1, path="per_step"):
            t0 = time.perf_counter()
            *out, finite = fn(*args)
            self._finite_flags.keep(finite)
            dispatch_s = time.perf_counter() - t0
            self._m_step_latency.labels("per_step").observe(dispatch_s)
            self._m_step_time.labels("host_dispatch").observe(
                dispatch_s)
            if sample_device:
                # the host blocks here: a child span, so that
                # train_step's self time stays the dispatch
                with tracer.span("train_device_sync", jax_annotation=True,
                                 iteration=iteration):
                    try:
                        jax.block_until_ready(out)
                        device_s = time.perf_counter() - t0
                    except Exception:
                        device_s = None
                    if device_s is not None:
                        self._m_step_time.labels("device").observe(
                            device_s)
                        self._m_device_step.set(device_s)
                        publish_mfu("train_step", device_s)
                    self._probe_barrier_wait()
                    # the host has just blocked on this step: every
                    # pending flag is ready
                    self.drain_finite()
                    if self.after_device_sync is not None:
                        done, self.after_device_sync = \
                            self.after_device_sync, None
                        done()
        if self._collective_bytes:
            from analytics_zoo_tpu.observability.collectives import (
                record_step_collectives)
            record_step_collectives(self._collective_bytes)
        self._m_steps.labels("per_step").inc()
        return tuple(out)

    def drain_finite(self) -> None:
        """Read the finite flags of the steps dispatched since the last
        drain and report them (``train_nonfinite_total{source="step"}``
        through the active watchdog, ``train_finite_checked_steps_total``).
        Call it where the host has already blocked on the newest
        dispatch: the flags are then ready and the read costs no wait.
        A caller that never does is bounded by ``PendingFiniteFlags``."""
        self._finite_flags.drain()

    def _estimate_collectives(self, params) -> Dict[str, float]:
        """One-time {op: bytes/step} estimate from the sharding
        contract; {} disables the per-dispatch accounting."""
        if not self._obs_collectives:
            return {}
        try:
            from analytics_zoo_tpu.observability.collectives import (
                estimate_train_step_collectives)
            return estimate_train_step_collectives(
                params, self.mesh, self.grad_sync_dtype)
        except Exception:
            return {}

    def account_collectives(self, params, steps: int) -> None:
        """Collective accounting for a FUSED dispatch of ``steps``
        steps (the chunked / epoch-scan paths, which bypass
        ``_dispatch_instrumented``): the per-step traffic is identical
        regardless of dispatch shape, so the counters stay comparable
        across engines.  Never raises."""
        if self._collective_bytes is None:
            self._collective_bytes = self._estimate_collectives(params)
        if self._collective_bytes and steps > 0:
            from analytics_zoo_tpu.observability.collectives import (
                record_step_collectives)
            record_step_collectives(self._collective_bytes,
                                    steps=steps)

    def _probe_barrier_wait(self) -> None:
        """Time a cross-host barrier on the sampled step (multi-host
        only): my wait = slowest host's remaining step time, the
        direct skew signal the aggregator attributes stragglers from.
        Piggybacks on the device-sample cadence so every process hits
        the barrier on the same dispatch count."""
        if not self._obs_barrier_probe or jax.process_count() <= 1 \
                or self._barrier_supported is False:
            return
        if self._barrier_supported is None:
            # capability gate, decided at the FIRST sampled step only:
            # every host reaches it at the same dispatch count, and a
            # does-this-backend-support-it failure is symmetric, so
            # all hosts disable together — participation stays in
            # lockstep
            try:
                from jax.experimental import multihost_utils
                t0 = time.perf_counter()
                multihost_utils.sync_global_devices(
                    "zoo_obs_barrier_probe")
                self._m_barrier_wait.observe(time.perf_counter() - t0)
                self._barrier_supported = True
            except Exception:
                self._barrier_supported = False
                import logging
                logging.getLogger(
                    "analytics_zoo_tpu.observability").exception(
                    "cross-host barrier probe unsupported here; "
                    "disabling it (straggler attribution loses the "
                    "barrier-wait signal)")
            return
        # past the gate, a failure means the collective fabric broke
        # mid-run: swallowing it would DESYNC the sampled barrier
        # (peers park waiting for us → cluster-wide silent hang), so
        # let it propagate into the step loop like any other
        # collective failure — the retry/failure machinery owns it
        from jax.experimental import multihost_utils
        t0 = time.perf_counter()
        multihost_utils.sync_global_devices("zoo_obs_barrier_probe")
        self._m_barrier_wait.observe(time.perf_counter() - t0)

    def train_step(self, params, opt_state, state, batch, rng):
        """Run one step; ``batch`` must already be device-placed
        (see ``prefetch``/``put_batch``)."""
        if self._train_step is None:
            self._train_step = self._build_train_step()
        return self._dispatch_instrumented(
            self._train_step, params, opt_state, state, batch, rng)

    def train_step_at(self, params, opt_state, state, batch, rng, step):
        """``train_step`` with the per-step rng derived IN-JIT:
        equivalent to ``train_step(..., fold_in(rng, step))`` but
        without dispatching a separate fold_in op per step.  ``step``
        must be a
        numpy scalar (traced arg — a Python int would retrace)."""
        if self._train_step_at is None:
            self._train_step_at = self._build_train_step(fold_rng=True)
        return self._dispatch_instrumented(
            self._train_step_at, params, opt_state, state, batch, rng,
            step, iteration=int(step))

    # --------------------------------------------------------- warm-start
    def warm_start(self, params, opt_state, state, host_batch,
                   rng) -> bool:
        """Compile the per-step train program BEFORE the first real
        batch arrives, so the compile — or the read from JAX's
        persistent compilation cache — is paid at startup where it is
        attributable, not inside the first training step.

        ``params``/``opt_state``/``state`` are the live device trees
        (their shardings are part of the program signature);
        ``host_batch`` is one representative HOST batch — it is
        device-placed exactly like a real step's batch (``put_batch``)
        so the warmed signature is bit-for-bit the one the training
        loop will dispatch.  Nothing is executed and nothing is
        donated.  Returns whether the program is compiled (False =
        the first step compiles — never an error)."""
        try:
            if self._train_step_at is None:
                self._train_step_at = self._build_train_step(
                    fold_rng=True)
            batch = self.put_batch(host_batch)
            with get_tracer().span("aot_warm_start",
                                   jax_annotation=True):
                # _MonitoredJit forwards .warm to the EngineJit
                return bool(self._train_step_at.warm(
                    params, opt_state, state, batch, rng, np.int32(0)))
        except Exception:   # noqa: BLE001 — warm-start is best-effort
            import logging
            logging.getLogger("analytics_zoo_tpu.compile").debug(
                "train-step warm start failed; compiling lazily",
                exc_info=True)
            return False

    # ------------------------------------------------- device-resident epoch
    def epoch_scan_fn(self, num_batches: int, batch_size: int,
                      unroll: int = 1, path: str = "epoch_scan"):
        """Whole-epoch trainer over DEVICE-RESIDENT data — the HBM tier
        of the FeatureSet cache hierarchy (the reference's DRAM cache,
        FeatureSet.scala:229-329, moved all the way onto the chip).

        One ``lax.scan`` runs ``num_batches`` steps with zero host
        involvement: no per-step dispatch, no H2D transfers.  Batches
        are contiguous slices of the (host-preshuffled) epoch arrays.
        Returns ``f(params, opt_state, state, x, y, rng) ->
        (params, opt_state, state, mean_loss)``; the compiled program's
        fifth output, the count of the dispatch's non-finite steps, is
        kept for ``drain_finite``.

        ``batch_size`` is PER-HOST, matching the per-step
        ``put_batch`` convention: when the data axes divide across
        processes, ``put_epoch`` builds a global epoch array of
        ``local_rows * process_count`` rows and each scan step slices
        the GLOBAL batch of ``batch_size * process_count`` rows —
        ``num_batches`` (= per-host rows // batch_size) steps then
        consume exactly the whole epoch.  When ``put_batch`` falls back
        to REPLICATING (dp doesn't divide across hosts), global rows ==
        local rows and the slice stays ``batch_size``.

        ``path`` is the engine the caller dispatches the program on, as
        ``train_steps_total{path}`` names it (``epoch_scan`` or
        ``chunked``): the label its traces are counted under.
        """
        local_bs = mesh_lib.local_batch_size(self.mesh, batch_size)
        del local_bs   # validation only
        global_bs = mesh_lib.global_batch_rows(self.mesh, batch_size)
        # multi-host: make_array_from_process_local_data lays the global
        # epoch out as CONTIGUOUS PER-HOST BLOCKS ([host0 rows][host1
        # rows]...), so step i must gather each host's rows
        # [i*bs:(i+1)*bs] from within its own block — a flat
        # [i*global_bs:(i+1)*global_bs] slice would hand step i ONE
        # host's data. The block-local slice is communication-free
        # (every device slices rows it already holds) and reproduces
        # the per-step put_batch batch composition exactly.
        nproc = jax.process_count() \
            if mesh_lib.data_split_across_hosts(self.mesh) else 1

        traces = self._m_program_traces.labels(path)

        def epoch(params, opt_state, state, x, y, rng, start_step=0):
            traces.inc()
            # rng for step i is fold_in(rng, start_step + i): with
            # start_step = the global iteration counter this matches
            # the per-step path's fold_in(rng, ts.iteration) exactly,
            # so chunked dispatch is a pure performance knob — same
            # rng stream, same batches, same updates
            def body(carry, i):
                params, opt_state, state, nonfinite = carry

                def take(a):
                    if nproc > 1:
                        r = a.reshape((nproc, num_batches, batch_size)
                                      + a.shape[1:])
                        blk = jax.lax.dynamic_slice_in_dim(r, i, 1,
                                                           axis=1)
                        return blk.reshape((nproc * batch_size,)
                                           + a.shape[1:])
                    out = jax.lax.dynamic_slice_in_dim(
                        a, i * global_bs, global_bs, axis=0)
                    # without this the partitioner all-gathers the
                    # row-sharded epoch and every device computes the
                    # WHOLE batch (seen in the program compiled for four
                    # v5e chips): keep the step's batch on the data axes
                    return jax.lax.with_sharding_constraint(
                        out, mesh_lib.data_sharding(self.mesh, out.ndim))
                batch = (jax.tree_util.tree_map(take, x),
                         jax.tree_util.tree_map(take, y))
                params, opt_state, state, loss, finite = \
                    self._step_checked(
                        params, opt_state, state, batch,
                        jax.random.fold_in(rng, start_step + i))
                if finite is not None:
                    nonfinite = nonfinite + (~finite).astype(jnp.int32)
                return (params, opt_state, state, nonfinite), loss

            # the dispatch's count of non-finite steps rides the carry
            # (None with the check off): stacking the flags beside the
            # losses read 1.0 ms a step slower on the v5e in the GPT
            # cell (PERF.md, PR 26)
            nonfinite = jnp.int32(0) if self._obs_check_finite else None
            (params, opt_state, state, nonfinite), losses = jax.lax.scan(
                body, (params, opt_state, state, nonfinite),
                jnp.arange(num_batches), unroll=unroll)
            return params, opt_state, state, losses.mean(), nonfinite

        donate = (0, 1, 2) if self.donate else ()
        jitted = engine_jit(
            epoch,
            out_shardings=(self._param_shardings, None, self._rep,
                           self._rep, self._rep),
            donate_argnums=donate, key_hint="train_epoch_scan")
        # cost analysis counts the scan BODY once (~ one step), so the
        # monitor's flops gauge stays per-step-comparable
        return _ScanDispatch(
            self._monitor.wrap("train_epoch_scan", jitted),
            self._finite_flags, num_batches)

    def put_epoch(self, x, y, epoch: int, feature_set=None):
        """Device-place a whole epoch, sharded on the data axis.

        If ``feature_set`` is given, its deterministic per-epoch
        permutation is applied host-side first (one gather per epoch
        instead of one per step).  Placement goes through
        ``put_epoch_source`` so ragged row counts pad-and-shard
        instead of silently replicating; ``epoch_scan_fn`` never
        reaches the padded rows (its ``num_batches`` covers only whole
        real batches)."""
        if feature_set is not None and feature_set.shuffle:
            perm = feature_set._epoch_perm(epoch)
            take = lambda a: a[perm]
            x = jax.tree_util.tree_map(take, x)
            y = jax.tree_util.tree_map(take, y) if y is not None else None
        return self.put_epoch_source(x, y)

    def put_epoch_source(self, x, y):
        """Place the UNPERMUTED whole dataset on device once — the HBM
        cache tier of the FeatureSet hierarchy (the reference's DRAM
        cache, FeatureSet.scala:585-662, promoted into device memory).

        Rows are zero-padded up to a multiple of the data-parallel
        width so ``put_batch`` SHARDS the source instead of falling
        back to replication; padded rows are never consumed — every
        epoch permutation only indexes the real ``n`` rows, and
        ``epoch_scan_fn``'s ``num_batches`` covers only whole real
        batches.  Padding applies single-process only: the multi-host
        ``epoch_scan_fn`` layout reshapes each host block to exactly
        ``num_batches * batch_size`` rows, which padding would break
        (multi-host callers already size their rows to the mesh)."""
        dp = self.mesh.shape[mesh_lib.DATA_AXIS] * \
            self.mesh.shape[mesh_lib.FSDP_AXIS]
        from analytics_zoo_tpu.feature.feature_set import pad_rows
        n = len(jax.tree_util.tree_leaves(x)[0])
        if jax.process_count() > 1:
            if mesh_lib.data_split_across_hosts(self.mesh):
                local_dp = dp // jax.process_count()
                if n % local_dp:
                    # multi-host rows must tile the mesh EXACTLY: the
                    # multi-host epoch_scan_fn layout reshapes each
                    # host block to num_batches * batch_size rows,
                    # which padding would break — refuse HERE with
                    # epoch-level context rather than letting
                    # put_batch raise its per-batch message deep in
                    # the placement
                    raise ValueError(
                        f"put_epoch_source: this host's {n} rows do "
                        f"not tile its data-parallel share "
                        f"({local_dp} of the {dp}-way data axes "
                        f"across {jax.process_count()} processes); "
                        f"pad or trim each host's rows to a multiple "
                        f"of {local_dp} (single-process callers are "
                        "padded automatically)")
            # non-split meshes replicate the epoch (put_batch's
            # replica branch) — no tiling requirement, no padding
            pad = 0
        else:
            pad = (-n) % dp
        if pad:
            x = pad_rows(x, pad)
            y = pad_rows(y, pad) if y is not None else None
        return self.put_batch((x, y))

    def permute_rows_fn(self):
        """Jitted DEVICE-SIDE row gather ``(x, y, perm) -> (x[perm],
        y[perm])`` with outputs sharded on the data axes.

        One on-device gather per epoch replaces re-transferring the
        whole (host-permuted) epoch over H2D — the per-epoch cost
        drops from epoch-bytes over the host link to an int32 index
        upload. The permutation values come from the FeatureSet's own
        deterministic per-epoch rng, so batch composition is
        bit-identical to the per-step / chunked paths."""
        if self._permute_rows is None:
            mesh = self.mesh

            def permute(x, y, perm):
                def take(a):
                    out = jnp.take(a, perm, axis=0)
                    return jax.lax.with_sharding_constraint(
                        out, mesh_lib.data_sharding(mesh, out.ndim))
                xe = jax.tree_util.tree_map(take, x)
                ye = jax.tree_util.tree_map(take, y) \
                    if y is not None else None
                return xe, ye

            self._permute_rows = engine_jit(permute,
                                            key_hint="permute_rows")
        return self._permute_rows

    # ----------------------------------------------------------- eval step
    def _build_eval_step(self, metrics):
        model = self.model

        def step(params, state, batch):
            x, y, mask = batch
            out, _ = model.apply(params, x, state=state, training=False)
            return tuple(m.batch_update(y, out, mask) for m in metrics)

        return engine_jit(step, out_shardings=self._rep,
                          key_hint="eval_step")

    def make_eval_runner(self, metrics):
        from analytics_zoo_tpu.pipeline.api.keras.metrics import accumulate
        step = self._build_eval_step(metrics)

        def run(params, state, batches):
            return accumulate(
                metrics, (step(params, state, batch)
                          for batch in self.prefetch(batches)))
        return run

    # -------------------------------------------------------- predict step
    def predict_fn(self):
        model = self.model
        if self._predict_step is None:
            def step(params, state, x):
                out, _ = model.apply(params, x, state=state, training=False)
                return out
            self._predict_step = engine_jit(step,
                                            out_shardings=self._rep,
                                            key_hint="predict_step")
        return self._predict_step

    # ------------------------------------------------------- data movement
    def put_batch(self, batch):
        """Place a host batch onto the mesh, sharded on the data axis.

        Single-host path: ``device_put`` with NamedSharding.  Multi-host
        path: ``jax.make_array_from_process_local_data`` — the per-host
        FeatureSet shard becomes this host's slice of the global batch
        (so the effective global batch = per-host batch x processes).

        Leaves whose leading dim doesn't tile the data axis (e.g. a
        group-aligned ranking-eval batch) are replicated instead — same
        math, no shard speedup for that batch.
        """
        dp = self.mesh.shape[mesh_lib.DATA_AXIS] * \
            self.mesh.shape[mesh_lib.FSDP_AXIS]
        nproc = jax.process_count()
        # data axes spread across processes only when they divide evenly;
        # otherwise (e.g. pure model-parallel, dp=1 over 2 hosts) every
        # host must feed the IDENTICAL batch, which is replicated below.
        data_split_across_hosts = mesh_lib.data_split_across_hosts(
            self.mesh)
        local_dp = dp // nproc if data_split_across_hosts else dp

        def put(a):
            if a is None:
                return None
            if nproc > 1:
                a = np.asarray(a)
                if a.ndim == 0 or not data_split_across_hosts:
                    # replica semantics: hosts must pass identical data
                    # (make_array_from_process_local_data requires it
                    # when global_shape == local shape)
                    return jax.make_array_from_process_local_data(
                        self._rep, a, a.shape)
                if a.shape[0] % local_dp != 0:
                    # replicating per-host-DIFFERENT rows would silently
                    # disagree across processes, and mixing global dims
                    # within one batch breaks the jitted step — refuse.
                    raise ValueError(
                        f"multi-host batch dim {a.shape[0]} must tile "
                        f"this host's data-parallel share {local_dp}")
                # this process's rows are one slice of the global batch
                return jax.make_array_from_process_local_data(
                    mesh_lib.data_sharding(self.mesh, a.ndim), a,
                    (a.shape[0] * nproc,) + a.shape[1:])
            if np.ndim(a) == 0 or np.shape(a)[0] % dp != 0:
                return jax.device_put(a, self._rep)
            return jax.device_put(
                a, mesh_lib.data_sharding(self.mesh, np.ndim(a)))

        return jax.tree_util.tree_map(
            put, batch, is_leaf=lambda v: v is None)

    def replicate(self, tree):
        """Replicate a pytree across the mesh, always copying: the train
        step donates its inputs, and ``device_put`` may alias an
        already-device-resident array — donating an alias would delete
        the caller's buffer."""
        if jax.process_count() > 1:
            return jax.tree_util.tree_map(
                lambda a: jax.make_array_from_process_local_data(
                    self._rep, np.asarray(a), np.shape(a)), tree)
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(jnp.array(a, copy=True), self._rep),
            tree)

    def prefetch(self, batches, depth: Optional[int] = None,
                 iteration: Optional[int] = None, stride: int = 1):
        """Overlap host batch assembly + H2D transfer with device compute.

        A background thread pulls host batches, places them on the mesh
        (``put_batch``) and queues them ``depth`` deep — the analogue of
        the reference's MTSampleToMiniBatch worker threads feeding the
        training tasks (MTSampleToMiniBatch.scala:28).  ``iteration``
        is the training step the first batch feeds and ``stride`` the
        steps a batch covers: the producer's spans and the consumer's
        ``data_wait`` for one batch then carry the same ``iteration``.
        """
        from analytics_zoo_tpu.data.stages import (
            PrefetchIterator, pull_with_wait_spans)
        if depth is None:
            depth = int(get_config().get("data.prefetch"))
        wait_hist = self._m_step_time.labels("data_wait")
        if depth <= 0:
            # data_wait here covers host batch assembly + H2D — the
            # whole input-side cost the device waits on
            placed = map(self.put_batch, batches)
        else:
            placed = PrefetchIterator(
                batches, depth, fn=self.put_batch,
                on_depth=self._m_prefetch_depth.set,
                iteration=iteration, stride=stride)
        try:
            for item, wait in pull_with_wait_spans(placed, iteration,
                                                   stride):
                # attribution: how long the consumer stalled waiting
                # for the next device-placed batch (0 ≈ input keeps up)
                wait_hist.observe(wait)
                yield item
        finally:
            # a consumer that stops early releases the thread and the
            # device batches it buffered
            if isinstance(placed, PrefetchIterator):
                placed.close()
