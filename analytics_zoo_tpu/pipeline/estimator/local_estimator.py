"""LocalEstimator — pure-local trainer with no distributed machinery.

Reference: ``LocalEstimator`` (zoo/pipeline/estimator/LocalEstimator.scala:39-71)
trains on one node without Spark: per-thread model replicas, parallel
gradient reduce, array-based ``fit(trainData, ..., batchSize, epochs)``.

TPU version: the "per-core thread replicas" role is played by a single
jit-compiled step on the local device — XLA already saturates the chip's
compute units, so host-side replica threads would only add overhead.  No
mesh, no triggers, no checkpoints: just epochs over shuffled batches,
which makes this the lightest-weight entry point (the analogue of the
reference's localEstimator examples, e.g. LenetLocalEstimator.scala).
"""

from __future__ import annotations

import logging
import time
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

log = logging.getLogger("analytics_zoo_tpu.local_estimator")


class LocalEstimator:
    """Train/evaluate/predict a Keras-API model on the local device.

    ``model`` may be compiled or not; ``criterion``/``optim_method``
    accept the same string or object forms as ``KerasNet.compile``.
    """

    def __init__(self, model, criterion, optim_method,
                 metrics: Optional[Sequence] = None):
        from analytics_zoo_tpu.pipeline.api.keras import (
            metrics as met, objectives, optimizers as opt)
        self.model = model
        self.loss_fn = objectives.get(criterion)
        self.optim = opt.get(optim_method)
        self.metrics = [met.get(m) for m in (metrics or [])]
        self.history: List[Dict] = []
        self._step = None
        self._eval_step = None
        self._predict_step = None

    # ------------------------------------------------------------- compile
    def _build_step(self):
        from analytics_zoo_tpu.common.config import get_config
        model, loss_fn, optim = self.model, self.loss_fn, self.optim
        remat = bool(get_config().get("train.remat"))
        check_finite = bool(get_config().get("observability.check_finite"))

        def step(params, opt_state, state, x, y, rng):
            def objective(p):
                out, new_state = model.apply(p, x, state=state,
                                             training=True, rng=rng)
                loss = loss_fn(y, out)
                return loss + model.regularization_loss(p), (new_state, loss)

            if remat:   # same knob as the distributed engine
                objective = jax.checkpoint(objective)
            grads, (new_state, loss) = jax.grad(
                objective, has_aux=True)(params)
            finite = None
            if check_finite:
                # watchdog NaN/Inf detector — the same fold the
                # distributed engine traces (one shared helper); the
                # flag is the step's fifth output
                from analytics_zoo_tpu.observability.watchdog import (
                    fold_finiteness_check)
                finite = fold_finiteness_check(loss, grads)
            import optax
            from analytics_zoo_tpu.parallel.trainer import (
                mask_frozen_params)
            updates, new_opt_state = optim.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            new_params = mask_frozen_params(model, params, new_params)
            return new_params, new_opt_state, new_state, loss, finite

        from analytics_zoo_tpu.compile import engine_jit
        from analytics_zoo_tpu.observability import get_compile_monitor
        return get_compile_monitor().wrap(
            "local_train_step",
            engine_jit(step, donate_argnums=(0, 1, 2),
                       key_hint="local_train_step"))

    def _current_step(self):
        """The jitted step, rebuilt whenever the model's frozen-layer
        set changes (it is baked in at trace time)."""
        frozen = (self.model.frozen_layer_names()
                  if hasattr(self.model, "frozen_layer_names") else set())
        if self._step is None or \
                getattr(self, "_step_frozen", None) != frozen:
            self._step = self._build_step()
            self._step_frozen = frozen
        return self._step

    # ----------------------------------------------------------------- fit
    def fit(self, x, y, validation_data=None, batch_size: int = 32,
            epochs: int = 1, rng=None):
        from analytics_zoo_tpu.data import DataPipeline
        from analytics_zoo_tpu.feature.feature_set import FeatureSet
        pipeline = x if isinstance(x, DataPipeline) else None
        if pipeline is not None:
            data = pipeline
            batch_size = pipeline.batch_size
        else:
            data = x if isinstance(x, FeatureSet) \
                else FeatureSet.from_ndarrays(x, y)
            if data.size < batch_size:
                raise ValueError(
                    f"batch_size {batch_size} exceeds dataset size "
                    f"{data.size}")
        rng = rng if rng is not None else jax.random.PRNGKey(0)

        variables = self.model.get_variables()
        # the jitted step donates (params, opt_state, state): copy the
        # model's live variables first so donation can never delete the
        # model's own buffers (e.g. after an exception mid-epoch)
        import jax.numpy as jnp
        copy = lambda t: jax.tree_util.tree_map(
            lambda a: jnp.array(a, copy=True), t)
        params = copy(variables["params"])
        state = copy(variables["state"])
        from analytics_zoo_tpu.compile import engine_jit
        opt_state = engine_jit(self.optim.init,
                               key_hint="local_init_opt_state")(params)
        self._current_step()

        it = 0
        validate = validation_data is not None and self.metrics

        def sync_to_host():
            self.model.set_variables({"params": jax.device_get(params),
                                      "state": jax.device_get(state)})

        from analytics_zoo_tpu.common.config import get_config
        from analytics_zoo_tpu.observability import (
            EPOCH_BUCKETS, get_registry, get_tracer)
        from analytics_zoo_tpu.observability.diagnostics import (
            publish_mfu, step_attribution_histogram)
        from analytics_zoo_tpu.observability.watchdog import (
            PendingFiniteFlags, TrainingHalted, TrainingWatchdog,
            set_active_watchdog)
        reg = get_registry()
        m_epoch = reg.histogram(
            "train_epoch_seconds", "wall time per completed epoch",
            labels=("engine",), buckets=EPOCH_BUCKETS)
        m_samples = reg.counter("train_samples_total",
                                "training samples consumed")
        # step-time attribution + sampled device bracket, same shape
        # as the distributed engine's (trainer._dispatch_instrumented)
        m_step_time = step_attribution_histogram(reg)
        device_every = int(
            get_config().get("observability.device_time_every") or 0)
        tracer = get_tracer()
        # training-health watchdog: the local engine has no checkpoint
        # machinery, so checkpoint_and_halt degrades to halt-only (the
        # host-side model variables still hold the last synced state)
        watchdog = TrainingWatchdog()
        prev_watchdog = set_active_watchdog(watchdog)
        watchdog.start_stall_monitor()
        # the steps' finite flags, read where this loop already blocks
        # on the device: the sampled device bracket and the epoch's end
        finite_flags = PendingFiniteFlags()

        def health_check():
            # poll() returns an issue only under checkpoint_and_halt;
            # the model deliberately keeps its LAST SYNCED host
            # variables (the halt-time device state may be poisoned)
            issue = watchdog.poll()
            if issue is not None:
                raise TrainingHalted(
                    f"local training halted by watchdog at step {it}: "
                    f"{issue}", issue=issue)

        try:
            for epoch in range(epochs):
                # monotonic interval math — wall-clock adjustments must
                # not yield negative epoch times
                t0 = time.perf_counter()
                seen = 0
                loss = None
                batches = iter(pipeline) if pipeline is not None \
                    else data.epoch_batches(epoch, batch_size, train=True)
                while True:
                    t_wait = time.perf_counter()
                    try:
                        bx, by = next(batches)
                    except StopIteration:
                        break
                    # host batch assembly = the local data_wait
                    m_step_time.labels("data_wait").observe(
                        time.perf_counter() - t_wait)
                    with tracer.span("train_step"):
                        # t_step, NOT t0: the epoch wall below reads t0
                        t_step = time.perf_counter()
                        params, opt_state, state, loss, finite = \
                            self._step(params, opt_state, state, bx, by,
                                       jax.random.fold_in(rng, it))
                        finite_flags.keep(finite)
                        m_step_time.labels("host_dispatch").observe(
                            time.perf_counter() - t_step)
                        if device_every > 0 and \
                                (it + 1) % device_every == 0:
                            # sampled dispatch->ready bracket + MFU
                            try:
                                jax.block_until_ready(loss)
                                device_s = time.perf_counter() - t_step
                            except Exception:
                                device_s = None
                            if device_s is not None:
                                m_step_time.labels("device").observe(
                                    device_s)
                                publish_mfu("local_train_step",
                                            device_s, reg)
                            finite_flags.drain()
                    it += 1
                    seen += batch_size
                    watchdog.beat()
                    health_check()
                wall = time.perf_counter() - t0
                m_epoch.labels("local").observe(wall)
                m_samples.inc(seen)
                record = {"epoch": epoch + 1, "loss": float(loss),
                          "throughput": seen / max(wall, 1e-9)}
                finite_flags.drain()
                watchdog.observe_loss(record["loss"])
                health_check()
                if validate:   # evaluate() reads the host-side variables
                    sync_to_host()
                    record["val"] = self.evaluate(
                        *validation_data, batch_size=batch_size)
                self.history.append(record)
                log.info("epoch %d loss %.4f%s (%.1f samples/s)",
                         epoch + 1, record["loss"],
                         f" val {record['val']}" if "val" in record else "",
                         record["throughput"])
        finally:
            watchdog.stop()
            set_active_watchdog(prev_watchdog)
        if not validate:
            sync_to_host()
        return self

    # ------------------------------------------------------------ evaluate
    def evaluate(self, x, y, batch_size: int = 32) -> Dict[str, float]:
        from analytics_zoo_tpu.feature.feature_set import FeatureSet
        from analytics_zoo_tpu.pipeline.api.keras.metrics import accumulate
        data = x if isinstance(x, FeatureSet) \
            else FeatureSet.from_ndarrays(x, y)
        model, metrics = self.model, self.metrics
        if self._eval_step is None:
            from analytics_zoo_tpu.compile import engine_jit

            def step(params, state, bx, by, mask):
                out, _ = model.apply(params, bx, state=state, training=False)
                return tuple(m.batch_update(by, out, mask) for m in metrics)
            self._eval_step = engine_jit(step,
                                         key_hint="local_eval_step")

        variables = self.model.get_variables()
        return accumulate(
            metrics,
            (self._eval_step(variables["params"], variables["state"],
                             bx, by, mask)
             for bx, by, mask in data.epoch_batches(0, batch_size,
                                                    train=False)))

    # ------------------------------------------------------------- predict
    def predict(self, x, batch_size: int = 256):
        from analytics_zoo_tpu.pipeline.estimator.estimator import (
            predict_in_batches)
        model = self.model
        if self._predict_step is None:
            from analytics_zoo_tpu.compile import engine_jit

            def step(params, state, bx):
                out, _ = model.apply(params, bx, state=state, training=False)
                return out
            self._predict_step = engine_jit(
                step, key_hint="local_predict_step")
        variables = self.model.get_variables()
        return predict_in_batches(
            lambda xb: self._predict_step(variables["params"],
                                          variables["state"], xb),
            x, batch_size)
