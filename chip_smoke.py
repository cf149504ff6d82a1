#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no children, the entry points a user calls, the default
config.  Run with no arguments it needs ONE TPU chip and drives three
phases at published widths (depth is the only cut, weights are random
from ``--seed``):

* ``train``       ResNet-50 (1000 classes, 224x224x3, batch 128, bf16
                  compute / f32 params, SGD momentum 0.9 with the
                  reference warm-up + poly schedule) through
                  ``model.compile`` + ``Estimator.train`` on a
                  ``FeatureSet``, then ``evaluate``, ``predict`` and a
                  checkpoint round trip;
* ``serve``       that model behind ``InferenceModel().load_zoo`` and
                  ``ClusterServing`` over an ``EmbeddedBroker``: JPEG
                  records in through ``InputQueue``, top-5 out through
                  ``OutputQueue``, ``/healthz`` and ``/metrics``;
* ``transformer`` ``TextClassifier(encoder="transformer")`` at BERT-base
                  block width (768 wide, 12 heads, FFN 3072, T 512,
                  batch 32, 2 blocks): the one listed model whose default
                  path meets flash attention, ``bias_gelu`` and
                  ``layernorm_act`` together, compared with the suite's
                  lax forms and with dense attention (the attention
                  backward's build counter is printed: one pass);
* ``hybrid``      the kernels of a decoder-hybrid-decoder at its widths
                  (5,120 scan channels of 16 states; 40 heads of 64 in
                  differential pairs on 20 K/V heads, under a 512-key
                  window and the causal mask; 2,048 positions): the
                  selective scan's two kernels against the sequential
                  ``lax.scan``, the flash kernels' two maps a pair
                  against dense attention, outputs and gradients (the
                  backward in one pass, by its build counter), each
                  side timed.

* ``latent``      the latent-attention cell's mechanisms at its widths
                  (32 heads of 128 | 64 | 128 and ONE shared rotary key,
                  2,048 positions): the latent flash kernels (the
                  forward and the one-pass backward, whose build counter
                  is printed) against dense attention over 192-wide
                  keys written out plainly, output and the four
                  gradients, each side timed; the sigmoid router of 128
                  experts under a selection bias against its formula.

``--phases a,b`` runs only the phases named.

``--chips 4`` runs ONLY the data-parallel ResNet-50 train path on a
``{"data": 4}`` mesh and the same steps on one of the four chips as its
comparison.

Every phase prints one JSON line (wall seconds split into compile and
run, the devices its arrays live on, its checks).  A phase that fails
makes the exit code non-zero.  The LAST line of stdout is
``{"ok": ..., "device": {"platform", "kind", "count"}}`` with the device
as JAX reports it.  Without a TPU the script exits non-zero at once: no
path continues on the CPU.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys
import tempfile
import time
import traceback
import urllib.request
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu import init_zoo_context, native
from analytics_zoo_tpu.common.config import get_config
from analytics_zoo_tpu.common.triggers import MaxEpoch
from analytics_zoo_tpu.feature.feature_set import FeatureSet
from analytics_zoo_tpu.feature.image import decode_image_bytes
from analytics_zoo_tpu.models.image.imageclassification import resnet
from analytics_zoo_tpu.models.textclassification import TextClassifier
from analytics_zoo_tpu.observability import get_registry
from analytics_zoo_tpu.ops.attention import scaled_dot_product_attention
from analytics_zoo_tpu.ops.pallas_attention import flash_attention
from analytics_zoo_tpu.parallel import mesh as mesh_lib
from analytics_zoo_tpu.parallel.trainer import DistributedTrainer
from analytics_zoo_tpu.pipeline.api.keras.engine import Layer
from analytics_zoo_tpu.pipeline.api.keras.optimizers import (
    SGD, Adam, poly, warmup_then)
from analytics_zoo_tpu.pipeline.estimator import Estimator
from analytics_zoo_tpu.pipeline.inference import InferenceModel
from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue
from analytics_zoo_tpu.serving.engine.executor import ModelExecutor
from analytics_zoo_tpu.serving.redis_client import EmbeddedBroker
from analytics_zoo_tpu.serving.server import ClusterServing, ServingConfig

LOSS = "sparse_categorical_crossentropy_with_logits"
# The reference warms the learning rate up over EPOCHS (thousands of
# iterations); benchmarks/resnet.py ramps to 0.1 in 5 iterations because
# it only times steps.  Followed here, that ramp diverged on the v5e at
# full width (loss 7.5, 5.7, 23, 52, 67 by step 10), so the smoke's few
# steps are the first steps of a 1000-iteration warm-up.
WARMUP_ITERATIONS = 1000
# Stated bf16 tolerances, as max|a - b| / max|b| (scale-relative, so a
# small-valued gradient is held as tightly as an O(1) logit).  Pallas
# and lax epilogues differ at f32 rounding, which the bf16 matmuls
# behind them amplify to ~1e-3; flash and dense attention round their
# logits differently in bf16 (tests/test_pallas_attention.py holds the
# pair to 5e-2 too).
EPILOGUE_TOL = 2e-2
ATTENTION_TOL = 5e-2
# the scan's kernels and the sequential lax.scan are both float32 and
# differ in the order of their sums over channels and positions
SCAN_TOL = 1e-3
# 4 chips and 1 chip run the same math in another reduction order
# (bf16 convolutions): 1e-2 while the parameters are still the same,
# 5e-2 after the 8 SGD steps that amplify it
DP_FIRST_TOL = 1e-2
DP_LOSS_TOL = 5e-2


def _emit(obj: Dict) -> None:
    print(json.dumps(obj), flush=True)


# ------------------------------------------------------------ measurement
class _CompileClock:
    """What ``jax.monitoring`` reports of the XLA backend compile: its
    seconds (a persistent-cache lookup counts, tracing and lowering do
    not: their events nest), how often each program was compiled, and
    how many compiles the persistent cache answered."""

    def __init__(self):
        self.seconds = 0.0
        self.programs: collections.Counter = collections.Counter()
        self.cache_hits = 0
        self._installed = False

    def install(self) -> None:
        if self._installed:
            return
        self._installed = True
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float,
                     fun_name: str = "?", **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            # "jit_epoch" on one device, "jit(epoch)" on several
            self.programs[fun_name.removeprefix("jit").strip("_()")] += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def read(self) -> Tuple[float, collections.Counter, int]:
        return self.seconds, self.programs.copy(), self.cache_hits


CLOCK = _CompileClock()


def _counters() -> Dict[str, float]:
    return dict(get_registry().snapshot()["counters"])


def _delta(after: Dict[str, float], before: Dict[str, float],
           prefix: str) -> Dict[str, float]:
    """Counters under ``prefix`` that moved, keyed by their label part."""
    out = {}
    for key, val in after.items():
        if key.startswith(prefix) and val != before.get(key, 0.0):
            out[key[len(prefix):]] = val - before.get(key, 0.0)
    return out


def _residency(min_bytes: int) -> Tuple[Dict, bool]:
    """Where the process's live arrays are.  True when they are all on
    the first device's platform and hold at least ``min_bytes`` (the
    model's parameters): nothing quietly left on the host platform."""
    want = jax.devices()[0].platform
    arrays = jax.live_arrays()
    platforms = sorted({d.platform for a in arrays for d in a.devices()})
    kinds = sorted({d.device_kind for a in arrays for d in a.devices()})
    nbytes = int(sum(a.nbytes for a in arrays))
    rec = {"platforms": platforms, "device_kinds": kinds,
           "live_arrays": len(arrays), "live_bytes": nbytes}
    return rec, platforms == [want] and nbytes >= min_bytes


def _rel_err(got, ref) -> float:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) + 1e-30))


def _param_bytes(model) -> int:
    return int(sum(np.asarray(a).nbytes for a in
                   jax.tree_util.tree_leaves(model.get_variables())))


def run_phase(name: str, fn: Callable, *args, **kwargs):
    """Run one phase and print its line.  ``fn`` returns a record whose
    ``checks`` are all booleans, or ``(record, carry)``; a phase that
    raises is a failed phase (traceback on stderr), never a warning.
    Returns ``(ok, carry)``."""
    sec0, prog0, hit0 = CLOCK.read()
    t0 = time.perf_counter()
    carry = None
    try:
        out = fn(*args, **kwargs)
        rec, carry = out if isinstance(out, tuple) else (out, None)
    except Exception:   # noqa: BLE001 — reported as a failed phase below
        traceback.print_exc()
        rec = {"checks": {}, "error": traceback.format_exc()[-1500:]}
    wall = time.perf_counter() - t0
    sec1, prog1, hit1 = CLOCK.read()
    checks = rec.get("checks", {})
    ok = bool(checks) and all(checks.values()) and "error" not in rec
    dev = jax.devices()[0]
    _emit({"phase": name, "ok": ok,
           "wall_s": wall, "compile_s": sec1 - sec0,
           "run_s": wall - (sec1 - sec0),
           "backend_compiles": sum((prog1 - prog0).values()),
           "persistent_cache_hits": hit1 - hit0,
           "platform": dev.platform, "device_kind": dev.device_kind,
           **rec})
    return ok, carry


# ------------------------------------------------------------------- data
def synthetic_images(n: int, image: int, classes: int, seed: int):
    """Seeded images as a mean/std-normalising input pipeline hands them
    over (zero mean, unit variance: raw pixel offsets leave the first
    conv's gradient a difference of large numbers, which a reordered
    reduction changes by 10%), with labels uniform over ``classes``.
    What SGD can learn from them in a few steps is to stop being
    confidently wrong: the loss falls from its random-init value
    towards ln(classes).  (Labels that a few classes share are learnt
    faster than the recipe's short warm-up can take: on the v5e the
    loss went 7.5, 5.7, 23, 52, 67.)"""
    rs = np.random.RandomState(seed)
    labels = rs.randint(0, classes, size=(n, 1)).astype(np.int32)
    x = rs.standard_normal((n, image, image, 3)).astype(np.float32)
    return x, labels


def _resnet_model(depth: int, classes: int, image: int):
    """ResNet with the reference ImageNet recipe: SGD momentum 0.9,
    linear warm-up into poly(0.5) decay (benchmarks/resnet.py)."""
    Layer.reset_name_counters()
    model = resnet(depth, num_classes=classes,
                   input_shape=(image, image, 3))
    sched = warmup_then(0.1, WARMUP_ITERATIONS,
                        poly(0.1, 0.5, max_iteration=10_000))
    model.compile(SGD(learning_rate=0.1, momentum=0.9, schedule=sched),
                  LOSS, metrics=["accuracy"])
    return model


def _fit(model, x, y, batch: int, epochs: int, mesh=None):
    """The user path: ``Estimator.train`` on a ``FeatureSet`` (what
    ``model.fit`` itself calls).  Returns the per-epoch history and how
    often the backend compiled each program meanwhile."""
    compiled = CLOCK.read()[1]
    est = Estimator(model, optim_method=model.optim_method, mesh=mesh)
    est.train(FeatureSet.from_ndarrays(x, y), model.loss,
              end_trigger=MaxEpoch(epochs), batch_size=batch)
    return est.history, CLOCK.read()[1] - compiled


def _train_checks(history, before, after, programs,
                  params) -> Tuple[Dict, Dict]:
    """Checks every training run shares: finite loss, the one-pass
    optimizer update on every leaf, one compile.  ``programs``
    counts the backend compiles of the fit by program name."""
    losses = [float(h["loss"]) for h in history]
    engines = _delta(after, before, "train_steps_total")
    compiles = _delta(after, before, "jax_compiles_total")
    builds = _delta(after, before, "fused_kernel_builds_total")
    leaves = jax.tree_util.tree_leaves(params)
    opt = {k: v for k, v in builds.items() if "fused_" in k}
    pallas = sum(v for k, v in opt.items() if 'path="pallas"' in k)
    lax = sum(v for k, v in opt.items() if 'path="lax"' in k)
    # the one-pass update built for every leaf, however often the step
    # was traced, and no optimizer custom call in the program: on one
    # chip and on a mesh alike
    kernel_ok = pallas == 0 and lax > 0 and lax % len(leaves) == 0
    train_fns = {k: v for k, v in compiles.items() if "train_" in k}
    slow = {k: v for k, v in programs.items() if v > 1}
    rec = {"loss_per_epoch": losses, "dispatch_engine": engines,
           "kernel_builds": builds, "compiles": compiles,
           "epoch_wall_s": [h["wall_s"] for h in history],
           "programs_compiled_more_than_once": slow}
    checks = {
        "loss_finite_every_epoch": bool(np.all(np.isfinite(losses))),
        "optimizer_one_pass_every_leaf": kernel_ok,
        # the registry's monitor keys on shapes and dtypes; a changed
        # input SHARDING recompiles unseen by it, so the backend's own
        # count of the step program (the scan engines' "epoch") decides
        "one_compile_then_none": bool(train_fns) and
        all(v == 1 for v in train_fns.values()) and
        not _delta(after, before, "jax_recompiles_total") and
        programs["epoch"] == 1,
    }
    return rec, checks


# ------------------------------------------------------------------ train
def train(*, depth: int = 50, classes: int = 1000, image: int = 224,
          batch: int = 128, steps_per_epoch: int = 2, epochs: int = 12,
          seed: int = 0, workdir: str):
    """ResNet training, evaluate, predict, checkpoint round trip."""
    model = _resnet_model(depth, classes, image)
    x, y = synthetic_images(batch * steps_per_epoch, image, classes, seed)
    before = _counters()
    history, programs = _fit(model, x, y, batch, epochs)
    rec, checks = _train_checks(history, before, _counters(), programs,
                                model.get_variables()["params"])
    checks["loss_fell"] = history[-1]["loss"] < history[0]["loss"]

    scores = model.evaluate(x[:batch], y[:batch], batch_size=batch)
    logits = model.predict(x[:batch], batch_size=batch)
    rec["evaluate"] = {k: float(v) for k, v in scores.items()}
    checks["evaluate_finite"] = bool(
        np.all(np.isfinite(list(scores.values()))))
    checks["predict_shape_finite"] = (
        logits.shape == (batch, classes)
        and bool(np.all(np.isfinite(logits))))
    rec["arrays"], checks["params_on_device"] = _residency(
        _param_bytes(model))

    path = os.path.join(workdir, "resnet.ckpt")
    model.save_model(path)
    restored = _resnet_model(depth, classes, image).load_weights(path)
    checks["checkpoint_bit_identical"] = bool(np.array_equal(
        restored.predict(x[:batch], batch_size=batch), logits))
    rec["checks"] = checks
    return rec, model


# ------------------------------------------------------------------ serve
def serve(model, *, image: int = 224, n_records: int = 8, seed: int = 0):
    """The trained model behind ClusterServing in this process."""
    import cv2
    rs = np.random.RandomState(seed + 1)
    jpegs = []
    for _ in range(n_records):
        ok, enc = cv2.imencode(
            ".jpg", (rs.rand(image, image, 3) * 255).astype(np.uint8))
        if not ok:
            raise RuntimeError("cv2.imencode failed")
        jpegs.append(enc.tobytes())
    # serving consumes BGR float32, exactly as server.decode_field does
    decoded = np.stack([decode_image_bytes(j, to_rgb=False)
                        .astype(np.float32) for j in jpegs])
    direct = ModelExecutor.postprocess(
        model.predict(decoded, batch_size=n_records), 5)

    served_before = get_registry().counter(
        "serving_records_total", "records served").value
    broker = EmbeddedBroker()
    serving = ClusterServing(
        InferenceModel().load_zoo(model),
        ServingConfig(batch_size=n_records, top_n=5, metrics_port=0,
                      metrics_host="127.0.0.1"), broker=broker)
    try:
        inq = InputQueue(broker=broker)
        for i, j in enumerate(jpegs):
            inq.enqueue_image(f"rec-{i}", j)
        deadline = time.monotonic() + 600
        while serving.total_records < n_records:
            serving.run_once(block_ms=0)
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"served {serving.total_records}/{n_records}")
        outq = OutputQueue(broker=broker)
        served = [outq.query(f"rec-{i}", timeout_s=10)
                  for i in range(n_records)]
        base = f"http://127.0.0.1:{serving.metrics_server.port}"
        with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
            health = (r.status, json.loads(r.read()))
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            metrics = r.read().decode()
    finally:
        serving.close()
    scraped = re.search(r"^serving_records_total (\S+)$", metrics, re.M)
    rec = {"top5_first_record": served[0], "healthz": health}
    checks = {
        "all_served": all(s is not None for s in served),
        "top5_classes_match_direct_predict": all(
            s is not None and [c for c, _ in s] == [c for c, _ in d]
            for s, d in zip(served, direct)),
        "top5_probs_match_direct_predict": all(
            s is not None and np.allclose(
                [p for _, p in s], [p for _, p in d], atol=1e-3)
            for s, d in zip(served, direct)),
        "healthz_ready": health[0] == 200 and
        health[1].get("ready") is True,
        "metrics_records_total": scraped is not None and
        float(scraped.group(1)) - served_before == n_records,
    }
    rec["arrays"], checks["weights_on_device"] = _residency(
        _param_bytes(model))
    rec["checks"] = checks
    return rec


# ------------------------------------------------------------ transformer
def _backward_in_one_pass(builds: Dict, kernel: str) -> bool:
    """The only backward form ``builds`` counted for ``kernel`` is the
    one-pass kernel (the shapes here fit its VMEM budget)."""
    return [k for k in builds if f'"{kernel}_backward"' in k] == [
        f'{{kernel="{kernel}_backward",path="one_pass"}}']


def _attention_vs_dense(shape: Sequence[int], dtype, seed: int) -> Dict:
    """flash_attention against the dense reference at ``shape``: output
    and the gradients of q, k and v, as scale-relative errors."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v, w = (jax.random.normal(kk, shape, jnp.float32).astype(dtype)
                  for kk in keys)

    def run(attn):
        # w is an argument: closed over, it would be baked into the
        # executable (140 MB in the persistent cache at the real shape)
        def loss(q, k, v, w):
            out = attn(q, k, v)
            return jnp.sum(out.astype(jnp.float32)
                           * w.astype(jnp.float32)), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v, w)
        return (out,) + grads

    got = run(flash_attention)
    ref = run(scaled_dot_product_attention)
    return {name: _rel_err(g, r)
            for name, g, r in zip(("out", "dq", "dk", "dv"), got, ref)}


def transformer(*, width: int = 768, heads: int = 12, seq: int = 512,
                batch: int = 32, blocks: int = 2, vocab: int = 5000,
                classes: int = 5, fit_steps: int = 3, seed: int = 0):
    """TextClassifier's transformer encoder: the three kernel families
    on their Pallas path, against the lax forms and dense attention."""
    cfg = get_config()
    if cfg.get("ops.fused") != "auto":
        raise RuntimeError("the smoke runs the default config")
    Layer.reset_name_counters()
    clf = TextClassifier(class_num=classes, token_length=width,
                         sequence_length=seq, encoder="transformer",
                         max_words_num=vocab, n_head=heads,
                         n_block=blocks)
    clf.compile(Adam(lr=1e-4), LOSS)
    rs = np.random.RandomState(seed)
    n = batch * fit_steps
    tokens = rs.randint(1, vocab, size=(n, seq)).astype(np.int32)
    labels = rs.randint(0, classes, size=(n, 1)).astype(np.int32)
    xb, yb = tokens[:batch], labels[:batch]

    before = _counters()
    logits = clf.predict(xb, batch_size=batch)
    model = clf.model
    variables = jax.device_put(model.get_variables())
    ffn = next(l.name for l in model.layers
               if type(l).__name__ == "PositionwiseFeedForward")

    def value_and_grad():
        # a fresh jit each call: the suite's mode is read while tracing
        def objective(params):
            out, _ = model.apply(params, xb, state=variables["state"],
                                 training=False)
            return model.loss(yb, out), out
        (loss, out), grads = jax.jit(jax.value_and_grad(
            objective, has_aux=True))(variables["params"])
        return float(loss), out, grads[ffn]["up_kernel"]

    loss, out, g_up = value_and_grad()
    builds = _delta(_counters(), before, "fused_kernel_builds_total")
    cfg.set("ops.fused", "lax")          # the reference run only
    try:
        ref_before = _counters()
        ref_loss, ref_out, ref_g_up = value_and_grad()
        ref_builds = _delta(_counters(), ref_before,
                            "fused_kernel_builds_total")
    finally:
        cfg.set("ops.fused", "auto")

    fit_before = _counters()
    history = clf.fit(tokens, labels, batch_size=batch, nb_epoch=1)
    fit_builds = _delta(_counters(), fit_before,
                        "fused_kernel_builds_total")

    head = width // heads
    attention = {
        np.dtype(dt).name: _attention_vs_dense(
            (batch, heads, seq, head), dt, seed)
        for dt in (jnp.bfloat16, jnp.float32)}
    errs = {"logits_predict_vs_lax": _rel_err(logits, ref_out),
            "logits_vs_lax": _rel_err(out, ref_out),
            "ffn_up_kernel_grad_vs_lax": _rel_err(g_up, ref_g_up)}

    def on_pallas(b: Dict, kernel: str) -> bool:
        return b.get(f'{{kernel="{kernel}",path="pallas"}}', 0) > 0 and \
            f'{{kernel="{kernel}",path="lax"}}' not in b

    rec = {"kernel_builds": builds, "kernel_builds_reference": ref_builds,
           "kernel_builds_fit": fit_builds, "errors_vs_lax": errs,
           "flash_vs_dense": attention, "loss": loss,
           "loss_lax": ref_loss,
           "fit_loss": [float(h["loss"]) for h in history]}
    checks = {
        "flash_attention_pallas": on_pallas(builds, "flash_attention"),
        "backward_in_one_pass": _backward_in_one_pass(
            builds, "flash_attention") and _backward_in_one_pass(
            fit_builds, "flash_attention"),
        "bias_gelu_pallas": on_pallas(builds, "bias_gelu"),
        "layernorm_act_pallas": on_pallas(builds, "layernorm_act"),
        "fit_kernels_pallas": all(
            on_pallas(fit_builds, k) for k in
            ("flash_attention", "bias_gelu", "layernorm_act")),
        "reference_epilogues_lax": all(
            f'{{kernel="{k}",path="pallas"}}' not in ref_builds
            for k in ("bias_gelu", "layernorm_act")),
        "logits_shape_finite": logits.shape == (batch, classes)
        and bool(np.all(np.isfinite(logits))),
        "agrees_with_lax": all(e <= EPILOGUE_TOL for e in errs.values()),
        "flash_agrees_with_dense": all(
            e <= ATTENTION_TOL for d in attention.values()
            for e in d.values()),
        "loss_finite": bool(np.isfinite([loss, ref_loss]).all()
                            and np.isfinite(rec["fit_loss"]).all()),
    }
    rec["arrays"], checks["arrays_on_device"] = _residency(
        _param_bytes(model))
    rec["checks"] = checks
    return rec


# ---------------------------------------------------------- data parallel
def data_parallel(devices, *, depth: int = 50, classes: int = 1000,
                  image: int = 224, batch: int = 128,
                  steps_per_epoch: int = 2, epochs: int = 4,
                  seed: int = 0):
    """Synchronous data-parallel SGD over ``devices`` on the ``data``
    axis, and the same steps from the same seed on ``devices[:1]``."""
    n = len(devices)
    mesh = mesh_lib.create_mesh({"data": n}, devices=devices)
    one = mesh_lib.create_mesh({"data": 1}, devices=devices[:1])
    x, y = synthetic_images(batch * steps_per_epoch, image, classes, seed)

    # what the four chips are asked to hold, by the estimator's own
    # placement functions, and the program it will run
    model = _resnet_model(depth, classes, image)
    trainer = DistributedTrainer(model, model.loss,
                                 optim_method=model.optim_method,
                                 mesh=mesh)
    variables = model.get_variables()
    params = trainer.place_params(variables["params"])
    xe, ye = trainer.put_epoch_source(x, y)
    compiled = trainer.epoch_scan_fn(steps_per_epoch, batch).lower(
        params, trainer.init_opt_state(params),
        trainer.replicate(variables["state"]), xe, ye,
        jax.random.PRNGKey(0), np.int32(0)).compile()
    text = compiled.as_text()
    # batch rows of the stem convolution's output, as one device runs it
    half = image // 2
    stem_rows = sorted({int(m) for m in re.findall(
        rf"\[(\d+),{half},{half},64\]\S* convolution\(", text)})
    xb, _ = trainer.put_batch((x[:batch], y[:batch]))
    rows = sorted({s.data.shape[0] for s in xb.addressable_shards})
    placement = {
        "mesh": {k: v for k, v in mesh.shape.items() if v > 1},
        "mesh_device_ids": [d.id for d in mesh.devices.flat],
        "param_device_set_sizes": sorted({
            len(a.sharding.device_set)
            for a in jax.tree_util.tree_leaves(params)}),
        "batch_rows_per_device": rows,
        "all_reduces": len(re.findall(r"\ball-reduce(-start)?\(", text)),
        "stem_conv_rows_per_device": stem_rows,
    }
    del params, xe, ye, xb, compiled, trainer

    before = _counters()
    dp_history, programs = _fit(model, x, y, batch, epochs, mesh=mesh)
    rec, checks = _train_checks(dp_history, before, _counters(), programs,
                                variables["params"])
    before = _counters()
    ref_history, _ = _fit(_resnet_model(depth, classes, image), x, y,
                          batch, epochs, mesh=one)
    ref_losses = [float(h["loss"]) for h in ref_history]
    rec.update(placement)
    rec["kernel_builds_one_device"] = _delta(
        _counters(), before, "fused_kernel_builds_total")
    rec["loss_per_epoch_one_device"] = ref_losses
    rec["loss_rel_diff_per_epoch"] = (
        np.abs(np.subtract(rec["loss_per_epoch"], ref_losses))
        / np.abs(ref_losses)).tolist()
    rec["epoch_wall_s_one_device"] = [h["wall_s"] for h in ref_history]
    checks.update({
        "params_on_every_device":
        placement["param_device_set_sizes"] == [n],
        "batch_split_evenly": rows == [batch // n],
        "step_computes_a_shard": stem_rows == [batch // n],
        "all_reduce_in_step": placement["all_reduces"] > 0,
        # the first epoch is the same parameters on both meshes (the
        # warm-up's first step has lr 0): batch statistics and the loss
        # all-reduced over four chips against one chip's own
        "first_epoch_loss_agrees":
        rec["loss_rel_diff_per_epoch"][0] <= DP_FIRST_TOL,
        "loss_agrees_with_one_device":
        max(rec["loss_rel_diff_per_epoch"]) <= DP_LOSS_TOL,
    })
    if devices[0].platform == "tpu":
        from jax.experimental import mesh_utils
        laid = mesh_utils.create_device_mesh(
            list(mesh.devices.shape), devices=list(devices),
            allow_split_physical_axes=True)
        checks["mesh_from_mesh_utils"] = \
            [d.id for d in laid.flat] == placement["mesh_device_ids"]
    rec["checks"] = checks
    return rec


# ----------------------------------------------------------------- hybrid
def _timed(fn, *args):
    """(result, seconds of the second call: the first compiles)."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def hybrid(*, seq: int = 2048, hidden: int = 2560, channels: int = 5120,
           states: int = 16, heads: int = 40, kv_heads: int = 20,
           head_dim: int = 64, window: int = 512, seed: int = 0):
    """The selective scan and differential flash attention on their
    Pallas paths, forward and backward, against the lax scan and dense
    attention at the same shapes; then a recomputed decoder layer round
    each, which keeps the kernels' results."""
    from analytics_zoo_tpu.ops import pallas_attention, selective_scan
    from analytics_zoo_tpu.ops.pallas_attention import (
        allowed_pairs, flash_attention_token_major, sliding_window)
    from analytics_zoo_tpu.ops.selective_scan import selective_scan_lax
    before = _counters()
    keys = jax.random.split(jax.random.PRNGKey(seed), 12)
    f32, bf16 = jnp.float32, jnp.bfloat16
    x = jax.random.normal(keys[0], (1, seq, channels), f32)
    dt = jax.nn.softplus(jax.random.normal(keys[1], x.shape, f32) - 3.0)
    a = -jnp.broadcast_to(jnp.arange(1, states + 1, dtype=f32),
                          (channels, states))
    b, c = (jax.random.normal(k, (1, seq, states), f32) for k in keys[2:4])
    state = jax.random.normal(keys[4], (1, channels, states), f32)
    w_y = jax.random.normal(keys[5], x.shape, f32)

    def scan_grads(scan):
        def loss(x, dt, a, b, c, state, w_y):
            y, last = scan(x, dt, a, b, c, state)
            return jnp.sum(y * w_y) + jnp.sum(last), (y, last)
        return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6)),
                                          has_aux=True))

    args = (x, dt, a, b, c, state, w_y)
    ((_, got_out), got_g), scan_s = _timed(
        scan_grads(selective_scan.selective_scan), *args)
    ((_, ref_out), ref_g), lax_s = _timed(scan_grads(selective_scan_lax),
                                          *args)
    names = ("y", "last", "dx", "ddt", "da", "db", "dc", "dstate")
    scan_err = {n: _rel_err(g, r) for n, g, r in
                zip(names, got_out + got_g, ref_out + ref_g)}

    q = jax.random.normal(keys[6], (1, seq, heads * head_dim), f32)
    k, v = (jax.random.normal(kk, (1, seq, kv_heads * head_dim), f32)
            for kk in keys[7:9])
    w_o = jax.random.normal(keys[9], (1, seq, 2 * heads * head_dim), f32)
    q, k, v, w_o = (t.astype(bf16) for t in (q, k, v, w_o))
    group = heads // kv_heads

    def dense(mask):
        ok = jnp.asarray(allowed_pairs(mask, seq))

        def maps(q, k, v):
            qp = q.reshape(1, seq, heads // 2, 2, head_dim)
            kp = jnp.repeat(k.reshape(1, seq, kv_heads // 2, 2, head_dim),
                            group, axis=2)
            vp = jnp.repeat(v.reshape(1, seq, kv_heads // 2, 2 * head_dim),
                            group, axis=2)
            s = jnp.einsum("bqjrd,bkjrd->bjrqk", qp, kp,
                           preferred_element_type=f32) * head_dim ** -0.5
            p = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
            out = jnp.einsum("bjrqk,bkje->bqjre", p.astype(bf16), vp,
                             preferred_element_type=f32)
            return out.reshape(1, seq, -1).astype(bf16)
        return maps

    def flash(mask):
        return lambda q, k, v: flash_attention_token_major(
            q, k, v, n_head=heads, differential=True,
            causal=mask == "causal", mask=None if mask == "causal" else mask)

    def attn_grads(maps):
        def loss(q, k, v, w_o):
            out = maps(q, k, v)
            return jnp.sum(out.astype(f32) * w_o.astype(f32)), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))

    attention = {}
    seconds = {"selective_scan": scan_s, "selective_scan_lax": lax_s}
    for name, mask in (("window", sliding_window(window)),
                       ("causal", "causal")):
        ((_, got_o), got_g), seconds["flash_" + name] = _timed(
            attn_grads(flash(mask)), q, k, v, w_o)
        ((_, ref_o), ref_g), seconds["dense_" + name] = _timed(
            attn_grads(dense(mask)), q, k, v, w_o)
        attention[name] = {n: _rel_err(g, r) for n, g, r in zip(
            ("out", "dq", "dk", "dv"), (got_o,) + got_g, (ref_o,) + ref_g)}

    builds = _delta(_counters(), before, "fused_kernel_builds_total")
    kept, forward_calls = _recomputed_layers(
        seq, hidden, channels, states, heads, kv_heads, head_dim, window,
        seconds)
    rec = {"scan_vs_lax": scan_err, "differential_flash_vs_dense": attention,
           "forward_backward_s": seconds, "kernel_builds": builds,
           "train_recompute_kept_bytes": kept,
           "forward_kernels_in_recomputed_gradient": forward_calls}
    rec["checks"] = {
        # a recomputed layer keeps what its kernels wrote and runs each
        # forward kernel once
        "recomputed_layers_keep_kernel_results":
            set(forward_calls.values()) == {1} and all(
                kept.get('{name="%s"}' % n, 0) > 0 for n in
                pallas_attention.KEPT_RESULTS + selective_scan.KEPT_RESULTS),
        "selective_scan_pallas":
            builds.get('{kernel="selective_scan",path="pallas"}', 0) > 0,
        # the pair's backward under the window and the causal mask
        "backward_in_one_pass": _backward_in_one_pass(
            builds, "flash_attention"),
        "scan_agrees_with_lax": all(e <= SCAN_TOL
                                    for e in scan_err.values()),
        "flash_agrees_with_dense": all(
            e <= ATTENTION_TOL for d in attention.values()
            for e in d.values()),
    }
    return rec


def _pallas_calls(jaxpr, name: str) -> int:
    """``pallas_call`` equations named ``name`` in ``jaxpr``, the
    jaxprs its equations hold included."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "pallas_call" \
            and eqn.params["name"] == name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _pallas_calls(sub, name)
    return n


def _recomputed_layers(seq, hidden, channels, states, heads, kv_heads,
                       head_dim, window, seconds):
    """One recomputed decoder layer round the scan and one round the
    windowed pair, forward and backward (timed into ``seconds``): -> (the
    gauge ``train_recompute_kept_bytes`` by name, how often each layer's
    forward kernel stands in its gradient)."""
    from analytics_zoo_tpu.ops.pallas_attention import sliding_window
    from analytics_zoo_tpu.pipeline.api.keras.layers import ssm
    mixers = {
        "selective_scan_fwd": ssm.Mamba(channels, states),
        "flash_attention_fwd": ssm.DifferentialAttention(
            heads, kv_heads, head_dim, 1, mask=sliding_window(window))}
    forward_calls = {}
    for kernel, mixer in mixers.items():
        layer = ssm.HybridDecoderLayer(mixer, ssm.GatedFeedForward(4 * hidden),
                                       recompute=True)
        shape = (1, seq, hidden)
        params = layer.build(jax.random.PRNGKey(0), shape)
        h = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)

        def loss(params, h, layer=layer):
            return jnp.sum(jnp.square(layer.call(params, h)))

        grads = jax.grad(loss, argnums=(0, 1))
        forward_calls[kernel] = _pallas_calls(
            jax.make_jaxpr(grads)(params, h).jaxpr, kernel)
        _, seconds["recomputed_layer_" + kernel] = _timed(
            jax.jit(grads), params, h)
    return (_delta(get_registry().snapshot()["gauges"], {},
                   "train_recompute_kept_bytes"), forward_calls)


# ----------------------------------------------------------------- latent
def latent(*, seq: int = 2048, heads: int = 32, hidden: int = 2048,
           experts: int = 128, top_k: int = 6, scale: float = 2.448,
           seed: int = 0):
    """The latent flash kernels (heads of 128 | 64 | 128, one shared
    rotary key) on their Pallas path, forward and backward (in one pass
    where a head pair's dq fits VMEM, as at every length this phase is
    run at), against dense attention over keys written out 192 wide;
    then the sigmoid router under a selection bias against its
    formula."""
    from analytics_zoo_tpu.ops import pallas_latent_attention as kernels
    from analytics_zoo_tpu.pipeline.api.keras.layers.moe import DroplessMoE
    before = _counters()
    n, r = kernels.NOPE, kernels.ROPE
    f32, bf16 = jnp.float32, jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    shapes = [(1, seq, heads * (n + r)), (1, seq, heads * r),
              (1, seq, heads * 2 * n), (1, seq, r), (1, seq, heads * n)]
    q, q_pe, kv, k_pe, w_o = (jax.random.normal(k, s, f32).astype(bf16)
                              for k, s in zip(keys, shapes))

    def flash(q, q_pe, kv, k_pe):
        return kernels.latent_flash_attention(
            q, q_pe, kv, k_pe, n_head=heads, causal=True,
            block_q=512 if seq % 1024 == 0 else 256,
            block_k=512 if seq % 1024 == 0 else 256)

    def dense(q, q_pe, kv, k_pe):
        # the keys written out: each head's own 128 beside the shared 64
        q_h = jnp.concatenate([q[..., :heads * n].reshape(1, seq, heads, n),
                               q_pe.reshape(1, seq, heads, r)], axis=-1)
        k_h = jnp.concatenate(
            [kv[..., :heads * n].reshape(1, seq, heads, n),
             jnp.broadcast_to(k_pe[:, :, None], (1, seq, heads, r))], axis=-1)
        v_h = kv[..., heads * n:].reshape(1, seq, heads, n)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_h, k_h,
                       preferred_element_type=f32) * (n + r) ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), s, -1e30)
        out = jnp.einsum("bhqk,bkhd->bqhd",
                         jax.nn.softmax(s, axis=-1).astype(bf16), v_h,
                         preferred_element_type=f32)
        return out.reshape(1, seq, heads * n).astype(bf16)

    def grads(attend):
        def loss(q, q_pe, kv, k_pe, w_o):
            out = attend(q, q_pe, kv, k_pe)
            return jnp.sum(out.astype(f32) * w_o.astype(f32)), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3),
                                          has_aux=True))

    seconds = {}
    ((_, got_o), got_g), seconds["flash_latent"] = _timed(
        grads(flash), q, q_pe, kv, k_pe, w_o)
    ((_, ref_o), ref_g), seconds["dense_latent"] = _timed(
        grads(dense), q, q_pe, kv, k_pe, w_o)
    # the rotary columns of q are not read: their cotangent is zero
    unread = float(jnp.max(jnp.abs(got_g[0][..., heads * n:].astype(f32))))
    got_g = (got_g[0][..., :heads * n],) + got_g[1:]
    ref_g = (ref_g[0][..., :heads * n],) + ref_g[1:]
    attention = {name: _rel_err(g, ref) for name, g, ref in zip(
        ("out", "dq_nope", "dq_pe", "dk_nope_dv", "dk_pe"),
        (got_o,) + got_g, (ref_o,) + ref_g)}

    layer = DroplessMoE(experts, 8, top_k=top_k, scoring="sigmoid",
                        routed_scaling_factor=scale)
    router = 0.02 * jax.random.normal(keys[5], (hidden, experts), f32)
    x = jax.random.normal(keys[6], (1, seq, hidden), f32)
    bias = 0.01 * jax.random.normal(keys[7], (experts,), f32)
    gates, picked, _ = jax.jit(layer.route)(router, x, bias)
    scores = jax.nn.sigmoid(jnp.matmul(
        x[0], router, precision=jax.lax.Precision.HIGHEST))
    _, want = jax.lax.top_k(scores + bias, top_k)
    want_gates = jnp.take_along_axis(scores, want, axis=-1)
    want_gates = want_gates / jnp.sum(want_gates, -1, keepdims=True) * scale
    _, unbiased = jax.lax.top_k(scores, top_k)
    router_rec = {
        "tokens_picking_other_experts": int(jnp.sum(jnp.any(
            jnp.sort(picked, -1) != jnp.sort(want, -1), axis=-1))),
        "gates_rel_err": _rel_err(jnp.sort(gates, -1),
                                  jnp.sort(want_gates, -1)),
        "tokens_the_bias_moved": int(jnp.sum(jnp.any(
            jnp.sort(unbiased, -1) != jnp.sort(want, -1), axis=-1)))}

    builds = _delta(_counters(), before, "fused_kernel_builds_total")
    rec = {"latent_flash_vs_dense": attention,
           "unread_q_columns_cotangent_max": unread,
           "forward_backward_s": seconds, "router": router_rec,
           "kernel_builds": builds}
    rec["checks"] = {
        "flash_agrees_with_dense": all(e <= ATTENTION_TOL
                                       for e in attention.values()),
        "unread_columns_get_no_gradient": unread == 0.0,
        "backward_in_one_pass": _backward_in_one_pass(
            builds, "flash_attention_latent"),
        # a float32 product at the highest precision picks the same
        # experts as the formula (a token in a thousand may sit on a tie)
        "router_picks_the_formulas_experts":
            router_rec["tokens_picking_other_experts"] <= seq // 1000 + 1
            and router_rec["gates_rel_err"] <= 1e-3,
        "bias_changes_selections": router_rec["tokens_the_bias_moved"] > 0,
    }
    return rec


# ------------------------------------------------------------------- main
def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the data-parallel train path and "
                         "its one-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phases",
                    default="train,serve,transformer,hybrid,latent",
                    help="one-chip phases to run (serve needs train)")
    args = ap.parse_args(argv)
    phases = args.phases.split(",")

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if devices[0].platform != "tpu" or len(devices) != args.chips:
        print(f"chip_smoke needs {args.chips} TPU chip(s); JAX reports "
              f"{device}", file=sys.stderr)
        _emit({"ok": False, "device": device})
        return 1

    CLOCK.install()
    init_zoo_context()
    oks = []
    _emit({"phase": "start", "ok": True, "jax": jax.__version__,
           "native_library_loaded": native.get_lib() is not None,
           "compilation_cache_dir": jax.config.jax_compilation_cache_dir})

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        if args.chips == 4:
            oks.append(run_phase("data_parallel", data_parallel, devices,
                                 seed=args.seed)[0])
        else:
            model = None
            if "train" in phases:
                ok, model = run_phase("train", train, seed=args.seed,
                                      workdir=workdir)
                oks.append(ok)
            if "serve" in phases and model is not None:
                oks.append(run_phase("serve", serve, model,
                                     seed=args.seed)[0])
            elif "serve" in phases:
                _emit({"phase": "serve", "ok": False,
                       "error": "no trained model: train failed"})
                oks.append(False)
            for name, fn in (("transformer", transformer),
                             ("hybrid", hybrid), ("latent", latent)):
                if name in phases:
                    oks.append(run_phase(name, fn, seed=args.seed)[0])
    ok = all(oks)
    _emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
