"""``engine_jit`` is ``jax.jit`` with a name (docs/aot-compile.md).

* a drop-in jit: identical results with statics, donation and
  shardings, and jit's own recompile on a drifting shape;
* ``warm()`` compiles ahead: the first call of the warmed signature
  fires no backend compile, for a bare program and for the two
  warm-start entry points (``InferenceModel.warm``,
  ``DistributedTrainer.warm_start``);
* ``_cache_size``, the one JAX-private name compile accounting reads
  (``observability/diagnostics.py``), is there;
* the acceptance gate: a SECOND PROCESS over a warm directory of
  JAX's persistent compilation cache reports cache hits, no miss, zero
  post-warm recompiles, and train/predict results bit-identical to
  the cold run (subprocess round trip).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.compile import engine_jit
from analytics_zoo_tpu.observability import get_registry
from analytics_zoo_tpu.observability.diagnostics import (
    install_compile_listener)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def backend_compiles() -> float:
    """How many XLA backend compiles ``jax.monitoring`` has reported
    to the registry so far."""
    install_compile_listener()
    return get_registry().counter(
        "jax_backend_compiles_total",
        "XLA backend compilations (jax.monitoring)").value


def _case(name):
    """``(fn, jit's keyword arguments, one argument maker a call)``;
    a maker builds fresh arguments, since donation consumes them."""
    x = np.random.RandomState(0).randn(8, 8).astype(np.float32)
    if name == "plain":
        return (lambda a, b: a @ b + jnp.sin(a).sum()), {}, \
            [lambda: (x, x)]
    if name == "static_argnums":
        # a changed STATIC VALUE must re-specialize, not reuse the
        # baked constant
        return (lambda a, n: a * n), dict(static_argnums=(1,)), \
            [lambda: (x, 3), lambda: (x, 5), lambda: (x, 3)]
    if name == "donate_argnums":
        return (lambda p, v: jax.tree_util.tree_map(
            lambda a: a + v.sum(), p)), dict(donate_argnums=(0,)), \
            [lambda: ({"w": jnp.ones((4,), jnp.float32)}, x[0])]
    mesh = Mesh(np.array(jax.devices()).reshape(8,), ("data",))
    sh = NamedSharding(mesh, P("data"))
    return (lambda a: a * 2 + a.sum()), \
        dict(in_shardings=(sh,), out_shardings=sh), \
        [lambda: (jax.device_put(np.arange(16, dtype=np.float32), sh),)]


# ================================================== engine_jit semantics


class TestEngineJitSemantics:
    @pytest.mark.parametrize("case", ["plain", "static_argnums",
                                      "donate_argnums", "shardings"])
    def test_matches_plain_jit(self, case):
        fn, kwargs, makers = _case(case)
        ej = engine_jit(fn, key_hint="t_" + case, **kwargs)
        ref = jax.jit(fn, **kwargs)
        assert ej.key_hint == "t_" + case
        for make in makers:
            want, got = ref(*make()), ej(*make())
            assert jax.tree_util.tree_structure(want) == \
                jax.tree_util.tree_structure(got)
            for w, g in zip(jax.tree_util.tree_leaves(want),
                            jax.tree_util.tree_leaves(got)):
                assert np.asarray(w).tobytes() == \
                    np.asarray(g).tobytes()
                assert w.sharding == g.sharding

    def test_shape_drift_compiles_a_second_entry(self):
        traces = []

        def fn(a):
            traces.append(1)   # trace-time marker
            return a * 2

        ej = engine_jit(fn, key_hint="t_drift")
        a4 = ej(np.ones((4,), np.float32))
        a8 = ej(np.ones((8,), np.float32))   # drift: a second program
        a4b = ej(np.ones((4,), np.float32))  # back: the first again
        assert np.asarray(a4).shape == (4,)
        assert np.asarray(a8).tolist() == [2.0] * 8
        assert np.asarray(a4b).tolist() == [2.0] * 4
        assert len(traces) == 2
        assert ej._cache_size() == 2

    def test_warm_with_specs_primes_the_concrete_call(self):
        def fn(a, b):
            return a + b

        ej = engine_jit(fn, key_hint="t_warm")
        spec = jax.ShapeDtypeStruct((4, 4), np.float32)
        before = backend_compiles()
        assert ej.warm(spec, spec) is True
        assert backend_compiles() == before + 1
        out = ej(np.ones((4, 4), np.float32), np.ones((4, 4), np.float32))
        assert np.asarray(out)[0, 0] == 2.0
        # the concrete call found the warmed executable
        assert backend_compiles() == before + 1
        # what cannot be compiled is False, never an exception
        assert ej.warm(spec, jax.ShapeDtypeStruct((5, 4),
                                                  np.float32)) is False

    def test_cache_size_is_there(self):
        """``_MonitoredJit`` counts a compile by the growth of jit's
        own cache, read through ``_cache_size``: the one JAX-private
        name the package takes on.  A JAX upgrade that removes or
        changes it fails HERE: replace the read in
        ``observability/diagnostics._MonitoredJit`` by whatever that
        JAX offers for "did this call add an executable"."""
        ej = engine_jit(lambda a: a + 1, key_hint="t_size")
        assert ej._cache_size() == 0
        ej(np.ones((4,), np.float32))
        ej(np.ones((4,), np.float32))
        assert ej._cache_size() == 1

    def test_config_has_no_compile_key(self):
        from analytics_zoo_tpu.common.config import get_config
        assert [k for k in get_config().as_dict()
                if k.startswith("compile.")] == []


# ============================================ warm-start entry points


class TestWarmStartEntrypoints:
    def test_inference_model_warm(self):
        from analytics_zoo_tpu.pipeline.api.keras import Sequential
        from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
        from analytics_zoo_tpu.pipeline.inference.inference_model import (
            InferenceModel)
        m = Sequential()
        m.add(Dense(4, input_shape=(8,)))
        m.init()
        im = InferenceModel().load_zoo(m)
        assert im.warm((8,), 16) is True
        before = backend_compiles()
        out = im.predict(np.ones((16, 8), np.float32), batch_size=16)
        assert out.shape == (16, 4)
        # the request found the warmed executable
        assert backend_compiles() == before

    def test_serving_config_parses_input_shape(self):
        from analytics_zoo_tpu.serving.server import ServingConfig
        assert ServingConfig(input_shape="224,224,3").input_shape == \
            (224, 224, 3)
        assert ServingConfig(input_shape=(8,)).input_shape == (8,)
        assert ServingConfig().input_shape is None

    def test_trainer_warm_start_preloads_the_step(self):
        from analytics_zoo_tpu.pipeline.api.keras import Sequential
        from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
        from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam
        from analytics_zoo_tpu.pipeline.api.keras import objectives
        from analytics_zoo_tpu.parallel.trainer import DistributedTrainer
        m = Sequential()
        m.add(Dense(4, input_shape=(8,)))
        m.init()
        trainer = DistributedTrainer(
            m, objectives.get(
                "sparse_categorical_crossentropy_with_logits"),
            optim_method=Adam(lr=1e-3))
        variables = m.get_variables()
        params = trainer.place_params(variables["params"])
        state = trainer.replicate(variables["state"])
        opt_state = trainer.init_opt_state(params)
        x = np.ones((32, 8), np.float32)
        y = np.zeros((32,), np.int32)
        rng = jax.random.PRNGKey(0)
        assert trainer.warm_start(params, opt_state, state, (x, y),
                                  rng) is True
        batch = trainer.put_batch((x, y))
        jax.block_until_ready(batch)
        before = backend_compiles()
        # sharded, donated arguments: the step the loop dispatches
        out = trainer.train_step_at(params, opt_state, state, batch,
                                    rng, np.int32(0))
        assert len(out) == 4
        assert backend_compiles() == before


# ================================== acceptance: second-process warm start


class TestSecondProcessWarmStart:
    def _run(self, cache_dir):
        env = dict(os.environ)
        env.pop("ZOO_TPU_RUN_DIR", None)
        env["PYTHONPATH"] = REPO_ROOT + os.pathsep + \
            env.get("PYTHONPATH", "")
        r = subprocess.run(
            [sys.executable,
             os.path.join(REPO_ROOT, "tests", "compile_cache_worker.py"),
             cache_dir],
            capture_output=True, text=True, timeout=420, env=env,
            cwd=REPO_ROOT)
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    def test_warm_second_process_is_hit_and_bit_identical(
            self, tmp_path):
        cache_dir = str(tmp_path / "warm-cache")
        cold = self._run(cache_dir)
        assert cold["cache_hits"] == 0
        assert cold["cache_misses"] >= 1       # full compiles paid
        assert len(os.listdir(cache_dir)) >= 1  # ... and persisted

        warm = self._run(cache_dir)
        # every compile of the warm process is answered by the cache,
        # the DEFAULT train step (finite check on: no host callback in
        # its program) among them; zero post-warm recompiles;
        # train/predict bit-identical to the cold run
        assert warm["cache_hits"] == cold["cache_misses"]
        assert warm["cache_misses"] == 0
        # ... and so is what compiles between init_zoo_context, where
        # the listener runs from since PR 35, and the compile monitor,
        # from where the worker counts the four numbers above
        early = cold["before_monitor"]
        assert warm["before_monitor"] == {
            "hits": early["hits"] + early["misses"], "misses": 0}
        assert warm["train_step_compiles"] == 1
        assert warm["recompiles_after_warmup"] == 0
        assert warm["params_digest"] == cold["params_digest"]
        assert warm["pred_digest"] == cold["pred_digest"]
