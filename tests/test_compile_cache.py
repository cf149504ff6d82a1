"""AOT compilation + persistent executable cache (docs/aot-compile.md).

Covers the tentpole contracts:

* ``engine_jit`` is a drop-in jit (identical results, statics /
  donation / shardings semantics), with the AOT fast path on top;
* the cache key changes whenever anything that determines the
  executable changes (shape, dtype, static-arg value, donation spec,
  mesh/backend geometry, XLA flags) and ONLY then;
* a cache hit returns bit-identical results to a fresh compile;
* corrupted and version-stale entries are evicted LOUDLY (error
  counters) and can never crash a caller;
* concurrent writers on one key race safely (write-then-rename);
* the size cap LRU-evicts with a counter;
* farm mode: host 0 persists, workers load instead of recompiling;
* the acceptance gate: a SECOND PROCESS over a warm cache dir reports
  >=1 cache hit, zero post-warm recompiles, and train/predict results
  bit-identical to the cold run (subprocess round trip).
"""

import json
import os
import pickle
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.compile import cache as cache_mod
from analytics_zoo_tpu.compile import engine_jit
from analytics_zoo_tpu.compile.cache import (
    ENTRY_SUFFIX, ExecutableCache, cache_key, reset_cache_state)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_env(tmp_path, monkeypatch):
    """A fresh cache dir wired through the real resolution path
    (ZOO_TPU_COMPILE_CACHE), with the per-directory singletons
    dropped before AND after so no other test sees this dir."""
    d = str(tmp_path / "exec-cache")
    monkeypatch.setenv("ZOO_TPU_COMPILE_CACHE", d)
    reset_cache_state()
    yield d
    reset_cache_state()


def counters_snapshot():
    from analytics_zoo_tpu.observability import get_registry
    return dict(get_registry().snapshot().get("counters", {}))


def counter_total(prefix, since=None):
    now = counters_snapshot()
    tot = sum(v for k, v in now.items() if k.startswith(prefix))
    if since is not None:
        tot -= sum(v for k, v in since.items() if k.startswith(prefix))
    return tot


def entries(cache_dir):
    if not os.path.isdir(cache_dir):
        return []
    return sorted(f for f in os.listdir(cache_dir)
                  if f.endswith(ENTRY_SUFFIX))


# ================================================== engine_jit semantics


class TestEngineJitSemantics:
    def test_matches_plain_jit_without_cache(self):
        # no cache dir resolved -> pure jax.jit dispatch, same numbers
        def fn(a, b):
            return a @ b + jnp.sin(a).sum()

        x = np.random.RandomState(0).randn(8, 8).astype(np.float32)
        ref = jax.jit(fn)(x, x)
        out = engine_jit(fn, key_hint="t_semantics")(x, x)
        assert np.array_equal(np.asarray(ref), np.asarray(out))

    def test_static_and_donate_semantics(self, cache_env):
        def fn(a, n):
            return a * n

        ej = engine_jit(fn, static_argnums=(1,), key_hint="t_static")
        x = jnp.ones((4,), jnp.float32)
        assert np.asarray(ej(x, 3)).sum() == 12
        # a changed STATIC VALUE must re-specialize, not reuse the
        # baked constant (the solo fast path is disabled for statics)
        assert np.asarray(ej(x, 5)).sum() == 20

        def step(params, x):
            return jax.tree_util.tree_map(lambda p: p + x.sum(), params)

        ejd = engine_jit(step, donate_argnums=(0,), key_hint="t_donate")
        p = {"w": jnp.ones((4,), jnp.float32)}
        out = ejd(p, jnp.ones((2,), jnp.float32))
        assert np.asarray(out["w"]).tolist() == [3.0] * 4

    def test_shape_drift_recompiles_through_solo_path(self, cache_env):
        calls = []

        def fn(a):
            calls.append(1)   # trace-time marker
            return a * 2

        ej = engine_jit(fn, key_hint="t_drift")
        a4 = ej(np.ones((4,), np.float32))
        a8 = ej(np.ones((8,), np.float32))   # drift: solo path rejects
        a4b = ej(np.ones((4,), np.float32))  # back: slow path finds it
        assert np.asarray(a4).shape == (4,)
        assert np.asarray(a8).shape == (8,)
        assert np.asarray(a4b).tolist() == [2.0] * 4
        assert ej.aot_signatures == 2

    def test_aot_returns_compiled_and_round_trips_the_cache(
            self, cache_env):
        """The bench idiom: hold the Compiled directly (cost analysis
        + repeated execution) while still riding the persistent cache
        — a second engine over the same dir deserializes it."""
        def fn(a):
            return a * 2

        exe = engine_jit(fn, key_hint="t_aot").aot(
            np.ones((4,), np.float32))
        assert np.asarray(exe(np.ones((4,), np.float32))
                          ).tolist() == [2.0] * 4
        before = counters_snapshot()
        exe2 = engine_jit(fn, key_hint="t_aot").aot(
            np.ones((4,), np.float32))
        assert counter_total("compile_cache_hits_total", before) == 1
        assert np.asarray(exe2(np.ones((4,), np.float32))
                          ).tobytes() == \
            np.asarray(exe(np.ones((4,), np.float32))).tobytes()

    def test_compile_aot_false_disables_the_whole_path(self, cache_env):
        """The kill switch: compile.aot=false means plain jax.jit
        dispatch — warm() must not compile-and-install a Compiled
        either, and nothing may land in the cache dir."""
        from analytics_zoo_tpu.common.config import get_config
        get_config().set("compile.aot", False)
        try:
            ej = engine_jit(lambda a: a * 2, key_hint="t_off")
            assert ej.warm(
                jax.ShapeDtypeStruct((4,), np.float32)) is False
            assert ej.aot_signatures == 0
            out = ej(np.ones((4,), np.float32))
            assert np.asarray(out).tolist() == [2.0] * 4
            assert ej.aot_signatures == 0          # plain jit dispatch
            assert entries(cache_env) == []        # nothing persisted
        finally:
            get_config().set("compile.aot", True)

    def test_warm_with_specs_primes_the_concrete_call(self, cache_env):
        def fn(a, b):
            return a + b

        ej = engine_jit(fn, key_hint="t_warm")
        spec = jax.ShapeDtypeStruct((4, 4), np.float32)
        assert ej.warm(spec, spec) is True
        assert ej.aot_signatures == 1
        before = counters_snapshot()
        out = ej(np.ones((4, 4), np.float32), np.ones((4, 4), np.float32))
        assert np.asarray(out)[0, 0] == 2.0
        # the concrete call used the warmed executable: no new lookup
        assert counter_total("compile_cache_misses_total",
                             before) == 0
        assert ej.aot_signatures == 1


# ========================================================== the cache key


class TestCacheKey:
    BASE = dict(hlo_digest="h", signature_repr="s", donate_repr="()",
                static_repr="()", backend_sig="cpu|x|8|1", xla_flags="")

    def key(self, **over):
        kw = dict(self.BASE)
        kw.update(over)
        return cache_key(kw.pop("hlo_digest"), kw.pop("signature_repr"),
                         **kw)

    def test_every_component_changes_the_key(self):
        base = self.key()
        assert self.key(hlo_digest="h2") != base          # program
        assert self.key(signature_repr="s2") != base      # shape/dtype
        assert self.key(donate_repr="(0,)") != base       # donation
        assert self.key(static_repr="(1,)") != base       # statics
        assert self.key(backend_sig="cpu|x|4|1") != base  # mesh geometry
        assert self.key(xla_flags="--flag") != base       # XLA flags
        assert self.key() == base                         # and ONLY then

    def test_shape_dtype_and_mesh_key_end_to_end(self, cache_env):
        """Through the real lowering path: distinct shapes, dtypes and
        mesh partitionings land in distinct cache entries."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from analytics_zoo_tpu.compile.cache import get_cache

        def fn(a):
            return a * 2

        ej = engine_jit(fn, key_hint="t_keys")
        ej(np.ones((4,), np.float32))
        ej(np.ones((8,), np.float32))            # shape
        ej(np.ones((4,), np.int32))              # dtype
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()).reshape(8,), ("data",))
        sh = NamedSharding(mesh, P("data"))
        ej2 = engine_jit(fn, in_shardings=(sh,), out_shardings=sh,
                         key_hint="t_keys")      # mesh partitioning
        ej2(jax.device_put(np.ones((8,), np.float32), sh))
        assert len(entries(get_cache().dir)) == 4


# ================================================= durability / eviction


class TestCacheDurability:
    def _store_one(self, cache_dir):
        cache = ExecutableCache(cache_dir)
        compiled = jax.jit(lambda x: x * 3).lower(
            jnp.ones((4,), jnp.float32)).compile()
        key = cache_key("h", "s")
        assert cache.store(key, compiled, key_hint="t") is True
        return cache, key, compiled

    def test_hit_is_bit_identical_to_fresh_compile(self, tmp_path):
        cache, key, compiled = self._store_one(str(tmp_path))
        loaded = cache.load(key)
        assert loaded is not None
        x = np.random.RandomState(1).randn(4).astype(np.float32)
        assert np.asarray(loaded(x)).tobytes() == \
            np.asarray(compiled(x)).tobytes()

    def test_corrupt_entry_is_loud_miss_and_evicted(self, tmp_path):
        cache, key, _ = self._store_one(str(tmp_path))
        with open(cache.path_for(key), "wb") as f:
            f.write(b"not a pickle")
        before = counters_snapshot()
        assert cache.load(key) is None
        assert not os.path.exists(cache.path_for(key))
        assert counter_total(
            'compile_cache_errors_total{kind="corrupt"}', before) == 1

    def test_version_stale_entry_is_loud_miss_and_evicted(self, tmp_path):
        cache, key, _ = self._store_one(str(tmp_path))
        with open(cache.path_for(key), "rb") as f:
            doc = pickle.load(f)
        doc["meta"]["versions"] = {"jax": "0.0.1", "jaxlib": "0.0.1",
                                   "backend": "other"}
        with open(cache.path_for(key), "wb") as f:
            pickle.dump(doc, f)
        before = counters_snapshot()
        assert cache.load(key) is None
        assert not os.path.exists(cache.path_for(key))
        assert counter_total(
            'compile_cache_errors_total{kind="stale"}', before) == 1

    def test_read_only_process_never_mutates_shared_entries(
            self, tmp_path):
        """A read-only cache (farm worker) treats a stale/corrupt
        entry as a plain miss — it must not unlink another host's
        file (a version-skewed worker would otherwise cold-start the
        whole same-version fleet)."""
        cache, key, _ = self._store_one(str(tmp_path))
        ro = ExecutableCache(str(tmp_path), write_enabled=False)
        with open(cache.path_for(key), "rb") as f:
            doc = pickle.load(f)
        doc["meta"]["versions"] = {"jax": "0.0.1", "jaxlib": "0.0.1",
                                   "backend": "other"}
        with open(cache.path_for(key), "wb") as f:
            pickle.dump(doc, f)
        assert ro.load(key) is None
        assert os.path.exists(cache.path_for(key))   # NOT evicted
        with open(cache.path_for(key), "wb") as f:
            f.write(b"garbage")
        assert ro.load(key) is None
        assert os.path.exists(cache.path_for(key))   # still there
        # the writer owns eviction
        assert cache.load(key) is None
        assert not os.path.exists(cache.path_for(key))

    def test_truncated_write_never_crashes(self, tmp_path):
        """A torn entry (partial pickle — what write-then-rename
        prevents, simulated here directly) is a miss, not a crash."""
        cache, key, _ = self._store_one(str(tmp_path))
        blob = open(cache.path_for(key), "rb").read()
        with open(cache.path_for(key), "wb") as f:
            f.write(blob[: len(blob) // 2])
        assert cache.load(key) is None

    def test_concurrent_writers_race_safely(self, tmp_path):
        """Two writers on the SAME key (the compile-farm race):
        whole-file rename means every load observes a complete entry —
        never a torn one — while stores overlap."""
        cache = ExecutableCache(str(tmp_path))
        compiled = jax.jit(lambda x: x + 1).lower(
            jnp.ones((4,), jnp.float32)).compile()
        key = cache_key("race", "s")
        errors = []

        def writer():
            try:
                for _ in range(10):
                    assert cache.store(key, compiled, key_hint="race")
            except Exception as e:   # noqa: BLE001
                errors.append(e)

        def reader():
            try:
                for _ in range(30):
                    exe = cache.load(key)
                    if exe is not None:
                        exe(jnp.ones((4,), jnp.float32))
            except Exception as e:   # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=writer) for _ in range(2)] \
            + [threading.Thread(target=reader)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errors == []
        loaded = cache.load(key)
        assert loaded is not None
        assert np.asarray(loaded(jnp.ones((4,), jnp.float32))
                          ).tolist() == [2.0] * 4

    def test_lru_cap_evicts_oldest_with_counter(self, tmp_path):
        cache = ExecutableCache(str(tmp_path), max_mb=0.02)   # ~20 KB
        compiled = jax.jit(lambda x: x * 2).lower(
            jnp.ones((4,), jnp.float32)).compile()
        before = counters_snapshot()
        keys = [cache_key(f"h{i}", "s") for i in range(8)]
        for i, k in enumerate(keys):
            cache.store(k, compiled, key_hint=f"k{i}")
            os.utime(cache.path_for(k), (1000 + i, 1000 + i)) \
                if os.path.exists(cache.path_for(k)) else None
            cache._enforce_cap()
        names = entries(str(tmp_path))
        assert 0 < len(names) < 8                      # cap enforced
        # the SURVIVORS are the most recently touched keys
        surviving = {n[:-len(ENTRY_SUFFIX)] for n in names}
        assert keys[-1] in surviving
        assert keys[0] not in surviving                # oldest gone
        assert counter_total("compile_cache_evictions_total",
                             before) >= 1


# ============================================================= farm mode


class TestFarmMode:
    def test_worker_loads_host0_entry(self, tmp_path, monkeypatch):
        """The PR 4 run-dir contract: host 0 persists into
        <run_dir>/compile-cache; a worker process (ZOO_TPU_PROCESS_ID
        != 0) resolves the same dir read-only and deserializes host
        0's executable instead of recompiling."""
        run_dir = str(tmp_path / "run")
        os.makedirs(run_dir)
        monkeypatch.delenv("ZOO_TPU_COMPILE_CACHE", raising=False)
        monkeypatch.setenv("ZOO_TPU_RUN_DIR", run_dir)

        # --- host 0 compiles + persists
        monkeypatch.setenv("ZOO_TPU_PROCESS_ID", "0")
        reset_cache_state()
        from analytics_zoo_tpu.compile.cache import get_cache
        host0 = get_cache()
        assert host0 is not None and host0.write_enabled
        assert host0.dir == os.path.join(run_dir, "compile-cache")
        ej = engine_jit(lambda a: a * 7, key_hint="farm")
        out0 = ej(np.ones((4,), np.float32))
        assert len(entries(host0.dir)) == 1

        # --- worker: read-only resolve, loads host 0's entry
        monkeypatch.setenv("ZOO_TPU_PROCESS_ID", "1")
        reset_cache_state()
        worker = get_cache()
        assert worker is not None and not worker.write_enabled
        before = counters_snapshot()
        ej2 = engine_jit(lambda a: a * 7, key_hint="farm")
        out1 = ej2(np.ones((4,), np.float32))
        assert np.asarray(out1).tobytes() == np.asarray(out0).tobytes()
        assert counter_total("compile_cache_hits_total", before) == 1
        # a worker never writes, even on a (hypothetical) miss
        ej3 = engine_jit(lambda a: a * 9, key_hint="farm_other")
        ej3(np.ones((4,), np.float32))
        assert len(entries(worker.dir)) == 1
        reset_cache_state()


# ============================================ warm-start entry points


class TestWarmStartEntrypoints:
    def test_inference_model_warm(self, cache_env):
        from analytics_zoo_tpu.pipeline.api.keras import Sequential
        from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
        from analytics_zoo_tpu.pipeline.inference.inference_model import (
            InferenceModel)
        m = Sequential()
        m.add(Dense(4, input_shape=(8,)))
        m.init()
        im = InferenceModel().load_zoo(m)
        assert im.warm((8,), 16) is True
        before = counters_snapshot()
        out = im.predict(np.ones((16, 8), np.float32), batch_size=16)
        assert out.shape == (16, 4)
        # the request used the warmed executable — no new cache lookup
        assert counter_total("compile_cache_misses_total", before) == 0

    def test_serving_config_parses_input_shape(self):
        from analytics_zoo_tpu.serving.server import ServingConfig
        assert ServingConfig(input_shape="224,224,3").input_shape == \
            (224, 224, 3)
        assert ServingConfig(input_shape=(8,)).input_shape == (8,)
        assert ServingConfig().input_shape is None

    def test_trainer_warm_start_preloads_the_step(self, cache_env):
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
        before = counters_snapshot()
        out = trainer.train_step_at(params, opt_state, state,
                                    trainer.put_batch((x, y)), rng,
                                    np.int32(0))
        assert len(out) == 4
        assert counter_total("compile_cache_misses_total", before) == 0


# ================================== acceptance: second-process warm start


@pytest.mark.usefixtures("cache_env")
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
        assert cold["cache_writes"] >= 1       # ... and persisted
        assert len(entries(cache_dir)) >= 1

        warm = self._run(cache_dir)
        # the acceptance gate (ISSUE 8): >=1 hit, zero post-warm
        # recompiles, train/predict bit-identical to the cold run
        assert warm["cache_hits"] >= 1
        # the DEFAULT train step (finite check on) is among them: its
        # program holds no host callback, so it serializes
        assert cold["train_step_hits"] == 0
        assert warm["train_step_hits"] >= 1
        assert warm["recompiles_after_warmup"] == 0
        assert warm["cache_errors"] == 0
        assert warm["params_digest"] == cold["params_digest"]
        assert warm["pred_digest"] == cold["pred_digest"]
        # the warm loads replace compiles and cost ~seconds, not ~minutes
        assert warm["cache_load_seconds"] < 60
