"""Subprocess worker for the warm-start acceptance test
(tests/test_engine_jit.py::TestSecondProcessWarmStart).

One full cold-vs-warm round trip through JAX's persistent compilation
cache: train a small model through the Estimator (per-step dispatch,
so the warmed ``train_step_at`` program is the one the loop uses) and
predict, with ``JAX_COMPILATION_CACHE_DIR`` pointing at the directory
argv[1] names and every program admitted whatever its compile time.
Everything that could differ between two runs is pinned (data via a
seeded RandomState, init via the per-process layer-name reset, the
training rng via ``data.shuffle_seed``), so a second process over the
SAME cache dir must be bit-identical to the first: an executable read
from the cache is the same machine code the cold run compiled.

Prints ONE JSON line: content digests of the trained params and the
predictions, plus the cache and recompile counters — the parent
asserts cold (misses, no hits) vs warm (hits, no misses, zero
post-warm recompiles, identical digests).  The cache counters are
reported as their growth since the process's compile monitor was built
(in the first trainer's construction), which is where the compile
listener was installed until PR 35.  It now runs from
``init_zoo_context`` on, and between the two the trainer's
construction compiles three small programs, two of which ``m.init()``
has compiled already under the configuration of before the context:
one HLO, two jit entries, so they answer each other in a cold process
(``before_monitor`` reports them).
"""

import hashlib
import json
import os
import sys


def main() -> int:
    cache_dir = sys.argv[1]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    # the suite's conftest turns the cache off for its children
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "true"
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

    import numpy as np

    from analytics_zoo_tpu.common.config import get_config
    from analytics_zoo_tpu.common.triggers import MaxEpoch
    from analytics_zoo_tpu.feature.feature_set import FeatureSet
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam
    from analytics_zoo_tpu.pipeline.estimator.estimator import Estimator

    # force the per-step dispatch path: it is the one Estimator.train
    # warms at startup, and the one serving/elastic recovery care
    # about
    cfg = get_config()
    cfg.set("train.steps_per_dispatch", 1)
    cfg.set("train.hbm_cache_mb", 0)
    # the DEFAULT train step (the watchdog's in-jit finite fold on: its
    # flag is a value the step returns, not a host callback) is the
    # program that must round-trip through the persistent cache

    rs = np.random.RandomState(0)
    x = rs.randn(256, 8).astype(np.float32)
    y = rs.randint(0, 2, (256,)).astype(np.int32)

    m = Sequential()
    m.add(Dense(16, input_shape=(8,), activation="relu"))
    m.add(Dense(2))
    m.init()

    from analytics_zoo_tpu.observability import diagnostics, get_registry

    def total(prefix):
        counters = get_registry().snapshot().get("counters", {})
        return sum(v for k, v in counters.items() if k.startswith(prefix))

    before_monitor = {}
    build = diagnostics.CompileMonitor.__init__

    def counted_from_here(self, *args, **kwargs):
        before_monitor.setdefault(
            "hits", total("compile_cache_hits_total"))
        before_monitor.setdefault(
            "misses", total("compile_cache_misses_total"))
        build(self, *args, **kwargs)

    diagnostics.CompileMonitor.__init__ = counted_from_here

    est = Estimator(m, optim_method=Adam(lr=1e-3))
    est.train(FeatureSet.from_ndarrays(x, y),
              "sparse_categorical_crossentropy_with_logits",
              end_trigger=MaxEpoch(2), batch_size=32)
    pred = np.asarray(est.predict(x[:64], batch_size=32))

    import jax
    digest = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(est.variables["params"]):
        digest.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    params_digest = digest.hexdigest()
    pred_digest = hashlib.sha256(
        np.ascontiguousarray(pred).tobytes()).hexdigest()

    print(json.dumps({
        "params_digest": params_digest,
        "pred_digest": pred_digest,
        "final_loss": est.train_state.last_loss,
        "cache_hits": total("compile_cache_hits_total")
        - before_monitor["hits"],
        "cache_misses": total("compile_cache_misses_total")
        - before_monitor["misses"],
        "before_monitor": before_monitor,
        "train_step_compiles": total(
            'jax_compiles_total{fn="train_step"}'),
        "recompiles_after_warmup": total("jax_recompiles_total"),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
