"""Subprocess worker for the generative compile-cache acceptance
(tests/test_generative_serving.py::TestDecodeCacheWarmStart).

Builds a deterministic small Seq2seq, registers it as a generative
endpoint, warms the decode-step scheduler's full
``(batch_bucket, state_bucket)`` program ladder with
``JAX_COMPILATION_CACHE_DIR`` pointing at argv[1], then serves a burst
of sequences through the engine.  A second process over the SAME cache
dir must read the decode-step executables from JAX's persistent
compilation cache (hits, no miss, zero post-warm backend compiles) and
produce identical tokens — the decode program a replica respawn runs
is the same machine code the first process compiled.

Prints ONE JSON line with the token digest and the cache counters.
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

    from analytics_zoo_tpu.models.seq2seq import Seq2seq
    from analytics_zoo_tpu.observability import get_registry
    from analytics_zoo_tpu.observability.diagnostics import (
        get_compile_monitor)
    from analytics_zoo_tpu.serving.engine import Request, ServingEngine

    get_compile_monitor()     # backend-compile listener active

    m = Seq2seq(vocab_size=16, embed_dim=8, hidden_sizes=(16,))
    m.init()                  # per-process layer-name reset pins init

    eng = ServingEngine()
    ep = eng.register_generative("gen", m, enc_len=6, start_sign=1,
                                 stop_sign=2, max_seq_len=12, slots=4)
    warmed = ep.warm()
    eng.start()

    compiles = get_registry().counter(
        "jax_backend_compiles_total",
        "XLA backend compilations (jax.monitoring)")
    before = compiles.value

    rs = np.random.RandomState(7)
    reqs = [Request(endpoint="gen", uri=f"g{i}",
                    data=rs.randint(3, 16, (6,)).astype(np.int32))
            for i in range(10)]
    eng.wait_all(eng.submit(reqs), timeout_s=120)
    assert all(r.error is None for r in reqs), \
        [str(r.error) for r in reqs if r.error]
    digest = hashlib.sha256(
        json.dumps([r.result for r in reqs]).encode()).hexdigest()
    eng.stop()

    counters = get_registry().snapshot().get("counters", {})

    def total(prefix):
        return sum(v for k, v in counters.items()
                   if k.startswith(prefix))

    print(json.dumps({
        "tokens_digest": digest,
        "warmed_programs": warmed,
        "post_warm_compiles": compiles.value - before,
        "cache_hits": total("compile_cache_hits_total"),
        "cache_misses": total("compile_cache_misses_total"),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
