"""Benchmark harnesses behind ``bench.py`` (BASELINE.md configs)."""

# bf16 peak FLOP/s per chip by ``device_kind`` (public spec sheets, e.g.
# Google Cloud's "TPU v5e" page: 197 TFLOP/s bf16).
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def _nominal_peak(kind) -> float:
    """bf16 peak FLOP/s for a device_kind string.  A device that is not
    in the table is an error, not a default."""
    for name, val in PEAK_FLOPS.items():
        if name.lower() in str(kind).lower():
            return val
    raise KeyError(
        f"no published peak for device_kind {kind!r}; add it to "
        "benchmarks.PEAK_FLOPS with its source")


def mfu_estimate(flops_per_step, step_time_s, device, peak=None):
    """Model FLOPs utilisation vs the chip's bf16 peak.  ``peak``
    (FLOP/s) overrides the device-kind lookup.  None when the FLOP
    count is unknown, or on the host platform without an explicit
    ``peak``: utilisation is a device metric and a CPU run does not
    measure it.  An accelerator missing from ``PEAK_FLOPS`` raises."""
    if not flops_per_step or step_time_s <= 0:
        return None
    if peak is None:
        if getattr(device, "platform", None) == "cpu":
            return None
        peak = _nominal_peak(getattr(device, "device_kind", ""))
    return round(flops_per_step / step_time_s / peak, 6)


def cost_of_compiled(compiled):
    """(flops, hbm_bytes) of an already-compiled XLA program via its
    cost analysis; (None, None) when the backend doesn't expose it.

    NOTE: XLA counts a while/scan BODY once, not multiplied by the trip
    count — for a whole-epoch scan program this is (approximately) the
    cost of one step (times any ``unroll`` factor)."""
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        return (float(cost.get("flops", 0.0)) or None,
                float(cost.get("bytes accessed", 0.0)) or None)
    except Exception:
        return None, None


def compiled_flops(jitted, *args):
    """FLOPs of a jitted program via XLA cost analysis (compiles it);
    None when the backend doesn't expose cost analysis."""
    try:
        return cost_of_compiled(jitted.lower(*args).compile())[0]
    except Exception:
        return None


def calibrate_chip(repeats: int = 4, matmul_n: int = 8192,
                   matmul_iters: int = 32, bw_mb: int = 1024,
                   bw_iters: int = 256):
    """Measure what THIS chip delivers on two ideal kernels, beside the
    nominal peak (PEAK_FLOPS):

    * ``deliverable_tflops`` — best-of-``repeats`` bf16 matmul-chain
      rate (``matmul_iters`` dependent NxN matmuls inside one jit, so
      dispatch amortises away);
    * ``hbm_gbps`` — best-of-``repeats`` streaming bandwidth from a
      read+write triad over a ``bw_mb``-MB f32 array.

    Each timed window ends with a D2H read of a dependent scalar.
    Returns a dict; on any
    failure returns ``{"error": ...}`` — calibration must never take
    down the workload that asked for it.
    """
    import time

    import jax
    import jax.numpy as jnp

    try:
        if jax.default_backend() != "tpu":
            # CPU rehearsal of the bench: measure the same quantities
            # at toy sizes so the code path runs in seconds (a CPU
            # would take ~20 min on the TPU-sized matmul chain)
            matmul_n, matmul_iters = min(matmul_n, 1024), min(matmul_iters, 4)
            bw_mb, bw_iters = min(bw_mb, 64), min(bw_iters, 4)
        k = jax.random.PRNGKey(0)
        a = jax.random.normal(k, (matmul_n, matmul_n), jnp.bfloat16)
        b = jax.random.normal(jax.random.fold_in(k, 1),
                              (matmul_n, matmul_n), jnp.bfloat16)

        from analytics_zoo_tpu.compile import engine_jit

        def mm_chain_fn(a, b):
            def body(c, _):
                return jax.lax.dot_general(
                    a, c, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.bfloat16), None
            out, _ = jax.lax.scan(body, b, None, length=matmul_iters)
            return out[0, 0].astype(jnp.float32)

        mm_chain = engine_jit(mm_chain_fn, key_hint="calibrate_mm_chain")

        float(mm_chain(a, b))              # compile + warm
        mm_flops = 2.0 * matmul_n ** 3 * matmul_iters
        best_tf = 0.0
        for _ in range(repeats):
            t0 = time.time()
            float(mm_chain(a, b))          # D2H sync
            best_tf = max(best_tf, mm_flops / (time.time() - t0) / 1e12)

        n_elem = bw_mb * (1 << 20) // 4
        x = jnp.ones((n_elem,), jnp.float32)

        def triad_fn(x):
            def body(c, _):
                return c * jnp.float32(1.0000001) + jnp.float32(1e-9), None
            out, _ = jax.lax.scan(body, x, None, length=bw_iters)
            return out[0]

        triad = engine_jit(triad_fn, key_hint="calibrate_triad")

        float(triad(x))
        bw_bytes = 2.0 * n_elem * 4 * bw_iters      # read + write
        best_bw = 0.0
        for _ in range(repeats):
            t0 = time.time()
            float(triad(x))
            best_bw = max(best_bw, bw_bytes / (time.time() - t0) / 1e9)

        dev = jax.devices()[0]
        nominal = None if dev.platform == "cpu" else _nominal_peak(
            dev.device_kind)
        return {
            "deliverable_tflops": round(best_tf, 3),
            "hbm_gbps": round(best_bw, 1),
            "nominal_tflops": nominal and nominal / 1e12,
            "deliverable_frac_of_nominal":
                nominal and round(best_tf * 1e12 / nominal, 3),
        }
    except Exception as e:            # noqa: BLE001 — diagnostic path
        return {"error": f"calibration failed: {e!r}"}
