"""ResNet-50 synthetic-ImageNet training benchmark (BASELINE.md
config 3; reference recipe examples/resnet/TrainImageNet.scala +
examples/inception/Train.scala:75-99 — SGD momentum 0.9, poly(0.5) LR
decay with warmup).

TPU recipe: bf16 compute / f32 master weights (``dtype.compute``),
donated buffers, and the trainer's device-resident ``lax.scan`` epoch
path — ``scan_steps`` training steps compile into ONE XLA program with
zero per-step host involvement, so the number measures the chip, not
the Python dispatch latency.

Timing discipline: every wall-clock measurement ends with a host read
of the scalar loss (D2H transfer) and ``block_until_ready`` on the full
output tree: a device→host copy of a value that depends on the final
step cannot return early.

MFU is computed from XLA's own cost analysis of the compiled epoch
program (not an analytic estimate — the published "4.1 GFLOPs" ResNet
figure counts multiply-adds once and underestimates FLOPs 2x).
"""

from __future__ import annotations

import time


def run_resnet_bench(device, batch_size: int = 128, image_size: int = 224,
                     num_classes: int = 1000, scan_steps: int = 48,
                     repeats: int = 3, compute_dtype: str = "bfloat16",
                     stem: str = "space_to_depth", unroll: int = 1,
                     trace_dir: str = None):
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.benchmarks import (
        calibrate_chip, cost_of_compiled, mfu_estimate)
    from analytics_zoo_tpu.models.image.imageclassification import resnet
    from analytics_zoo_tpu.ops import dtypes
    from analytics_zoo_tpu.parallel import mesh as mesh_lib
    from analytics_zoo_tpu.parallel.trainer import DistributedTrainer
    from analytics_zoo_tpu.pipeline.api.keras import objectives
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import (
        SGD, poly, warmup_then)

    dtypes.set_policy(param_dtype="float32", compute_dtype=compute_dtype)

    model = resnet(50, num_classes=num_classes,
                   input_shape=(image_size, image_size, 3), stem=stem)
    # reference ImageNet recipe: warmup into poly(0.5) decay
    sched = warmup_then(0.1, 5, poly(0.1, 0.5, max_iteration=10_000))
    optim = SGD(learning_rate=0.1, momentum=0.9, schedule=sched)
    loss_fn = objectives.get("sparse_categorical_crossentropy_with_logits")
    trainer = DistributedTrainer(model, loss_fn, optim_method=optim)

    variables = model.init()
    params = trainer.place_params(variables["params"])
    state = trainer.replicate(variables["state"])
    opt_state = trainer.init_opt_state(params)
    rng = jax.random.PRNGKey(0)

    # Synthetic epoch generated ON DEVICE (no 5 GB H2D),
    # bf16 images sharded on the data axis — the HBM tier of the
    # FeatureSet cache hierarchy holding `scan_steps` batches.
    # epoch_scan_fn treats batch_size as PER-HOST: each scan step
    # slices global_batch_rows(...) rows, so size the epoch to match.
    n_rows = scan_steps * mesh_lib.global_batch_rows(trainer.mesh,
                                                     batch_size)
    x_shard = mesh_lib.data_sharding(trainer.mesh, 4)
    y_shard = mesh_lib.data_sharding(trainer.mesh, 2)
    from analytics_zoo_tpu.compile import engine_jit
    gen = engine_jit(
        lambda k: (
            jax.random.uniform(
                k, (n_rows, image_size, image_size, 3), jnp.bfloat16),
            jax.random.randint(
                jax.random.fold_in(k, 1), (n_rows, 1), 0, num_classes),
        ),
        out_shardings=(x_shard, y_shard), key_hint="resnet_synth_epoch")
    x_dev, y_dev = gen(jax.random.PRNGKey(1))
    jax.block_until_ready((x_dev, y_dev))

    epoch_fn = trainer.epoch_scan_fn(scan_steps, batch_size,
                                     unroll=unroll)

    # Compile ONCE; the compiled object serves every execution AND
    # the FLOPs query.  JAX's persistent compilation cache answers
    # this compile on a later round over the same cache directory.
    t_compile = time.time()
    compiled = epoch_fn.lower(params, opt_state, state, x_dev, y_dev,
                              rng).compile()

    flops, hbm_bytes = cost_of_compiled(compiled)
    if flops:
        flops /= unroll        # unrolled scan body holds `unroll` steps
    if hbm_bytes:
        hbm_bytes /= unroll

    # first execution (donates params/opt_state/state); the first
    # run after a compile is not timed
    # (the raw Compiled hands back the program's five outputs; the
    # fifth, its count of non-finite steps, is not this bench's)
    params, opt_state, state, mloss, _ = compiled(
        params, opt_state, state, x_dev, y_dev, rng)
    float(mloss)                       # D2H sync — see module docstring
    compile_s = time.time() - t_compile

    # Repeat discipline (BENCH_r05 showed a 2.3s/2.3s/5.4s tail
    # outlier — deferred work billed to whichever repeat ran last):
    # every repeat window is SYMMETRIC — block_until_ready on the full
    # output tree before t0 (nothing from the previous dispatch can
    # leak in) AND before the window closes (nothing this repeat
    # started can leak out), with the float(mloss) D2H read kept as the
    # can't-return-early anchor (see module docstring).  One
    # extra WARMUP repeat runs first and is discarded — it absorbs
    # one-time tails (executable-cache writes, allocator warm-up) the
    # post-compile run doesn't fully drain.
    walls = []
    for r in range(repeats + 1):
        jax.block_until_ready((params, opt_state, state))
        t0 = time.time()
        params, opt_state, state, mloss, _ = compiled(
            params, opt_state, state, x_dev, y_dev,
            jax.random.fold_in(rng, r))
        loss_val = float(mloss)        # D2H sync
        jax.block_until_ready((params, opt_state, state))
        walls.append(time.time() - t0)
    warmup_wall, walls = walls[0], walls[1:]
    wall = min(walls)

    if trace_dir:
        # one profiled epoch AFTER the timed window (profiling adds
        # overhead; it must never contaminate the recorded walls) —
        # feeds dev/trace-summary's MXU/HBM/infeed split
        jax.profiler.start_trace(trace_dir)
        try:
            params, opt_state, state, mloss = compiled(
                params, opt_state, state, x_dev, y_dev,
                jax.random.fold_in(rng, repeats + 1))
            float(mloss)
        finally:
            jax.profiler.stop_trace()

    imgs_per_sec = scan_steps * batch_size / wall
    step_ms = wall / scan_steps * 1e3
    mfu = mfu_estimate(flops, wall / scan_steps, device)

    # Calibrate what the chip delivers RIGHT NOW (shared hardware can
    # throttle well below nominal peak), then place the
    # measured step on the chip's own roofline: nominal MFU alone
    # cannot distinguish "model leaves the MXU idle" from "the
    # platform only delivers half its spec sheet".

    calib = calibrate_chip()
    mfu_deliverable = roofline_ms = roofline_frac = None
    if not calib.get("error"):
        if flops and calib.get("deliverable_tflops"):
            mfu_deliverable = round(
                flops / (wall / scan_steps)
                / (calib["deliverable_tflops"] * 1e12), 3)
        if hbm_bytes and calib.get("hbm_gbps"):
            # bandwidth-roofline step time: every byte the compiled
            # program touches (XLA's own counter), streamed at the
            # measured rate — the floor for an HBM-bound program
            roofline_ms = round(
                hbm_bytes / (calib["hbm_gbps"] * 1e9) * 1e3, 2)
            roofline_frac = round(roofline_ms / step_ms, 3)

    return {
        "metric": "resnet50_imagenet_train_throughput",
        "value": round(imgs_per_sec, 1),
        "unit": "imgs/sec/chip",
        "vs_baseline": None,
        "workload": "resnet50",
        "batch_size": batch_size,
        "image_size": image_size,
        "step_time_ms": round(step_ms, 2),
        "scan_steps": scan_steps,
        "repeats": repeats,
        "wall_s_per_repeat": [round(w, 3) for w in walls],
        "warmup_repeat_wall_s": round(warmup_wall, 3),
        "compile_time_s": round(compile_s, 2),
        "compute_dtype": compute_dtype,
        "stem": stem,
        "final_loss": loss_val,
        "xla_flops_per_step": flops,
        "xla_bytes_per_step": hbm_bytes,
        "mfu_est": mfu,
        "calibration": calib,
        "mfu_vs_deliverable": mfu_deliverable,
        "hbm_roofline_step_ms": roofline_ms,
        "roofline_attainment": roofline_frac,
        "device": str(device),
        "device_kind": getattr(device, "device_kind", "?"),
    }
