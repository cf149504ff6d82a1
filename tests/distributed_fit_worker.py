"""Worker for the real 2-process ``jax.distributed`` end-to-end test.

Launched (2x) by tests/test_multiprocess.py via ``ZooCluster`` — each
process owns 4 virtual CPU devices of a shared 8-device ``{"data": 8}``
mesh, the analogue of the reference's ``local[N]`` DistriEstimatorSpec
runs (zoo/src/test/.../estimator/DistriEstimatorSpec.scala) but with
TWO OS processes doing a real coordinator handshake and gloo
cross-process collectives.

Exercises the multi-host branches that a single-process suite can
never reach (``jax.process_count() > 1``):
  * trainer.place_params / replicate / place_like —
    make_array_from_process_local_data paths (parallel/trainer.py)
  * trainer.put_batch host-slice-vs-replicate rules
  * estimator.predict per-host row slicing (estimator.py)
  * coordinator-only checkpoint write + all-host restore/resume

Writes per-host results to $ZOO_TEST_OUT/worker{pid}.npz for the
parent test to compare across hosts and against the single-process
8-device oracle run.
"""

import os
import sys

# a host worker unless the parent says otherwise
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402

try:  # cross-process CPU collectives
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
except Exception:  # already the default on this jaxlib
    pass

import numpy as np  # noqa: E402


def build_model():
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.engine import Layer
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    Layer.reset_name_counters()
    m = Sequential()
    m.add(Dense(16, activation="relu", input_shape=(8,)))
    m.add(Dense(4))
    return m


def make_data():
    """The full 64-row dataset — identical on every host (seeded)."""
    rs = np.random.RandomState(0)
    x = rs.randn(64, 8).astype(np.float32)
    y = rs.randn(64, 4).astype(np.float32)
    return x, y


def main():
    out_dir = os.environ["ZOO_TEST_OUT"]

    from analytics_zoo_tpu.common.zoo_context import init_zoo_context
    ctx = init_zoo_context(mesh_shape={"data": 8})
    assert ctx.process_count == 2, ctx
    assert ctx.num_devices == 8 and len(ctx.local_devices) == 4, ctx
    pid = ctx.process_index

    from analytics_zoo_tpu.ops import dtypes
    dtypes.set_policy(param_dtype="float32", compute_dtype="float32")

    from analytics_zoo_tpu.common.triggers import EveryEpoch, MaxEpoch
    from analytics_zoo_tpu.feature.feature_set import FeatureSet
    from analytics_zoo_tpu.pipeline.estimator import Estimator
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import SGD

    x, y = make_data()
    # each host feeds ITS OWN half — batch_size below is per-host, so
    # every global step consumes 16 rows from each host (32 global)
    lo, hi = pid * 32, (pid + 1) * 32
    train_set = FeatureSet.from_ndarrays(x[lo:hi], y[lo:hi],
                                         shuffle=False)
    ckpt_dir = os.path.join(out_dir, "ckpt")

    # --- phase 1: fit 2 epochs, checkpointing every epoch -------------
    model = build_model()
    est = Estimator(model, optim_method=SGD(learning_rate=0.1),
                    model_dir=ckpt_dir)
    est.train(train_set, "mse", end_trigger=MaxEpoch(2),
              checkpoint_trigger=EveryEpoch(), batch_size=16)
    params_2ep = est.variables["params"]
    losses = [h["loss"] for h in est.history]

    # --- phase 2: fresh estimator resumes from the checkpoint ---------
    model_b = build_model()
    est_b = Estimator(model_b, optim_method=SGD(learning_rate=0.1),
                      model_dir=ckpt_dir)
    est_b.train(train_set, "mse", end_trigger=MaxEpoch(3),
                checkpoint_trigger=EveryEpoch(), batch_size=16)
    assert est_b.train_state.epoch == 3, est_b.train_state.epoch
    params_3ep = est_b.variables["params"]

    # --- predict: each host passes its own rows, gets its own back ----
    preds = est_b.predict(x[lo:hi], batch_size=16)

    flat = {}
    for tag, tree in (("p2", params_2ep), ("p3", params_3ep)):
        leaves = jax.tree_util.tree_leaves(tree)
        for i, leaf in enumerate(leaves):
            flat[f"{tag}_{i}"] = np.asarray(leaf)
    np.savez(os.path.join(out_dir, f"worker{pid}.npz"),
             preds=np.asarray(preds), losses=np.asarray(losses),
             **flat)
    print(f"worker {pid} done", flush=True)


if __name__ == "__main__":
    main()
    sys.exit(0)
