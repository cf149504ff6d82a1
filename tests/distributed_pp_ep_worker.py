"""Worker for the real 2-process pipeline-/expert-parallel test.

Launched (2x) by tests/test_multiprocess_pp_ep.py via ``ZooCluster``.
Round-4 gap: the pp microbatch routing (ppermute baton passing) and
MoE expert dispatch had only ever executed single-process on the
conftest 8-device mesh — their ``process_count > 1`` branches (gloo
cross-process collectives, global-array placement) never ran.

Mesh layouts are chosen so the INTERESTING axis spans the process
boundary:

  * pp section — mesh {pipe: 2, data: 4}: stage 0 lives on process
    0's devices, stage 1 on process 1's, so every pipeline tick's
    ppermute crosses processes.
  * ep section — mesh {expert: 2, data: 4}: half the experts live on
    each process, so dispatch/combine and the gradient psum cross
    processes every step.

Each section asserts parity against the SAME computation run
sequentially / single-device in-process (both workers compute the
identical oracle from seeded inputs), then saves results for the
parent to cross-check between workers.

Also exercises the put_epoch_source multi-host tiling refusal: rows
that don't tile this host's data-parallel share must raise, not
silently degrade.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402

try:
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
except Exception:
    pass

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402


def _put(arr, mesh, spec):
    """Global array from an identical-on-every-host numpy array."""
    sharding = NamedSharding(mesh, spec)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx])


def _stage_weights(num_stages, d, seed):
    rs = np.random.RandomState(seed)
    return [{"w": rs.randn(d, d).astype(np.float32) * 0.3,
             "b": rs.randn(d).astype(np.float32) * 0.1}
            for _ in range(num_stages)]


def _stage_fn(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])


def run_pp(out, mesh_lib):
    from analytics_zoo_tpu.parallel.pipeline import (
        pipeline_apply, stack_stage_params)

    mesh = mesh_lib.create_mesh({"pipe": 2, "data": 4})
    d, batch, micro = 8, 16, 4
    per_stage = _stage_weights(2, d, seed=11)
    rs = np.random.RandomState(12)
    x = rs.randn(batch, d).astype(np.float32)
    y = rs.randn(batch, d).astype(np.float32)

    stacked_np = jax.tree_util.tree_map(
        lambda *ls: np.stack(ls), *per_stage)
    stacked = jax.tree_util.tree_map(
        lambda a: _put(a, mesh, P("pipe")), stacked_np)
    xd = _put(x, mesh, P())
    yd = _put(y, mesh, P())

    def loss_fn(params, xx, yy):
        with mesh:
            h = pipeline_apply(_stage_fn, params, xx, mesh,
                               num_microbatches=micro)
        return jnp.mean((h - yy) ** 2)

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(stacked, xd, yd)
    loss = float(loss)

    # sequential oracle, no mesh — identical on both workers
    h = jnp.asarray(x)
    for p in per_stage:
        h = _stage_fn(p, h)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda ps: jnp.mean(
            (_stage_fn(ps[1], _stage_fn(ps[0], jnp.asarray(x)))
             - jnp.asarray(y)) ** 2))(per_stage)
    assert abs(loss - float(ref_loss)) < 1e-5, (loss, float(ref_loss))

    # this process's stage grads (the pipe-sharded leading axis) match
    # the sequential grads for the stage its shard actually holds —
    # shard.index names the global stage slice, so no assumption about
    # how create_mesh laid processes onto the pipe axis
    for key in ("w", "b"):
        shard = grads[key].addressable_shards[0]
        stage = shard.index[0].start or 0
        local = np.asarray(shard.data)[0]
        want = np.asarray(ref_grads[stage][key])
        np.testing.assert_allclose(
            local, want, rtol=1e-4, atol=1e-5,
            err_msg=f"pp grad {key} (stage {stage})")
    out["pp_loss"] = np.float32(loss)
    out["pp_ref_loss"] = np.float32(float(ref_loss))


def run_ep(out, mesh_lib):
    import optax

    from analytics_zoo_tpu.pipeline.api.keras.layers import MoE

    mesh = mesh_lib.create_mesh({"expert": 2, "data": 4})
    d, e, rows = 8, 4, 32
    layer = MoE(num_experts=e, hidden_dim=16, capacity_factor=4.0)
    params0 = layer.init(jax.random.PRNGKey(7), (None, d))["params"]
    params0 = jax.tree_util.tree_map(np.asarray, params0)
    rs = np.random.RandomState(13)
    x = rs.randn(rows, d).astype(np.float32)
    w_true = rs.randn(d, d).astype(np.float32)
    y = x @ w_true

    tx = optax.adam(5e-2)

    def loss_fn(p, xx, yy):
        return jnp.mean((layer.call(p, xx) - yy) ** 2)

    # ---- single-device oracle trajectory (identical on both hosts)
    ref_losses = []
    p_ref = jax.tree_util.tree_map(jnp.asarray, params0)
    st_ref = tx.init(p_ref)
    for _ in range(4):
        l, g = jax.value_and_grad(loss_fn)(p_ref, jnp.asarray(x),
                                           jnp.asarray(y))
        up, st_ref = tx.update(g, st_ref, p_ref)
        p_ref = optax.apply_updates(p_ref, up)
        ref_losses.append(float(l))

    # ---- sharded trajectory over the cross-process expert mesh
    sharded = {k: _put(np.asarray(v), mesh,
                       layer.param_pspecs.get(k, P()))
               for k, v in params0.items()}
    xd = _put(x, mesh, P(("data",)))
    yd = _put(y, mesh, P(("data",)))

    @jax.jit
    def step(p, st, xx, yy):
        l, g = jax.value_and_grad(loss_fn)(p, xx, yy)
        up, st = tx.update(g, st, p)
        return optax.apply_updates(p, up), st, l

    st = jax.jit(tx.init)(sharded)
    losses = []
    for _ in range(4):
        sharded, st, l = step(sharded, st, xd, yd)
        losses.append(float(l))

    np.testing.assert_allclose(losses, ref_losses, rtol=1e-4,
                               atol=1e-5, err_msg="ep loss trajectory")
    out["ep_losses"] = np.asarray(losses, np.float32)
    out["ep_ref_losses"] = np.asarray(ref_losses, np.float32)


def run_sp(out, mesh_lib):
    """Sequence parallelism: ring attention with the seq axis ACROSS
    processes — every ring step's ppermute moves K/V blocks over gloo.
    Loss and q/k/v grads must match dense attention computed locally."""
    from analytics_zoo_tpu.ops.attention import (
        scaled_dot_product_attention)
    from analytics_zoo_tpu.parallel.ring_attention import ring_attention

    mesh = mesh_lib.create_mesh({"seq": 2, "data": 4})
    rs = np.random.RandomState(21)
    b, h, t, d = 2, 3, 16, 8
    q, k, v = (rs.randn(b, h, t, d).astype(np.float32)
               for _ in range(3))
    spec = P(None, None, "seq", None)
    qd, kd, vd = (_put(a, mesh, spec) for a in (q, k, v))

    def loss_fn(qq, kk, vv):
        out_ = ring_attention(qq, kk, vv, mesh, causal=True)
        return jnp.mean(out_ ** 2)

    loss, grads = jax.jit(jax.value_and_grad(
        loss_fn, argnums=(0, 1, 2)))(qd, kd, vd)
    loss = float(loss)

    def ref_loss_fn(qq, kk, vv):
        return jnp.mean(scaled_dot_product_attention(
            qq, kk, vv, causal=True) ** 2)

    ref_loss, ref_grads = jax.value_and_grad(
        ref_loss_fn, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    assert abs(loss - float(ref_loss)) < 1e-5, (loss, float(ref_loss))

    # each grad is seq-sharded: this process's shard must equal the
    # dense-attention grad's same global slice
    for name, g, ref in zip("qkv", grads, ref_grads):
        shard = g.addressable_shards[0]
        local = np.asarray(shard.data)
        want = np.asarray(ref)[tuple(shard.index)]
        np.testing.assert_allclose(local, want, rtol=1e-4, atol=1e-5,
                                   err_msg=f"sp grad d{name}")
    out["sp_loss"] = np.float32(loss)
    out["sp_ref_loss"] = np.float32(float(ref_loss))


def run_put_epoch_guard(out):
    """Multi-host put_epoch_source with non-tiling rows must refuse
    loudly (round-4 weak spot: docstring-only constraint)."""
    from analytics_zoo_tpu.parallel import mesh as mesh_lib
    from analytics_zoo_tpu.parallel.trainer import DistributedTrainer
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.engine import Layer
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    Layer.reset_name_counters()
    m = Sequential()
    m.add(Dense(4, input_shape=(8,)))
    m.init()
    trainer = DistributedTrainer(m, None,
                                 mesh=mesh_lib.create_mesh({"data": 8}))
    # 8-way data axis over 2 hosts -> each host's share is 4; 7 rows
    # cannot tile it
    bad_x = [np.zeros((7, 8), np.float32)]
    bad_y = np.zeros((7, 4), np.float32)
    try:
        trainer.put_epoch_source(bad_x, bad_y)
    except ValueError as err:
        msg = str(err)
        assert "put_epoch_source" in msg and "tile" in msg, msg
        out["guard_raised"] = np.int32(1)
    else:
        out["guard_raised"] = np.int32(0)
    # …and rows that DO tile place fine: each host's 8 rows become
    # its slice of the 16-row global epoch
    ok_x = [np.zeros((8, 8), np.float32)]
    ok_y = np.zeros((8, 4), np.float32)
    xd, yd = trainer.put_epoch_source(ok_x, ok_y)
    assert xd[0].shape == (16, 8), xd[0].shape


def main():
    out_dir = os.environ["ZOO_TEST_OUT"]

    from analytics_zoo_tpu.common.zoo_context import init_zoo_context
    from analytics_zoo_tpu.parallel import mesh as mesh_lib

    ctx = init_zoo_context(mesh_shape={"data": 8})
    assert ctx.process_count == 2, ctx
    pid = ctx.process_index

    from analytics_zoo_tpu.ops import dtypes
    dtypes.set_policy(param_dtype="float32", compute_dtype="float32")

    out = {}
    run_pp(out, mesh_lib)
    run_ep(out, mesh_lib)
    run_sp(out, mesh_lib)
    run_put_epoch_guard(out)
    np.savez(os.path.join(out_dir, f"worker{pid}.npz"), **out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
