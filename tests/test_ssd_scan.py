"""The Mamba-2 chunked scan (``ops/ssd_scan.py``): its kernels
(interpreted) and its lax form against the sequential recurrence, forward
and gradients, on sequences that are and are not whole chunks, with a
state carried in, in one group and in two."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import ssd_scan as ssd

F32 = jnp.float32


def recurrence(x, dt, a, b, c, state, groups):
    """The recurrence a position at a time, float32: ``x`` (B, T, H·P),
    ``dt`` (B, T, H), ``b``, ``c`` (B, T, G·N), ``state`` (B, H, N, P)."""
    bsz, t, width = x.shape
    h = dt.shape[-1]
    p, n = width // h, b.shape[-1] // groups
    x = x.reshape(bsz, t, h, p)
    b, c = (jnp.repeat(v.reshape(bsz, t, groups, n), h // groups, axis=2)
            for v in (b, c))

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs               # (B, H, P) (B, H) (B, H, N)
        s = jnp.exp(dt_t * a)[..., None, None] * s \
            + dt_t[..., None, None] * b_t[..., :, None] * x_t[..., None, :]
        return s, jnp.einsum("bhn,bhnp->bhp", c_t, s)

    last, y = jax.lax.scan(step, state,
                           tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1).reshape(bsz, t, width), last


def operands(seed, t, h=4, p=64, n=16, groups=1, batch=2, with_state=True):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(keys[0], (batch, t, h * p), F32)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (batch, t, h)) - 2.0)
    a = -jnp.exp(jax.random.uniform(keys[2], (h,), F32, 0.0, 1.5))
    b = jax.random.normal(keys[3], (batch, t, groups * n), F32) / n ** 0.5
    c = jax.random.normal(keys[4], (batch, t, groups * n), F32) / n ** 0.5
    state = jax.random.normal(keys[5], (batch, h, n, p), F32) \
        if with_state else None
    cot = jax.random.normal(keys[6], (batch, t, h * p), F32)
    return (x, dt, a, b, c, state), cot


def loss_of(fn, cot, last_weight):
    def loss(*args):
        y, last = fn(*args)
        return jnp.sum(y * cot) + last_weight * jnp.sum(last * last)
    return loss


# (T, groups, states, state carried in): 384 is three chunks of 128, 320
# two and a half, 96 less than one
CASES = [(384, 1, 16, True), (320, 1, 16, False), (96, 1, 16, True),
         (384, 2, 128, True)]
IDS = ["whole-chunks", "padded-tail", "under-a-chunk", "two-groups"]


@pytest.mark.parametrize("form", ["kernel", "lax"])
@pytest.mark.parametrize("t,groups,n,with_state", CASES, ids=IDS)
def test_forward_and_gradients_match_the_recurrence(form, t, groups, n,
                                                    with_state):
    args, cot = operands(t + groups, t, n=n, groups=groups,
                         with_state=with_state)
    state = args[5] if with_state else jnp.zeros((2, 4, n, 64), F32)

    def scan(x, dt, a, b, c, s):
        return ssd.ssd_scan(x, dt, a, b, c, s, groups=groups, chunk=128,
                            interpret=form == "kernel")

    def oracle(x, dt, a, b, c, s):
        return recurrence(x, dt, a, b, c, s, groups)

    full = args[:5] + (state,)
    with jax.default_matmul_precision("highest"):
        y, last = jax.jit(scan)(*full)
        y_ref, last_ref = jax.jit(oracle)(*full)
        np.testing.assert_allclose(y, y_ref, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(last, last_ref, rtol=2e-4, atol=2e-4)
        argnums = tuple(range(6))
        grads = jax.jit(jax.grad(loss_of(scan, cot, 0.5), argnums))(*full)
        want = jax.jit(jax.grad(loss_of(oracle, cot, 0.5), argnums))(*full)
    for name, got, ref in zip("x dt a b c state".split(), grads, want):
        scale = float(jnp.max(jnp.abs(ref)))
        np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-4 * scale,
                                   err_msg=name)


def test_the_kernel_form_is_the_kernel_and_counts_its_build():
    from analytics_zoo_tpu.observability import get_registry
    args, _ = operands(0, 256)
    counter = 'fused_kernel_builds_total{kernel="ssd_scan",path="%s"}'
    before = get_registry().snapshot()["counters"]
    jax.make_jaxpr(lambda *a: ssd.ssd_scan(*a, chunk=128,
                                           interpret=True))(*args)
    jax.make_jaxpr(lambda *a: ssd.ssd_scan(*a, chunk=128))(*args)
    after = get_registry().snapshot()["counters"]
    for path in ("pallas", "lax"):
        key = counter % path
        assert after.get(key, 0) - before.get(key, 0) == 1


def test_the_grids_walk_chunks_then_tiles(pallas_grids):
    """Eight heads of 64 a step (512 lanes): 64 heads are 8 tiles, and a
    sequence of 8,192 positions 32 chunks of 256."""
    x = jax.ShapeDtypeStruct((1, 8192, 4096), jnp.bfloat16)
    dt = jax.ShapeDtypeStruct((1, 8192, 64), F32)
    a = jax.ShapeDtypeStruct((64,), F32)
    bc = jax.ShapeDtypeStruct((1, 8192, 128), jnp.bfloat16)

    def step(x, dt, a, b, c):
        return jax.grad(lambda x: jnp.sum(ssd.ssd_scan(
            x, dt, a, b, c, interpret=True)[0].astype(F32)))(x)

    grids = pallas_grids(step, x, dt, a, bc, bc)
    assert grids == {"ssd_scan_fwd": (1, 32, 8), "ssd_scan_bwd": (1, 32, 8)}


@pytest.mark.parametrize("h,groups,p,n,chunk,fits", [
    (64, 1, 64, 128, 256, True),      # the cell's layer
    (4, 2, 64, 128, 128, True),
    (4, 2, 64, 16, 128, False),       # a group's B not a lane block
    (64, 1, 48, 128, 256, False),     # a head that neither fills nor
    (64, 1, 64, 128, 100, False),     # divides the lanes; a ragged chunk
])
def test_which_shapes_the_kernels_take(h, groups, p, n, chunk, fits):
    assert ssd.pallas_fits(h, groups, p, n, chunk) == fits


def test_heads_a_step():
    assert ssd._heads_per_step(64, 1, 64) == 8
    assert ssd._heads_per_step(4, 2, 64) == 2
    assert ssd._heads_per_step(8, 1, 128) == 4
    assert ssd._heads_per_step(2, 1, 1024) == 1
