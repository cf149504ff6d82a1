"""The selective scan: the Pallas kernels (interpreted) and the lax form
against a scan written out position by position, forward and backward,
over several chunks of positions and from a carried-in state."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import selective_scan as ss

ARGS = ("x", "dt", "a", "b", "c", "state")


def inputs(batch=2, t=192, channels=2048, states=4, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(k[0], (batch, t, channels))
    dt = jax.nn.softplus(jax.random.normal(k[1], x.shape) - 2.0)
    a = -jnp.exp(0.5 * jax.random.normal(k[2], (channels, states)))
    b, c = (jax.random.normal(kk, (batch, t, states)) for kk in k[3:5])
    state = jax.random.normal(k[5], (batch, channels, states))
    weights = (jax.random.normal(k[6], x.shape),
               jax.random.normal(k[7], state.shape))
    return (x, dt, a, b, c, state), weights


def by_hand(x, dt, a, b, c, state):
    """The recurrence of the module's docstring, a Python loop."""
    ys = []
    for t in range(x.shape[1]):
        decay = jnp.exp(dt[:, t, :, None] * a)
        state = decay * state + (dt[:, t] * x[:, t])[..., None] \
            * b[:, t, None, :]
        ys.append(jnp.sum(state * c[:, t, None, :], axis=-1))
    return jnp.stack(ys, axis=1), state


def weighted(scan, weights):
    def loss(*args):
        y, last = scan(*args)
        return jnp.sum(y * weights[0]) + jnp.sum(last * weights[1])
    return loss


FORMS = {"pallas": lambda *a: ss.selective_scan(*a, interpret=True),
         "lax": ss.selective_scan_lax}


@pytest.mark.parametrize("form", sorted(FORMS))
def test_forward_matches_the_recurrence(form):
    args, _ = inputs(t=128, channels=1024)
    y, last = FORMS[form](*args)
    want_y, want_last = by_hand(*args)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(last, want_last, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name", ARGS)
def test_kernel_gradients_match_the_sequential_scan(name):
    """Three chunks of 64 positions, two channel groups, a state carried
    in that is not zero: every gradient of the kernels against the lax
    form's (which the test above holds to the recurrence)."""
    args, weights = inputs()
    i = ARGS.index(name)
    got = jax.grad(weighted(FORMS["pallas"], weights), argnums=i)(*args)
    want = jax.grad(weighted(FORMS["lax"], weights), argnums=i)(*args)
    assert got.shape == want.shape == args[i].shape
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-5 * float(jnp.max(jnp.abs(want))))


def test_forward_call_outside_the_vjp_cuts_no_cotangent(pallas_calls):
    """The forward kernel is an ordinary call on stopped operands and
    the ``custom_vjp`` only attaches the backward kernel: outside any
    ``jax.checkpoint`` the gradient's jaxpr holds each kernel once, and
    all six operands' gradients, the carried-in state's included, are
    the sequential scan's, from ``y`` and from the last state alike."""
    args, weights = inputs(batch=1, t=128, channels=1024)
    every = tuple(range(6))
    kernels = jax.grad(weighted(FORMS["pallas"], weights), every)
    assert pallas_calls(kernels, *args) == {"selective_scan_fwd": 1,
                                            "selective_scan_bwd": 1}
    want = jax.grad(weighted(FORMS["lax"], weights), every)(*args)
    for name, g, w in zip(ARGS, kernels(*args), want):
        assert float(jnp.max(jnp.abs(w))) > 0
        np.testing.assert_allclose(
            g, w, rtol=2e-4, atol=2e-5 * float(jnp.max(jnp.abs(w))),
            err_msg=name)


def test_lax_gradient_matches_the_recurrence():
    args, weights = inputs(batch=1, t=128, channels=128, states=3)
    got = jax.grad(weighted(ss.selective_scan_lax, weights),
                   argnums=tuple(range(6)))(*args)
    want = jax.grad(weighted(by_hand, weights),
                    argnums=tuple(range(6)))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def test_a_state_carried_in_continues_the_scan():
    """Two halves, the second from the first's last state, are the
    whole."""
    args, _ = inputs(batch=1, t=256, channels=1024)
    x, dt, a, b, c, state = args
    whole, last = FORMS["pallas"](*args)
    first, mid = FORMS["pallas"](x[:, :128], dt[:, :128], a, b[:, :128],
                                 c[:, :128], state)
    second, end = FORMS["pallas"](x[:, 128:], dt[:, 128:], a, b[:, 128:],
                                  c[:, 128:], mid)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), whole,
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(end, last, rtol=1e-6, atol=1e-6)


def test_routing_and_build_counts(monkeypatch):
    """On the CPU the scan is the lax form; shapes that do not fill
    whole chunks and channel groups are, anywhere."""
    from analytics_zoo_tpu.observability import get_registry
    from analytics_zoo_tpu.ops import fused

    def builds():
        return {k: v for k, v in get_registry().snapshot()["counters"].items()
                if 'kernel="selective_scan"' in k}

    args, _ = inputs(batch=1, t=64, channels=1024)
    before = builds()
    ss.selective_scan(*args[:5])
    key = 'fused_kernel_builds_total{kernel="selective_scan",path="%s"}'
    assert builds()[key % "lax"] == before.get(key % "lax", 0) + 1
    assert ss.pallas_fits(8192, 5120) and not ss.pallas_fits(8192, 5000)
    assert not ss.pallas_fits(100, 1024)
    monkeypatch.setattr(fused, "_use_pallas", lambda: True)
    odd, _ = inputs(batch=1, t=32, channels=256)
    mid = builds()
    ss.selective_scan(*odd[:5])
    assert builds()[key % "lax"] == mid[key % "lax"] + 1
