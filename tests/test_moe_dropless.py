"""The dropless expert layer against a plain masked dense sum: top-8 of
32 under a skewed router with no row lost, the shares of four ranks
adding up to the uncut layer, and the routed-row counters."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.observability import get_registry, get_tracer
from analytics_zoo_tpu.observability.moe_stats import MoeStatsReader
from analytics_zoo_tpu.ops import grouped_matmul as gmm
from analytics_zoo_tpu.pipeline.api.keras.layers import DroplessMoE

E, TOP, D, H, B, T = 32, 8, 16, 24, 2, 48
KEYS = ("gate", "up", "down")


@pytest.fixture
def weights():
    rs = np.random.RandomState(0)
    full = {"router": rs.randn(D, E).astype(np.float32),
            "gate": 0.3 * rs.randn(E, D, H).astype(np.float32),
            "up": 0.3 * rs.randn(E, D, H).astype(np.float32),
            "down": 0.3 * rs.randn(E, H, D).astype(np.float32)}
    x = rs.randn(B, T, D).astype(np.float32)
    # a skewed router: expert 3 wins for every token by a wide margin
    # (not so wide that the other probabilities underflow to ties)
    full["router"][:, 3] = np.sign(x.reshape(-1, D).mean(0))
    x += 1.5 * np.sign(x.reshape(-1, D).mean(0))
    return {k: jnp.asarray(v) for k, v in full.items()}, jnp.asarray(x)


def reference(full, x, first, count):
    """The equations, plainly: softmax over all experts, the 8 largest,
    renormalised, a masked dense sum over the experts held."""
    xt = x.reshape(-1, D)
    probs = jax.nn.softmax(xt @ full["router"], -1)
    gates, picked = jax.lax.top_k(probs, TOP)
    gates = gates / gates.sum(-1, keepdims=True)
    y = jnp.zeros_like(xt)
    for e in range(first, first + count):
        w = jnp.sum(jnp.where(picked == e, gates, 0), -1)
        hidden = jax.nn.silu(xt @ full["gate"][e]) * (xt @ full["up"][e])
        y = y + w[:, None] * (hidden @ full["down"][e])
    share = jnp.mean(jnp.sum(jax.nn.one_hot(picked, E), 1).reshape(B, T, E),
                     axis=1) / TOP
    aux = E * jnp.sum(share * jnp.mean(probs.reshape(B, T, E), 1), -1)
    return y.reshape(x.shape), aux, picked


def held_layer(full, first, count):
    layer = DroplessMoE(E, H, top_k=TOP, experts_held=(first, count),
                        block_rows=8)
    variables = layer.init(jax.random.PRNGKey(0), (None, T, D))
    params = {"router": full["router"],
              **{k: full[k][first:first + count] for k in KEYS}}
    assert jax.tree_util.tree_map(jnp.shape, params) == \
        jax.tree_util.tree_map(jnp.shape, variables["params"])
    return layer, params, variables["state"]


@pytest.fixture(params=["pallas", "lax"])
def path(request, monkeypatch):
    if request.param == "pallas":
        monkeypatch.setattr(gmm, "grouped_matmul", functools.partial(
            gmm.grouped_matmul, interpret=True))
    return request.param


def test_skewed_router_loses_no_row(f32_policy, weights, path):
    full, x = weights
    layer, params, state = held_layer(full, 0, E)
    (y, aux), new_state = layer.apply(params, x, state=state)
    want, want_aux, picked = reference(full, x, 0, E)
    rows = np.bincount(np.asarray(picked).ravel(), minlength=E)
    assert rows[3] == B * T > rows.sum() / TOP - 1   # every token picks 3
    assert rows.max() > 4 * np.median(rows)
    np.testing.assert_allclose(y, want, atol=2e-5)
    np.testing.assert_allclose(aux, want_aux, rtol=1e-5)
    # rows in = rows out: each expert's count, and nothing in the tail
    counted = np.asarray(new_state["rows_routed"])
    assert np.array_equal(counted[:-1], rows) and counted[-1] == 0
    assert counted.sum() == B * T * TOP


def test_one_expert_taking_over_half_the_rows(f32_policy, weights, path):
    """8 of 32 held, and one of the 8 takes more than half of the rows
    that arrive here: nothing is dropped, whatever the split."""
    full, x = weights
    # the other seven experts held here are every token's last choice
    # (nearly: a pick needs eight experts, so a few rows still arrive)
    others = np.array([0, 1, 2, 4, 5, 6, 7])
    full = dict(full, router=full["router"].at[:, others].set(
        -full["router"][:, 3:4]))
    layer, params, state = held_layer(full, 0, 8)
    (y, _), new_state = layer.apply(params, x, state=state)
    counted = np.asarray(new_state["rows_routed"])
    assert counted[3] == B * T and counted[3] > counted[:-1].sum() / 2
    assert counted.sum() == B * T * TOP
    np.testing.assert_allclose(y, reference(full, x, 0, 8)[0], atol=2e-5)


def test_the_shares_add_up_to_the_uncut_layer(f32_policy, weights, path):
    """Four ranks of 8 experts each: their parts of the sum add up to
    what the reference gives for all 32 (guide section 4), and so do
    the gradients with respect to the input."""
    full, x = weights
    total, d_total, rows = 0, 0, 0
    for first in range(0, E, 8):
        layer, params, state = held_layer(full, first, 8)
        (y, _), new_state = layer.apply(params, x, state=state)
        np.testing.assert_allclose(
            y, reference(full, x, first, 8)[0], atol=2e-5)
        total = total + y
        d_total = d_total + jax.grad(lambda x: jnp.sum(jnp.square(
            layer.apply(params, x, state=state)[0][0])))(x)
        rows += int(new_state["rows_routed"][:-1].sum())
    whole = reference(full, x, 0, E)[0]
    np.testing.assert_allclose(total, whole, atol=5e-5)
    assert rows == B * T * TOP
    d_want = sum(jax.grad(lambda x, f=f: jnp.sum(jnp.square(
        reference(full, x, f, 8)[0])))(x) for f in range(0, E, 8))
    np.testing.assert_allclose(d_total, d_want, atol=2e-3)


def test_gradients_match_the_reference(f32_policy, weights, path):
    full, x = weights
    layer, params, state = held_layer(full, 8, 16)

    def loss(p, x):
        (y, aux), _ = layer.apply(p, x, state=state)
        return jnp.sum(jnp.square(y)) + jnp.sum(aux)

    def want(p, x):
        merged = {"router": p["router"],
                  **{k: full[k].at[8:24].set(p[k]) for k in KEYS}}
        y, aux, _ = reference(merged, x, 8, 16)
        return jnp.sum(jnp.square(y)) + jnp.sum(aux)

    got, ref = jax.grad(loss, (0, 1))(params, x), \
        jax.grad(want, (0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(a, b, atol=1e-3 * float(
            jnp.max(jnp.abs(b))))


def test_bad_arguments_are_refused():
    with pytest.raises(ValueError, match="top_k"):
        DroplessMoE(8, 4, top_k=9)
    with pytest.raises(ValueError, match="experts_held"):
        DroplessMoE(8, 4, top_k=2, experts_held=(6, 4))


def test_stats_reader_publishes_rows_between_reads():
    class Net:
        layers = [DroplessMoE(8, 4, top_k=2, experts_held=(0, 4),
                              name="moe_a")]
    state = {"moe_a": {"rows_routed": jnp.array(
        [5, 0, 1, 2, 2 ** 31 - 5], jnp.int32)}}
    reader = MoeStatsReader(Net, state)
    # int32 counters that wrapped: differences are taken mod 2**32
    wrapped = np.array([5 + 30, 10, 1, 2, 2 ** 31 - 5 + 100],
                       np.int64).astype(np.int32)      # the tail wraps
    assert wrapped[-1] < 0
    later = {"moe_a": {"rows_routed": jnp.asarray(wrapped)}}
    reader.read(later, iteration=7)
    counters = get_registry().snapshot()
    assert counters["counters"][
        'moe_rows_routed_total{layer="moe_a",held="1"}'] == 40
    assert counters["counters"][
        'moe_rows_routed_total{layer="moe_a",held="0"}'] == 100
    assert counters["gauges"][
        'moe_expert_load_max_over_mean{layer="moe_a"}'] == 3.0
    spans = [e for e in get_tracer().events()
             if e["name"] == "moe_stats_read"]
    assert spans and spans[-1]["args"]["iteration"] == 7
    # a restored state (counts that went back) is counted on from
    reader.read(state, iteration=8)
    assert get_registry().snapshot()["counters"][
        'moe_rows_routed_total{layer="moe_a",held="1"}'] == 40
    # a model without expert layers costs nothing and publishes nothing
    class Plain:
        layers = []
    MoeStatsReader(Plain, {}).read({}, 0)
