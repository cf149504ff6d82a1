"""The grouped matrix product: forward and both gradients against a
per-group loop, with an empty group, padding inside the last tile of a
group and a tail that holds garbage — on the Pallas kernels
(interpreted) and on the ``ragged_dot`` form a non-TPU backend takes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.observability import get_registry
from analytics_zoo_tpu.ops.grouped_matmul import (
    buffer_rows, group_layout, grouped_matmul)

G, K, N, BLOCK = 4, 32, 48, 8
SIZES = np.array([13, 0, 8, 5], np.int32)      # 26 rows, one group empty
ROWS = 40                                      # 14 more fall in the tail


@pytest.fixture(scope="module")
def case():
    rs = np.random.RandomState(0)
    m = buffer_rows(ROWS, G, BLOCK)
    layout = group_layout(jnp.asarray(SIZES), m, BLOCK)
    lhs = np.zeros((m, K), np.float32)
    group_of = -np.ones(m, int)
    for g in range(G):
        s = int(layout.starts[g])
        lhs[s:s + SIZES[g]] = rs.randn(SIZES[g], K)
        group_of[s:s + SIZES[g]] = g
    # what the tail holds must not matter to any of the three products
    lhs[int(layout.n_active[0]) * BLOCK:] = 1e6
    rhs = rs.randn(G, K, N).astype(np.float32)
    weight = rs.randn(m, N).astype(np.float32)
    return layout, jnp.asarray(lhs), jnp.asarray(rhs), group_of, weight


def test_layout_is_block_aligned_and_covers_every_group(case):
    layout = case[0]
    assert np.array_equal(layout.starts, [0, 16, 24, 32])
    assert np.array_equal(layout.padded, [16, 8, 8, 8])
    assert int(layout.n_active[0]) == 5
    assert np.array_equal(layout.tile_group[:5], [0, 0, 1, 2, 3])
    assert buffer_rows(ROWS, G, BLOCK) == 40 + G * BLOCK


def loop(lhs, rhs, group_of):
    out = jnp.zeros((lhs.shape[0], N))
    for g in range(G):
        out = out + jnp.where(jnp.asarray(group_of == g)[:, None],
                              lhs @ rhs[g], 0)
    return out


@pytest.mark.parametrize("path", ["pallas", "lax"])
def test_forward_and_both_gradients_match_a_per_group_loop(case, path):
    layout, lhs, rhs, group_of, weight = case
    valid = jnp.asarray(group_of >= 0)[:, None]

    def product(l, r):
        return grouped_matmul(l, r, layout, interpret=path == "pallas")

    def loss(fn):
        return lambda l, r: jnp.sum(jnp.where(valid, fn(l, r), 0) * weight)

    want = loop(lhs, rhs, group_of)
    got = product(lhs, rhs)
    np.testing.assert_allclose(np.where(valid, got, 0), want, atol=1e-4)
    d_got = jax.grad(loss(product), (0, 1))(lhs, rhs)
    d_want = jax.grad(loss(lambda l, r: loop(l, r, group_of)),
                      (0, 1))(lhs, rhs)
    np.testing.assert_allclose(np.where(valid, d_got[0], 0),
                               np.where(valid, d_want[0], 0), atol=1e-4)
    np.testing.assert_allclose(d_got[1], d_want[1], atol=1e-4)
    # the empty group's weights get a zero gradient, not garbage
    assert not np.asarray(d_got[1][1]).any()
    built = get_registry().snapshot()["counters"]
    assert built['fused_kernel_builds_total{kernel="grouped_matmul",'
                 'path="%s"}' % path] >= 1


def test_weights_enter_as_stored_and_their_gradient_leaves_in_float32(case):
    """bfloat16 rows against float32 expert matrices: the cast is the
    kernel's, the weights' gradient comes back float32."""
    layout, lhs, rhs, group_of, _ = case
    lhs = jnp.where(jnp.asarray(group_of >= 0)[:, None], lhs, 0)
    out, vjp = jax.vjp(lambda l, r: grouped_matmul(
        l, r, layout, interpret=True), lhs.astype(jnp.bfloat16), rhs)
    assert out.dtype == jnp.bfloat16
    d_lhs, d_rhs = vjp(jnp.ones_like(out))
    assert d_lhs.dtype == jnp.bfloat16 and d_rhs.dtype == jnp.float32


def test_shapes_that_do_not_fit_are_refused(case):
    layout, lhs, rhs, _, _ = case
    with pytest.raises(ValueError, match="do not fit"):
        grouped_matmul(lhs, rhs[:3], layout, interpret=True)
    with pytest.raises(ValueError, match="row block"):
        group_layout(jnp.asarray(SIZES), 70, BLOCK)
