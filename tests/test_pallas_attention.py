"""Pallas flash-attention kernel: interpret-mode correctness on the CPU
mesh (real-TPU perf is exercised by bench/verification runs)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops.attention import scaled_dot_product_attention
from analytics_zoo_tpu.ops.pallas_attention import flash_attention


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    rs = np.random.RandomState(0)
    q, k, v = (jnp.array(rs.randn(2, 3, 128, 32), jnp.float32)
               for _ in range(3))
    ref = scaled_dot_product_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=64,
                          block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_block_divisibility_checked():
    q = jnp.zeros((1, 1, 100, 32))
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, q, q, block_q=64, block_k=64, interpret=True)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_dense(causal):
    """The custom-VJP flash backward (dq/dk/dv Pallas kernels) must
    match autodiff through dense attention."""
    rs = np.random.RandomState(1)
    q, k, v = (jnp.array(rs.randn(2, 3, 128, 32), jnp.float32)
               for _ in range(3))

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=64,
                               block_k=64, interpret=True).sum()

    def loss_dense(q, k, v):
        return scaled_dot_product_attention(q, k, v,
                                            causal=causal).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_flash_backward_weighted_loss():
    """Non-uniform cotangents (not just sum()) flow correctly."""
    rs = np.random.RandomState(2)
    q, k, v = (jnp.array(rs.randn(1, 2, 128, 32), jnp.float32)
               for _ in range(3))
    w = jnp.array(rs.randn(1, 2, 128, 32), jnp.float32)

    gf = jax.grad(lambda q: (flash_attention(
        q, k, v, causal=True, block_q=64, block_k=64,
        interpret=True) * w).sum())(q)
    gd = jax.grad(lambda q: (scaled_dot_product_attention(
        q, k, v, causal=True) * w).sum())(q)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                               rtol=1e-4, atol=1e-5)


def test_flash_backward_bf16_close_to_f32_reference(causal=True):
    """bf16 training path: flash grads must track the f32 dense grads
    within bf16 resolution (the backward recomputes logits at the
    forward's precision so P matches the saved lse)."""
    rs = np.random.RandomState(3)
    qf, kf, vf = (np.asarray(rs.randn(1, 2, 128, 64) * 0.5, np.float32)
                  for _ in range(3))
    qb, kb, vb = (jnp.asarray(a, jnp.bfloat16) for a in (qf, kf, vf))

    gb = jax.grad(lambda q: flash_attention(
        q, kb, vb, causal=causal, block_q=64, block_k=64,
        interpret=True).astype(jnp.float32).sum())(qb)
    gref = jax.grad(lambda q: scaled_dot_product_attention(
        q, jnp.asarray(kf), jnp.asarray(vf),
        causal=causal).sum())(jnp.asarray(qf))
    # bf16 has ~3 decimal digits; compare at bf16 tolerance
    np.testing.assert_allclose(np.asarray(gb, np.float32),
                               np.asarray(gref), rtol=0.05, atol=0.05)


# ------------------------------------------- masks, grouped K/V heads
from analytics_zoo_tpu.ops.pallas_attention import (  # noqa: E402
    _tile_pairs, allowed_pairs, block_diffusion)


def _dense(q, k, v, allowed):
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
    return scaled_dot_product_attention(q, k, v, mask=jnp.asarray(allowed))


def _rule(seq_len, block):
    """The block-diffusion mask as ISSUE 27 states it, pair by pair."""
    i = np.arange(2 * seq_len)[:, None]
    j = np.arange(2 * seq_len)[None, :]
    bi, bj = (i % seq_len) // block, (j % seq_len) // block
    ni, nj = i < seq_len, j < seq_len
    return (ni & nj & (bi == bj)) | (ni & ~nj & (bj < bi)) \
        | (~ni & ~nj & (bj <= bi))


MASKS = {"causal": ("causal", 128, 32),
         "block_diffusion-b4": (block_diffusion(64, 4), 128, 32),
         "block_diffusion-b16": (block_diffusion(64, 16), 128, 16)}


@pytest.mark.parametrize("case", sorted(MASKS))
def test_masked_flash_on_grouped_heads_matches_dense(case):
    """8 query heads on 2 K/V heads, forward and all three gradients,
    under each mask the kernels evaluate from iotas."""
    mask, t, blk = MASKS[case]
    rs = np.random.RandomState(4)
    q = jnp.array(rs.randn(2, 8, t, 16), jnp.float32)
    k, v = (jnp.array(rs.randn(2, 2, t, 16), jnp.float32) for _ in "kv")
    w = jnp.array(rs.randn(2, 8, t, 16), jnp.float32)
    allowed = allowed_pairs(mask, t)
    if mask == "causal":
        assert np.array_equal(allowed, np.tril(np.ones((t, t), bool)))
        kw = dict(causal=True)
    else:
        assert np.array_equal(allowed, _rule(*mask))
        # a quarter of the dense pairs, and L B more
        assert allowed.sum() == mask.seq_len ** 2 \
            + mask.seq_len * mask.block
        kw = dict(mask=mask)

    def flash(q, k, v):
        return jnp.sum(w * flash_attention(
            q, k, v, block_q=blk, block_k=blk, interpret=True, **kw))

    def dense(q, k, v):
        return jnp.sum(w * _dense(q, k, v, allowed))

    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), rtol=1e-4)
    for got, want in zip(jax.grad(flash, (0, 1, 2))(q, k, v),
                         jax.grad(dense, (0, 1, 2))(q, k, v)):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)


def test_tiles_with_no_allowed_pair_are_not_walked():
    """At the benchmark's sizes three quarters of the tiles are out of
    every kernel's walk, and only the diagonal ones evaluate the mask."""
    by_q, by_k = _tile_pairs(block_diffusion(4096, 4), 8192, 512, 512)
    n = 8192 // 512
    assert len(by_q[0]) == len(by_k[0]) == 8 * 8 + 2 * 8     # of 256
    assert len(by_q[0]) / n ** 2 < 0.32
    partial = (by_q[2] & 4) != 0
    assert partial.sum() == 3 * 8          # noisy-noisy, noisy-clean, clean
    # both walks hold the same pairs; each starts and ends every run
    assert set(zip(*by_q[:2])) == set(zip(*by_k[:2]))
    assert ((by_q[2] & 1) != 0).sum() == ((by_q[2] & 2) != 0).sum() == n
    causal, _ = _tile_pairs("causal", 512, 256, 256)
    assert list(zip(*causal[:2])) == [(0, 0), (1, 0), (1, 1)]


def test_a_length_over_the_old_vmem_cap(monkeypatch):
    """4,608 positions of 128: over the 4,096 x 128 a head's whole K/V
    had to fit in before K and V came through the grid."""
    t, d, mask = 4608, 128, block_diffusion(2304, 4)
    assert t * d > 4096 * 128
    rs = np.random.RandomState(5)
    q = jnp.array(rs.randn(1, 2, t, d), jnp.float32)
    k, v = (jnp.array(rs.randn(1, 1, t, d), jnp.float32) for _ in "kv")
    allowed = allowed_pairs(mask, t)

    def flash(q, k, v):
        return jnp.sum(jnp.square(flash_attention(
            q, k, v, mask=mask, interpret=True)))

    def dense(q, k, v):
        return jnp.sum(jnp.square(_dense(q, k, v, allowed)))

    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), rtol=1e-4)
    for got, want in zip(jax.grad(flash, (0, 1, 2))(q, k, v),
                         jax.grad(dense, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-5)


def test_bad_mask_arguments_are_refused():
    q = jnp.zeros((1, 4, 128, 32))
    kv = jnp.zeros((1, 3, 128, 32))
    with pytest.raises(ValueError, match="do not fit"):
        flash_attention(q, kv, kv, interpret=True)
    with pytest.raises(ValueError, match="exclude"):
        flash_attention(q, q, q, causal=True, mask=block_diffusion(64, 4),
                        interpret=True)
    with pytest.raises(ValueError, match="masks 64 positions"):
        flash_attention(q, q, q, mask=block_diffusion(32, 4),
                        interpret=True)
    with pytest.raises(ValueError, match="must divide"):
        block_diffusion(64, 5)
