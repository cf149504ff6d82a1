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


# ------------------------------------------ the backward's two forms
from analytics_zoo_tpu.ops import pallas_attention as kernels  # noqa: E402

FORMS = ["one_pass", "two_pass"]


@pytest.fixture
def backward_form(request, monkeypatch):
    """The backward in the form named: every shape here fits the
    one-pass form's budget, so the two-pass form is reached by a budget
    nothing fits."""
    if request.param == "two_pass":
        monkeypatch.setattr(kernels, "_RESIDENT_VMEM", 0)
    return request.param


def backward_builds():
    """{form: how often the counter says the backward was built so}."""
    from analytics_zoo_tpu.observability import get_registry
    counters = get_registry().snapshot()["counters"]
    return {form: counters.get(
        'fused_kernel_builds_total{kernel="flash_attention_backward",'
        'path="%s"}' % form, 0) for form in FORMS}


def forms_built_since(before):
    return {form for form, n in backward_builds().items()
            if n > before[form]}


# ------------------------- the token-major core, heads sharing a tile
from analytics_zoo_tpu.ops.pallas_attention import (  # noqa: E402
    _heads_per_tile, flash_attention_token_major)

# (query heads, K/V heads, head width, q k v as ONE array)
HEADS = {"two-to-a-tile": (4, 4, 64, False),
         "two-to-a-tile-fused": (4, 4, 64, True),
         "one-to-a-tile": (2, 2, 128, False),
         "grouped-d128": (4, 2, 128, False),
         "grouped-d128-fused": (4, 2, 128, True)}
CORE_MASKS = {"none": None, "causal": "causal",
              "block_diffusion": block_diffusion(128, 4)}


def _head_major(a, n, d):
    return jnp.moveaxis(a.reshape(a.shape[0], a.shape[1], n, d), 1, 2)


@pytest.mark.parametrize("backward_form", FORMS, indirect=True)
@pytest.mark.parametrize("mask", sorted(CORE_MASKS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads", sorted(HEADS))
def test_token_major_core_matches_dense(heads, dtype, mask, backward_form):
    """The kernels on (B, T, H·D) operands, a head a block of the last
    dimension (two 64-wide heads to a 128-lane block, or one of 128;
    q, k and v apart or side by side in one array), against dense
    attention over the same values: forward and all three gradients,
    the backward in one pass and as the dq and dkv kernels."""
    h, h_kv, d, fused = HEADS[heads]
    assert _heads_per_tile(h, h_kv, d) == 128 // d
    mask, b, t = CORE_MASKS[mask], 1, 256
    rs = np.random.RandomState(6)
    qkv = jnp.asarray(rs.randn(b, t, (h + 2 * h_kv) * d) * 0.5, dtype)
    w = jnp.asarray(rs.randn(b, t, h * d), jnp.float32)
    cuts = (h * d, (h + h_kv) * d)
    kw = dict(causal=True) if mask == "causal" else dict(mask=mask)

    def flash(qkv):
        ops = (qkv,) if fused else jnp.split(qkv, cuts, axis=-1)
        out = flash_attention_token_major(
            *ops, n_head=h, n_kv_head=h_kv if fused else None,
            block_q=128, block_k=128, interpret=True, **kw)
        assert out.shape == (b, t, h * d) and out.dtype == qkv.dtype
        return jnp.sum(w * out.astype(jnp.float32))

    def dense(qkv):
        q, k, v = jnp.split(qkv.astype(jnp.float32), cuts, axis=-1)
        out = _dense(_head_major(q, h, d), _head_major(k, h_kv, d),
                     _head_major(v, h_kv, d), allowed_pairs(mask, t))
        return jnp.sum(w * jnp.moveaxis(out, 1, 2).reshape(b, t, h * d))

    tol = dict(rtol=1e-4, atol=2e-5) if dtype == "float32" \
        else dict(rtol=0.05, atol=0.05)
    np.testing.assert_allclose(flash(qkv), dense(qkv),
                               rtol=tol["rtol"] * 10)
    before = backward_builds()
    got, want = jax.grad(flash)(qkv), jax.grad(dense)(qkv)
    assert forms_built_since(before) == {backward_form}
    assert got.dtype == qkv.dtype
    # dq, dk and dv, side by side as q, k and v are
    for g, r in zip(jnp.split(got.astype(jnp.float32), cuts, axis=-1),
                    jnp.split(want, cuts, axis=-1)):
        np.testing.assert_allclose(g, r, **tol)


@pytest.mark.parametrize("heads", ["grouped-d128", "grouped-d128-fused"])
def test_forward_call_outside_the_vjp_cuts_no_cotangent(heads, pallas_calls):
    """The forward kernel is an ordinary call on stopped operands and
    the ``custom_vjp`` only attaches the backward kernels: outside any
    ``jax.checkpoint`` the gradient's jaxpr holds each kernel once, and
    every operand's gradient is dense attention's, the K/V of a grouped
    head included (q, k and v apart, or side by side in one array)."""
    h, h_kv, d, fused = HEADS[heads]
    b, t = 1, 256
    rs = np.random.RandomState(9)
    ops = [jnp.asarray(rs.randn(b, t, n * d) * 0.5, jnp.float32)
           for n in (h, h_kv, h_kv)]
    w = jnp.asarray(rs.randn(b, t, h * d), jnp.float32)

    def flash(q, k, v):
        ops = (jnp.concatenate([q, k, v], -1),) if fused else (q, k, v)
        return jnp.sum(w * flash_attention_token_major(
            *ops, n_head=h, n_kv_head=h_kv, causal=True, block_q=128,
            block_k=128, interpret=True))

    def dense(q, k, v):
        out = _dense(_head_major(q, h, d), _head_major(k, h_kv, d),
                     _head_major(v, h_kv, d), allowed_pairs("causal", t))
        return jnp.sum(w * jnp.moveaxis(out, 1, 2).reshape(b, t, h * d))

    assert pallas_calls(jax.grad(flash, (0, 1, 2)), *ops) == {
        "flash_attention_fwd": 1, "flash_attention_bwd": 1}
    for name, got, want in zip("qkv", jax.grad(flash, (0, 1, 2))(*ops),
                               jax.grad(dense, (0, 1, 2))(*ops)):
        assert float(jnp.max(jnp.abs(want))) > 0
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5,
                                   err_msg=name)


@pytest.mark.parametrize("heads", ["two-to-a-tile", "grouped-d128"])
def test_head_major_wrapper_is_the_core(heads):
    """``flash_attention`` over (B, H, T, D) is the core round two
    ``moveaxis``: the same values, forward and backward, bit for bit."""
    h, h_kv, d, _ = HEADS[heads]
    b, t = 1, 256
    rs = np.random.RandomState(7)
    q = jnp.asarray(rs.randn(b, t, h * d), jnp.float32)
    k, v = (jnp.asarray(rs.randn(b, t, h_kv * d), jnp.float32)
            for _ in "kv")

    def core(q, k, v):
        return flash_attention_token_major(
            q, k, v, n_head=h, causal=True, block_q=128, block_k=128,
            interpret=True)

    def wrapped(q, k, v):
        out = flash_attention(
            _head_major(q, h, d), _head_major(k, h_kv, d),
            _head_major(v, h_kv, d), causal=True, block_q=128,
            block_k=128, interpret=True)
        assert out.shape == (b, h, t, d)
        return jnp.moveaxis(out, 1, 2).reshape(b, t, h * d)

    np.testing.assert_array_equal(core(q, k, v), wrapped(q, k, v))
    for got, want in zip(
            jax.grad(lambda *a: jnp.sum(jnp.square(wrapped(*a))),
                     (0, 1, 2))(q, k, v),
            jax.grad(lambda *a: jnp.sum(jnp.square(core(*a))),
                     (0, 1, 2))(q, k, v)):
        np.testing.assert_array_equal(got, want)


def test_heads_that_fill_no_lane_tile():
    """64-wide heads on FEWER K/V heads, or an odd number of them, have
    no lane-aligned block: the layers send them to the lax path on the
    chip; interpret mode runs them one head a block (the grouped test
    above, at 16 wide)."""
    assert _heads_per_tile(12, 12, 64) == 2
    assert _heads_per_tile(8, 8, 32) == 4
    assert _heads_per_tile(32, 4, 128) == 1
    assert _heads_per_tile(8, 8, 256) == 1
    assert _heads_per_tile(8, 2, 64) == 0
    assert _heads_per_tile(3, 3, 64) == 0
    assert _heads_per_tile(4, 4, 96) == 0
    with pytest.raises(ValueError, match="do not fit"):
        flash_attention_token_major(jnp.zeros((1, 128, 100)), n_head=3,
                                    interpret=True)


# ------------------------------- which form a traced layer was given
def _builds_while_tracing(layer, input_shapes, *inputs):
    from analytics_zoo_tpu.observability import get_registry
    params = jax.eval_shape(
        lambda: layer.build(jax.random.PRNGKey(0), input_shapes))
    prefix = "fused_kernel_builds_total"

    def flash_series():
        return {k[len(prefix):]: v for k, v in
                get_registry().snapshot()["counters"].items()
                if k.startswith(prefix) and "flash_attention" in k}

    before = flash_series()
    jax.eval_shape(lambda p, *x: layer.call(
        p, list(x) if len(x) > 1 else x[0]), params, *inputs)
    return {k: v - before.get(k, 0.0) for k, v in flash_series().items()
            if v != before.get(k, 0.0)}


def test_build_counter_says_which_form_a_traced_layer_got(one_chip_routing):
    """``fused_kernel_builds_total``: the GPT block's 12 heads of 64
    share lane tiles (the packed series beside the kernels' own), the
    sparse cell's 32 on 4 of 128 do not, and 64-wide heads on fewer K/V
    heads have no lane-aligned block and take the lax path, counted as
    any other fallback."""
    from analytics_zoo_tpu.pipeline.api.keras.layers.attention import (
        GroupedQueryAttention, MultiHeadSelfAttention)
    f32 = jax.ShapeDtypeStruct((2, 512, 768), jnp.float32)
    assert _builds_while_tracing(
        MultiHeadSelfAttention(768, 12, causal=True), (None, 512, 768),
        f32) == {'{kernel="flash_attention",path="pallas"}': 1.0,
                 '{kernel="flash_attention_packed",path="pallas"}': 1.0}
    x = jax.ShapeDtypeStruct((1, 512, 256), jnp.float32)
    pos = jax.ShapeDtypeStruct((1, 512), jnp.int32)
    shapes = [(None, 512, 256), (None, 512)]
    assert _builds_while_tracing(
        GroupedQueryAttention(32, 4, 128, mask=block_diffusion(256, 4)),
        shapes, x, pos) == {'{kernel="flash_attention",path="pallas"}': 1.0}
    assert _builds_while_tracing(
        GroupedQueryAttention(8, 2, 64, mask="causal"),
        shapes, x, pos) == {'{kernel="flash_attention",path="lax"}': 1.0}
    # a padding-mask INPUT still sends the dense layer to the lax path
    assert _builds_while_tracing(
        MultiHeadSelfAttention(768, 12), [(None, 512, 768), (None, 512)],
        f32, jax.ShapeDtypeStruct((2, 512), jnp.float32)) == {
            '{kernel="flash_attention",path="lax"}': 1.0}


# --------------- the backward in one pass and as the dq and dkv kernels
from analytics_zoo_tpu.ops.pallas_attention import (  # noqa: E402
    _FIRST, _LAST, sliding_window)

FORM_MASKS = {"causal": "causal", "window": sliding_window(100),
              "block_diffusion": block_diffusion(128, 4)}


def _pair_dense(q, k, v, h, h_kv, allowed):
    """The differential pair written out: every head's map (over its own
    K head) applied to its K/V pair's two V heads side by side."""
    b, t, _ = q.shape
    d = q.shape[-1] // h
    q = q.reshape(b, t, h // 2, 2, d)
    k = k.reshape(b, t, h_kv // 2, 2, d)
    v = v.reshape(b, t, h_kv // 2, 2 * d)
    group = h // h_kv
    outs = []
    for j in range(h // 2):
        for r in range(2):
            s = jnp.einsum("btd,bsd->bts", q[:, :, j, r],
                           k[:, :, j // group, r]) / np.sqrt(d)
            p = jax.nn.softmax(jnp.where(allowed, s, -1e30), axis=-1)
            outs.append(jnp.einsum("bts,bse->bte", p, v[:, :, j // group]))
    return jnp.concatenate(outs, axis=-1)


def _operands(b, t, h, h_kv, d, out_lanes, seed):
    rs = np.random.RandomState(seed)
    return [jnp.asarray(rs.randn(b, t, n) * 0.5, jnp.float32)
            for n in (h * d, h_kv * d, h_kv * d, out_lanes)]


def _mask_kw(mask):
    return dict(causal=True) if mask == "causal" else dict(mask=mask)


@pytest.mark.parametrize("backward_form", FORMS, indirect=True)
@pytest.mark.parametrize("mask", sorted(FORM_MASKS))
@pytest.mark.parametrize("group", [4, 8])
def test_grouped_heads_of_128_in_both_forms(group, mask, backward_form):
    """``group`` query heads of 128 on ONE K/V head (the sparse cell has
    eight): the one-pass kernel keeps the K/V tile's whole dk and dv
    while the group's query tiles go by one at a time, the dkv kernel
    walks the group innermost; either is the sum over the group."""
    mask, t, d = FORM_MASKS[mask], 256, 128
    q, k, v, w = _operands(1, t, group, 1, d, group * d, 10)
    allowed = allowed_pairs(mask, t)

    def flash(q, k, v):
        return jnp.sum(w * flash_attention_token_major(
            q, k, v, n_head=group, block_q=128, block_k=128,
            interpret=True, **_mask_kw(mask)))

    def dense(q, k, v):
        out = _dense(_head_major(q, group, d), _head_major(k, 1, d),
                     _head_major(v, 1, d), allowed)
        return jnp.sum(w * jnp.moveaxis(out, 1, 2).reshape(1, t, group * d))

    before = backward_builds()
    got = jax.grad(flash, (0, 1, 2))(q, k, v)
    assert forms_built_since(before) == {backward_form}
    for name, g, r in zip("qkv", got, jax.grad(dense, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("backward_form", FORMS, indirect=True)
@pytest.mark.parametrize("fused", [False, True], ids=["q-k-v", "one-array"])
@pytest.mark.parametrize("mask", sorted(FORM_MASKS))
def test_differential_pair_with_group_2_in_both_forms(mask, fused,
                                                      backward_form):
    """8 query heads of 64 in pairs on 4 K/V heads: two query tiles to a
    K/V tile, two heads to a tile, each head's map over the pair's whole
    V and a cotangent two tiles wide; ``delta`` a head is the row sum
    over its 128 output lanes."""
    mask, t, h, h_kv, d = FORM_MASKS[mask], 256, 8, 4, 64
    q, k, v, w = _operands(2, t, h, h_kv, d, 2 * h * d, 11)
    allowed = jnp.asarray(allowed_pairs(mask, t))

    def flash(q, k, v):
        ops = (jnp.concatenate([q, k, v], -1),) if fused else (q, k, v)
        return jnp.sum(w * flash_attention_token_major(
            *ops, n_head=h, n_kv_head=h_kv, differential=True,
            block_q=128, block_k=128, interpret=True, **_mask_kw(mask)))

    def dense(q, k, v):
        return jnp.sum(w * _pair_dense(q, k, v, h, h_kv, allowed))

    before = backward_builds()
    got = jax.grad(flash, (0, 1, 2))(q, k, v)
    assert forms_built_since(before) == {backward_form}
    for name, g, r in zip("qkv", got, jax.grad(dense, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=2e-5, err_msg=name)


# (query heads, K/V heads, head width, the pair, mask)
AGREE = {"packed-causal": (4, 4, 64, False, "causal"),
         "group-8-block_diffusion": (8, 1, 128, False,
                                     block_diffusion(256, 4)),
         "pair-group-2-window": (8, 4, 64, True, sliding_window(200))}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(AGREE))
def test_the_two_forms_agree_to_rounding(monkeypatch, case, dtype):
    """One per-head function (``_key_side``), the same products on the
    same dtypes from the same ``lse``: the forms differ in the order
    float32 sums are taken (dq over key tiles, dk and dv over the
    group's query tiles, ``delta`` outside the kernel)."""
    h, h_kv, d, pair, mask = AGREE[case]
    t = 512
    ops = [a.astype(dtype) for a in _operands(
        1, t, h, h_kv, d, (2 if pair else 1) * h * d, 12)]
    w = ops.pop().astype(jnp.float32)

    def grads():
        return jax.grad(lambda *a: jnp.sum(w * flash_attention_token_major(
            *a, n_head=h, differential=pair, block_q=128, block_k=128,
            interpret=True, **_mask_kw(mask)).astype(jnp.float32)),
            (0, 1, 2))(*ops)

    one = grads()
    monkeypatch.setattr(kernels, "_RESIDENT_VMEM", 0)
    two = grads()
    # bfloat16 results are each rounded once, from float32 sums taken in
    # another order: a unit in the last place at most
    tol = 2e-6 if dtype == "float32" else 2 ** -7
    for a, b in zip(one, two):
        a, b = (x.astype(jnp.float32) for x in (a, b))
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0
        assert float(jnp.max(jnp.abs(a - b))) <= tol * scale


def test_the_backward_form_follows_the_length(monkeypatch, pallas_calls):
    """One pass wherever a query tile's whole dq and its K/V tile's
    whole dk and dv (float32 accumulators, two buffers of each output
    block) fit ``_RESIDENT_VMEM``; the dq and dkv kernels past it.  The
    group's size does not enter.  The counter says which a program got."""
    # the sparse cell's 8,192 positions of 128 lanes in bfloat16 (eight
    # query heads to a K/V head) fit, and the hybrid cell's; twice that
    # does not; the GPT cell's 512 in float32 do
    assert kernels._fits_resident(8192, 128, 2)
    assert not kernels._fits_resident(16384, 128, 2)
    assert kernels._fits_resident(512, 128, 4)
    assert not kernels._fits_resident(8192, 128, 4)
    # at float32: three arrays of 128 lanes, 4 + 2 x 4 bytes a position
    monkeypatch.setattr(kernels, "_RESIDENT_VMEM", 256 * 3 * 128 * 12)
    assert kernels._fits_resident(256, 128, 4)
    assert not kernels._fits_resident(257, 128, 4)
    names = {256: {"flash_attention_bwd"},
             512: {"flash_attention_dq", "flash_attention_dkv"}}
    for t, form in ((256, "one_pass"), (512, "two_pass")):
        q, k, v, w = _operands(1, t, 8, 1, 128, 8 * 128, 13)
        grads = jax.grad(lambda *a: jnp.sum(w * flash_attention_token_major(
            *a, n_head=8, causal=True, interpret=True)), (0, 1, 2))
        before = backward_builds()
        calls = pallas_calls(grads, q, k, v)
        assert set(calls) == {"flash_attention_fwd"} | names[t]
        assert all(n == 1 for n in calls.values())
        assert forms_built_since(before) == {form}


@pytest.mark.parametrize("mask", sorted(FORM_MASKS))
def test_the_one_pass_walk_goes_through_each_key_tile_once(
        mask, monkeypatch, pallas_grids):
    """The one-pass backward walks ``_tile_pairs``' by-k-tile list as it
    stands, once a query tile of the group: key tile by key tile, each
    key tile's run of q tiles contiguous, in order and met once, the
    pairs the by-q walk's.  Its grid is (batch, K/V lane tiles, query
    tiles of a group, the walk), against the dkv kernel's (batch, K/V
    lane tiles, the walk, the group innermost)."""
    mask, t, blk = FORM_MASKS[mask], 256, 64
    (aq, ak, _), (qi, ki, fl) = _tile_pairs(mask, t, blk, blk)
    assert list(ki) == sorted(ki)                    # key tile by key tile
    assert sorted(zip(qi, ki)) == sorted(zip(aq, ak))
    assert set(ki) == set(range(t // blk))           # every key tile read
    for k in range(t // blk):
        run = np.flatnonzero(ki == k)
        assert list(run) == list(range(run[0], run[-1] + 1))
        assert list(qi[run]) == sorted(qi[run])
        assert fl[run[0]] & _FIRST and fl[run[-1]] & _LAST
        assert (fl[run] & _FIRST != 0).sum() == 1
        assert (fl[run] & _LAST != 0).sum() == 1
    # 8 query heads of 128 on 2 K/V heads, batch 2
    q, k, v, w = _operands(2, t, 8, 2, 128, 8 * 128, 14)

    def grads():         # a function of its own a form: a trace is cached
        return jax.grad(lambda *a: jnp.sum(w * flash_attention_token_major(
            *a, n_head=8, block_q=blk, block_k=blk, interpret=True,
            **_mask_kw(mask))), (0, 1, 2))
    n = len(qi)
    assert pallas_grids(grads(), q, k, v) == {
        "flash_attention_fwd": (2, 8, n),
        "flash_attention_bwd": (2, 2, 4, n)}
    monkeypatch.setattr(kernels, "_RESIDENT_VMEM", 0)
    assert pallas_grids(grads(), q, k, v) == {
        "flash_attention_fwd": (2, 8, n), "flash_attention_dq": (2, 8, n),
        "flash_attention_dkv": (2, 2, n, 4)}


def test_packed_heads_walk_once_a_lane_tile(pallas_grids):
    """Two 64-wide heads to a lane tile, every query head its own K/V
    head: the group axis has one entry, and the fused operand's three
    parts are block offsets."""
    qkv = jnp.zeros((2, 256, 3 * 4 * 64))
    grads = jax.grad(lambda a: jnp.sum(flash_attention_token_major(
        a, n_head=4, causal=True, block_q=128, block_k=128,
        interpret=True)))
    assert pallas_grids(grads, qkv) == {
        "flash_attention_fwd": (2, 2, 3), "flash_attention_bwd": (2, 2, 1, 3)}


def test_rows_no_pair_reaches_leave_as_zeros(monkeypatch):
    """Every key tile is read under the three masks; a walk that leaves
    one out (here: the causal list less every pair of the last key tile
    and of the first q tile) must still write that key tile's dk and dv,
    and that q tile's dq, as zeros: the resident accumulators are zeroed
    before a walk, not at a tile's first pair.  The rows the walk does
    reach are what the whole list gives them where the dropped pairs do
    not enter."""
    t, blk, h, d = 512, 128, 2, 128
    q, k, v, w = _operands(1, t, h, 1, d, h * d, 15)
    cfg = ("causal", d ** -0.5, blk, blk, True, h, 1, False)
    out, lse = kernels._flash_fwd_impl((q, k, v), cfg)
    whole = kernels._one_pass(((q, k, v), out, lse), w, cfg)

    by_q, (qi, ki, fl) = _tile_pairs("causal", t, blk, blk)
    keep = (ki != t // blk - 1) & (qi != 0)
    qi, ki, fl = qi[keep], ki[keep], fl[keep] & ~(_FIRST | _LAST)
    fl[np.r_[True, ki[1:] != ki[:-1]]] |= _FIRST
    fl[np.r_[ki[1:] != ki[:-1], True]] |= _LAST
    monkeypatch.setattr(kernels, "_tile_pairs",
                        lambda *a: (by_q, (qi, ki, fl)))
    dq, dk, dv = kernels._one_pass(((q, k, v), out, lse), w, cfg)
    assert not np.asarray(dk[:, -blk:]).any()
    assert not np.asarray(dv[:, -blk:]).any()
    assert not np.asarray(dq[:, :blk]).any()
    # key tile 0 lost its pair with q tile 0; key tiles 1 and 2 lost none
    for got, want in zip((dk, dv), whole[1:]):
        np.testing.assert_allclose(got[:, blk:-blk], want[:, blk:-blk],
                                   rtol=1e-6, atol=1e-6)
        assert float(jnp.max(jnp.abs(got[:, :blk] - want[:, :blk]))) > 0
    # q tiles 1 and 2 never read the last key tile; q tile 3 did
    np.testing.assert_allclose(dq[:, blk:-blk], whole[0][:, blk:-blk],
                               rtol=1e-6, atol=1e-6)
    assert float(jnp.max(jnp.abs(dq[:, -blk:] - whole[0][:, -blk:]))) > 0
