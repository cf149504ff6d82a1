"""The Mamba-2 hybrid decoder's parts at small sizes on the CPU: flash
attention on heads of 64 over fewer K/V heads (the K/V heads repeated to
the group before the kernels), ``GroupedQueryAttention`` with a softmax
scale and no positions, the ``Mamba2`` mixer against its equations on
the scan's kernels and its lax form, the kernels' results a recomputed
layer keeps, Granite's scalars, and a two-period model against the
benchmark's plain reference."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import pallas_attention as kernels
from analytics_zoo_tpu.ops.pallas_attention import flash_attention_token_major
from analytics_zoo_tpu.pipeline.api.keras.engine import Layer
from analytics_zoo_tpu.pipeline.api.keras.layers.attention import (
    GroupedQueryAttention, _flash_route)
from analytics_zoo_tpu.pipeline.api.keras.layers.normalization import (
    rms_norm)
from analytics_zoo_tpu.pipeline.api.keras.layers.ssm import (
    GatedFeedForward, Mamba2, PreNormDecoderLayer)

F32 = jnp.float32


def counters(prefix):
    from analytics_zoo_tpu.observability import get_registry
    return {k[len(prefix):]: v for k, v in
            get_registry().snapshot()["counters"].items()
            if k.startswith(prefix)}


def dense_attention(q, k, v, h, h_kv, scale):
    b, t, _ = q.shape
    d = q.shape[-1] // h
    qh = q.reshape(b, t, h, d)
    kh, vh = (jnp.repeat(a.reshape(b, t, h_kv, d), h // h_kv, axis=2)
              for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) * scale
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vh)
    return out.reshape(b, t, h * d)


@pytest.mark.parametrize("fused", [False, True], ids=["q-k-v", "qkv"])
def test_narrow_heads_on_fewer_kv_heads_take_the_kernels(fused):
    """8 query heads of 64 on 2 K/V heads (groups of 4), causal, at the
    softmax scale 1/64: the kernels (interpreted) give dense attention's
    output and gradients, with q, k and v apart or in one array; the
    repeat of the K/V heads is counted."""
    t, h, h_kv, d = 256, 8, 2, 64
    rs = np.random.RandomState(3)
    q, k, v, w = (jnp.asarray(rs.randn(1, t, n * d) * 0.5, F32)
                  for n in (h, h_kv, h_kv, h))
    assert not kernels._heads_per_tile(h, h_kv, d)
    assert kernels._broadcasts_kv(h, h_kv, d)

    def flash(q, k, v):
        ops = (jnp.concatenate([q, k, v], -1),) if fused else (q, k, v)
        return jnp.sum(w * flash_attention_token_major(
            *ops, n_head=h, n_kv_head=h_kv, causal=True, scale=1 / 64,
            block_q=128, block_k=128, interpret=True))

    def dense(q, k, v):
        return jnp.sum(w * dense_attention(q, k, v, h, h_kv, 1 / 64))

    key = '{kernel="flash_attention_packed",path="kv_broadcast"}'
    before = counters("fused_kernel_builds_total").get(key, 0)
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), rtol=1e-5)
    assert counters("fused_kernel_builds_total")[key] == before + 1
    got = jax.grad(flash, (0, 1, 2))(q, k, v)
    for name, g, r in zip("qkv", got, jax.grad(dense, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=2e-5, err_msg=name)


def test_the_route_takes_grouped_heads_of_64(one_chip_routing):
    """The cell's attention (32 heads of 64 on 8) goes to the kernels two
    heads a tile; the differential pair's route is as it was."""
    assert _flash_route(8192, 32, 8, 64) == 2
    assert _flash_route(8192, 12, 12, 64) == 2
    assert _flash_route(8192, 40, 20, 64, differential=True) == 2
    assert _flash_route(8192, 28, 4, 128) == 1


@pytest.fixture(params=["kernels", "lax"])
def path(request):
    if request.param == "kernels":
        request.getfixturevalue("interpreted_kernels")
    return request.param


def test_grouped_attention_with_a_scale_and_no_positions(f32_policy, path):
    """``GroupedQueryAttention(scale=1/64, rope_theta=None)`` over ``x``
    alone: dense attention of its projections at that scale, forward and
    gradients, on the kernels (the K/V heads repeated) and the lax
    path; the build counter reads which."""
    t, d_model = 256, 64
    layer = GroupedQueryAttention(8, 2, 64, rope_theta=None, qk_norm=False,
                                  mask="causal", scale=1 / 64)
    params = layer.build(jax.random.PRNGKey(0), (None, t, d_model))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, t, d_model))
    w = jax.random.normal(jax.random.PRNGKey(2), (1, t, d_model))
    before = counters("fused_kernel_builds_total")

    def got(p, x):
        return jnp.sum(w * layer.call(p, x))

    def want(p, x):
        ctx = dense_attention(x @ p["q_kernel"], x @ p["k_kernel"],
                              x @ p["v_kernel"], 8, 2, 1 / 64)
        return jnp.sum(w * (ctx @ p["o_kernel"]))

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(got(params, x), want(params, x),
                                   rtol=1e-4)
        for g, r in zip(jax.tree.leaves(jax.grad(got, (0, 1))(params, x)),
                        jax.tree.leaves(jax.grad(want, (0, 1))(params, x))):
            np.testing.assert_allclose(
                g, r, rtol=1e-3, atol=1e-4 * float(jnp.max(jnp.abs(r))))
    moved = counters("fused_kernel_builds_total")
    key = '{kernel="flash_attention",path="%s"}' % (
        "pallas" if path == "kernels" else "lax")
    assert moved[key] > before.get(key, 0)


# ------------------------------------------------------------ the mixer
H, P, N, DM = 4, 64, 16, 128


def mamba2_dense(p, u):
    """The mixer's equations with the scan a position at a time."""
    t = u.shape[1]
    inner, conv = H * P, H * P + 2 * N
    proj = u @ p["in_kernel"]
    z, xbc, dt = proj[..., :inner], proj[..., inner:inner + conv], \
        proj[..., inner + conv:]
    pad = jnp.pad(xbc, ((0, 0), (3, 0), (0, 0)))
    xbc = jax.nn.silu(sum(pad[:, k:k + t] * p["conv_kernel"][k]
                          for k in range(4)) + p["conv_bias"])
    x = xbc[..., :inner].reshape(1, t, H, P)
    b, c = xbc[..., inner:inner + N], xbc[..., inner + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["a_log"])

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs
        s = jnp.exp(dt_t * a)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None, :] * b_t[:, None, :, None]
        return s, jnp.einsum("bhnp,bn->bhp", s, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((1, H, N, P)),
                        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    y = jnp.moveaxis(y, 0, 1) + p["d"][:, None] * x
    gated = rms_norm(y.reshape(1, t, inner) * jax.nn.silu(z),
                     p["norm_gamma"], 1e-5)
    return gated @ p["out_kernel"]


def test_mamba2_mixer_is_its_equations(f32_policy, path):
    """Four heads of 64 on one group of 16 states, 320 positions (two and
    a half chunks of 128): forward and every weight's gradient on the
    scan's kernels (interpreted) and on its lax form."""
    t = 320
    layer = Mamba2(H, P, N, chunk=128)
    params = layer.build(jax.random.PRNGKey(0), (None, t, DM))
    assert [(k, v.shape) for k, v in params.items()] == [
        ("in_kernel", (DM, 2 * H * P + 2 * N + H)), ("conv_kernel", (4, 288)),
        ("conv_bias", (288,)), ("dt_bias", (H,)), ("a_log", (H,)),
        ("d", (H,)), ("norm_gamma", (H * P,)), ("out_kernel", (H * P, DM))]
    params = dict(params, in_kernel=params["in_kernel"] * 4,
                  conv_kernel=params["conv_kernel"] * 8)
    u = jax.random.normal(jax.random.PRNGKey(1), (1, t, DM))
    w = jax.random.normal(jax.random.PRNGKey(2), (1, t, DM))
    key = '{kernel="ssd_scan",path="%s"}' % (
        "pallas" if path == "kernels" else "lax")
    before = counters("fused_kernel_builds_total").get(key, 0)

    def got(p, u):
        return jnp.sum(w * layer.call(p, u))

    def want(p, u):
        return jnp.sum(w * mamba2_dense(p, u))

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(got(params, u), want(params, u),
                                   rtol=2e-4)
        grads = jax.grad(got, (0, 1))(params, u)
        wants = jax.grad(want, (0, 1))(params, u)
    assert counters("fused_kernel_builds_total")[key] > before
    for name in params:
        r = wants[0][name]
        np.testing.assert_allclose(grads[0][name], r, rtol=2e-3,
                                   atol=2e-4 * float(jnp.max(jnp.abs(r))),
                                   err_msg=name)
    np.testing.assert_allclose(grads[1], wants[1], rtol=2e-3, atol=1e-5)


def mamba2_layer(recompute, t=256):
    Layer.reset_name_counters()
    layer = PreNormDecoderLayer(Mamba2(H, P, N, chunk=128),
                                GatedFeedForward(64), 1e-5,
                                recompute=recompute, residual_multiplier=0.22)
    params = layer.build(jax.random.PRNGKey(0), (None, t, DM))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, t, DM))
    return layer, params, x


def test_a_recomputed_mamba2_layer_keeps_the_scans_results(
        f32_policy, interpreted_kernels, pallas_calls):
    """The recomputed layer's gradient holds the scan's forward kernel
    once, as the plain layer's does, gives the same gradients, and puts
    the scan's kept results in ``train_recompute_kept_bytes``."""
    from analytics_zoo_tpu.observability import get_registry
    plain, params, x = mamba2_layer(False)

    def grads(layer):
        return lambda p, x: jax.grad(lambda p, x: jnp.sum(jnp.square(
            layer.apply(p, x)[0])), (0, 1))(p, x)

    once = pallas_calls(grads(plain), params, x)
    assert once == {"ssd_scan_fwd": 1, "ssd_scan_bwd": 1}
    again, _, _ = mamba2_layer(True)
    assert pallas_calls(grads(again), params, x) == once
    kept = {k: v for k, v in get_registry().snapshot()["gauges"].items()
            if k.startswith("train_recompute_kept_bytes")
            and "ssd_scan" in k}
    # y (256 x 256 float32), two chunk-start states and the last one
    # (16 x 256 float32 each)
    assert kept == {
        'train_recompute_kept_bytes{name="ssd_scan_y"}': 256 * 256 * 4,
        'train_recompute_kept_bytes{name="ssd_scan_starts"}':
            2 * 16 * 256 * 4,
        'train_recompute_kept_bytes{name="ssd_scan_last"}': 16 * 256 * 4}
    for g, r in zip(jax.tree.leaves(grads(again)(params, x)),
                    jax.tree.leaves(grads(plain)(params, x))):
        assert float(jnp.max(jnp.abs(r))) > 0
        np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-6)


def test_granites_scalars(f32_policy):
    """The residual multiplier scales both branches; the embedding's
    multiplier its vectors, not the table it hands the head; the head's
    ``logits_scaling`` divides the logits."""
    from analytics_zoo_tpu.pipeline.api.keras.layers.embedding import (
        Embedding)
    from analytics_zoo_tpu.pipeline.api.keras.layers.ssm import NextTokenLoss
    layer, params, x = mamba2_layer(False, t=128)
    plain = PreNormDecoderLayer(layer.attention, layer.ffn, 1e-5)
    full = plain.apply(params, x)[0] - x
    a = rms_norm(x, params["ln1_gamma"], 1e-5)
    mixed = layer.attention.call(
        {k[5:]: v for k, v in params.items() if k.startswith("attn_")}, a)
    h = x + 0.22 * mixed
    m = rms_norm(h, params["ln2_gamma"], 1e-5)
    ffn = layer.ffn.call(
        {k[4:]: v for k, v in params.items() if k.startswith("ffn_")}, m)
    np.testing.assert_allclose(layer.apply(params, x)[0], h + 0.22 * ffn,
                               rtol=1e-5, atol=1e-6)
    assert float(jnp.max(jnp.abs(full - (layer.apply(params, x)[0] - x)))) \
        > 1e-3

    emb = Embedding(50, 8, init="normal", tie_head=True, multiplier=12)
    e_params = emb.build(jax.random.PRNGKey(0), (None, 4))
    ids = jnp.array([[1, 2, 3, 4]])
    out, table = emb.call(e_params, ids)
    np.testing.assert_allclose(out, 12 * e_params["embeddings"][ids[0]][None])
    assert table is e_params["embeddings"]

    h = jax.random.normal(jax.random.PRNGKey(3), (1, 4, 8))
    loss = NextTokenLoss(logits_scaling=8).call({}, [h, ids, table])
    logits = h[0, :3] @ table.T / 8
    want = jnp.mean(jax.nn.logsumexp(logits, -1)
                    - logits[jnp.arange(3), ids[0, 1:]])
    np.testing.assert_allclose(loss[0], want, rtol=1e-5)


def test_the_model_is_the_plain_reference(f32_policy, interpreted_kernels):
    """Two periods of a toy layout (``mamba, mamba, attention``) built
    from the benchmark's configuration file, on the reference's seeded
    weights: the loss (every logit through the softmax) and one step's
    gradient of every leaf against the plain reference's, the scan and
    attention on their kernels."""
    from benchmark import harness
    from tests.benchmark.test_bench_granite import CONFIG, TOY_CFG, cfg_of
    cfg = {**cfg_of(), **TOY_CFG}
    model_file = harness.load_module("configs", CONFIG)
    reference = harness.load_module("reference", CONFIG)
    Layer.reset_name_counters()
    model = model_file.build(cfg)
    like = model.get_variables()["params"]
    order = reference.param_order(cfg)
    ref_params = reference.init(cfg, 2 ** 31 + 5)
    params = harness.to_program(order, ref_params, like)
    ids = jax.random.randint(jax.random.PRNGKey(4), (1, cfg["seq_len"]), 0,
                             cfg["vocab_size"])

    def program_loss(p):
        return model.apply(p, [ids, ids])[0][0]

    def reference_loss(p):
        return reference.sequence_loss(cfg, p, ids[0])

    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(program_loss)(params)
        want, wants = jax.value_and_grad(reference_loss)(ref_params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert math.isfinite(float(got)) and float(got) > 1
    slots = harness.program_leaves(like)
    for name, g in harness.from_program(order, slots, grads).items():
        r = wants[name]
        np.testing.assert_allclose(g, r, rtol=2e-3,
                                   atol=1e-3 * float(jnp.max(jnp.abs(r))),
                                   err_msg=name)
