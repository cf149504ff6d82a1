"""Latent attention and the router's second form, on the CPU at small
sizes with seeded weights, each against a dense formula or the plain
reference of the ``kanana-2-30b-a3b-instruct-2601`` configuration
(``benchmark/reference``): the latent flash kernels (interpreted) and
their lax form, forward and the four gradients, the backward in its
one-pass and its two-pass form; the layer; the sigmoid
router under a selection bias; the shared experts; the eight shares of
an expert-parallel group adding up to the uncut layer; the recomputed
decoder layer keeping its kernels' results."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from benchmark import harness  # noqa: E402

from analytics_zoo_tpu.ops import pallas_latent_attention as latent  # noqa: E402
from analytics_zoo_tpu.ops.pallas_attention import (  # noqa: E402
    _FIRST, _LAST, _tile_pairs)
from analytics_zoo_tpu.pipeline.api.keras.engine import Layer  # noqa: E402
from analytics_zoo_tpu.pipeline.api.keras.layers.latent import (  # noqa: E402
    LatentAttention, LatentDecoderLayer)
from analytics_zoo_tpu.pipeline.api.keras.layers.moe import (  # noqa: E402
    DroplessMoE)
from analytics_zoo_tpu.pipeline.api.keras.layers.ssm import (  # noqa: E402
    GatedFeedForward)

CONFIG = "kanana-2-30b-a3b-instruct-2601"


@pytest.fixture(scope="module")
def reference():
    return harness.load_module("reference", CONFIG)


def operands(h, t, b=2, nope=128, rope=64, v=128, extra=0, seed=0):
    """(q with ``extra`` unread columns, q_pe, kv, k_pe, a cotangent)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    shapes = [(b, t, h * nope + extra), (b, t, h * rope),
              (b, t, h * (nope + v)), (b, t, rope), (b, t, h * v)]
    return [0.5 * jax.random.normal(k, s) for k, s in zip(ks, shapes)]


def keys_written_out(q, q_pe, kv, k_pe, h, nope, v, causal):
    """Attention with the (nope + rope)-wide keys built plainly: every
    head's own part beside a copy of the shared rotary key."""
    b, t, _ = q.shape
    r = k_pe.shape[-1]
    q_h = jnp.concatenate([q[..., :h * nope].reshape(b, t, h, nope),
                           q_pe.reshape(b, t, h, r)], axis=-1)
    k_h = jnp.concatenate([kv[..., :h * nope].reshape(b, t, h, nope),
                           jnp.broadcast_to(k_pe[:, :, None], (b, t, h, r))],
                          axis=-1)
    v_h = kv[..., h * nope:].reshape(b, t, h, v)
    s = jnp.einsum("bqhd,bkhd->bhqk", q_h, k_h) / np.sqrt(nope + r)
    if causal:
        s = jnp.where(np.tril(np.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1),
                      v_h).reshape(b, t, h * v)


def loss_and_grads(fn, ops, w):
    return jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2, 3))(*ops)


def close(got, want, tol=2e-5):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        assert float(jnp.max(jnp.abs(a - b))) <= tol * scale


# ------------------------------------------------------------ the kernels
FORMS = ["one_pass", "two_pass"]


@pytest.fixture
def backward_form(request, monkeypatch):
    """The backward in the form named: every shape here fits the
    one-pass form's budget, so the two-pass form is reached by a budget
    nothing fits."""
    if request.param == "two_pass":
        monkeypatch.setattr(latent, "_RESIDENT_VMEM", 0)
    return request.param


def kernel_grads(ops, w, h, causal, block=256):
    return loss_and_grads(
        lambda *a: latent.latent_flash_attention(
            *a, n_head=h, causal=causal, block_q=block, block_k=block,
            interpret=True), ops, w)


def backward_builds():
    """{form: how often the counter says the backward was built so}."""
    builds = fused_builds()
    return {form: builds.get(
        '{kernel="flash_attention_latent_backward",path="%s"}' % form, 0)
        for form in FORMS}


def forms_built_since(before):
    return {form for form, n in backward_builds().items()
            if n > before[form]}


def traced_grads(h, w):
    """The four gradients on the interpreted causal kernels, a function
    of the operands to trace."""
    return jax.grad(lambda *a: jnp.sum(latent.latent_flash_attention(
        *a, n_head=h, causal=True, interpret=True) * w),
        argnums=(0, 1, 2, 3))


@pytest.mark.parametrize("backward_form", FORMS, indirect=True)
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("t,block", [(256, 256), (384, 128)],
                         ids=["one-tile", "t384-tiles-of-128"])
def test_kernels_match_the_keys_written_out(causal, t, block, backward_form):
    """Forward and the four gradients of the interpreted kernels (two
    heads a step, the shared rotary key twice in one tile) against
    attention with 192-wide keys built plainly, the backward in one
    pass and as the dq and dkv kernels; 384 positions are no multiple
    of the layers' 256-position tile and walk 128-tiles here.
    ``dk_pe`` is the sum over all four heads by construction of the
    formula it is held against."""
    *ops, w = operands(4, t)
    before = backward_builds()
    got = kernel_grads(ops, w, 4, causal, block)
    want = loss_and_grads(
        lambda *a: keys_written_out(*a, 4, 128, 128, causal), ops, w)
    close(got, want)
    assert forms_built_since(before) == {backward_form}


@pytest.mark.parametrize("backward_form", FORMS, indirect=True)
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_dk_pe_is_the_sum_over_all_heads(causal, backward_form):
    """32 heads are 16 head pairs, each on a grid step of its own: the
    one-pass kernel writes a pair's share of ``dk_pe`` a key tile and
    the sum over the pairs is taken outside it; the dkv kernel sums in
    scratch.  Either is the shared key's whole cotangent."""
    *ops, w = operands(32, 256, b=1)
    got = kernel_grads(ops, w, 32, causal, block=128)
    want = loss_and_grads(
        lambda *a: keys_written_out(*a, 32, 128, 128, causal), ops, w)
    close(got, want)
    # and no one pair's share: the heads' shares do not cancel
    two_heads = [ops[0][..., :256], ops[1][..., :128],
                 jnp.concatenate([ops[2][..., :256],
                                  ops[2][..., 32 * 128:32 * 128 + 256]], -1),
                 ops[3]]
    one_pair = kernel_grads(two_heads, w[..., :256], 2, causal, block=128)
    assert float(jnp.max(jnp.abs(got[1][3] - one_pair[1][3]))) > \
        0.1 * float(jnp.max(jnp.abs(want[1][3])))


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_the_two_forms_agree_to_rounding(monkeypatch, causal):
    """Same products, same dtypes, same ``lse``: the forms differ in the
    order float32 sums are taken (dq over key tiles, ``delta`` and the
    head pairs' ``dk_pe`` outside the kernel)."""
    *ops, w = operands(4, 512, b=1, seed=3)
    one = kernel_grads(ops, w, 4, causal, block=128)
    monkeypatch.setattr(latent, "_RESIDENT_VMEM", 0)
    two = kernel_grads(ops, w, 4, causal, block=128)
    assert float(one[0]) == float(two[0])
    close(one[1], two[1], 2e-6)


def test_the_backward_form_follows_the_length(monkeypatch, pallas_calls):
    """One pass wherever a head pair's whole dq (float32 accumulators,
    two buffers of each output block) fits ``_RESIDENT_VMEM``; the dq
    and dkv kernels past it.  The counter says which form a program
    got."""
    # the cell's 8,192 positions in bfloat16 fit; twice that, and the
    # model's longest sequence, do not
    assert latent._fits_resident(8192, 2)
    assert not latent._fits_resident(16384, 2)
    assert not latent._fits_resident(32768, 2)
    # at float32: 384 lanes of 4 + 2 x 4 bytes a position
    monkeypatch.setattr(latent, "_RESIDENT_VMEM", 256 * 384 * 12)
    assert latent._fits_resident(256, 4) and not latent._fits_resident(
        257, 4)
    names = {256: {"flash_attention_latent_bwd"},
             512: {"flash_attention_latent_dq", "flash_attention_latent_dkv"}}
    for t, form in ((256, "one_pass"), (512, "two_pass")):
        *ops, w = operands(6, t, b=1)
        before = backward_builds()
        calls = pallas_calls(traced_grads(6, w), *ops)
        assert set(calls) == {"flash_attention_latent_fwd"} | names[t]
        assert all(n == 1 for n in calls.values())
        assert forms_built_since(before) == {form}


@pytest.mark.parametrize("t,sizes", [(200, (128, 64, 128)),
                                     (96, (16, 8, 24))],
                         ids=["t200-no-tile-multiple", "small-heads"])
def test_lax_form_matches_the_keys_written_out(t, sizes):
    nope, rope, v = sizes
    *ops, w = operands(3, t, nope=nope, rope=rope, v=v)
    got = loss_and_grads(
        lambda *a: latent.latent_attention_dense(
            *a, n_head=3, nope_dim=nope, v_dim=v, causal=True), ops, w)
    want = loss_and_grads(
        lambda *a: keys_written_out(*a, 3, nope, v, True), ops, w)
    close(got, want)


def test_what_lies_after_the_nope_heads_is_not_read():
    """``q`` may be the whole query projection's result: the columns
    after the nope heads change nothing and get a zero cotangent."""
    *ops, w = operands(2, 256, extra=2 * 64)
    narrow = [ops[0][..., :2 * 128], *ops[1:]]

    def run(*a):
        return latent.latent_flash_attention(*a, n_head=2, causal=True,
                                             interpret=True)
    (l1, g1), (l2, g2) = (loss_and_grads(run, o, w) for o in (ops, narrow))
    assert float(l1) == float(l2)
    np.testing.assert_array_equal(g1[0][..., :256], g2[0])
    assert not np.asarray(g1[0][..., 256:]).any()
    close(g1[1:], g2[1:], 0.0)


def test_the_kernels_take_their_sizes_only():
    assert latent.kernel_fits(32, 128, 64, 128)
    assert not latent.kernel_fits(3, 128, 64, 128)
    assert not latent.kernel_fits(32, 128, 64, 64)
    *ops, _ = operands(2, 256, rope=32)
    with pytest.raises(ValueError, match="even number of heads"):
        latent.latent_flash_attention(*ops, n_head=2, interpret=True)
    *ops, _ = operands(2, 256)
    with pytest.raises(ValueError, match="do not fit"):
        latent.latent_flash_attention(ops[0], ops[1], ops[2][..., :-1],
                                      ops[3], n_head=2, interpret=True)


def test_the_one_pass_walk_goes_through_each_key_tile_once(pallas_grids):
    """The one-pass backward walks ``_tile_pairs``' by-k-tile list as it
    stands, once a head pair (the pairs are a grid axis outside it):
    each key tile's run of q tiles is contiguous and met once, its first
    and last entry flagged (``dk_nope``, ``dv`` and the pair's ``dk_pe``
    are zeroed and leave there), and the pairs are the by-q walk's."""
    (aq, ak, _), (qi, ki, fl) = _tile_pairs("causal", 1024, 256, 256)
    assert len(qi) == 10
    assert list(ki) == sorted(ki)                    # key tile by key tile
    assert sorted(zip(qi, ki)) == sorted(zip(aq, ak))
    for k in range(4):
        run = np.flatnonzero(ki == k)
        assert list(run) == list(range(run[0], run[-1] + 1))
        assert list(qi[run]) == list(range(k, 4))    # its q tiles, in order
        assert fl[run[0]] & _FIRST and fl[run[-1]] & _LAST
        assert (fl[run] & _FIRST != 0).sum() == 1
        assert (fl[run] & _LAST != 0).sum() == 1
    # the grid: (batch, head pairs, the walk), against the dkv kernel's
    # (batch, the walk once a head pair)
    *ops, w = operands(6, 1024, b=2)
    grids = pallas_grids(traced_grads(6, w), *ops)
    assert grids["flash_attention_latent_bwd"] == (2, 3, 10)


def test_the_dkv_walk_goes_through_each_key_tile_once_a_head_pair(
        monkeypatch, pallas_grids):
    """The two-pass form's dkv walk.  Per key tile: every head pair's
    run of q tiles, the run's own first and last flagged (``dk_nope``
    and ``dv`` leave there), the key tile's first and last entry flagged
    once (``dk_pe`` leaves there)."""
    tiles = 3
    qi, ki, gi, fl = latent._dkv_walk("causal", 1024, 256, 256, tiles)
    _, (bq, bk, bf) = _tile_pairs("causal", 1024, 256, 256)
    assert len(qi) == tiles * len(bq) == tiles * 10
    assert list(ki) == sorted(ki)                    # key tile by key tile
    for k in range(4):
        here = np.flatnonzero(ki == k)
        assert list(gi[here]) == sorted(gi[here])    # pair by pair within
        for g in range(tiles):
            run = here[gi[here] == g]
            assert list(qi[run]) == list(bq[bk == k])
            assert fl[run[0]] & _FIRST and fl[run[-1]] & _LAST
            assert not (fl[run[1:]] & _FIRST).any()
        assert (fl[here] & latent._KFIRST != 0).sum() == 1
        assert fl[here[0]] & latent._KFIRST and fl[here[-1]] & latent._KLAST
        assert (fl[here] & latent._KLAST != 0).sum() == 1
    monkeypatch.setattr(latent, "_RESIDENT_VMEM", 0)
    *ops, w = operands(6, 1024, b=2)
    grids = pallas_grids(traced_grads(6, w), *ops)
    assert grids["flash_attention_latent_dq"] == (2, 3, 10)
    assert grids["flash_attention_latent_dkv"] == (2, 30)


def test_the_tile_gauge_is_set_for_the_causal_walk():
    from analytics_zoo_tpu.observability import get_registry
    _tile_pairs.cache_clear()
    *ops, _ = operands(2, 512, b=1)
    latent.latent_flash_attention(*ops, n_head=2, causal=True,
                                  interpret=True)
    gauges = get_registry().snapshot()["gauges"]
    for which in ("walked", "causal"):
        assert gauges['flash_attention_tiles{mask="causal",which="%s"}'
                      % which] == 3


# -------------------------------------------------------------- the layer
def attention_cfg(**over):
    cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                         CONFIG + ".json"))
    return {**cfg, "hidden_size": 64, "num_attention_heads": 2,
            "kv_lora_rank": 32, **over}


@pytest.mark.parametrize("route,t,sizes", [
    ("lax", 200, (16, 8, 24)), ("lax", 384, (128, 64, 128)),
    ("pallas", 256, (128, 64, 128))],
    ids=["lax-small-heads", "lax-t384-not-a-tile-multiple", "kernels"])
def test_layer_matches_the_reference(f32_policy, interpreted_kernels,
                                     reference, route, t, sizes):
    """``LatentAttention`` (columns in the program's order, rotate-half
    rotation) against the reference's attention (the published
    interleaved rotation on columns put back in the published order),
    output and gradients, on the lax path and on the kernels."""
    nope, rope, v = sizes
    cfg = attention_cfg(qk_nope_head_dim=nope, qk_rope_head_dim=rope,
                        v_head_dim=v)
    layer = LatentAttention(2, 32, nope, rope, v, rope_theta=1e6)
    params = layer.build(jax.random.PRNGKey(0), (None, t, 64))
    params = {k: (v_ if k == "kv_a_norm" else 4 * v_)
              for k, v_ in params.items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (1, t, 64))
    w = jax.random.normal(jax.random.PRNGKey(2), (1, t, 64))
    names = {"q_kernel": "attn.q", "kv_a_kernel": "attn.kv_a",
             "kv_a_norm": "attn.kv_a_norm", "kv_b_kernel": "attn.kv_b",
             "o_kernel": "attn.o"}
    before = dict(fused_builds())

    got = jax.value_and_grad(
        lambda p, x: jnp.sum(layer.call(p, x) * w), (0, 1))(params, x)
    want = jax.value_and_grad(
        lambda p, x: jnp.sum(reference.attention(
            cfg, jnp.matmul, {names[k]: v_ for k, v_ in p.items()}, x[0])
            * w[0]), (0, 1))(params, x)
    close(got, want, 5e-5)
    moved = {k: v_ - before.get(k, 0) for k, v_ in fused_builds().items()}
    assert moved.get('{kernel="flash_attention_latent",path="%s"}' % route)


def fused_builds():
    from analytics_zoo_tpu.observability import get_registry
    prefix = "fused_kernel_builds_total"
    return {k[len(prefix):]: v for k, v in
            get_registry().snapshot()["counters"].items()
            if k.startswith(prefix)}


# ------------------------------------------------------------- the router
def moe(experts_held=None, shared=24, **over):
    Layer.reset_name_counters()
    return DroplessMoE(16, 20, top_k=3, experts_held=experts_held,
                       block_rows=8, scoring="sigmoid",
                       routed_scaling_factor=2.448, shared_hidden=shared,
                       **over)


def moe_inputs(layer, seed=0):
    params = layer.build(jax.random.PRNGKey(seed), (None, 24, 32))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 24, 32))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(seed + 2), (16,))
    state = {**layer.init_state((None, 24, 32)), "selection_bias": bias}
    return params, x, state


def test_router_scores_by_sigmoid_and_selects_under_the_bias(f32_policy):
    """Weights are the UNBIASED scores of the experts selected under the
    bias, normalised, times the scale; the bias flips choices; there is
    one group (``n_group`` 1: no group-limited selection exists)."""
    layer = moe()
    params, x, state = moe_inputs(layer)
    gates, experts, aux = layer.route(params["router"], x,
                                      state["selection_bias"])
    s = 1 / (1 + np.exp(-np.asarray(x, np.float64).reshape(48, 32)
                        @ np.asarray(params["router"], np.float64)))
    order = np.argsort(-(s + np.asarray(state["selection_bias"])), axis=1)
    assert np.array_equal(np.sort(np.asarray(experts), 1),
                          np.sort(order[:, :3], 1))
    picked = np.take_along_axis(s, np.asarray(experts), axis=1)
    np.testing.assert_allclose(
        gates, picked / picked.sum(1, keepdims=True) * 2.448, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates).sum(1), 2.448, rtol=1e-5)
    unbiased = np.argsort(-s, axis=1)[:, :3]
    flipped = sum(set(a) != set(b) for a, b in zip(unbiased, order[:, :3]))
    assert flipped >= 1
    _, plain, _ = layer.route(params["router"], x, jnp.zeros((16,)))
    assert np.array_equal(np.sort(np.asarray(plain), 1), np.sort(unbiased, 1))
    assert not np.asarray(aux).any()


def test_the_bias_is_state_and_gets_no_gradient(f32_policy):
    layer = moe()
    params, x, state = moe_inputs(layer)
    assert set(state) == {"rows_routed", "selection_bias"}
    assert set(params) == {"router", "gate", "up", "down",
                           "shared_gate_up", "shared_down"}
    (y, _), new = layer.apply(params, x, state=state)
    np.testing.assert_array_equal(new["selection_bias"],
                                  state["selection_bias"])
    assert int(new["rows_routed"].sum()) == 48 * 3
    grad = jax.grad(lambda b: jnp.sum(layer.apply(
        params, x, state={**state, "selection_bias": b})[0][0]))(
            state["selection_bias"])
    assert not np.asarray(grad).any()
    # the leaf comes with the sigmoid scores, zeros until set, and the
    # softmax router has none
    assert not np.asarray(
        layer.init_state((None, 24, 32))["selection_bias"]).any()
    assert set(DroplessMoE(16, 20).init_state((None, 24, 32))) == {
        "rows_routed"}


def test_shared_experts_are_a_gated_mlp_beside_the_routed_sum(f32_policy):
    layer = moe()
    params, x, state = moe_inputs(layer)
    (with_shared, _), _ = layer.apply(params, x, state=state)
    routed_only = moe(shared=0)
    (routed, _), _ = routed_only.apply(
        {k: v for k, v in params.items() if not k.startswith("shared")}, x,
        state=state)
    g, u = jnp.split(x @ params["shared_gate_up"], 2, axis=-1)
    np.testing.assert_allclose(
        with_shared - routed, (jax.nn.silu(g) * u) @ params["shared_down"],
        atol=2e-5)


def test_eight_shares_and_the_shared_experts_once_are_the_uncut_layer(
        f32_policy):
    """Eight ranks of 2 of the 16 experts, the shared experts counted
    once, add up to the layer that holds all 16: the output, and the
    gradients of the input, the router, each expert's matrices (a rank's
    are the uncut layer's slice) and the shared experts'."""
    whole = moe()
    params, x, state = moe_inputs(whole)
    w = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def objective(layer, state):
        return lambda p, x: jnp.sum(layer.apply(p, x, state=state)[0][0] * w)

    want = jax.value_and_grad(objective(whole, state), (0, 1))(params, x)
    total, grads_x, grad_router = 0.0, 0.0, 0.0
    grads, rows = {}, []
    for rank in range(8):
        first = 2 * rank
        share = moe(experts_held=(first, 2), shared=24 if rank == 0 else 0)
        p = {"router": params["router"],
             **{k: params[k][first:first + 2] for k in ("gate", "up",
                                                        "down")}}
        if rank == 0:
            p.update({k: v for k, v in params.items()
                      if k.startswith("shared")})
        s = {"rows_routed": jnp.zeros((3,), jnp.int32),
             "selection_bias": state["selection_bias"]}
        value, (g, gx) = jax.value_and_grad(objective(share, s), (0, 1))(p, x)
        total, grads_x = total + value, grads_x + gx
        grad_router = grad_router + g["router"]
        for k in g:
            if k != "router":
                grads.setdefault(k, []).append(g[k])
        rows.append(share.apply(p, x, state=s)[1]["rows_routed"])
    np.testing.assert_allclose(total, want[0], rtol=1e-5)
    close(grads_x, want[1][1], 1e-5)
    close(grad_router, want[1][0]["router"], 1e-5)
    for k in ("gate", "up", "down"):
        close(jnp.concatenate(grads[k]), want[1][0][k], 1e-5)
    for k in ("shared_gate_up", "shared_down"):
        assert len(grads[k]) == 1
        close(grads[k][0], want[1][0][k], 1e-5)
    # every assignment is held by exactly one rank
    held = np.stack(rows)[:, :2]
    assert held.sum() == 48 * 3
    np.testing.assert_array_equal(
        held.reshape(16),
        whole.apply(params, x, state=state)[1]["rows_routed"][:16])


# ------------------------------------------------------ the decoder layer
def decoder_layer(recompute, sparse=True):
    Layer.reset_name_counters()
    ffn = moe() if sparse else GatedFeedForward(48)
    Layer.reset_name_counters()
    layer = LatentDecoderLayer(
        LatentAttention(2, 32, 128, 64, 128, rope_theta=1e6), ffn,
        recompute=recompute)
    shape = (None, 256, 32)
    params = layer.build(jax.random.PRNGKey(0), shape)
    state = layer.init_state(shape)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 32))

    def grads(params, x):
        return jax.grad(lambda p, x: jnp.sum(jnp.square(
            layer.apply(p, x, state=state)[0])), (0, 1))(params, x)
    return layer, grads, (params, x), state


@pytest.mark.parametrize("sparse", [True, False], ids=["sparse", "dense"])
def test_a_recomputed_layer_keeps_its_kernels_results(
        f32_policy, interpreted_kernels, pallas_calls, sparse):
    """The gradient of a recomputed layer holds the latent forward
    kernel once, as an unrecomputed layer's does, gives the same
    gradients, and moves ``train_recompute_kept_bytes`` by the output
    and the log-sum-exp of its two heads."""
    from analytics_zoo_tpu.observability import get_registry

    def kept():
        return {k: v for k, v in get_registry().snapshot()["gauges"].items()
                if k.startswith("train_recompute_kept_bytes")}

    before = kept()
    _, plain, args, _ = decoder_layer(False, sparse)
    once = pallas_calls(plain, *args)
    assert once["flash_attention_latent_fwd"] == 1
    assert once["flash_attention_latent_bwd"] == 1
    assert not {"flash_attention_latent_dq",
                "flash_attention_latent_dkv"} & set(once)
    assert kept() == before
    _, again, args, _ = decoder_layer(True, sparse)
    calls = pallas_calls(again, *args)
    assert {k: v for k, v in calls.items() if k.startswith("flash")} == \
        {k: v for k, v in once.items() if k.startswith("flash")}
    moved = {k: v - before.get(k, 0) for k, v in kept().items()}
    assert {k: v for k, v in moved.items() if v} == {
        'train_recompute_kept_bytes{name="flash_attention_out"}':
            256 * 2 * 128 * 4,
        'train_recompute_kept_bytes{name="flash_attention_lse"}':
            2 * 256 * 4}
    for a, b in zip(jax.tree.leaves(again(*args)),
                    jax.tree.leaves(plain(*args))):
        assert float(jnp.max(jnp.abs(b))) > 0
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_the_decoder_layer_carries_the_expert_layers_state(f32_policy):
    """``rows_routed`` and ``selection_bias`` are the decoder layer's
    state, so ``MoeStatsReader`` finds the layer by what its state
    holds; a dense layer has none and is not read."""
    from analytics_zoo_tpu.observability.moe_stats import MoeStatsReader
    layer, _, (params, x), state = decoder_layer(True)
    assert set(state) == {"rows_routed", "selection_bias"}
    _, new = layer.apply(params, x, state=state)
    assert int(new["rows_routed"].sum()) == 256 * 3
    dense, _, (dparams, _), dstate = decoder_layer(False, sparse=False)
    assert dstate == {} and dense.apply(dparams, x, state={})[1] == {}

    class Model:
        layers = [layer, dense]

    dense.name = "dense_layer"
    reader = MoeStatsReader(Model, {layer.name: state, dense.name: {}})
    assert reader.layers == [layer.name]
