"""The pre-routed decoder's parts (PR 37) against plain ``jnp`` at small
sizes: ReGLU experts routed from another input than their own, four
shares of them adding up to the uncut layer with the router counted
once; grouped-query attention with no position encoding and under a
window at groups of 7 query heads, on the kernels and on the lax path;
both long flash backward forms under the window at group 7; the decoder
layer whose router reads the attention's input."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import grouped_matmul as gmm
from analytics_zoo_tpu.ops import pallas_attention as kernels
from analytics_zoo_tpu.ops.pallas_attention import (
    allowed_pairs, flash_attention_token_major, sliding_window)
from analytics_zoo_tpu.pipeline.api.keras.engine import Layer
from analytics_zoo_tpu.pipeline.api.keras.layers import (
    DroplessMoE, GroupedQueryAttention, PreRoutedDecoderLayer)
from analytics_zoo_tpu.pipeline.api.keras.layers.normalization import (
    rms_norm)

E, TOP, D, H, B, T = 16, 3, 32, 24, 2, 40
HIGHEST = jax.lax.Precision.HIGHEST
KEYS = ("gate", "up", "down")


def counters(prefix):
    from analytics_zoo_tpu.observability import get_registry
    return {k[len(prefix):]: v for k, v in
            get_registry().snapshot()["counters"].items()
            if k.startswith(prefix)}


# ------------------------------------- ReGLU experts routed from elsewhere
@pytest.fixture
def weights():
    rs = np.random.RandomState(0)
    full = {"router": rs.randn(D, E).astype(np.float32),
            "gate": 0.3 * rs.randn(E, D, H).astype(np.float32),
            "up": 0.3 * rs.randn(E, D, H).astype(np.float32),
            "down": 0.3 * rs.randn(E, H, D).astype(np.float32)}
    a, m = (rs.randn(B, T, D).astype(np.float32) for _ in "am")
    return ({k: jnp.asarray(v) for k, v in full.items()}, jnp.asarray(a),
            jnp.asarray(m))


def reference(full, a, m, first, count):
    """The equations, plainly: the softmax router over all experts on
    ``a``, the 3 largest renormalised; ReGLU experts on ``m``, a masked
    dense sum over the experts held."""
    probs = jax.nn.softmax(a.reshape(-1, D) @ full["router"], -1)
    gates, picked = jax.lax.top_k(probs, TOP)
    gates = gates / gates.sum(-1, keepdims=True)
    mt = m.reshape(-1, D)
    y = jnp.zeros_like(mt)
    for e in range(first, first + count):
        w = jnp.sum(jnp.where(picked == e, gates, 0), -1)
        hidden = jax.nn.relu(mt @ full["gate"][e]) * (mt @ full["up"][e])
        y = y + w[:, None] * (hidden @ full["down"][e])
    return y.reshape(m.shape), picked


def held_layer(full, first, count):
    layer = DroplessMoE(E, H, top_k=TOP, experts_held=(first, count),
                        block_rows=8, activation="relu")
    variables = layer.init(jax.random.PRNGKey(0), (None, T, D))
    params = {"router": full["router"],
              **{k: full[k][first:first + count] for k in KEYS}}
    assert jax.tree_util.tree_map(jnp.shape, params) == \
        jax.tree_util.tree_map(jnp.shape, variables["params"])
    return layer, params, variables["state"]


def routed_apply(layer, params, state, a, m):
    routing = layer.route(params["router"], a, precision=HIGHEST)
    (y, aux), new_state = layer.apply(params, m, state=state,
                                      routing=routing)
    return y, new_state


@pytest.fixture(params=["pallas", "lax"])
def path(request, monkeypatch):
    if request.param == "pallas":
        monkeypatch.setattr(gmm, "grouped_matmul", functools.partial(
            gmm.grouped_matmul, interpret=True))
    return request.param


def test_reglu_experts_routed_from_another_input(f32_policy, weights, path):
    """The experts ``a`` selects, with ``a``'s gates, applied to ``m``;
    the rows counted are ``a``'s picks; the trace counts a ReGLU
    build."""
    full, a, m = weights
    layer, params, state = held_layer(full, 4, 8)
    before = counters("fused_kernel_builds_total")
    y, new_state = routed_apply(layer, params, state, a, m)
    want, picked = reference(full, a, m, 4, 8)
    np.testing.assert_allclose(y, want, atol=2e-5)
    rows = np.bincount(np.asarray(picked).ravel(), minlength=E)
    counted = np.asarray(new_state["rows_routed"])
    assert np.array_equal(counted[:-1], rows[4:12])
    assert counted[-1] == rows.sum() - rows[4:12].sum()
    moved = counters("fused_kernel_builds_total")
    key = '{kernel="moe_experts",path="reglu"}'
    assert moved[key] == before.get(key, 0) + 1
    # routed from its own input it is another layer's output
    (own, _), _ = layer.apply(params, m, state=state)
    np.testing.assert_allclose(own, reference(full, m, m, 4, 8)[0],
                               atol=2e-5)
    assert float(jnp.max(jnp.abs(own - y))) > 1e-2


def test_gradients_match_the_reference(f32_policy, weights, path):
    """The router's gradient comes through ``a``'s gates, the experts'
    through ``m``'s rows; both inputs get theirs."""
    full, a, m = weights
    layer, params, state = held_layer(full, 8, 8)

    def loss(p, a, m):
        return jnp.sum(jnp.square(routed_apply(layer, p, state, a, m)[0]))

    def want(p, a, m):
        merged = {"router": p["router"],
                  **{k: full[k].at[8:16].set(p[k]) for k in KEYS}}
        return jnp.sum(jnp.square(reference(merged, a, m, 8, 8)[0]))

    got = jax.grad(loss, (0, 1, 2))(params, a, m)
    ref = jax.grad(want, (0, 1, 2))(params, a, m)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        assert float(jnp.max(jnp.abs(r))) > 0
        np.testing.assert_allclose(g, r, atol=1e-3 * float(
            jnp.max(jnp.abs(r))))


def test_four_shares_and_the_router_once_are_the_uncut_layer(
        f32_policy, weights):
    """Four ranks of 4 of the 16 experts: their outputs add up to the
    layer that holds all 16, and so do the gradients of both inputs and
    of the router (each rank's router is the same replicated leaf);
    the experts' gradients are the uncut layer's slices, and every
    assignment is held by exactly one rank."""
    full, a, m = weights
    whole, wparams, wstate = held_layer(full, 0, E)
    w = jax.random.normal(jax.random.PRNGKey(3), m.shape)

    def objective(layer, state):
        return lambda p, a, m: jnp.sum(
            routed_apply(layer, p, state, a, m)[0] * w)

    value, (gp, ga, gm) = jax.value_and_grad(
        objective(whole, wstate), (0, 1, 2))(wparams, a, m)
    total, ga_sum, gm_sum, router, rows = 0.0, 0.0, 0.0, 0.0, []
    experts = {k: [] for k in KEYS}
    for first in range(0, E, 4):
        layer, params, state = held_layer(full, first, 4)
        v, (p, ga_r, gm_r) = jax.value_and_grad(
            objective(layer, state), (0, 1, 2))(params, a, m)
        total, ga_sum, gm_sum = total + v, ga_sum + ga_r, gm_sum + gm_r
        router = router + p["router"]
        for k in KEYS:
            experts[k].append(p[k])
        rows.append(routed_apply(layer, params, state, a, m)[1][
            "rows_routed"][:-1])
    np.testing.assert_allclose(total, value, rtol=1e-5)
    for got, want in ((ga_sum, ga), (gm_sum, gm), (router, gp["router"])):
        np.testing.assert_allclose(got, want, rtol=1e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(want))))
    for k in KEYS:
        np.testing.assert_allclose(jnp.concatenate(experts[k]), gp[k],
                                   rtol=1e-4, atol=1e-6)
    held = np.concatenate(rows)
    assert held.sum() == B * T * TOP
    np.testing.assert_array_equal(
        held, routed_apply(whole, wparams, wstate, a, m)[1][
            "rows_routed"][:-1])


def test_the_softmax_router_can_take_float32_at_the_highest_precision(
        weights):
    """Under the default bfloat16 policy the softmax router rounds its
    operands; ``route(..., precision=HIGHEST)`` keeps them: its gates
    are the float64 formula's to float32 rounding."""
    full, a, _ = weights
    exact, params, _ = held_layer(full, 0, E)
    gates, experts, _ = exact.route(params["router"], a,
                                    precision=HIGHEST)
    logits = np.asarray(a, np.float64).reshape(-1, D) \
        @ np.asarray(full["router"], np.float64)
    probs = np.exp(logits - logits.max(1, keepdims=True))
    probs /= probs.sum(1, keepdims=True)
    order = np.argsort(-probs, axis=1)[:, :TOP]
    assert np.array_equal(np.asarray(experts), order)
    picked = np.take_along_axis(probs, order, axis=1)
    np.testing.assert_allclose(
        gates, picked / picked.sum(1, keepdims=True), rtol=2e-5)
    rounded = DroplessMoE(E, H, top_k=TOP, block_rows=8)
    rough, _, _ = rounded.route(params["router"], a)
    assert float(jnp.max(jnp.abs(rough - gates))) > 1e-4


def test_bad_activation_is_refused():
    with pytest.raises(ValueError, match="silu or relu"):
        DroplessMoE(E, H, activation="gelu")


# ----------------------------------------------- attention at groups of 7
ATTN = {"nope-window": (None, sliding_window(100)),
        "rotary-window": (1e6, sliding_window(100)),
        "nope-causal": (None, "causal")}


def attention_dense(params, x, rope, mask, h=7, h_kv=1, d=128):
    """Grouped-query attention written out: no bias, no q/k norm,
    rotate-half rotary at positions 0 .. T-1 or nothing, query head
    ``i`` on K/V head ``i // 7``, the mask dense."""
    b, t, _ = x.shape
    q = (x @ params["q_kernel"]).reshape(b, t, h, d)
    k = (x @ params["k_kernel"]).reshape(b, t, h_kv, d)
    v = (x @ params["v_kernel"]).reshape(b, t, h_kv, d)
    if rope is not None:
        half = d // 2
        angles = np.arange(t)[:, None] * rope ** (-np.arange(half) / half)
        cos, sin = (jnp.asarray(f(angles), jnp.float32)[:, None, :]
                    for f in (np.cos, np.sin))

        def rotate(y):
            y1, y2 = y[..., :half], y[..., half:]
            return jnp.concatenate([y1 * cos - y2 * sin,
                                    y2 * cos + y1 * sin], -1)
        q, k = rotate(q), rotate(k)
    k, v = (jnp.repeat(a, h // h_kv, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    s = jnp.where(jnp.asarray(allowed_pairs(mask, t)), s, -1e30)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return ctx.reshape(b, t, h * d) @ params["o_kernel"]


@pytest.fixture(params=["kernels", "lax"])
def attention_path(request):
    if request.param == "kernels":
        request.getfixturevalue("interpreted_kernels")
    return request.param


@pytest.mark.parametrize("case", sorted(ATTN))
def test_attention_with_no_positions_and_the_window_at_group_7(
        f32_policy, attention_path, case):
    """7 query heads of 128 on one K/V head under the window or the
    causal mask, with rotary or none, forward and gradients, on the
    flash kernels (interpreted) and on the lax path."""
    rope, mask = ATTN[case]
    t = 256
    layer = GroupedQueryAttention(7, 1, 128, rope_theta=rope, qk_norm=False,
                                  mask=mask)
    params = layer.build(jax.random.PRNGKey(0), [(None, t, 32), (None, t)])
    assert set(params) == {"q_kernel", "k_kernel", "v_kernel", "o_kernel"}
    x = jax.random.normal(jax.random.PRNGKey(1), (1, t, 32))
    w = jax.random.normal(jax.random.PRNGKey(2), (1, t, 32))
    positions = jnp.arange(t, dtype=jnp.int32)[None]
    before = counters("fused_kernel_builds_total")

    def got(p, x):
        return jnp.sum(w * layer.call(p, [x, positions]))

    def want(p, x):
        return jnp.sum(w * attention_dense(p, x, rope, mask))

    np.testing.assert_allclose(got(params, x), want(params, x), rtol=1e-4)
    for g, r in zip(jax.tree_util.tree_leaves(jax.grad(got, (0, 1))(
            params, x)), jax.tree_util.tree_leaves(jax.grad(want, (0, 1))(
                params, x))):
        np.testing.assert_allclose(g, r, rtol=1e-3,
                                   atol=1e-4 * float(jnp.max(jnp.abs(r))))
    moved = counters("fused_kernel_builds_total")
    form = "pallas" if attention_path == "kernels" else "lax"
    key = '{kernel="flash_attention",path="%s"}' % form
    assert moved[key] > before.get(key, 0)


def test_no_rotary_reads_no_position():
    """With no position encoding the layer's output does not depend on
    the position ids it is handed; with rotary it does."""
    t = 256
    shapes = [(None, t, 32), (None, t)]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, t, 32))
    zero, ramp = (jnp.zeros((1, t), jnp.int32),
                  jnp.arange(t, dtype=jnp.int32)[None])
    for rope, same in ((None, True), (1e6, False)):
        layer = GroupedQueryAttention(7, 1, 128, rope_theta=rope,
                                      qk_norm=False, mask="causal")
        params = layer.build(jax.random.PRNGKey(0), shapes)
        a, b = (layer.call(params, [x, p]) for p in (zero, ramp))
        assert bool(jnp.allclose(a, b)) == same


@pytest.mark.parametrize(
    "mask,form", [("window", "two_pass"), ("causal", "two_pass"),
                  ("window", "one_pass_heads"), ("causal", "one_pass_heads")],
    ids=["window", "causal", "window-one_pass_heads",
         "causal-one_pass_heads"])
def test_two_pass_backward_at_group_7(monkeypatch, mask, form):
    """The forms past the one-pass budget, reached at 512 positions: the
    one 16,384 positions take (dq alone resident, dk and dv a query head
    summed over the group), with a budget a query tile's whole dq fits,
    and ``_dq`` + ``_dkv``, with a budget nothing fits; under the window
    (and the causal mask) with 14 query heads on 2 K/V heads, against
    dense attention's gradients."""
    monkeypatch.setattr(kernels, "_RESIDENT_VMEM",
                        512 * 128 * 12 if form == "one_pass_heads" else 0)
    what = sliding_window(200) if mask == "window" else "causal"
    t, h, h_kv, d = 512, 14, 2, 128
    rs = np.random.RandomState(5)
    q, k, v, w = (jnp.asarray(rs.randn(1, t, n * d) * 0.5, jnp.float32)
                  for n in (h, h_kv, h_kv, h))
    allowed = jnp.asarray(allowed_pairs(what, t))

    def flash(q, k, v):
        kw = dict(causal=True) if what == "causal" else dict(mask=what)
        return jnp.sum(w * flash_attention_token_major(
            q, k, v, n_head=h, block_q=128, block_k=128, interpret=True,
            **kw))

    def dense(q, k, v):
        qh = q.reshape(1, t, h, d)
        kh, vh = (jnp.repeat(a.reshape(1, t, h_kv, d), h // h_kv, axis=2)
                  for a in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(allowed, s, -1e30), -1)
        return jnp.sum(w * jnp.einsum("bhqk,bkhd->bqhd", p, vh).reshape(
            1, t, h * d))

    key = '{kernel="flash_attention_backward",path="%s"}' % form
    before = counters("fused_kernel_builds_total").get(key, 0)
    got = jax.grad(flash, (0, 1, 2))(q, k, v)
    assert counters("fused_kernel_builds_total")[key] == before + 1
    for name, g, r in zip("qkv", got, jax.grad(dense, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=2e-5, err_msg=name)


# ------------------------------------------------------ the decoder layer
def decoder_layer(recompute=False, t=256):
    Layer.reset_name_counters()
    layer = PreRoutedDecoderLayer(
        GroupedQueryAttention(7, 1, 128, rope_theta=1e6, qk_norm=False,
                              mask=sliding_window(100)),
        DroplessMoE(E, H, top_k=TOP, experts_held=(4, 8), block_rows=8,
                    init="normal", activation="relu"),
        recompute=recompute)
    shape = (None, t, D)
    params = layer.build(jax.random.PRNGKey(0), shape)
    state = layer.init_state(shape)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, t, D))
    return layer, params, state, x


def spy_routing(layer, monkeypatch):
    """The routing each call of the layer's expert layer is handed."""
    seen, apply = [], layer.ffn.apply

    def recording(*args, routing=None, **kwargs):
        seen.append(routing)
        return apply(*args, routing=routing, **kwargs)
    monkeypatch.setattr(layer.ffn, "apply", recording)
    return seen


def test_the_router_reads_the_attention_input(f32_policy, monkeypatch):
    """The routing is ``route(RMSNorm(h; g1))``: a change of what only
    the expert layer's norm sees (``g2``) changes the output and leaves
    the selection and the counts as they were; a change of ``g1``
    changes them."""
    layer, params, state, x = decoder_layer()
    seen = spy_routing(layer, monkeypatch)
    out, new = layer.apply(params, x, state=state)
    a = rms_norm(x, params["ln1_gamma"], 1e-6)
    gates, experts, _ = layer.ffn.route(params["ffn_router"], a,
                                        precision=HIGHEST)
    np.testing.assert_array_equal(seen[0][1], experts)
    np.testing.assert_allclose(seen[0][0], gates, rtol=1e-6)
    params_g2 = dict(params, ln2_gamma=params["ln2_gamma"] * jnp.linspace(
        0.2, 3.0, D))
    out_g2, new_g2 = layer.apply(params_g2, x, state=state)
    np.testing.assert_array_equal(seen[1][1], experts)
    np.testing.assert_array_equal(new_g2["rows_routed"],
                                  new["rows_routed"])
    assert float(jnp.max(jnp.abs(out_g2 - out))) > 1e-3
    params_g1 = dict(params, ln1_gamma=params["ln1_gamma"] * jnp.linspace(
        0.2, 3.0, D))
    layer.apply(params_g1, x, state=state)
    assert not np.array_equal(seen[2][1], experts)


def test_a_recomputed_layer_keeps_its_kernels_results(
        f32_policy, interpreted_kernels, pallas_calls):
    """On the kernels the recomputed layer's gradient holds the forward
    flash kernel once, as the plain layer's does, and gives the same
    gradients; the trace counts the router on the attention input and
    the ReGLU experts once a layer traced."""
    plain, params, state, x = decoder_layer(False)

    def grads(layer):
        return lambda p, x: jax.grad(lambda p, x: jnp.sum(jnp.square(
            layer.apply(p, x, state=state)[0])), (0, 1))(p, x)

    before = counters("fused_kernel_builds_total")
    once = pallas_calls(grads(plain), params, x)
    moved = counters("fused_kernel_builds_total")
    for key in ('{kernel="moe_router",path="attention_input"}',
                '{kernel="moe_experts",path="reglu"}'):
        assert moved[key] == before.get(key, 0) + 1
    assert once["flash_attention_fwd"] == 1
    again, _, _, _ = decoder_layer(True)
    calls = pallas_calls(grads(again), params, x)
    assert {k: v for k, v in calls.items() if k.startswith("flash")} == \
        {k: v for k, v in once.items() if k.startswith("flash")}
    for g, r in zip(jax.tree.leaves(grads(again)(params, x)),
                    jax.tree.leaves(grads(plain)(params, x))):
        assert float(jnp.max(jnp.abs(r))) > 0
        np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-6)


def test_the_decoder_follows_the_published_layout():
    """Layer ``l`` is global with no positions where both layouts read 0
    and windowed with rotary where they read 1; every layer carries its
    expert layer's counts and routes from its attention input."""
    from analytics_zoo_tpu.pipeline.api.keras.layers.prerouted import (
        prerouted_moe_decoder)
    layout = [0, 1, 1, 1] * 2
    model = prerouted_moe_decoder(
        seq_len=256, vocab_size=400, vocab_held=(100, 100), hidden_size=D,
        num_layers=4, n_head=7, n_kv_head=1, head_dim=128, num_experts=E,
        experts_held=(0, 4), top_k=TOP, expert_hidden=H,
        sliding_window=100, sliding_window_layout=layout,
        rope_layout=layout, rope_theta=1.5e6)
    layers = [l for l in model.layers
              if isinstance(l, PreRoutedDecoderLayer)]
    kinds = [(l.attention.mask, l.attention.rope_theta) for l in layers]
    assert kinds == [("causal", None)] + 3 * [(sliding_window(100), 1.5e6)]
    assert all(l.ffn.activation == "relu" and not l.attention.qk_norm
               for l in layers)
    state = model.get_variables()["state"]
    assert [l.name for l in layers] == [n for n in state
                                        if "rows_routed" in state[n]]


# --------------------------------- the windowed cell's program, at toy size
def test_the_cell_in_the_backward_form_16k_positions_take(
        interpreted_kernels, f32_policy, monkeypatch):
    """The benchmark's windowed cell (``run_cell``) at 768 positions, with
    a budget that a query tile's whole dq fits and the three resident
    arrays do not: every layer's backward takes the form the cell's 16,384
    positions take (dq alone resident, dk and dv a query head), and the
    program's first epoch agrees with the float32 reference to rounding."""
    from analytics_zoo_tpu.observability import get_registry
    from benchmark import harness
    monkeypatch.setattr(kernels, "_RESIDENT_VMEM", 768 * 128 * 12)
    toy = dict(seq_len=768, num_hidden_layers=2, hidden_size=64,
               num_attention_heads=7, num_key_value_heads=1,
               moe_num_primary_experts_published=16, experts_held=[4, 4],
               moe_num_primary_experts=4, moe_num_active_primary_experts=3,
               moe_ffn_hidden_size=32, vocab_size=97, vocab_held=[0, 97],
               vocab_size_published=388, sliding_window_size=128,
               initializer_range=0.2,
               recompute=dict(decoder_layers=True, loss_chunk_rows=256))
    before = get_registry().snapshot()
    line = harness.run_cell(
        "smallthinker-21ba3b-instruct.sft_16k_hbm", 2 ** 31 + 13, 1.0, False,
        require_chip=False, cfg_override=toy,
        cell_override=dict(rows=4, limits={"loss": 1e-5, "grad": 1e-3,
                                           "dparam": 1e-3}))
    assert line["correct"], line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    built = harness.counter_delta(get_registry().snapshot(), before,
                                  "fused_kernel_builds_total")
    assert built.get('{kernel="flash_attention",path="pallas"}')
    assert built.get(
        '{kernel="flash_attention_backward",path="one_pass_heads"}')
    assert not {'{kernel="flash_attention_backward",path="%s"}' % form
                for form in ("one_pass", "two_pass")} & set(built)
