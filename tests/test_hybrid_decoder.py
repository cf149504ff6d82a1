"""The decoder-hybrid-decoder's layers against the plain reference of
the ``phi-4-mini-flash-reasoning`` configuration (``benchmark/reference``)
at a small size on the CPU, seeded weights, loss AND gradients: each
kind of layer with the layer that writes what it reads, then the whole
six; the window mask against the tile tables of all three flash kernels;
the differential pair on 64-wide heads over fewer K/V heads against
dense attention; the shared K/V's cotangent as the sum over its readers;
and the vocabulary share tied to the model."""

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

from analytics_zoo_tpu.ops import (  # noqa: E402
    fused, pallas_attention, selective_scan)
from analytics_zoo_tpu.ops.pallas_attention import (  # noqa: E402
    _PARTIAL, _tile_pairs, allowed_pairs, flash_attention_token_major,
    sliding_window)
from analytics_zoo_tpu.pipeline.api.keras.engine import Layer  # noqa: E402
from analytics_zoo_tpu.pipeline.api.keras.layers import ssm  # noqa: E402

CONFIG = "phi-4-mini-flash-reasoning"
TOY = dict(seq_len=96, hidden_size=64, intermediate_size=96,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           mamba=dict(d_inner=128, d_state=4, d_conv=4, dt_rank=4),
           sliding_window=24, vocab_size=97, vocab_held=[0, 97],
           vocab_size_published=776, initializer_range=0.2,
           recompute=dict(decoder_layers=True, loss_chunk_rows=32))
# the policy of a recomputed layer
KEEP_KERNEL_RESULTS = jax.checkpoint_policies.save_only_these_names(
    *pallas_attention.KEPT_RESULTS, *selective_scan.KEPT_RESULTS)
# a layer of each kind, with the layer that writes what it reads
LAYERS = {"mamba": [0], "window_attention": [1],
          "mamba_memory+memory_unit": [16, 18],
          "full_attention+cross_attention": [17, 19],
          "all_six": [0, 1, 16, 17, 18, 19]}


@pytest.fixture(scope="module")
def reference():
    return harness.load_module("reference", CONFIG)


def toy(layer_ids, **over):
    cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                         CONFIG + ".json"))
    return {**cfg, **TOY, "layer_ids_published": list(layer_ids),
            "num_hidden_layers": len(layer_ids), **over}


def build(cfg):
    Layer.reset_name_counters()
    model = harness.load_module("configs", CONFIG).build(cfg)
    return model, model.get_variables()


def program_loss_and_grads(cfg, reference, seed, ids):
    model, variables = build(cfg)
    order = reference.param_order(cfg)
    start = reference.init(cfg, seed)
    params = harness.to_program(order, start, variables["params"])

    def loss(params):
        out, _ = model.apply(params, [ids, ids], state=variables["state"],
                             training=True)
        return jnp.mean(out)

    value, grads = jax.value_and_grad(loss)(params)
    slots = harness.program_leaves(variables["params"])
    return value, harness.from_program(order, slots, grads), start


def token_ids(cfg, n, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, cfg["vocab_size"], size=(n, cfg["seq_len"])), jnp.int32)


@pytest.mark.parametrize("kinds", sorted(LAYERS))
def test_layers_match_the_reference(f32_policy, reference, kinds):
    cfg = toy(LAYERS[kinds])
    ids = token_ids(cfg, 2)
    value, grads, start = program_loss_and_grads(cfg, reference, 7, ids)

    def ref_loss(p):
        return sum(reference.sequence_loss(cfg, p, row) for row in ids) / 2

    want, want_grads = jax.value_and_grad(ref_loss)(start)
    np.testing.assert_allclose(value, want, rtol=1e-5)
    assert set(grads) == set(want_grads)
    for name, g in want_grads.items():
        scale = float(jnp.max(jnp.abs(g)))
        assert scale > 0 or "in_bias" in name, name
        np.testing.assert_allclose(grads[name], g, rtol=2e-3,
                                   atol=2e-4 * scale + 1e-9, err_msg=name)


def test_layer_kinds_by_depth():
    kinds = [ssm.hybrid_layer_kind(i, 32) for i in range(32)]
    assert kinds[:4] == ["mamba", "window_attention"] * 2
    assert kinds[14:20] == ["mamba", "window_attention", "mamba_memory",
                            "full_attention", "memory_unit",
                            "cross_attention"]
    assert kinds.count("mamba") + 1 == 9 and kinds.count("memory_unit") == 7
    assert kinds.count("window_attention") == 8
    assert kinds.count("cross_attention") == 7
    assert ssm.differential_lambda_init(17) == pytest.approx(
        0.8 - 0.6 * np.exp(-5.1))
    with pytest.raises(ValueError, match="reads what no layer"):
        build(toy([0, 18]))


# ------------------------------------------------- the window mask's tiles
@pytest.mark.parametrize("window,t,block", [(512, 8192, 256), (512, 2048, 512),
                                            (100, 1024, 128), (1, 256, 128),
                                            (300, 256, 128)])
def test_window_tile_tables_hold_the_allowed_pairs(window, t, block):
    """``allowed_pairs(sliding_window(W), t)`` against the tables all
    three kernels walk: the forward and dq kernels' (by q tile) and the
    dkv kernel's (by k tile) hold exactly the tiles with an allowed
    pair, and a tile is walked without the mask's arithmetic only where
    every pair of it is allowed."""
    mask = sliding_window(window)
    allowed = allowed_pairs(mask, t)
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    assert np.array_equal(allowed, (j <= i) & (j > i - window))
    n = t // block
    tiles = allowed.reshape(n, block, n, block)
    some, whole = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    by_q, by_k = _tile_pairs(mask, t, block, block)
    for walk in (by_q, by_k):
        qi, ki, flags = walk
        assert sorted(zip(qi, ki)) == sorted(zip(*np.nonzero(some)))
        partial = (flags & _PARTIAL) != 0
        assert np.array_equal(~partial, whole[qi, ki])
    # by q tile in the forward and dq kernels, by k tile in dkv
    assert list(by_q[0]) == sorted(by_q[0])
    assert list(by_k[1]) == sorted(by_k[1])


def test_the_cells_band_walks_a_sixth_of_the_causal_tiles():
    from analytics_zoo_tpu.observability import get_registry
    _tile_pairs.cache_clear()
    by_q, _ = _tile_pairs(sliding_window(512), 8192, 256, 256)
    assert len(by_q[0]) == 93
    gauges = get_registry().snapshot()["gauges"]
    key = 'flash_attention_tiles{mask="sliding_window",which="%s"}'
    assert gauges[key % "walked"] == 93 and gauges[key % "causal"] == 528


# ------------------------------ the differential pair on the flash kernels
def dense_pair(q, k, v, h, h_kv, mask):
    """Every head's map over its pair's V, one pair and one map at a
    time: the layer's docstring, written out."""
    b, t, _ = q.shape
    d = q.shape[-1] // h
    q = q.reshape(b, t, h // 2, 2, d)
    k = k.reshape(b, t, h_kv // 2, 2, d)
    v = v.reshape(b, t, h_kv // 2, 2 * d)
    group = (h // 2) // (h_kv // 2)
    ok = jnp.asarray(allowed_pairs(mask, t))
    outs = []
    for j in range(h // 2):
        for r in range(2):
            s = jnp.einsum("btd,bsd->bts", q[:, :, j, r],
                           k[:, :, j // group, r]) / np.sqrt(d)
            p = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
            outs.append(jnp.einsum("bts,bse->bte", p, v[:, :, j // group]))
    return jnp.concatenate(outs, axis=-1)


def pair_inputs(b, t, h, h_kv, d, seed=1):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (b, t, h * d)),
            jax.random.normal(k[1], (b, t, h_kv * d)),
            jax.random.normal(k[2], (b, t, h_kv * d)),
            jax.random.normal(k[3], (b, t, 2 * h * d)))


MASKS = {"causal": "causal", "window-100": sliding_window(100),
         "window-128": sliding_window(128)}


@pytest.mark.parametrize("fused", [False, True], ids=["q-k-v", "one-array"])
@pytest.mark.parametrize("mask", sorted(MASKS))
def test_64_wide_heads_on_grouped_kv_match_dense(mask, fused):
    """8 query heads of 64 on 4 K/V heads: two pairs of query heads to a
    K/V pair, two heads to a 128-lane tile, both maps over the pair's
    128-wide V; forward and all three gradients, K/V as operands of
    their own or q, k and v side by side in the projection's result."""
    h, h_kv, d, t = 8, 4, 64, 384
    mask = MASKS[mask]
    q, k, v, w = pair_inputs(2, t, h, h_kv, d)
    kw = dict(causal=True) if mask == "causal" else dict(mask=mask)

    def flash(q, k, v):
        ops = (jnp.concatenate([q, k, v], -1),) if fused else (q, k, v)
        return flash_attention_token_major(
            *ops, n_head=h, n_kv_head=h_kv, differential=True,
            interpret=True, block_q=128, block_k=128, **kw)

    want = dense_pair(q, k, v, h, h_kv, mask)
    np.testing.assert_allclose(flash(q, k, v), want, rtol=1e-4, atol=1e-5)
    got_g = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want_g = jax.grad(lambda *a: jnp.sum(dense_pair(*a, h, h_kv, mask) * w),
                      (0, 1, 2))(q, k, v)
    for got, ref in zip(got_g, want_g):
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=2e-5)


def test_the_pair_needs_heads_that_fill_a_tile():
    from analytics_zoo_tpu.ops.pallas_attention import _heads_per_tile
    assert _heads_per_tile(40, 20, 64, differential=True) == 2
    assert _heads_per_tile(40, 20, 128, differential=True) == 0
    assert _heads_per_tile(6, 3, 64, differential=True) == 0
    with pytest.raises(ValueError, match="differential pair"):
        flash_attention_token_major(
            *pair_inputs(1, 128, 4, 2, 32)[:3], n_head=4, differential=True,
            interpret=True)


@pytest.mark.parametrize("recompute", [False, True],
                         ids=["kept", "recomputed"])
def test_shared_kv_cotangent_is_the_sum_over_its_readers(recompute):
    """Two cross-attention readers of one K/V (each with its own
    queries): the cotangent that reaches K and V is the sum of what each
    reader alone sends back, also when each reader is recomputed under
    the policy that keeps the kernels' results."""
    h, h_kv, d, t = 4, 2, 64, 256
    q1, k, v, w1 = pair_inputs(1, t, h, h_kv, d, seed=2)
    q2, _, _, w2 = pair_inputs(1, t, h, h_kv, d, seed=3)

    def reader(q, w):
        def read(k, v):
            return jnp.sum(w * flash_attention_token_major(
                q, k, v, n_head=h, differential=True, causal=True,
                interpret=True))
        return jax.checkpoint(read, policy=KEEP_KERNEL_RESULTS) \
            if recompute else read

    both = jax.grad(lambda k, v: reader(q1, w1)(k, v) + reader(q2, w2)(k, v),
                    (0, 1))(k, v)
    first = jax.grad(reader(q1, w1), (0, 1))(k, v)
    second = jax.grad(reader(q2, w2), (0, 1))(k, v)
    for total, a, b in zip(both, first, second):
        np.testing.assert_allclose(total, a + b, rtol=1e-5, atol=1e-5)
        assert float(jnp.max(jnp.abs(a))) > 0 < float(jnp.max(jnp.abs(b)))


def test_the_models_kv_and_memory_reach_their_readers(f32_policy, reference):
    """In the model the writers' gradients hold every reader's share:
    with the cross-decoder's layers taken out, layer 17's K/V projection
    and layer 16's scan get a different gradient."""
    ids = token_ids(toy([0]), 1)
    with_readers = program_loss_and_grads(
        toy([16, 17, 18, 19]), reference, 3, ids)[1]
    without = program_loss_and_grads(toy([16, 17]), reference, 3, ids)[1]
    for leaf in ("l17.attn.in", "l16.mamba.x"):
        assert not np.allclose(with_readers[leaf], without[leaf], rtol=1e-3)


# ------------------------------------- the vocabulary share, tied to the model
def test_eight_vocabulary_slices_lay_out_the_uncut_logits(f32_policy,
                                                         reference):
    """One decoder layer over a vocabulary of 776 ids, uncut in the
    reference; eight ranks of the program each hold 97 rows of the
    embedding and tied head.  Their lookups add up to the uncut one,
    their logits laid side by side are the uncut reference's, and a
    rank's loss over its slice is the reference's over that slice."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import Embedding
    whole = toy([1], vocab_size=776, vocab_held=[0, 776])
    full = reference.init(whole, 11)
    ids = token_ids(whole, 1, seed=5)
    h_ref = reference.forward(whole, full, ids[0])
    logits_ref = h_ref @ full["embed"].T

    lookups, logits = 0, []
    for rank in range(8):
        first = 97 * rank
        emb = Embedding(776, whole["hidden_size"], vocab_held=(first, 97),
                        tie_head=True)
        vectors, table = emb.call(
            {"embeddings": full["embed"][first:first + 97]}, ids)
        lookups = lookups + vectors
        logits.append(h_ref @ table.T)
    np.testing.assert_allclose(lookups[0], full["embed"][ids[0]])
    np.testing.assert_allclose(jnp.concatenate(logits, -1), logits_ref,
                               rtol=1e-6)

    # rank 3's model over ids drawn from its slice
    cut = toy([1], vocab_held=[291, 97])
    local = ids % 97 + 291
    params = {k: (v[291:388] if k == "embed" else v) for k, v in full.items()}
    model, variables = build(cut)
    order = reference.param_order(cut)
    out, _ = model.apply(
        harness.to_program(order, params, variables["params"]),
        [local, local], state=variables["state"], training=True)
    np.testing.assert_allclose(
        out[0], reference.sequence_loss(cut, params, local[0]), rtol=1e-5)


def test_tied_tables_gradient_is_the_sum_of_its_two_uses(f32_policy):
    """The embedding's table is looked up at the bottom and multiplied
    at the top: its gradient is what each use alone would give, added."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import Embedding
    emb = Embedding(40, 8, tie_head=True)
    loss_layer = ssm.NextTokenLoss(chunk_rows=4)
    rs = np.random.RandomState(0)
    table = jnp.asarray(rs.randn(40, 8), jnp.float32)
    mix = jnp.asarray(rs.randn(8, 8), jnp.float32)
    ids = jnp.asarray(rs.randint(0, 40, size=(2, 16)), jnp.int32)

    def loss(lookup_table, head_table):
        vectors, _ = emb.call({"embeddings": lookup_table}, ids)
        return jnp.mean(loss_layer.call({}, [vectors @ mix, ids, head_table]))

    tied = jax.grad(lambda t: loss(t, t))(table)
    g_lookup, g_head = jax.grad(loss, (0, 1))(table, table)
    np.testing.assert_allclose(tied, g_lookup + g_head, rtol=1e-5, atol=1e-7)
    assert float(jnp.max(jnp.abs(g_lookup))) > 0
    assert float(jnp.max(jnp.abs(g_head))) > 0


def test_chunked_loss_is_the_whole_loss(f32_policy):
    rs = np.random.RandomState(1)
    h = jnp.asarray(rs.randn(2, 16, 8), jnp.float32)
    table = jnp.asarray(rs.randn(40, 8), jnp.float32)
    ids = jnp.asarray(rs.randint(10, 50, size=(2, 16)), jnp.int32)
    whole = ssm.NextTokenLoss(vocab_first=10).call({}, [h, ids, table])
    chunked = ssm.NextTokenLoss(vocab_first=10, chunk_rows=4).call(
        {}, [h, ids, table])
    lsm = jax.nn.log_softmax(h @ table.T, axis=-1)
    want = -jnp.mean(jnp.take_along_axis(
        lsm[:, :-1], (ids[:, 1:] - 10)[..., None], axis=-1)[..., 0], axis=-1)
    np.testing.assert_allclose(whole, want, rtol=1e-5)
    np.testing.assert_allclose(chunked, want, rtol=1e-5)


def test_recomputed_layers_give_the_same_gradients(f32_policy, reference):
    ids = token_ids(toy([0]), 1)
    kept = program_loss_and_grads(
        toy([0, 1], recompute=dict(decoder_layers=False, loss_chunk_rows=0)),
        reference, 5, ids)
    again = program_loss_and_grads(toy([0, 1]), reference, 5, ids)
    np.testing.assert_allclose(kept[0], again[0], rtol=1e-6)
    for name in kept[1]:
        np.testing.assert_allclose(kept[1][name], again[1][name], rtol=1e-4,
                                   atol=1e-7, err_msg=name)


# ------------------------- a recomputed layer keeps what its kernels wrote
# (mixer, what the layer reads besides the stream, its forward kernel,
# bytes kept by name at the toy's sizes: 256 positions, 4 heads in pairs
# on 2 K/V heads of 64, 1,024 channels of 4 states, float32)
def kernel_layers():
    attention = dict(n_head=4, n_kv_head=2, head_dim=64)
    maps = {"flash_attention_out": 256 * 4 * 128 * 4,
            "flash_attention_lse": 4 * 256 * 4}
    return {
        "mamba": (ssm.Mamba(1024, 4, 4, 4), [], "selective_scan_fwd",
                  {"selective_scan_y": 256 * 1024 * 4,
                   "selective_scan_starts": 4 * 4 * 1024 * 4,
                   "selective_scan_last": 4 * 1024 * 4}),
        "window_attention": (ssm.DifferentialAttention(
            layer_index=1, mask=sliding_window(100), **attention), [],
            "flash_attention_fwd", maps),
        "cross_attention": (ssm.DifferentialAttention(
            layer_index=19, cross=True, **attention),
            [(1, 256, 128)] * 2, "flash_attention_fwd", maps),
    }


def layer_gradients(kind, recompute):
    """-> (the gradient function of a toy layer of ``kind``, its
    arguments, its forward kernel, the bytes it keeps by name)."""
    Layer.reset_name_counters()
    mixer, reads, kernel, kept = kernel_layers()[kind]
    layer = ssm.HybridDecoderLayer(mixer, ssm.GatedFeedForward(96),
                                   recompute=recompute)
    stream = (1, 256, 64)
    params = layer.build(jax.random.PRNGKey(0),
                         [stream, *reads] if reads else stream)
    keys = jax.random.split(jax.random.PRNGKey(1), 1 + len(reads))
    inputs = [jax.random.normal(k, shape)
              for k, shape in zip(keys, [stream, *reads])]

    def loss(params, h, *extra):
        return jnp.sum(jnp.square(
            layer.call(params, [h, *extra] if extra else h)))

    # what the layer reads besides the stream gets its gradient too
    grads = jax.grad(loss, argnums=tuple(range(1 + len(inputs))))
    return grads, (params, *inputs), kernel, kept


def kept_bytes():
    from analytics_zoo_tpu.observability import get_registry
    prefix = "train_recompute_kept_bytes"
    return {k[len(prefix):]: v
            for k, v in get_registry().snapshot()["gauges"].items()
            if k.startswith(prefix)}


@pytest.mark.parametrize("kind", sorted(kernel_layers()))
def test_a_recomputed_layer_runs_its_forward_kernel_once(
        f32_policy, interpreted_kernels, pallas_calls, kind):
    """The gradient of a recomputed layer holds its forward kernel once,
    as an unrecomputed layer's does (a bare ``jax.checkpoint`` would run
    a ``custom_vjp``'s forward rule again with everything else); the
    gauge moves by the bytes of the named values, once however often the
    layer is traced, and not at all for a layer that keeps everything."""
    before = kept_bytes()
    kept_all, args, kernel, _ = layer_gradients(kind, recompute=False)
    once = pallas_calls(kept_all, *args)
    assert once[kernel] == 1 and kept_bytes() == before

    grads, args, kernel, kept = layer_gradients(kind, recompute=True)
    assert pallas_calls(grads, *args) == once
    pallas_calls(grads, *args)
    moved = {k: v - before.get(k, 0) for k, v in kept_bytes().items()}
    assert {k: v for k, v in moved.items() if v} == {
        '{name="%s"}' % name: n for name, n in kept.items()}


@pytest.mark.parametrize("kind", sorted(kernel_layers()))
def test_a_recomputed_layers_gradients_are_the_kept_layers(
        f32_policy, interpreted_kernels, kind):
    """Exactly, in float32: the kept values are what a second run of the
    kernels would have formed again."""
    grads, args, _, _ = layer_gradients(kind, recompute=True)
    again = grads(*args)
    grads, args, _, _ = layer_gradients(kind, recompute=False)
    kept = grads(*args)
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(kept)):
        assert float(jnp.max(jnp.abs(b))) > 0
        np.testing.assert_array_equal(a, b)


def test_the_last_states_cotangent_survives_recomputation(pallas_calls):
    """A loss on the carried-out state alone, inside a checkpoint that
    keeps the kernels' results: its cotangent reaches x, dt, A, B, C and
    the carried-in state as the sequential scan's does, and the forward
    kernel stands once in the gradient."""
    k = jax.random.split(jax.random.PRNGKey(4), 7)
    x = jax.random.normal(k[0], (1, 128, 1024))
    dt = jax.nn.softplus(jax.random.normal(k[1], x.shape) - 2.0)
    a = -jnp.exp(0.5 * jax.random.normal(k[2], (1024, 4)))
    b, c = (jax.random.normal(kk, (1, 128, 4)) for kk in k[3:5])
    state = jax.random.normal(k[5], (1, 1024, 4))
    w = jax.random.normal(k[6], state.shape)

    def on_last(scan):
        return lambda *args: jnp.sum(scan(*args)[1] * w)

    kernels = jax.checkpoint(
        on_last(lambda *args: selective_scan.selective_scan(
            *args, interpret=True)), policy=KEEP_KERNEL_RESULTS)
    every = tuple(range(6))
    args = (x, dt, a, b, c, state)
    assert pallas_calls(jax.grad(kernels, every), *args) == {
        "selective_scan_fwd": 1, "selective_scan_bwd": 1}
    got = jax.grad(kernels, every)(*args)
    want = jax.grad(on_last(selective_scan.selective_scan_lax), every)(*args)
    for name, g, r in zip("x dt a b c state".split(), got, want):
        # C does not reach the last state
        assert (float(jnp.max(jnp.abs(r))) > 0) == (name != "c"), name
        np.testing.assert_allclose(
            g, r, rtol=2e-4, atol=2e-5 * float(jnp.max(jnp.abs(r))),
            err_msg=name)
