"""The block-diffusion layers against the plain reference of the
``sdar-30b-a3b-chat`` configuration (``benchmark/reference``): the noise
layer's tokens, positions and weights, the attention layer, the sliced
embedding, and the whole decoder's loss and gradients at 2 layers, 16
experts and 64-token sequences; then a short ``Estimator.train`` whose
expert counters come out of the compiled epoch with no host callback."""

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

from analytics_zoo_tpu.pipeline.api.keras.layers import (  # noqa: E402
    BlockDiffusionNoise, Embedding, GroupedQueryAttention, RMSNorm)
from analytics_zoo_tpu.ops.pallas_attention import (  # noqa: E402
    block_diffusion)

CONFIG = "sdar-30b-a3b-chat"
TOY = dict(seq_len=64, num_hidden_layers=2, hidden_size=64, head_dim=16,
           num_attention_heads=8, num_key_value_heads=2,
           num_experts_published=32, experts_held=[8, 16], num_experts=16,
           num_experts_per_tok=4, moe_intermediate_size=32, vocab_size=97,
           vocab_held=[0, 97], vocab_size_published=776,
           initializer_range=0.3)


@pytest.fixture(scope="module")
def toy():
    cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                         CONFIG + ".json"))
    return dict(cfg, **TOY)


@pytest.fixture(scope="module")
def reference():
    return harness.load_module("reference", CONFIG)


def records(cfg, n, seed=0):
    rs = np.random.RandomState(seed)
    L = cfg["seq_len"]
    return rs.randint(0, cfg["vocab_size"] - 1,
                      size=(n, 2 * L + L // cfg["block_length"])
                      ).astype(np.int32)


def test_noise_layer_follows_the_references_rule(toy, reference):
    L, B = toy["seq_len"], toy["block_length"]
    layer = BlockDiffusionNoise(L, B, mask_id=96, t_min=toy["t_min"],
                                draws="record", draw_range=96)
    rows = records(toy, 3)
    tokens, positions, targets, weights = layer.call({}, jnp.asarray(rows))
    for r in range(3):
        want = reference.noise(toy, jnp.asarray(rows[r]))
        for got, w in zip((tokens, positions, targets, weights), want):
            np.testing.assert_array_equal(got[r], w)
    masked = np.asarray(tokens[:, :L] == 96)
    assert 0.2 < masked.mean() < 0.8
    # the clean half is untouched, positions restart, and a block's
    # weight is 1 / (t L) with t its own level
    np.testing.assert_array_equal(tokens[:, L:], rows[:, :L])
    np.testing.assert_array_equal(positions[0], np.tile(np.arange(L), 2))
    t = toy["t_min"] + (1 - toy["t_min"]) * rows[:, 2 * L:] / np.float32(96)
    np.testing.assert_allclose(
        weights, np.where(masked, 1 / (np.repeat(t, B, 1) * L), 0),
        rtol=1e-5)
    assert layer.compute_output_shape((None, layer.record_len())) == [
        (None, 2 * L), (None, 2 * L), (None, L), (None, L)]


def test_noise_layer_draws_from_its_rng_when_training():
    layer = BlockDiffusionNoise(64, 4, mask_id=99, draws="rng")
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 99, (4, 64)))
    a = layer.call({}, ids, training=True, rng=jax.random.PRNGKey(0))
    b = layer.call({}, ids, training=True, rng=jax.random.PRNGKey(1))
    assert (a[0] != b[0]).any() and (a[0][:, :64] == 99).any()
    # blocks differ in level: the masked share varies from block to block
    share = np.asarray(a[0][:, :64] == 99).reshape(4, 16, 4).mean(-1)
    assert share.std() > 0.2
    clean = layer.call({}, ids)
    np.testing.assert_array_equal(clean[0][:, :64], ids)
    assert not np.asarray(clean[3]).any()
    with pytest.raises(ValueError, match="needs rng"):
        layer.call({}, ids, training=True)
    with pytest.raises(ValueError, match="draw_range"):
        BlockDiffusionNoise(64, 4, mask_id=9, draws="record")


def test_rms_norm_and_sliced_embedding(f32_policy):
    x = jnp.asarray(np.random.RandomState(2).randn(3, 5, 8), jnp.float32)
    layer = RMSNorm(1e-6)
    params = layer.init(jax.random.PRNGKey(0), (None, 5, 8))["params"]
    params = {"gamma": params["gamma"] * 1.5}
    want = x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-6) * 1.5
    np.testing.assert_allclose(layer.call(params, x), want, rtol=1e-5)
    # rank 1 of 4 over a 40-id vocabulary holds ids 10-19: its rows for
    # those, zeros for the rest; the four ranks' lookups add up
    table = jnp.asarray(np.random.RandomState(3).randn(40, 6), jnp.float32)
    ids = jnp.asarray([[3, 10, 19, 20, 39]])
    total = 0
    for rank in range(4):
        emb = Embedding(40, 6, vocab_held=(10 * rank, 10))
        assert emb.init(jax.random.PRNGKey(0), (None, 5))["params"][
            "embeddings"].shape == (10, 6)
        total = total + emb.call(
            {"embeddings": table[10 * rank:10 * rank + 10]}, ids)
    np.testing.assert_allclose(total, table[ids])
    with pytest.raises(ValueError, match="vocab_held"):
        Embedding(40, 6, vocab_held=(35, 10))


@pytest.mark.parametrize("mask", ["causal", "block_diffusion"])
def test_attention_layer_matches_the_reference(f32_policy, toy, reference,
                                               mask):
    L = toy["seq_len"]
    rs = np.random.RandomState(4)
    ps = {"attn.q": rs.randn(64, 128), "attn.k": rs.randn(64, 32),
          "attn.v": rs.randn(64, 32), "attn.o": rs.randn(128, 64),
          "attn.q_norm": 1 + 0.1 * rs.randn(16),
          "attn.k_norm": 1 + 0.1 * rs.randn(16)}
    ps = {k: jnp.asarray(0.2 * v if v.ndim == 2 else v, jnp.float32)
          for k, v in ps.items()}
    x = jnp.asarray(rs.randn(2, 2 * L, 64), jnp.float32)
    positions = jnp.tile(jnp.arange(L), 2)
    layer = GroupedQueryAttention(
        8, 2, 16, rope_theta=toy["rope_theta"],
        mask="causal" if mask == "causal" else block_diffusion(L, 4))
    shapes = layer.init(jax.random.PRNGKey(0),
                        [(None, 2 * L, 64), (None, 2 * L)])["params"]
    params = dict(zip(shapes, ps.values()))
    assert [a.shape for a in params.values()] == \
        [a.shape for a in shapes.values()]
    allowed = jnp.tril(jnp.ones((2 * L, 2 * L), bool)) \
        if mask == "causal" else reference.allowed(toy)
    got = layer.call(params, [x, jnp.broadcast_to(positions, (2, 2 * L))])
    for r in range(2):
        want = reference.attention(toy, reference.common.matmul, ps, x[r],
                                   positions, allowed)
        np.testing.assert_allclose(got[r], want, atol=2e-4)


def build(toy):
    from analytics_zoo_tpu import init_zoo_context
    init_zoo_context()
    return harness.load_module("configs", CONFIG).build(toy)


def test_decoder_loss_and_gradients_match_the_reference(f32_policy, toy,
                                                        reference):
    """2 layers, 16 of 32 experts held, 64-token sequences: the keras
    graph and the plain equations give the same loss and the same
    gradient for every parameter."""
    model = build(toy)
    variables = model.get_variables()
    order = reference.param_order(toy)
    ref_params = reference.init(toy, 3)
    params = harness.to_program(order, ref_params, variables["params"])
    rows = records(toy, 2, seed=5)

    def program(p):
        out, _ = model.apply(p, [jnp.asarray(rows), jnp.asarray(rows)],
                             state=variables["state"], training=True)
        return model.loss(None, out)

    def plain(p):
        return sum(reference.sequence_loss(toy, p, jnp.asarray(r))
                   for r in rows) / len(rows)

    loss, grads = jax.value_and_grad(program)(params)
    want, ref_grads = jax.value_and_grad(plain)(ref_params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    grads = harness.from_program(
        order, harness.program_leaves(variables["params"]), grads)
    for name in order:
        scale = float(jnp.max(jnp.abs(ref_grads[name])))
        assert scale > 0, name
        np.testing.assert_allclose(grads[name], ref_grads[name],
                                   atol=1e-4 * scale, err_msg=name)


def test_training_counts_routed_rows_without_a_callback(toy, monkeypatch):
    """``Estimator.train`` on the scan engine: the expert layers' counts
    ride the carry, are read under ``moe_stats_read``, and every
    assignment of every step is counted once."""
    from analytics_zoo_tpu.common import zoo_context
    from analytics_zoo_tpu.common.config import get_config
    from analytics_zoo_tpu.feature.feature_set import FeatureSet
    from analytics_zoo_tpu.parallel import mesh as mesh_lib
    # one chip of the suite's eight: a batch of one sequence
    monkeypatch.setattr(zoo_context, "_context", zoo_context.ZooContext(
        get_config(),
        mesh_lib.create_mesh({"data": 1}, devices=jax.devices()[:1])))
    from analytics_zoo_tpu.common.triggers import MaxEpoch
    from analytics_zoo_tpu.observability import get_registry, get_tracer
    from analytics_zoo_tpu.pipeline.estimator import Estimator
    model = harness.load_module("configs", CONFIG).build(toy)
    rows = records(toy, 4, seed=6)
    data = FeatureSet.from_ndarrays([rows, rows], np.zeros((4, 1), np.int32),
                                    shuffle=True)
    before = get_registry().snapshot()["counters"]
    est = Estimator(model, optim_method=model.optim_method)
    est.train(data, model.loss, end_trigger=MaxEpoch(2), batch_size=1)
    after = get_registry().snapshot()["counters"]
    moved = harness.counter_delta({"counters": after}, {"counters": before},
                                  "moe_rows_routed_total")
    assert harness.counter_delta(
        {"counters": after}, {"counters": before},
        "train_steps_total") == {'{path="epoch_scan"}': 8.0}
    positions, picks = 2 * toy["seq_len"], toy["num_experts_per_tok"]
    assert sum(moved.values()) == 8 * positions * picks * 2
    held = sum(v for k, v in moved.items() if 'held="1"' in k)
    assert 0 < held < sum(moved.values())
    assert any(e["name"] == "moe_stats_read"
               for e in get_tracer().events())
    state = est.variables["state"]
    total = sum(int(np.asarray(s["rows_routed"]).sum())
                for s in state.values() if "rows_routed" in s)
    assert total == sum(moved.values())
