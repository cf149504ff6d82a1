"""Fused kernel suite (ops/fused.py): numerics vs the unfused paths.

Tolerance contract (documented in docs/perf-tuning.md "Kernel suite"):

* fused optimizer update — BIT-IDENTICAL to the optax/unfused forms:
  it executes the same ops in the same order inside the same jitted
  program, so a real train run under the fused update reproduces the
  optax triple pass exactly (asserted below with zero tolerance).  It
  is plain ``jnp`` arithmetic on every backend: no kernel.
* epilogue Pallas kernels (interpret mode here; compiled on TPU) — the
  same formulas evaluated blockwise: ≤ 2e-6 absolute against the lax
  form at unit-scale inputs (float32 reassociation across blocks,
  nothing structural).
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from analytics_zoo_tpu.common.config import get_config
from analytics_zoo_tpu.ops import activations as acts
from analytics_zoo_tpu.ops import fused
from analytics_zoo_tpu.parallel.trainer import (
    ClipSpec, DistributedTrainer, _apply_clipping)
from analytics_zoo_tpu.pipeline.api.keras.optimizers import (
    SGD, Adam, RMSprop, poly, warmup_then)


def _tree(rs, shapes=((16, 128), (128,), (8, 8))):
    return {f"w{i}": jnp.array(rs.randn(*s), jnp.float32)
            for i, s in enumerate(shapes)}


# ------------------------------------------------ fused update vs optax
def _assert_fused_is_optax(optim, clip, shapes, grad_dtypes, steps):
    """Fused clip+update+apply ≡ optax global_norm → update →
    apply_updates, bit for bit, over ``steps`` steps in one jitted
    program each; the optax state pytree comes back with the same
    structure, shapes and dtypes (checkpoints, shardings and
    ``init_opt_state`` are unaffected)."""
    fu = fused.build_fused_update(optim, clip)
    assert fu is not None

    def unfused(g, s, p):
        g = _apply_clipping(g, clip)
        upd, s = optim.tx.update(g, s, p)
        return optax.apply_updates(p, upd), s
    # deliberately plain jax.jit: a numerics fixture, not an engine
    # program
    step_f, step_o = jax.jit(fu), jax.jit(unfused)

    rs = np.random.RandomState(0)
    params = _tree(rs, shapes)
    st_f = st_o = optim.tx.init(params)
    p_f = p_o = params
    for _ in range(steps):
        grads = {k: jnp.array(rs.randn(*v.shape), dt)
                 for (k, v), dt in zip(params.items(), grad_dtypes)}
        p_f, st_f = step_f(grads, st_f, p_f)
        p_o, st_o = step_o(grads, st_o, p_o)
    assert jax.tree_util.tree_structure((p_f, st_f)) == \
        jax.tree_util.tree_structure((p_o, st_o))
    for a, b in zip(jax.tree_util.tree_leaves((p_f, st_f)),
                    jax.tree_util.tree_leaves((p_o, st_o))):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestFusedUpdateVsOptax:
    @pytest.mark.parametrize("name,optim,clip", [
        ("sgd_mom", SGD(0.1, momentum=0.9), None),
        ("sgd_nesterov_wd",
         SGD(0.05, momentum=0.8, nesterov=True, weight_decay=1e-4),
         ClipSpec("l2norm", 1.0)),
        ("sgd_plain", SGD(0.1), ClipSpec("const", -0.01, 0.01)),
        ("sgd_sched",
         SGD(0.1, momentum=0.9,
             schedule=warmup_then(0.1, 3, poly(0.1, 0.5, 50))), None),
        ("adam", Adam(lr=1e-3), None),
        ("adam_clip", Adam(lr=1e-3), ClipSpec("l2norm", 0.5)),
        ("adam_decay", Adam(lr=1e-3, decay=0.01), None),
    ])
    def test_bit_identical_under_jit(self, name, optim, clip):
        _assert_fused_is_optax(optim, clip, ((16, 128), (128,), (8, 8)),
                               [jnp.float32] * 3, steps=6)

    @pytest.mark.parametrize("clip", [
        None, ClipSpec("const", -0.01, 0.01), ClipSpec("l2norm", 0.5)],
        ids=["noclip", "const", "l2norm"])
    @pytest.mark.parametrize("optim", [
        SGD(0.05, momentum=0.9, weight_decay=1e-4), Adam(lr=1e-3)],
        ids=["sgd", "adam"])
    def test_awkward_leaves_bit_identical_under_jit(self, optim, clip):
        """Every leaf takes the one path, whatever its shape or its
        gradient's dtype: an odd-sized leaf, a 1-D one and a bfloat16
        gradient beside an (8, 128)-tileable one."""
        _assert_fused_is_optax(
            optim, clip, ((7, 13), (100,), (3, 5, 8), (16, 128)),
            [jnp.float32, jnp.float32, jnp.bfloat16, jnp.float32], steps=4)

    def test_unsupported_combinations_decline(self):
        assert fused.build_fused_update(RMSprop(1e-3), None) is None
        assert fused.build_fused_update(None, None) is None
        # dampening has no optax twin — must fall back, not silently
        # drop the knob
        assert fused.build_fused_update(
            SGD(0.1, momentum=0.9, dampening=0.5), None) is None

    def test_off_switch(self):
        get_config().set("ops.fused", "off")
        assert fused.build_fused_update(Adam(1e-3), None) is None
        assert not fused.fused_enabled()


class TestTrainerFusedPath:
    def _run(self, steps=6):
        from analytics_zoo_tpu.pipeline.api.keras import (
            Layer, Sequential, objectives)
        from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
        Layer.reset_name_counters()
        rs = np.random.RandomState(0)
        x = rs.randn(64, 16).astype(np.float32)
        y = rs.randn(64, 1).astype(np.float32)
        m = Sequential()
        m.add(Dense(32, activation="relu", input_shape=(16,)))
        m.add(Dense(1))
        trainer = DistributedTrainer(
            m, objectives.get("mse"),
            optim_method=Adam(lr=1e-2),
            clip=ClipSpec("l2norm", 1.0))
        v = m.init(jax.random.PRNGKey(0))
        params = trainer.place_params(v["params"])
        state = trainer.replicate(v["state"])
        opt_state = trainer.init_opt_state(params)
        rng = jax.random.PRNGKey(7)
        batch = trainer.put_batch((x, y))
        for i in range(steps):
            params, opt_state, state, loss = trainer.train_step(
                params, opt_state, state, batch,
                jax.random.fold_in(rng, i))
        return trainer, jax.device_get(params), float(loss)

    def test_real_train_run_matches_optax_triple_pass(self):
        """THE acceptance check: a real DistributedTrainer run with the
        fused update produces the same params as the optax triple pass
        (train.fused_optimizer=false), to zero tolerance."""
        trainer_f, params_f, loss_f = self._run()
        assert trainer_f.fused_optimizer_active, \
            "fused update should engage by default for Adam + l2norm"
        get_config().set("train.fused_optimizer", False)
        trainer_o, params_o, loss_o = self._run()
        assert not trainer_o.fused_optimizer_active
        flat_f = jax.tree_util.tree_leaves(params_f)
        flat_o = jax.tree_util.tree_leaves(params_o)
        for a, b in zip(flat_f, flat_o):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert loss_f == loss_o

    def test_optim_groups_keep_optax_path(self):
        from analytics_zoo_tpu.pipeline.api.keras import (
            Sequential, objectives)
        from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
        m = Sequential()
        m.add(Dense(4, input_shape=(4,)))
        trainer = DistributedTrainer(
            m, objectives.get("mse"), optim_method=None,
            optim_groups={"all": (SGD(0.1), "*")})
        assert not trainer.fused_optimizer_active


# ----------------------------------- epilogue pallas kernels (interpret)
class TestPallasKernelsInterpret:
    def test_bias_gelu_matches_unfused(self):
        rs = np.random.RandomState(3)
        x = jnp.array(rs.randn(4, 8, 256), jnp.float32)
        b = jnp.array(rs.randn(256), jnp.float32)
        got = fused.bias_gelu(x, b, interpret=True)
        want = acts.gelu(x + b)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6, rtol=0)

    def test_layernorm_gelu_matches_unfused(self):
        rs = np.random.RandomState(4)
        x = jnp.array(rs.randn(16, 256), jnp.float32)
        gamma = jnp.array(rs.rand(256) + 0.5, jnp.float32)
        beta = jnp.array(rs.randn(256), jnp.float32)
        got = fused.layernorm_act(x, gamma, beta, eps=1e-5,
                                  activation=acts.gelu, interpret=True)
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        want = acts.gelu((x - mean) / jnp.sqrt(var + 1e-5)
                         * gamma + beta)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6, rtol=0)

    @pytest.mark.parametrize("wrt", ["x", "bias"])
    def test_bias_gelu_grad_matches_lax(self, wrt):
        """The kernel sits in the training forward: jax.grad through it
        (custom_vjp) must be the lax form's own derivative."""
        rs = np.random.RandomState(5)
        x = jnp.array(rs.randn(4, 8, 256), jnp.float32)
        b = jnp.array(rs.randn(256), jnp.float32)
        w = jnp.array(rs.randn(4, 8, 256), jnp.float32)
        argnum = ["x", "bias"].index(wrt)

        def grad(**kw):
            return jax.grad(
                lambda x, b: jnp.sum(fused.bias_gelu(x, b, **kw) * w),
                argnums=argnum)(x, b)

        np.testing.assert_allclose(
            np.asarray(grad(interpret=True)), np.asarray(grad()),
            atol=2e-6, rtol=1e-6)

    @pytest.mark.parametrize("wrt", ["x", "gamma", "beta"])
    def test_layernorm_act_grad_matches_lax(self, wrt):
        rs = np.random.RandomState(6)
        x = jnp.array(rs.randn(16, 256), jnp.float32)
        gamma = jnp.array(rs.rand(256) + 0.5, jnp.float32)
        beta = jnp.array(rs.randn(256), jnp.float32)
        w = jnp.array(rs.randn(16, 256), jnp.float32)
        argnum = ["x", "gamma", "beta"].index(wrt)

        def grad(**kw):
            return jax.grad(
                lambda x, g, b: jnp.sum(fused.layernorm_act(
                    x, g, b, activation=acts.gelu, **kw) * w),
                argnums=argnum)(x, gamma, beta)

        np.testing.assert_allclose(
            np.asarray(grad(interpret=True)), np.asarray(grad()),
            atol=2e-6, rtol=1e-6)

    @pytest.mark.parametrize("d", [768, 1024, 3072, 4096])
    def test_epilogue_row_block_fits_vmem_budget(self, d):
        """The v5e compiler refused the old fixed 1024-row block at
        every real width (48 MiB asked of a 16 MiB scoped limit at
        d=3072): the block is now bounded by what it keeps in VMEM —
        x in and y out, double-buffered, plus the body's temporaries."""
        rows = 16384
        x = jax.ShapeDtypeStruct((32, 512, d), jnp.float32)
        br = fused._epilogue_row_block(x, d)
        assert rows % br == 0 and br % 8 == 0
        footprint = br * d * 4 * (2 * 2 + fused._BODY_TEMPS)
        assert footprint <= fused._VMEM_BUDGET < 16 << 20
        # and it is the LARGEST such block: twice the rows would not fit
        assert 2 * footprint > fused._VMEM_BUDGET or 2 * br > 1024

    def test_epilogue_too_wide_for_vmem_takes_lax(self):
        # 8 rows of a 1M-wide f32 activation are 32 MiB per buffer
        x = jax.ShapeDtypeStruct((8, 1 << 20), jnp.float32)
        assert fused._epilogue_row_block(x, 1 << 20) is None

    def test_exact_gelu_takes_lax(self):
        """erf has no Mosaic lowering: approximate=False must never
        reach the kernel, which is the tanh form."""
        rs = np.random.RandomState(7)
        x = jnp.array(rs.randn(8, 256), jnp.float32)
        b = jnp.array(rs.randn(256), jnp.float32)
        got = fused.bias_gelu(x, b, approximate=False, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(acts.gelu_erf(x + b)))


# ----------------------------------------------------- epilogue wiring
class TestEpilogueWiring:
    def test_dense_gelu_identical_with_suite_off(self):
        from analytics_zoo_tpu.pipeline.api.keras import Sequential
        from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
        rs = np.random.RandomState(0)
        x = rs.randn(8, 32).astype(np.float32)

        def build_and_run():
            from analytics_zoo_tpu.pipeline.api.keras import Layer
            Layer.reset_name_counters()
            m = Sequential()
            m.add(Dense(64, activation="gelu", input_shape=(32,)))
            m.init(jax.random.PRNGKey(0))
            v = m.get_variables()
            out, _ = m.apply(v["params"], jnp.asarray(x),
                             state=v["state"], training=False)
            return np.asarray(out)

        on = build_and_run()
        get_config().set("ops.fused", "off")
        off = build_and_run()
        np.testing.assert_array_equal(on, off)

    def test_layernorm_activation_param(self):
        from analytics_zoo_tpu.pipeline.api.keras.layers.normalization \
            import LayerNorm
        rs = np.random.RandomState(1)
        x = jnp.array(rs.randn(8, 64), jnp.float32)
        ln = LayerNorm(activation="gelu")
        params = ln.init(jax.random.PRNGKey(0), (None, 64))["params"]
        got = ln.call(params, x)
        plain = LayerNorm()
        base = plain.call(params, x)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(acts.gelu(base)),
                                   atol=1e-6, rtol=0)

    def test_ffn_gelu_stays_golden(self):
        """PositionwiseFeedForward with the fused epilogue ≡ the
        unfused compute (gelu(up+bias) then down-proj)."""
        from analytics_zoo_tpu.pipeline.api.keras.layers.attention \
            import PositionwiseFeedForward
        rs = np.random.RandomState(2)
        x = jnp.array(rs.randn(2, 4, 32), jnp.float32)
        ffn = PositionwiseFeedForward(32, 64)
        params = ffn.init(jax.random.PRNGKey(0), (None, None, 32))[
            "params"]
        got = np.asarray(ffn.call(params, x))
        from analytics_zoo_tpu.pipeline.api.keras.layers.attention \
            import _mm
        h = acts.gelu(_mm(x, params["up_kernel"]) + params["up_bias"])
        want = np.asarray((_mm(h, params["down_kernel"])
                           + params["down_bias"]).astype(x.dtype))
        np.testing.assert_array_equal(got, want)


# ------------------------------------------------- ring numerics (sat 3)
class TestRingAttentionNumerics:
    """Satellite: ring_attention vs the dense ops/attention.py
    reference on a small mesh, incl. the causal edge at block
    boundaries."""

    def _qkv(self, t=8, d=4):
        rs = np.random.RandomState(0)
        return tuple(jnp.array(rs.randn(2, 2, t, d), jnp.float32)
                     for _ in range(3))

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        from analytics_zoo_tpu.ops.attention import (
            scaled_dot_product_attention)
        from analytics_zoo_tpu.parallel.mesh import create_mesh
        from analytics_zoo_tpu.parallel.ring_attention import (
            ring_attention)
        mesh = create_mesh({"seq": 4, "data": 2})
        q, k, v = self._qkv()
        out = ring_attention(q, k, v, mesh, causal=causal)
        ref = scaled_dot_product_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_causal_edge_at_block_boundaries(self):
        """T=8 over seq=4 → 2-row blocks with boundaries at positions
        2/4/6.  For a query ON a boundary row, perturbing every k/v
        strictly in its future must leave the output row bit-identical
        — the mask edge is exact even where the ring hands over
        blocks."""
        from analytics_zoo_tpu.parallel.mesh import create_mesh
        from analytics_zoo_tpu.parallel.ring_attention import (
            ring_attention)
        mesh = create_mesh({"seq": 4, "data": 2})
        q, k, v = self._qkv()
        base = np.asarray(ring_attention(q, k, v, mesh, causal=True))
        for pos in (1, 2, 3, 4, 6):      # boundary rows + neighbours
            k2 = k.at[:, :, pos + 1:, :].add(100.0)
            v2 = v.at[:, :, pos + 1:, :].add(-50.0)
            pert = np.asarray(
                ring_attention(q, k2, v2, mesh, causal=True))
            np.testing.assert_array_equal(base[:, :, pos], pert[:, :, pos])
            if pos + 1 < 8:
                # sanity: the future rows DID change
                assert not np.array_equal(base[:, :, pos + 1],
                                          pert[:, :, pos + 1])

    def test_text_classifier_transformer_ring_parity(self):
        """The opt-in wiring: TextClassifier's transformer encoder on a
        seq-populated mesh (ring attention over ICI) matches the same
        params on a data-only mesh (dense attention)."""
        from analytics_zoo_tpu.common import zoo_context
        from analytics_zoo_tpu.models.textclassification import (
            TextClassifier)
        zoo_context.reset_zoo_context()
        zoo_context.init_zoo_context(mesh_shape={"data": 2, "seq": 4})
        m = TextClassifier(class_num=3, token_length=32,
                           sequence_length=16, encoder="transformer",
                           encoder_output_dim=64, max_words_num=50,
                           n_head=4, n_block=1)
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randint(0, 50, (8, 16)).astype(np.int32))
        v = m.get_variables()
        ring, _ = m.model.apply(v["params"], x, state=v["state"],
                                training=False)
        zoo_context.reset_zoo_context()
        zoo_context.init_zoo_context(mesh_shape={"data": 8})
        dense, _ = m.model.apply(v["params"], x, state=v["state"],
                                 training=False)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(dense),
                                   rtol=1e-5, atol=1e-6)


# --------------------------------------------- obs_report + bench gates
def test_obs_report_renders_kernel_suite(capsys):
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "obs_report_for_kernels",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "obs_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    snap = {
        "counters": {
            'fused_kernel_builds_total{kernel="fused_adam",path="lax"}':
                12,
            'fused_kernel_builds_total{kernel="bias_gelu",'
            'path="pallas"}': 3,
        },
        "gauges": {
            # bench emits its gauges under the SAME kernel label the
            # build counters use, so one kernel renders as ONE row
            'kernel_bytes_saved_per_step{kernel="fused_adam"}': 48e6,
            'kernel_roofline_attainment{kernel="fused_adam"}': 0.91,
        },
        "histograms": {},
    }
    out = mod.render_report("kernels", snap)
    assert "fused kernel suite" in out
    assert "0.91x" in out
    assert "bias_gelu" in out and "pallas" in out
    # builds + bytes-saved + roofline merge into a single fused_adam row
    row = next(l for l in out.splitlines()
               if l.startswith("fused_adam"))
    assert "lax" in row and "12" in row and "0.91x" in row


def test_bench_compare_treats_int8_as_new_metric(tmp_path, monkeypatch,
                                                 capsys):
    """Satellite: an int8 metric absent from an f32-era baseline must
    neither gate nor regress; and the baseline's f32 metrics still
    gate normally."""
    import bench
    artifact = tmp_path / "bench_results.json"
    artifact.write_text(json.dumps({"results": [
        {"metric": "ncf_movielens1m_train_throughput", "value": 100.0},
        {"metric": "ncf_int8_predict_rows_per_sec", "value": 5000.0},
    ]}))
    monkeypatch.setattr(bench, "ARTIFACT_PATH", str(artifact))
    base = tmp_path / "base.json"
    base.write_text(json.dumps(
        {"ncf_movielens1m_train_throughput": 99.0}))
    rc = bench._compare_against_baseline(str(base), threshold=0.10)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["ok"]
    assert line["metrics_compared"] == 1      # int8 metric not gated
    # and a real f32 regression still fails
    base.write_text(json.dumps(
        {"ncf_movielens1m_train_throughput": 200.0}))
    rc = bench._compare_against_baseline(str(base), threshold=0.10)
    assert rc == 1
