"""The ``granite-4.0-h-micro.sft_8k_hbm`` cell at a toy size on the CPU,
through the same ``run_cell`` the chip runs: the chunked SSD scan's
kernels and grouped 64-wide flash attention (interpreted), recomputed
layers, the scan engine, the comparison with the plain reference; the
reference's chunked scan against the recurrence; then the cell's FLOP
functions by hand and each of its metrics on a synthetic ``run``."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness

CELL = "granite-4.0-h-micro.sft_8k_hbm"
CONFIG = "granite-4.0-h-micro"
# two periods of a toy layout (two Mamba-2 layers, then attention): 8
# heads of 64 on 16 states by chunks of 128, and 4 query heads of 64 on
# 2 K/V heads; 512-token sequences (four chunks, two flash tiles), a
# quarter of a 388-id vocabulary
TOY_CELL = dict(rows=4)
TOY_CFG = dict(seq_len=512, num_hidden_layers=6, hidden_size=256,
               layer_types=["mamba", "mamba", "attention"] * 2,
               shared_intermediate_size=128, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=2,
               mamba_n_heads=8, mamba_d_head=64, mamba_d_state=16,
               mamba_chunk_size=128, vocab_size=97, vocab_held=[0, 97],
               vocab_size_published=388, initializer_range=0.2,
               recompute=dict(decoder_layers=True, loss_chunk_rows=256))
METRICS = ["ssd_step_mfu", "ssd_step_device_ms", "ssd_scan_ms_per_step",
           "ssd_scan_roofline_pct"]


def cfg_of():
    return harness.load_json(os.path.join(harness.HERE, "configs",
                                          CONFIG + ".json"))


def run(trace=False, seed=2 ** 31 + 19, limits=None):
    cell = dict(TOY_CELL, **({"limits": limits} if limits else {}))
    return harness.run_cell(CELL, seed, 1.0, trace, require_chip=False,
                            cell_override=cell, cfg_override=TOY_CFG)


def builds_since(before):
    from analytics_zoo_tpu.observability import get_registry
    return harness.counter_delta(get_registry().snapshot(), before,
                                 "fused_kernel_builds_total")


def test_cell_end_to_end_matches_the_reference_in_float32(one_chip,
                                                          f32_program):
    """The program's first epoch (the SSD scan's kernels, grouped
    64-wide flash attention with its K/V heads repeated to the group,
    recomputed layers, the chunked tied head, Adam, all interpreted)
    against the plain reference: in float32 they agree to rounding."""
    from analytics_zoo_tpu.observability import get_registry
    before = get_registry().snapshot()
    line = run(limits={"loss": 1e-5, "grad": 1e-3, "dparam": 1e-3})
    assert line["correct"], line["compared"]
    assert set(line["metrics"]) == {"train_records_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line["info"]["engine"]) == ['{path="epoch_scan"}']
    json.dumps(line)
    moved = builds_since(before)
    assert moved.get('{kernel="ssd_scan",path="pallas"}')
    assert '{kernel="ssd_scan",path="lax"}' not in moved
    assert moved.get('{kernel="flash_attention",path="pallas"}')
    assert '{kernel="flash_attention",path="lax"}' not in moved
    assert moved.get('{kernel="flash_attention_packed",path="kv_broadcast"}')


def test_traced_run_reports_the_cells_metrics(one_chip):
    """The line holds the cell's metrics and no accepted one, and the
    recomputed layers keep the scan's results by name."""
    from analytics_zoo_tpu.observability import get_registry
    line = run(trace=True)
    # the device's busy time is read anywhere; a kernel's events only
    # where the chip names them, and shares of a peak on a known device
    assert "ssd_step_device_ms" in line["metrics"]
    assert set(line["metrics"]) <= set(METRICS)
    kept = {k: v for k, v in get_registry().snapshot()["gauges"].items()
            if k.startswith("train_recompute_kept_bytes")}
    # y (512 x 512 bf16) and the four chunk-start states (16 x 512 f32)
    # of the four Mamba-2 layers
    assert kept['train_recompute_kept_bytes{name="ssd_scan_y"}'] \
        >= 4 * 512 * 512 * 2
    assert kept['train_recompute_kept_bytes{name="ssd_scan_starts"}'] \
        >= 4 * 4 * 16 * 512 * 4
    assert kept['train_recompute_kept_bytes{name="flash_attention_out"}'] \
        >= 2 * 512 * 256 * 2


def recurrence(x, a, b, c):
    """``S_t = exp(a_t) S_{t-1} + b_t x_t^T``, ``y_t = C_t^T S_t``, a
    position at a time: x (T, H, P) already times dt, a (T, H), b, c (T,
    H, N)."""
    def step(s, xs):
        x_t, a_t, b_t, c_t = xs
        s = jnp.exp(a_t)[:, None, None] * s + x_t[:, :, None] * b_t[:, None]
        return s, jnp.einsum("hpn,hn->hp", s, c_t)

    h, p, n = x.shape[1], x.shape[2], b.shape[2]
    last, y = jax.lax.scan(step, jnp.zeros((h, p, n)), (x, a, b, c))
    return y, last


def test_the_references_chunked_scan_is_the_recurrence():
    """The minimal listing's chunked form, forward and gradients, against
    the recurrence a position at a time."""
    reference = harness.load_module("reference", CONFIG)
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    t, h, p, n = 96, 3, 8, 4
    x = jax.random.normal(keys[0], (t, h, p))
    a = -jax.nn.softplus(jax.random.normal(keys[1], (t, h)))
    b, c = (jax.random.normal(k, (t, h, n)) for k in keys[2:4])
    cot = jax.random.normal(keys[4], (t, h, p))

    def chunked(*args):
        return reference.ssd_minimal(*args, chunk=32)

    def loss(fn):
        def f(*args):
            y, last = fn(*args)
            return jnp.sum(y * cot) + jnp.sum(jnp.square(last))
        return f

    with jax.default_matmul_precision("highest"):
        y, last = chunked(x, a, b, c)
        y_ref, last_ref = recurrence(x, a, b, c)
        np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(last, last_ref, rtol=1e-5, atol=1e-5)
        grads = jax.grad(loss(chunked), (0, 1, 2, 3))(x, a, b, c)
        want = jax.grad(loss(recurrence), (0, 1, 2, 3))(x, a, b, c)
    for got, ref in zip(grads, want):
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_flops_by_hand():
    flops = harness.load_module("flops", CONFIG)
    cfg = cfg_of()
    T, d, L = 8192, 2048, 256
    assert flops.layer_kinds(cfg) == ["mamba"] * 5 + ["attention"] \
        + ["mamba"] * 4
    # the scan a position: C B^T (128) and the map (64 heads of 64) over
    # the causal half of a chunk, C S and B^T x (128 x 64) a head
    scan = 2 * (L + 1) / 2 * (128 + 64 * 64) + 4 * 64 * 128 * 64
    assert flops.scan_flops_per_position(cfg) == scan
    assert 3.17e6 < scan < 3.19e6
    mlp = 2 * (d * 16384 + 8192 * d)
    mamba = mlp + 2 * (d * 8512 + 4096 * d) + scan
    attention = mlp + 2 * d * (2 * 2048 + 2 * 512) \
        + 2 * (T + 1) / 2 * 32 * 128
    head = 2 * d * 12544
    assert mamba / 1e6 == pytest.approx(155.5, abs=0.05)
    assert attention / 1e6 == pytest.approx(155.2, abs=0.05)
    assert head / 1e6 == pytest.approx(51.4, abs=0.05)
    assert flops.forward_flops_per_record(cfg) == pytest.approx(
        T * (9 * mamba + attention + head))
    # about 39.5 TFLOP a record
    assert 39.4e12 < flops.train_flops_per_record(cfg) < 39.6e12
    n = sum(int(np.prod(s)) for s in flops.param_shapes(cfg))
    assert n == 772160448
    s_flops, s_bytes = flops.ssd_scan_per_step(cfg)
    assert s_flops == pytest.approx(9 * 3 * T * scan)
    per_layer = T * (2 * 2 * (2 * 4096 + 2 * 128) + 2 * 4 * 64) \
        + 2 * 32 * 64 * 128 * 64 * 4
    assert s_bytes == pytest.approx(9 * per_layer)
    # about 0.7 TFLOP and 3.8 GB: a floor of 4.6 ms, the memory's
    assert 0.69e12 < s_flops < 0.71e12 and 3.7e9 < s_bytes < 3.9e9
    assert s_bytes / 819e9 > s_flops / 197e12


def test_flops_by_hand_at_the_toy_size():
    """The toy's four Mamba-2 layers (8 heads of 64, 16 states, chunks of
    128) and two attention layers (4 heads of 64 on 2) of 512 positions,
    counted product by product."""
    flops = harness.load_module("flops", CONFIG)
    cfg = {**cfg_of(), **TOY_CFG}
    T, d, ff = 512, 256, 128
    scan = 2 * 64.5 * (16 + 8 * 64) + 2 * 2 * 8 * 16 * 64
    assert flops.scan_flops_per_position(cfg) == scan
    mlp = 2 * 3 * d * ff
    mamba = mlp + 2 * (d * (2 * 512 + 2 * 16 + 8) + 512 * d) + scan
    attention = mlp + 2 * d * (2 * 256 + 2 * 128) + 2 * 256.5 * 4 * 128
    assert flops.forward_flops_per_record(cfg) == pytest.approx(
        T * (4 * mamba + 2 * attention + 2 * d * 97))
    s_flops, s_bytes = flops.ssd_scan_per_step(cfg)
    assert s_flops == pytest.approx(4 * 3 * T * scan)
    assert s_bytes == pytest.approx(4 * (
        T * (2 * 2 * (2 * 512 + 2 * 16) + 2 * 4 * 8)
        + 2 * 4 * 8 * 16 * 64 * 4))


def test_reference_param_order_fits_the_flops_shapes():
    reference = harness.load_module("reference", CONFIG)
    flops = harness.load_module("flops", CONFIG)
    cfg = cfg_of()
    assert [s for _, s, _ in reference._spec(cfg)] == \
        flops.param_shapes(cfg)


def test_the_configuration_holds_every_published_number():
    """Every key of the catalog's ``config`` at its value, except the
    two that ``reduced`` names, which stand beside their published
    values; ``layer_types`` is the published 40 entries whole."""
    cfg = cfg_of()
    published = dict(
        attention_bias=False, attention_multiplier=0.015625,
        embedding_multiplier=12, hidden_act="silu", hidden_size=2048,
        intermediate_size=8192, logits_scaling=8, mamba_chunk_size=256,
        mamba_conv_bias=True, mamba_d_conv=4, mamba_d_head=64,
        mamba_d_state=128, mamba_expand=2, mamba_n_groups=1,
        mamba_n_heads=64, mamba_proj_bias=False,
        max_position_embeddings=131072, model_type="granitemoehybrid",
        normalization_function="rmsnorm", num_attention_heads=32,
        num_experts_per_tok=0, num_key_value_heads=8, num_local_experts=0,
        position_embedding_type="nope", residual_multiplier=0.22,
        rms_norm_eps=1e-5, rope_scaling=None, rope_theta=10000,
        shared_intermediate_size=8192, tie_word_embeddings=True)
    assert {k: cfg[k] for k in published} == published
    period = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert cfg["layer_types"] == (period[:5] + ["attention"] + ["mamba"] * 9
                                  + ["attention"] + ["mamba"] * 9
                                  + ["attention"] + ["mamba"] * 9
                                  + ["attention"] + ["mamba"] * 4)
    assert cfg["layer_types"][:10] == period
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (10, 12544)
    assert (cfg["num_hidden_layers_published"],
            cfg["vocab_size_published"]) == (40, 100352)
    assert cfg["vocab_held"] == [0, 12544] and cfg["seq_len"] == 8192
    assert set(cfg["reduced_why"].split()) >= {"num_hidden_layers",
                                               "vocab_size"}
    assert set(cfg["assumed"]) >= {"in_proj_columns", "mlp_columns",
                                   "mamba_norm", "init", "optimizer",
                                   "recompute", "seq_len", "batch_size"}
    assert cfg["recompute"] == {"decoder_layers": True,
                                "loss_chunk_rows": 1024}


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(harness.HERE, "reference", CONFIG + ".py")
    source = open(path).read()
    assert "analytics_zoo_tpu" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source


def synthetic_run(**over):
    """A traced window of 10 steps, 680 ms of device time a step, 25 ms
    of it the scan's two kernels."""
    flops = harness.load_module("flops", CONFIG)
    cfg = cfg_of()
    call = '%%%s.1 = bf16[8] custom-call(), custom_call_target=' \
        '"tpu_custom_call", metadata={op_name="jit(f)/%s/pallas_call"}'
    run = {
        "cfg": cfg, "steps": 10, "records": 10, "window_s": 7.4,
        "device": {"count": 1}, "peak_bytes": 15 * 2 ** 30,
        "peaks": harness.peaks_for("TPU v5 lite"), "flops": flops,
        "before": {"counters": {}, "gauges": {}},
        "after": {"counters": {}, "gauges": {}},
        "trace": {"busy_s": 6.8, "window_s": 7.4, "by_name": {
            call % ("ssd_scan_fwd", "ssd_scan_fwd"): 0.07,
            call % ("ssd_scan_bwd", "ssd_scan_bwd"): 0.18,
            # another family's kernels are not this metric's
            call % ("flash_attention_fwd", "flash_attention_fwd"): 0.1,
            call % ("selective_scan_fwd", "selective_scan_fwd"): 0.2,
            "%fusion.7 = f32[] fusion()": 1.0}},
    }
    run.update(over)
    return run, flops, cfg


def read(name, run):
    return harness.load_module("metrics", name).read(run)


def test_metrics_on_a_synthetic_run():
    run, flops, cfg = synthetic_run()
    assert read("ssd_step_device_ms", run) == pytest.approx(680.0)
    assert read("ssd_scan_ms_per_step", run) == pytest.approx(25.0)
    need = flops.train_flops_per_record(cfg)
    assert read("ssd_step_mfu", run) == pytest.approx(
        100 * need * 10 / 7.4 / 197e12)
    s_flops, s_bytes = flops.ssd_scan_per_step(cfg)
    assert read("ssd_scan_roofline_pct", run) == pytest.approx(
        100 * max(s_flops / 197e12, s_bytes / 819e9) / 25e-3)
    for name in ("ssd_scan_roofline_pct", "ssd_step_mfu"):
        assert 0 < read(name, run) < 100


@pytest.mark.parametrize("name", METRICS)
def test_a_program_without_the_kernels_reads_nothing(name):
    """The parent of this cell: no such kernels, no steps.  Each reader
    returns ``None`` and does not raise."""
    run, _, _ = synthetic_run(
        trace={"busy_s": 0.0, "window_s": 2.5, "by_name": {}}, steps=0,
        records=0)
    assert read(name, run) is None


@pytest.mark.parametrize("name", METRICS)
def test_each_metric_is_declared_for_the_cell_alone(name):
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    entry = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(entry) == 1 and entry[0]["workloads"] == [CELL]
    assert entry[0]["moves"] == "train_records_per_s"


def test_no_accepted_list_names_the_cell():
    """A cell listed under a metric has to report it: the cell is on its
    own four metrics' lists and on no other."""
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    named = [m["name"] for m in bench["per_layer"] + bench["end_to_end"]
             if CELL in m.get("workloads", [])]
    assert sorted(named) == sorted(METRICS)
    entry = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(entry) == 1 and entry[0]["chips"] == 1
    assert entry[0]["config"] == CONFIG


# ------------------------------------------------- the control, at toy size
@pytest.fixture(scope="module")
def control_readings():
    """The plain reference in the program's place, judged as a run is:
    ``correctness.compare`` and the limits of the cell's own file."""
    from benchmark import control, correctness
    readings = control.read_cell(
        CELL, 2 ** 31 + 29, ["round:fp8", "round:bf16", "fault:half_batch"],
        TOY_CELL, TOY_CFG, require_chip=False)
    limits = harness.load_cell(CELL)[1]["limits"]
    return {variant: correctness.judge(readings[variant], limits)["compared"]
            for variant in ("round:fp8", "round:bf16", "fault:half_batch")}


def test_the_cell_has_its_limits_and_their_reasons():
    cell = harness.load_cell(CELL)[1]
    assert set(cell["limits"]) == {"loss", "grad", "dparam"}
    assert set(cell["limits_why"]) == set(cell["limits"])


@pytest.mark.parametrize("key", ["grad", "dparam"])
def test_float8_reads_well_above_bfloat16(control_readings, key):
    """The reference in float8 in the program's place reads well above
    the reference in the configuration's own bfloat16."""
    fp8, bf16 = (control_readings[v][key] for v in ("round:fp8",
                                                    "round:bf16"))
    assert fp8["value"] > 3 * bf16["value"] > 0, (fp8, bf16)


def test_float8_control_is_not_correct(control_readings):
    """... and is not correct by the cell's limits: by one of them, not
    by each (the readings the limits were set from are the chip's, at
    the cell's size: ``limits_why``)."""
    assert not all(c["ok"] for c in control_readings["round:fp8"].values())


def test_half_batch_fault_is_not_correct(control_readings):
    """Half of the loss positions left out: the loss and the first
    moment halve."""
    half = control_readings["fault:half_batch"]
    assert half["loss"]["value"] > 0.3 and not half["loss"]["ok"]
    assert half["grad"]["value"] > 0.2 and not half["grad"]["ok"]
