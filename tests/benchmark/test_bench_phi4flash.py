"""The ``phi-4-mini-flash-reasoning.reasoning_sft_hbm`` cell at a toy
size on the CPU, through the same ``run_cell`` the chip runs: the scan
and the flash kernels on their Pallas branch (interpreted), every layer
recomputed in the backward pass, the scan engine, the comparison with
the plain reference; then the cell's FLOP functions by hand and each of
its metrics on a synthetic ``run``."""

import json
import os

import numpy as np
import pytest

from benchmark import harness

CELL = "phi-4-mini-flash-reasoning.reasoning_sft_hbm"
CONFIG = "phi-4-mini-flash-reasoning"
# the six layers at a width of 128: 4 query heads on 2 K/V heads of 64
# (two pairs on one), 1,024 scan channels of 4 states, 256 positions
# (one flash tile, four chunks of the scan) under a window of 100, an
# eighth of a 776-id vocabulary
TOY_CELL = dict(rows=4)
TOY_CFG = dict(seq_len=256, hidden_size=128, intermediate_size=256,
               num_attention_heads=4, num_key_value_heads=2, head_dim=64,
               mamba=dict(d_inner=1024, d_state=4, d_conv=4, dt_rank=8),
               sliding_window=100, vocab_size=97, vocab_held=[0, 97],
               vocab_size_published=776, initializer_range=0.1,
               recompute=dict(decoder_layers=True, loss_chunk_rows=64))
METRICS = ["hybrid_step_mfu", "hybrid_step_device_ms",
           "selective_scan_ms_per_step", "selective_scan_roofline_pct",
           "hybrid_attention_ms_per_step", "hybrid_attention_roofline_pct",
           "attention_tiles_walked_pct"]


def run(trace=False, seed=2 ** 31 + 17, limits=None):
    cell = dict(TOY_CELL, **({"limits": limits} if limits else {}))
    return harness.run_cell(CELL, seed, 1.0, trace, require_chip=False,
                            cell_override=cell, cfg_override=TOY_CFG)


def builds(before, after):
    return harness.counter_delta(after, before, "fused_kernel_builds_total")


def test_cell_end_to_end_matches_the_reference_in_float32(one_chip,
                                                          f32_program):
    """The program's first epoch (the scan's two kernels, differential
    flash attention under three masks, the tied chunked head, fused
    Adam, all interpreted) against the plain reference: in float32 they
    agree to rounding.  The parameters' change is held by its median
    leaf: the keys' third of an attention layer's input bias has no
    gradient (a softmax does not see a constant added to every key), so
    under Adam that leaf moves by round-off and is the worst by far."""
    from analytics_zoo_tpu.observability import get_registry
    before = get_registry().snapshot()
    line = run(limits={"loss": 1e-5, "grad": 1e-3,
                       "dparam": {"of": "median_gap", "limit": 1e-4}})
    assert line["correct"], line["compared"]
    assert set(line["metrics"]) == {"train_records_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line["info"]["engine"]) == ['{path="epoch_scan"}']
    built = builds(before, get_registry().snapshot())
    for kernel in ("selective_scan", "flash_attention_window",
                   "flash_attention_differential"):
        assert built.get('{kernel="%s",path="pallas"}' % kernel), built
        assert '{kernel="%s",path="lax"}' % kernel not in built
    json.dumps(line)


def test_traced_run_reports_the_cells_metrics(one_chip):
    line = run(trace=True)
    # counts are read anywhere; shares of a peak only on a known device
    assert {"hybrid_step_device_ms",
            "attention_tiles_walked_pct"} <= set(line["metrics"])
    assert set(line["metrics"]) <= set(METRICS)
    # 256 positions are one tile: the window walks what the causal mask does
    assert line["metrics"]["attention_tiles_walked_pct"]["value"] == 100.0


def cfg_file():
    return harness.load_json(os.path.join(harness.HERE, "configs",
                                          CONFIG + ".json"))


def test_flops_by_hand():
    flops, cfg = harness.load_module("flops", CONFIG), cfg_file()
    T, d, ff, c = 8192, 2560, 10240, 5120
    assert flops.layer_kinds(cfg) == [
        "mamba", "window_attention", "mamba", "full_attention",
        "memory_unit", "cross_attention"]
    window = 512 * 513 // 2 + (T - 512) * 512
    causal = T * (T + 1) // 2
    assert flops.allowed_pairs(cfg, "window_attention") == window
    assert flops.allowed_pairs(cfg, "cross_attention") == causal
    mlp = 6 * T * d * ff
    mamba = 2 * T * (d * 2 * c + c * 192 + 160 * c + c * d) \
        + 2 * T * c * 4 + 7 * T * c * 16
    maps = lambda pairs: 2 * pairs * 40 * (64 + 128)       # noqa: E731
    self_attn = 2 * T * d * (5120 + 2560)
    cross = 2 * T * d * (2560 + 2560)
    gmu = 2 * T * 2 * d * c
    head = 2 * T * d * 25008
    want = 6 * mlp + 2 * mamba + 2 * self_attn + cross + gmu \
        + maps(window) + 2 * maps(causal) + head
    assert flops.forward_flops_per_record(cfg) == pytest.approx(want)
    # 37.6 TFLOP a record, of which the six MLPs 23.2 and the maps 3.3
    assert flops.train_flops_per_record(cfg) == pytest.approx(37.56e12,
                                                              rel=1e-3)
    assert 3 * 6 * mlp == pytest.approx(23.2e12, rel=1e-2)
    n = sum(int(np.prod(s)) for s in flops.param_shapes(cfg))
    assert n == 697_094_272
    a_flops, a_bytes = flops.attention_per_step(cfg)
    assert a_flops == pytest.approx(3 * (maps(window) + 2 * maps(causal)))
    assert a_bytes == 3 * (3 * T * 2560 * 2 + 6 * T * 1280 * 2
                           + 3 * T * 5120 * 2)
    s_ops, s_bytes = flops.selective_scan_per_step(cfg)
    assert s_ops == 2 * 3 * 7 * T * c * 16
    assert s_bytes == 2 * T * 4 * (8 * c + 8 * 16)
    # the scan is bound by memory on paper: 3.3 ms against 0.14
    assert s_bytes / 819e9 > 20 * s_ops / 197e12


def test_reference_param_order_fits_the_flops_shapes():
    reference = harness.load_module("reference", CONFIG)
    flops, cfg = harness.load_module("flops", CONFIG), cfg_file()
    assert [s for _, s, _ in reference._spec(cfg)] == \
        flops.param_shapes(cfg)


def test_configuration_file_states_the_cut():
    cfg = cfg_file()
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    entry = [c for c in bench["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers",
                                                  "vocab_size"]
    assert cfg["layer_ids_published"] == [0, 1, 16, 17, 18, 19]
    assert cfg["num_hidden_layers"] == len(cfg["layer_ids_published"])
    assert cfg["vocab_held"] == [0, 25008] and cfg["vocab_size"] == 25008
    assert cfg["vocab_size_published"] == 8 * cfg["vocab_size"] == 200064
    for key in ("mamba", "differential_pairing", "lam0", "sliding_window",
                "positions", "init", "optimizer", "seq_len", "recompute"):
        assert key in cfg["assumed"], key
    assert "float32" in cfg["precision_stated"]


def synthetic_run(**over):
    """A traced window of 10 steps: 40 ms of scan kernels and 60 ms of
    flash attention a step."""
    flops, cfg = harness.load_module("flops", CONFIG), cfg_file()
    call = '%%%s.1 = bf16[8] custom-call(), custom_call_target=' \
        '"tpu_custom_call", metadata={op_name="jit(f)/%s/pallas_call"}'
    tiles = 'flash_attention_tiles{mask="sliding_window",which="%s"}'
    run = {
        "cfg": cfg, "steps": 10, "records": 10, "window_s": 6.0,
        "device": {"count": 1},
        "peaks": harness.peaks_for("TPU v5 lite"), "flops": flops,
        "before": {"counters": {}, "gauges": {}},
        "after": {"counters": {},
                  "gauges": {tiles % "walked": 93.0, tiles % "causal": 528.0}},
        "trace": {"busy_s": 5.9, "window_s": 6.0, "by_name": {
            call % ("selective_scan_fwd", "selective_scan_fwd"): 0.1,
            call % ("selective_scan_bwd", "selective_scan_bwd"): 0.3,
            call % ("flash_attention_dkv", "flash_attention_dkv"): 0.6,
            "%fusion.7 = f32[] fusion()": 1.0}},
    }
    run.update(over)
    return run, flops, cfg


def read(name, run):
    return harness.load_module("metrics", name).read(run)


def test_metrics_on_a_synthetic_run():
    run, flops, cfg = synthetic_run()
    assert read("hybrid_step_device_ms", run) == pytest.approx(590.0)
    assert read("selective_scan_ms_per_step", run) == pytest.approx(40.0)
    assert read("hybrid_attention_ms_per_step", run) == pytest.approx(60.0)
    assert read("attention_tiles_walked_pct", run) == pytest.approx(
        100 * 93 / 528)
    need = flops.train_flops_per_record(cfg)
    assert read("hybrid_step_mfu", run) == pytest.approx(
        100 * need * (10 / 6.0) / 197e12)
    s_ops, s_bytes = flops.selective_scan_per_step(cfg)
    assert read("selective_scan_roofline_pct", run) == pytest.approx(
        100 * (s_bytes / 819e9) / 40e-3)
    a_flops, a_bytes = flops.attention_per_step(cfg)
    assert read("hybrid_attention_roofline_pct", run) == pytest.approx(
        100 * (a_flops / 197e12) / 60e-3)
    for name in ("hybrid_step_mfu", "selective_scan_roofline_pct",
                 "hybrid_attention_roofline_pct"):
        assert 0 < read(name, run) < 100


@pytest.mark.parametrize("name", METRICS)
def test_a_program_without_the_counters_or_kernels_reads_nothing(name):
    """The parent of this cell: no such kernels, no tile gauge.  Each
    reader returns ``None`` and does not raise."""
    run, _, _ = synthetic_run(
        after={"counters": {}, "gauges": {}},
        trace={"busy_s": 0.0, "window_s": 6.0, "by_name": {}}, steps=0,
        records=0)
    assert read(name, run) is None


@pytest.mark.parametrize("name", METRICS)
def test_each_metric_is_declared_for_the_cell_alone(name):
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    entry = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(entry) == 1 and entry[0]["workloads"] == [CELL]
    assert entry[0]["moves"] == "train_records_per_s"


def test_no_accepted_metric_names_the_cell():
    """A cell may not be appended to an accepted metric's list (PERF.md,
    Open questions): it reports the metrics its own PR added."""
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    named = [m["name"] for m in bench["per_layer"]
             if CELL in m.get("workloads", [])]
    assert sorted(named) == sorted(METRICS)


# ------------------------------------------------- the control, at toy size
@pytest.fixture(scope="module")
def control_readings():
    from benchmark import control
    return control.read_cell(
        CELL, 2 ** 31 + 23, ["round:fp8", "round:bf16", "fault:half_batch"],
        TOY_CELL, TOY_CFG, require_chip=False)


def median_gap(rec, key):
    return rec[key]["value" if key == "loss" else "median_gap"]


@pytest.mark.parametrize("key", ["grad", "dparam"])
def test_float8_control_stands_clear_of_bfloat16(control_readings, key):
    """The reference in float8 in the program's place reads well above
    the reference in the configuration's own bfloat16 (the readings the
    limits were set from are the chip's: PERF.md)."""
    fp8 = median_gap(control_readings["round:fp8"], key)
    bf16 = median_gap(control_readings["round:bf16"], key)
    assert fp8 > 3 * bf16 > 0, (fp8, bf16)


def test_half_batch_fault_is_not_correct(control_readings):
    """Half of the loss positions left out: the loss and the first
    moment halve (Adam's step does not see a gradient's scale, so the
    parameters' change is no witness here)."""
    half = control_readings["fault:half_batch"]
    assert median_gap(half, "loss") > 0.3
    assert median_gap(half, "grad") > 0.05
