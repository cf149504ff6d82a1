"""The ``smallthinker-21ba3b-instruct.sft_16k_hbm`` cell at a toy size on
the CPU, through the same ``run_cell`` the chip runs: flash attention
(interpreted) in its two-pass backward under the window and the causal
mask at groups of 7, the router placed before attention, the ReGLU
experts' grouped products, recomputed layers, the scan engine, the
comparison with the plain reference; then the cell's FLOP functions by
hand and each of its metrics on a synthetic ``run``."""

import json
import os

import numpy as np
import pytest

from benchmark import harness

CELL = "smallthinker-21ba3b-instruct.sft_16k_hbm"
CONFIG = "smallthinker-21ba3b-instruct"
# layers 0 (global, no positions) and 1 (128-key window, rotary) of 7
# heads of 128 on one K/V head, 4 of 16 experts held (rank 1) with 3 a
# token, 768-token sequences (three flash tiles: the window leaves one
# out, the reference's blocks read a band), a quarter of a 388-id
# vocabulary
TOY_CELL = dict(rows=4)
TOY_CFG = dict(seq_len=768, num_hidden_layers=2, hidden_size=64,
               num_attention_heads=7, num_key_value_heads=1,
               moe_num_primary_experts_published=16, experts_held=[4, 4],
               moe_num_primary_experts=4, moe_num_active_primary_experts=3,
               moe_ffn_hidden_size=32, vocab_size=97, vocab_held=[0, 97],
               vocab_size_published=388, sliding_window_size=128,
               initializer_range=0.2,
               recompute=dict(decoder_layers=True, loss_chunk_rows=256))
METRICS = ["windowed_step_mfu", "windowed_step_device_ms",
           "windowed_attention_ms_per_step",
           "windowed_attention_bwd_ms_per_step",
           "windowed_attention_roofline_pct", "prerouted_moe_rows_per_step",
           "prerouted_expert_load_max_over_mean", "prerouted_moe_ms_per_step",
           "prerouted_moe_roofline_pct"]
# read on any program that runs the cell (the parent cannot): the device's
# idle share, its peak memory, the host's dispatch
WHOLE_RUN = ["windowed_device_idle_pct", "windowed_peak_hbm_gib",
             "windowed_host_dispatch_ms_per_step"]


def cfg_of():
    return harness.load_json(os.path.join(harness.HERE, "configs",
                                          CONFIG + ".json"))


@pytest.fixture
def two_pass(monkeypatch):
    """The backward in the form the cell's 16,384 positions take, at the
    toy's 768 (a budget nothing fits)."""
    from analytics_zoo_tpu.ops import pallas_attention
    monkeypatch.setattr(pallas_attention, "_RESIDENT_VMEM", 0)


def run(trace=False, seed=2 ** 31 + 11, limits=None):
    cell = dict(TOY_CELL, **({"limits": limits} if limits else {}))
    return harness.run_cell(CELL, seed, 1.0, trace, require_chip=False,
                            cell_override=cell, cfg_override=TOY_CFG)


def builds_since(before):
    from analytics_zoo_tpu.observability import get_registry
    return harness.counter_delta(get_registry().snapshot(), before,
                                 "fused_kernel_builds_total")


def test_cell_end_to_end_matches_the_reference_in_float32(one_chip, two_pass,
                                                          f32_program):
    """The program's first epoch (flash attention under both masks with
    its two-pass backward, the router on the attention input, ReGLU
    grouped products, recomputed layers, the chunked untied head, Adam,
    all interpreted) against the plain reference: in float32 they agree
    to rounding."""
    from analytics_zoo_tpu.observability import get_registry
    before = get_registry().snapshot()
    line = run(limits={"loss": 1e-5, "grad": 1e-3, "dparam": 1e-3})
    assert line["correct"], line["compared"]
    assert set(line["metrics"]) == {"train_records_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line["info"]["engine"]) == ['{path="epoch_scan"}']
    json.dumps(line)
    moved = builds_since(before)
    assert moved.get('{kernel="flash_attention",path="pallas"}')
    assert '{kernel="flash_attention",path="lax"}' not in moved
    assert moved.get(
        '{kernel="flash_attention_backward",path="two_pass"}')
    assert '{kernel="flash_attention_backward",path="one_pass"}' \
        not in moved


def test_traced_run_reports_the_cells_metrics(one_chip, two_pass):
    """Both build counters the issue names move where the program is
    traced (the engine's path is asserted the same way), the layers'
    expert counts are published, and the line holds the cell's metrics
    and no accepted one."""
    from analytics_zoo_tpu.observability import get_registry
    before = get_registry().snapshot()
    line = run(trace=True)
    moved = builds_since(before)
    assert moved.get('{kernel="moe_router",path="attention_input"}')
    assert moved.get('{kernel="moe_experts",path="reglu"}')
    assert moved['{kernel="moe_router",path="attention_input"}'] == \
        moved['{kernel="moe_experts",path="reglu"}']
    # counts are read anywhere; kernels' events and shares of a peak
    # only on a known device
    assert {"windowed_step_device_ms", "prerouted_moe_rows_per_step",
            "prerouted_expert_load_max_over_mean"} <= set(line["metrics"])
    assert set(line["metrics"]) <= set(METRICS + WHOLE_RUN)
    m = line["metrics"]
    rows = m["prerouted_moe_rows_per_step"]["value"]
    assert 0 < rows <= 768 * 3 * 2
    after = get_registry().snapshot()
    routed = harness.counter_delta(after, before, "moe_rows_routed_total")
    held = sum(v for k, v in routed.items() if 'held="1"' in k)
    absent = sum(v for k, v in routed.items() if 'held="0"' in k)
    steps = harness.counter_delta(after, before, "train_steps_total")[
        '{path="epoch_scan"}']
    # no row is lost: every assignment of both layers is counted
    assert held + absent == steps * 768 * 3 * 2
    assert len({k.split('layer="')[1].split('"')[0] for k in routed}) == 2
    assert m["prerouted_expert_load_max_over_mean"]["value"] >= 1
    kept = {k: v for k, v in after["gauges"].items()
            if k.startswith("train_recompute_kept_bytes")}
    # out (768 x 7 x 128 bf16) of both layers
    assert kept['train_recompute_kept_bytes{name="flash_attention_out"}'] \
        >= 2 * 768 * 7 * 128 * 2


def test_flops_by_hand():
    flops = harness.load_module("flops", CONFIG)
    cfg = cfg_of()
    T, d, W, layers = 16384, 2560, 4096, 4
    causal = T * (T + 1) // 2
    window = W * (W + 1) // 2 + (T - W) * W
    assert (causal, window) == (134225920, 58722304)
    assert [flops.layer_pairs(cfg, l) for l in range(4)] == \
        [causal, window, window, window]
    assert flops.allowed_pairs(cfg) == causal + 3 * window
    rows = 16384 * 6 * 16 // 64 * 4
    assert flops.expected_expert_rows(cfg) == rows == 98304
    per_position = 2 * (2 * d * 3584 + 2 * d * 512) + 2 * d * 64
    maps = 2 * (causal + 3 * window) * 28 * 256
    experts = rows * 3 * 2 * d * 768
    head = 2 * (T - 1) * d * 37984
    want = layers * per_position * T + maps + experts + head
    assert flops.forward_flops_per_record(cfg) == pytest.approx(want)
    # about 34.7 TFLOP a record, 13.35 of them attention's maps
    assert 34.5e12 < flops.train_flops_per_record(cfg) < 34.9e12
    assert 3 * maps == pytest.approx(13.35e12, rel=1e-3)
    more = flops.train_flops_per_record(cfg, 2 * rows)
    assert more - flops.train_flops_per_record(cfg) == pytest.approx(
        3 * experts)
    n = sum(int(np.prod(s)) for s in flops.param_shapes(cfg))
    assert n == 656529920
    a_flops, a_bytes = flops.attention_per_step(cfg)
    assert a_flops == pytest.approx(3 * maps)
    # compute-bound: 68 ms at the peak against 3.9 ms of traffic
    assert a_flops / 197e12 == pytest.approx(67.8e-3, rel=1e-2)
    q_side, kv_side = T * 28 * 128 * 2, T * 4 * 128 * 2
    assert a_bytes == layers * 6 * (q_side + kv_side)


def test_reference_param_order_fits_the_flops_shapes():
    reference = harness.load_module("reference", CONFIG)
    flops = harness.load_module("flops", CONFIG)
    cfg = cfg_of()
    assert [s for _, s, _ in reference._spec(cfg)] == \
        flops.param_shapes(cfg)


def test_the_configuration_holds_every_published_width():
    """Every number of the catalog's ``config`` under the same key,
    except the three keys ``reduced`` names, which stand beside their
    published values; the layouts are the published 52 entries whole,
    and the four layers held are one period of them."""
    cfg = cfg_of()
    published = dict(
        head_dim=128, hidden_size=2560, max_position_embeddings=16384,
        moe_ffn_hidden_size=768, moe_num_active_primary_experts=6,
        num_attention_heads=28, num_key_value_heads=4, rms_norm_eps=1e-6,
        rope_theta=1500000, sliding_window_size=4096)
    assert {k: cfg[k] for k in published} == published
    assert (cfg["moe_primary_router_apply_softmax"], cfg["norm_topk_prob"],
            cfg["rope_scaling"], cfg["tie_word_embeddings"]) == (
                True, True, None, False)
    layout = [0, 1, 1, 1] * 13
    assert cfg["rope_layout"] == cfg["sliding_window_layout"] == layout
    assert cfg["reduced"] == ["num_hidden_layers", "moe_num_primary_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["moe_num_primary_experts"],
            cfg["vocab_size"]) == (4, 16, 37984)
    assert (cfg["num_hidden_layers_published"],
            cfg["moe_num_primary_experts_published"],
            cfg["vocab_size_published"]) == (52, 64, 151936)
    assert cfg["experts_held"] == [0, 16] and cfg["vocab_held"] == [0, 37984]
    assert "4 chips" in cfg["deployment"]
    assert set(cfg["assumed"]) >= {"router_input", "experts", "window",
                                   "qk_norm", "auxiliary_loss", "optimizer",
                                   "seq_len", "recompute"}


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(harness.HERE, "reference", CONFIG + ".py")
    source = open(path).read()
    assert "analytics_zoo_tpu" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source


def synthetic_run(**over):
    """A traced window of 10 steps: 300 ms of flash attention a step, 180
    of it the backward's two kernels, 98,000 rows a step routed to the
    held experts."""
    flops = harness.load_module("flops", CONFIG)
    cfg = cfg_of()
    call = '%%%s.1 = bf16[8] custom-call(), custom_call_target=' \
        '"tpu_custom_call", metadata={op_name="jit(f)/%s/pallas_call"}'
    run = {
        "cfg": cfg, "steps": 10, "records": 10, "window_s": 6.5,
        "device": {"count": 1}, "peak_bytes": 15 * 2 ** 30,
        "peaks": harness.peaks_for("TPU v5 lite"), "flops": flops,
        "before": {"counters": {}, "gauges": {}},
        "after": {
            "counters": {
                'span_self_seconds_total{name="train_epoch_scan"}': 0.05,
                'moe_rows_routed_total{layer="a",held="1"}': 500000.0,
                'moe_rows_routed_total{layer="b",held="1"}': 480000.0,
                'moe_rows_routed_total{layer="a",held="0"}': 9e6},
            "gauges": {'moe_expert_load_max_over_mean{layer="a"}': 1.25,
                       'moe_expert_load_max_over_mean{layer="b"}': 1.75}},
        "trace": {"busy_s": 6.0, "window_s": 6.5, "by_name": {
            call % ("flash_attention_fwd", "flash_attention_fwd"): 1.2,
            call % ("flash_attention_dq", "flash_attention_dq"): 0.8,
            call % ("flash_attention_dkv", "flash_attention_dkv"): 1.0,
            # another family's kernels are not this metric's
            call % ("grouped_matmul_fwd", "grouped_matmul_fwd"): 0.2,
            "%fusion.7 = f32[] fusion()": 1.0}},
    }
    run.update(over)
    return run, flops, cfg


def read(name, run):
    return harness.load_module("metrics", name).read(run)


def test_metrics_on_a_synthetic_run():
    run, flops, cfg = synthetic_run()
    assert read("prerouted_moe_rows_per_step", run) == 98000
    assert read("prerouted_expert_load_max_over_mean", run) == 1.75
    assert read("windowed_step_device_ms", run) == pytest.approx(600.0)
    assert read("windowed_attention_ms_per_step", run) == \
        pytest.approx(300.0)
    assert read("windowed_attention_bwd_ms_per_step", run) == \
        pytest.approx(180.0)
    need = flops.train_flops_per_record(cfg, 98000)
    assert read("windowed_step_mfu", run) == pytest.approx(
        100 * need * 10 / 6.5 / 197e12)
    a_flops, a_bytes = flops.attention_per_step(cfg)
    assert a_flops / 197e12 > a_bytes / 819e9
    assert read("windowed_attention_roofline_pct", run) == pytest.approx(
        100 * (a_flops / 197e12) / 300e-3)
    assert read("prerouted_moe_ms_per_step", run) == pytest.approx(20.0)
    g_flops, g_bytes = flops.grouped_matmul_per_step(cfg, 98000)
    assert g_flops == pytest.approx(9 * 2 * 98000 * 2560 * 768)
    assert g_bytes == pytest.approx(9 * 4 * 16 * 2560 * 768 * 4
                                    + 9 * 98000 * (2560 + 768) * 2)
    assert read("prerouted_moe_roofline_pct", run) == pytest.approx(
        100 * max(g_flops / 197e12, g_bytes / 819e9) / 20e-3)
    for name in ("windowed_attention_roofline_pct", "windowed_step_mfu"):
        assert 0 < read(name, run) < 100
    assert read("windowed_device_idle_pct", run) == pytest.approx(
        100 / 13)
    assert read("windowed_peak_hbm_gib", run) == 15.0
    assert read("windowed_host_dispatch_ms_per_step", run) == \
        pytest.approx(5.0)


def test_the_one_pass_backward_is_the_backward_too():
    """Below the one-pass budget the backward is one kernel, read by the
    same metric."""
    call = '%%%s.1 = bf16[8] custom-call(), custom_call_target=' \
        '"tpu_custom_call", metadata={op_name="jit(f)/%s/pallas_call"}'
    run, _, _ = synthetic_run(trace={"busy_s": 6.0, "window_s": 6.5,
                                     "by_name": {
        call % ("flash_attention_fwd", "flash_attention_fwd"): 1.2,
        call % ("flash_attention_bwd", "flash_attention_bwd"): 1.5}})
    assert read("windowed_attention_bwd_ms_per_step", run) == \
        pytest.approx(150.0)
    assert read("windowed_attention_ms_per_step", run) == \
        pytest.approx(270.0)


@pytest.mark.parametrize("name", METRICS)
def test_a_program_without_the_counters_or_kernels_reads_nothing(name):
    """The parent of this cell: no expert counters, no such kernels.
    Each reader returns ``None`` and does not raise."""
    run, _, _ = synthetic_run(
        after={"counters": {}, "gauges": {}},
        trace={"busy_s": 0.0, "window_s": 2.5, "by_name": {}}, steps=0,
        records=0)
    assert read(name, run) is None


@pytest.mark.parametrize("name", METRICS + WHOLE_RUN)
def test_each_metric_is_declared_for_the_cell_alone(name):
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    entry = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(entry) == 1 and entry[0]["workloads"] == [CELL]
    assert entry[0]["moves"] == "train_records_per_s"


def test_no_accepted_list_names_the_cell():
    """A cell listed under a metric has to report it: the cell stays off
    every list of the accepted benchmark, the seven set-up metrics' too
    (PERF.md, Open questions 22).  Found by name, not by position."""
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    named = [m["name"] for m in bench["per_layer"] + bench["end_to_end"]
             if CELL in m.get("workloads", [])]
    assert sorted(named) == sorted(METRICS + WHOLE_RUN)
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
        CELL, 2 ** 31 + 23, ["round:fp8", "round:bf16", "fault:half_batch"],
        TOY_CELL, TOY_CFG, require_chip=False)
    limits = harness.load_cell(CELL)[1]["limits"]
    return {variant: correctness.judge(readings[variant], limits)["compared"]
            for variant in ("round:fp8", "round:bf16", "fault:half_batch")}


def test_the_cell_holds_the_worst_leaf():
    limits = harness.load_cell(CELL)[1]["limits"]
    assert set(limits) == {"loss", "grad", "dparam"}
    assert all(isinstance(v, float) for v in limits.values())
    assert set(harness.load_cell(CELL)[1]["limits_why"]) == set(limits)


@pytest.mark.parametrize("key", ["grad", "dparam"])
def test_float8_reads_well_above_bfloat16(control_readings, key):
    """The reference in float8 in the program's place reads, at the
    WORST leaf, well above the reference in the configuration's own
    bfloat16."""
    fp8, bf16 = (control_readings[v][key] for v in ("round:fp8",
                                                    "round:bf16"))
    assert fp8["of"] == bf16["of"] == "value"
    assert fp8["value"] > 3 * bf16["value"] > 0, (fp8, bf16)


def test_float8_control_is_not_correct(control_readings):
    """... and is not correct by the cell's limits, as the contract asks:
    by one of them, not by each (the readings the limits were set from
    are the chip's, at the cell's size: ``limits_why``)."""
    assert not all(c["ok"] for c in control_readings["round:fp8"].values())


def test_half_batch_fault_is_not_correct(control_readings):
    """Half of the loss positions left out: the loss and the first
    moment halve (Adam's step does not see a gradient's scale, so the
    parameters' change is the weaker witness)."""
    half = control_readings["fault:half_batch"]
    assert half["loss"]["value"] > 0.3 and not half["loss"]["ok"]
    assert half["grad"]["value"] > 0.2 and not half["grad"]["ok"]
