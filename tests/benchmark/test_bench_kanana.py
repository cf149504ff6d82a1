"""The ``kanana-2-30b-a3b-instruct-2601.sft_8k_hbm`` cell at a toy size
on the CPU, through the same ``run_cell`` the chip runs: the latent flash
kernels and the grouped products on their Pallas branch (interpreted),
recomputed layers, the scan engine, the comparison with the plain
reference; then the cell's FLOP functions by hand and each of its
metrics on a synthetic ``run``."""

import json
import os

import numpy as np
import pytest

from benchmark import harness

CELL = "kanana-2-30b-a3b-instruct-2601.sft_8k_hbm"
CONFIG = "kanana-2-30b-a3b-instruct-2601"
# 1 dense + 2 sparse layers of 2 heads at the kernels' (128 | 64 | 128)
# out of a 32-wide latent, 8 of 32 experts held with 4 a token, 256-token
# sequences (one flash tile), an eighth of a 776-id vocabulary
TOY_CELL = dict(rows=4)
TOY_CFG = dict(seq_len=256, num_hidden_layers=3, hidden_size=64,
               intermediate_size=96, num_attention_heads=2, kv_lora_rank=32,
               n_routed_experts_published=32, experts_held=[8, 8],
               n_routed_experts=8, num_experts_per_tok=4,
               moe_intermediate_size=32, vocab_size=97, vocab_held=[0, 97],
               vocab_size_published=776, initializer_range=0.2,
               selection_bias=dict(std=0.05, seed=7),
               recompute=dict(decoder_layers=True, loss_chunk_rows=64))
METRICS = ["latent_step_mfu", "latent_step_device_ms",
           "latent_attention_ms_per_step", "latent_attention_roofline_pct",
           "latent_moe_ms_per_step", "latent_moe_rows_per_step",
           "latent_expert_load_max_over_mean", "latent_moe_roofline_pct"]


def run(trace=False, seed=2 ** 31 + 11, limits=None):
    cell = dict(TOY_CELL, **({"limits": limits} if limits else {}))
    return harness.run_cell(CELL, seed, 1.0, trace, require_chip=False,
                            cell_override=cell, cfg_override=TOY_CFG)


def test_cell_end_to_end_matches_the_reference_in_float32(one_chip,
                                                          f32_program):
    """The program's first epoch (latent flash attention, grouped
    products, recomputed layers, the chunked untied head, Adam, all
    interpreted) against the plain reference: in float32 they agree to
    rounding."""
    from analytics_zoo_tpu.observability import get_registry
    before = get_registry().snapshot()
    line = run(limits={"loss": 1e-5, "grad": 1e-3, "dparam": 1e-3})
    assert line["correct"], line["compared"]
    assert set(line["metrics"]) == {"train_records_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line["info"]["engine"]) == ['{path="epoch_scan"}']
    json.dumps(line)
    moved = harness.counter_delta(get_registry().snapshot(), before,
                                  "fused_kernel_builds_total")
    assert moved.get('{kernel="flash_attention_latent",path="pallas"}')
    assert '{kernel="flash_attention_latent",path="lax"}' not in moved


def test_traced_run_reports_the_cells_metrics(one_chip):
    from analytics_zoo_tpu.observability import get_registry
    before = get_registry().snapshot()
    line = run(trace=True)
    # counts are read anywhere; shares of a peak only on a known device
    assert {"latent_step_device_ms", "latent_moe_rows_per_step",
            "latent_expert_load_max_over_mean"} <= set(line["metrics"])
    # all eight of the cell's and no accepted one
    assert set(line["metrics"]) <= set(METRICS)
    rows = line["metrics"]["latent_moe_rows_per_step"]["value"]
    assert 0 < rows <= 256 * 4 * 2
    after = get_registry().snapshot()
    moved = harness.counter_delta(after, before, "moe_rows_routed_total")
    held = sum(v for k, v in moved.items() if 'held="1"' in k)
    absent = sum(v for k, v in moved.items() if 'held="0"' in k)
    steps = harness.counter_delta(after, before, "train_steps_total")[
        '{path="epoch_scan"}']
    # no row is lost: every assignment of both sparse layers is counted
    assert held + absent == steps * 256 * 4 * 2
    assert len({k.split('layer="')[1].split('"')[0] for k in moved}) == 2
    assert line["metrics"]["latent_expert_load_max_over_mean"]["value"] >= 1
    kept = {k: v for k, v in after["gauges"].items()
            if k.startswith("train_recompute_kept_bytes")}
    # out (256 x 2 x 128 bf16) and lse (2 x 256 f32) of three layers
    assert kept['train_recompute_kept_bytes{name="flash_attention_out"}'] \
        >= 3 * 256 * 256 * 2


def test_flops_by_hand():
    flops = harness.load_module("flops", CONFIG)
    cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                         CONFIG + ".json"))
    T, d, layers = 8192, 2048, 6
    pairs = T * (T + 1) // 2
    assert flops.causal_pairs(cfg) == pairs
    assert flops.expected_expert_rows(cfg) == 8192 * 6 * 16 // 128 * 5
    attn = 2 * (d * 6144 + d * 576 + 512 * 8192 + 4096 * d)   # a position
    maps = 2 * pairs * 32 * (192 + 128)
    dense = 2 * 3 * d * 6144
    sparse = 2 * d * 128 + 2 * 3 * d * 1536
    experts = 30720 * 3 * 2 * d * 768
    head = 2 * (T - 1) * d * 16032
    want = layers * (attn * T + maps) + dense * T + 5 * sparse * T \
        + experts + head
    assert flops.forward_flops_per_record(cfg) == pytest.approx(want)
    # about 27 TFLOP a record, 12.4 of them attention's maps
    assert 26e12 < flops.train_flops_per_record(cfg) < 28e12
    assert 3 * layers * maps == pytest.approx(12.37e12, rel=1e-3)
    more = flops.train_flops_per_record(cfg, 2 * 30720)
    assert more - flops.train_flops_per_record(cfg) == pytest.approx(
        3 * experts)
    n = sum(int(np.prod(s)) for s in flops.param_shapes(cfg))
    assert n == 687502336
    a_flops, a_bytes = flops.attention_per_step(cfg)
    assert a_flops == pytest.approx(3 * layers * maps)
    # compute-bound: 63 ms at the peak against 7 ms of traffic
    assert a_flops / 197e12 == pytest.approx(62.8e-3, rel=1e-2)
    q_side, k_side, out = T * 32 * 192 * 2, T * 32 * 256 * 2 + T * 64 * 2, \
        T * 32 * 128 * 2
    assert a_bytes == layers * (3 * (q_side + k_side) + 3 * out)
    g_flops, g_bytes = flops.grouped_matmul_per_step(cfg, 30720)
    assert g_flops == pytest.approx(3 * experts)
    assert g_bytes == 9 * 5 * 16 * d * 768 * 4 + 9 * 30720 * (d + 768) * 2


def test_reference_param_order_fits_the_flops_shapes():
    reference = harness.load_module("reference", CONFIG)
    flops = harness.load_module("flops", CONFIG)
    cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                         CONFIG + ".json"))
    assert [s for _, s, _ in reference._spec(cfg)] == \
        flops.param_shapes(cfg)


def test_the_configuration_holds_every_published_width():
    """Every number of the catalog's ``config`` under the same key,
    except the three keys ``reduced`` names, which stand beside their
    published values."""
    cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                         CONFIG + ".json"))
    published = dict(
        first_k_dense_replace=1, head_dim=64, hidden_size=2048,
        intermediate_size=6144, kv_lora_rank=512,
        max_position_embeddings=32768, moe_intermediate_size=768,
        moe_layer_freq=1, n_group=1, n_shared_experts=2,
        num_attention_heads=32, num_experts_per_tok=6,
        num_key_value_heads=32, qk_head_dim=192, qk_nope_head_dim=128,
        qk_rope_head_dim=64, rms_norm_eps=1e-6, rope_theta=1000000,
        routed_scaling_factor=2.448, topk_group=1, v_head_dim=128)
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (6, 16, 16032)
    assert (cfg["num_hidden_layers_published"],
            cfg["n_routed_experts_published"],
            cfg["vocab_size_published"]) == (48, 128, 128256)
    assert set(cfg["assumed"]) >= {"selection_bias", "seq_len", "optimizer",
                                   "recompute", "column_order"}


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(harness.HERE, "reference", CONFIG + ".py")
    source = open(path).read()
    assert "analytics_zoo_tpu" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source


def synthetic_run(**over):
    """A traced window of 10 steps: 20 ms of grouped products and 300 ms
    of latent flash attention a step, 32,000 rows a step routed to the
    held experts."""
    flops = harness.load_module("flops", CONFIG)
    cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                         CONFIG + ".json"))
    call = '%%%s.1 = bf16[8] custom-call(), custom_call_target=' \
        '"tpu_custom_call", metadata={op_name="jit(f)/%s/pallas_call"}'
    run = {
        "cfg": cfg, "steps": 10, "records": 10, "window_s": 5.5,
        "device": {"count": 1},
        "peaks": harness.peaks_for("TPU v5 lite"), "flops": flops,
        "before": {"counters": {}, "gauges": {}},
        "after": {
            "counters": {
                'moe_rows_routed_total{layer="a",held="1"}': 170000.0,
                'moe_rows_routed_total{layer="b",held="1"}': 150000.0,
                'moe_rows_routed_total{layer="a",held="0"}': 9e6},
            "gauges": {'moe_expert_load_max_over_mean{layer="a"}': 1.5,
                       'moe_expert_load_max_over_mean{layer="b"}': 2.5}},
        "trace": {"busy_s": 5.0, "window_s": 5.5, "by_name": {
            call % ("grouped_matmul_fwd", "grouped_matmul_fwd"): 0.12,
            call % ("grouped_matmul_drhs", "grouped_matmul_drhs"): 0.08,
            call % ("flash_attention_latent_fwd",
                    "flash_attention_latent_fwd"): 1.0,
            call % ("flash_attention_latent_dkv",
                    "flash_attention_latent_dkv"): 2.0,
            # another cell's kernels are not this metric's
            call % ("flash_attention_dkv", "flash_attention_dkv"): 7.0,
            "%fusion.7 = f32[] fusion()": 1.0}},
    }
    run.update(over)
    return run, flops, cfg


def read(name, run):
    return harness.load_module("metrics", name).read(run)


def test_metrics_on_a_synthetic_run():
    run, flops, cfg = synthetic_run()
    assert read("latent_moe_rows_per_step", run) == 32000
    assert read("latent_expert_load_max_over_mean", run) == 2.5
    assert read("latent_step_device_ms", run) == pytest.approx(500.0)
    assert read("latent_moe_ms_per_step", run) == pytest.approx(20.0)
    assert read("latent_attention_ms_per_step", run) == pytest.approx(300.0)
    need = flops.train_flops_per_record(cfg, 32000)
    assert read("latent_step_mfu", run) == pytest.approx(
        100 * need * 10 / 5.5 / 197e12)
    a_flops, a_bytes = flops.attention_per_step(cfg)
    assert read("latent_attention_roofline_pct", run) == pytest.approx(
        100 * (a_flops / 197e12) / 300e-3)
    assert 0 < read("latent_attention_roofline_pct", run) < 100
    g_flops, g_bytes = flops.grouped_matmul_per_step(cfg, 32000)
    assert read("latent_moe_roofline_pct", run) == pytest.approx(
        100 * max(g_flops / 197e12, g_bytes / 819e9) / 20e-3)
    assert 0 < read("latent_moe_roofline_pct", run) < 100
    assert 0 < read("latent_step_mfu", run) < 100


@pytest.mark.parametrize("name", METRICS)
def test_a_program_without_the_counters_or_kernels_reads_nothing(name):
    """The parent of this cell: no expert counters, no such kernels.
    Each reader returns ``None`` and does not raise."""
    run, _, _ = synthetic_run(
        after={"counters": {}, "gauges": {}},
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
    """A cell listed under a metric has to report it: the cell stays
    off every list of the accepted benchmark (PERF.md, Open questions
    22)."""
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    named = [m["name"] for m in bench["per_layer"] + bench["end_to_end"]
             if CELL in m.get("workloads", [])]
    assert sorted(named) == sorted(METRICS)
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["workloads"][-1]["chips"] == 1


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


@pytest.mark.parametrize("key", ["grad", "dparam"])
def test_float8_control_is_not_correct(control_readings, key):
    """The reference in float8 in the program's place reads, at the
    WORST leaf, well above the reference in the configuration's own
    bfloat16 and above the cell's limit (the readings the limits were
    set from are the chip's, at the cell's size: ``limits_why``)."""
    fp8, bf16 = (control_readings[v][key] for v in ("round:fp8",
                                                    "round:bf16"))
    assert fp8["of"] == bf16["of"] == "value"
    assert fp8["value"] > 3 * bf16["value"] > 0, (fp8, bf16)
    assert not fp8["ok"] and fp8["value"] > 3 * fp8["limit"]


def test_half_batch_fault_is_not_correct(control_readings):
    """Half of the loss positions left out: the loss and the first
    moment halve (Adam's step does not see a gradient's scale, so the
    parameters' change is the weaker witness)."""
    half = control_readings["fault:half_batch"]
    assert half["loss"]["value"] > 0.3 and not half["loss"]["ok"]
    assert half["grad"]["value"] > 0.2 and not half["grad"]["ok"]
    assert not half["dparam"]["ok"]
