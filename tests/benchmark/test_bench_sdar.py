"""The ``sdar-30b-a3b-chat.blockdiff_sft_hbm`` cell at a toy size on the
CPU, through the same ``run_cell`` the chip runs: the kernels on their
Pallas branch (interpreted), the scan engine, the comparison with the
plain reference; then the cell's FLOP functions by hand and each of its
metrics on a synthetic ``run``."""

import json
import os

import numpy as np
import pytest

from benchmark import harness

CELL = "sdar-30b-a3b-chat.blockdiff_sft_hbm"
CONFIG = "sdar-30b-a3b-chat"
# two layers of 8 query heads over 2 K/V heads of 64, 8 of 32 experts
# held with 4 a token, 128-token sequences (256 positions: one flash
# tile a half), an eighth of a 776-id vocabulary
TOY_CELL = dict(rows=4)
TOY_CFG = dict(seq_len=128, num_hidden_layers=2, hidden_size=64,
               head_dim=64, num_attention_heads=8, num_key_value_heads=2,
               num_experts_published=32, experts_held=[8, 8],
               num_experts=8, num_experts_per_tok=4,
               moe_intermediate_size=32, vocab_size=97, vocab_held=[0, 97],
               vocab_size_published=776, initializer_range=0.2)
METRICS = ["sparse_step_mfu", "sparse_step_device_ms",
           "grouped_matmul_ms_per_step", "grouped_matmul_roofline_pct",
           "masked_attention_ms_per_step", "masked_attention_roofline_pct",
           "moe_rows_per_step", "expert_load_max_over_mean"]
# the accepted metrics of PR 25: a cell listed under a metric has to
# report it, two of these read nothing in any cell since PR 26, and the
# others' lists are not this cell's PR's to change: it stays off all six
SHARED = ["host_dispatch_ms_per_step", "host_sync_ms_per_step",
          "boundary_host_ms_per_step", "callback_host_ms_per_step",
          "finite_check_device_ms_per_step", "optimizer_kernel_ms_per_step"]


def run(trace=False, seed=2 ** 31 + 11, limits=None):
    cell = dict(TOY_CELL, **({"limits": limits} if limits else {}))
    return harness.run_cell(CELL, seed, 1.0, trace, require_chip=False,
                            cell_override=cell, cfg_override=TOY_CFG)


def test_cell_end_to_end_matches_the_reference_in_float32(one_chip,
                                                          f32_program):
    """The program's first epoch (grouped products, masked flash
    attention, fused Adam, all interpreted) against the plain reference:
    in float32 they agree to rounding."""
    line = run(limits={"loss": 1e-5, "grad": 1e-3, "dparam": 1e-3})
    assert line["correct"], line["compared"]
    assert set(line["metrics"]) == {"train_records_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line["info"]["engine"]) == ['{path="epoch_scan"}']
    json.dumps(line)


def test_traced_run_reports_the_cells_metrics(one_chip):
    from analytics_zoo_tpu.observability import get_registry, get_tracer
    before = get_registry().snapshot()
    line = run(trace=True)
    # counts are read anywhere; shares of a peak only on a known device
    assert {"sparse_step_device_ms", "moe_rows_per_step",
            "expert_load_max_over_mean"} <= set(line["metrics"])
    assert set(line["metrics"]) <= set(METRICS)
    # 256 positions x 4 picks x 2 layers, of which about a quarter are
    # held; the same rows on the program's own counters
    rows = line["metrics"]["moe_rows_per_step"]["value"]
    assert 0 < rows <= 256 * 4 * 2
    after = get_registry().snapshot()
    moved = harness.counter_delta(after, before, "moe_rows_routed_total")
    held = sum(v for k, v in moved.items() if 'held="1"' in k)
    absent = sum(v for k, v in moved.items() if 'held="0"' in k)
    steps = harness.counter_delta(after, before, "train_steps_total")[
        '{path="epoch_scan"}']
    # no row is lost: every assignment of every step is counted once
    assert held + absent == steps * 256 * 4 * 2
    assert line["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0
    assert any(e["name"] == "moe_stats_read"
               for e in get_tracer().events())
    assert harness.counter_delta(after, before, "spans_total")[
        '{name="moe_stats_read"}'] >= steps / 4        # one an epoch


def test_flops_by_hand():
    flops = harness.load_module("flops", CONFIG)
    cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                         CONFIG + ".json"))
    L, d, layers = 4096, 2048, 4
    assert flops.allowed_pairs(cfg) == L * L + L * 4
    assert flops.allowed_pairs(cfg) / (2 * L) ** 2 == pytest.approx(
        0.25, rel=1e-3)
    assert flops.expected_expert_rows(cfg) == 8192 * layers
    dense = 2 * d * (2 * 4096 + 2 * 512) + 2 * d * 128     # per position
    attn = 4 * (L * L + 4 * L) * 32 * 128
    experts = 8192 * layers * 3 * 2 * d * 768
    head = 2 * L * d * 18992
    want = layers * (dense * 2 * L + attn) + experts + head
    assert flops.forward_flops_per_record(cfg) == pytest.approx(want)
    # about 9 TFLOP a record
    assert 8.5e12 < flops.train_flops_per_record(cfg) < 9.5e12
    # the experts' share follows the rows really routed
    more = flops.train_flops_per_record(cfg, 2 * 8192 * layers)
    assert more - flops.train_flops_per_record(cfg) == pytest.approx(
        3 * experts)
    n = sum(int(np.prod(s)) for s in flops.param_shapes(cfg))
    assert 456e6 < n < 457e6
    a_flops, a_bytes = flops.attention_per_step(cfg)
    assert a_flops == pytest.approx(layers * 6 * attn / 2)
    assert a_bytes == layers * 6 * 2 * L * (32 + 4) * 128 * 2
    g_flops, g_bytes = flops.grouped_matmul_per_step(cfg, 8192 * layers)
    assert g_flops == pytest.approx(3 * experts)
    assert g_bytes == 9 * layers * 16 * d * 768 * 4 \
        + 9 * 8192 * layers * (d + 768) * 2


def test_reference_param_order_fits_the_flops_shapes():
    reference = harness.load_module("reference", CONFIG)
    flops = harness.load_module("flops", CONFIG)
    cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                         CONFIG + ".json"))
    assert [s for _, s, _ in reference._spec(cfg)] == \
        flops.param_shapes(cfg)


def synthetic_run(**over):
    """A traced window of 10 steps: 10 ms of grouped products and 30 ms
    of flash attention a step, 32,000 rows a step routed to the held
    experts."""
    flops = harness.load_module("flops", CONFIG)
    cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                         CONFIG + ".json"))
    call = '%%%s.1 = bf16[8] custom-call(), custom_call_target=' \
        '"tpu_custom_call", metadata={op_name="jit(f)/%s/pallas_call"}'
    run = {
        "cfg": cfg, "steps": 10, "records": 10, "window_s": 2.5,
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
        "trace": {"busy_s": 2.4, "window_s": 2.5, "by_name": {
            call % ("grouped_matmul_fwd", "grouped_matmul_fwd"): 0.06,
            call % ("grouped_matmul_drhs", "grouped_matmul_drhs"): 0.04,
            call % ("flash_attention_dkv", "flash_attention_dkv"): 0.3,
            "%fusion.7 = f32[] fusion()": 1.0}},
    }
    run.update(over)
    return run, flops, cfg


def read(name, run):
    return harness.load_module("metrics", name).read(run)


def test_metrics_on_a_synthetic_run():
    run, flops, cfg = synthetic_run()
    assert read("moe_rows_per_step", run) == 32000
    assert read("expert_load_max_over_mean", run) == 2.5
    assert read("sparse_step_device_ms", run) == pytest.approx(240.0)
    assert read("grouped_matmul_ms_per_step", run) == pytest.approx(10.0)
    assert read("masked_attention_ms_per_step", run) == pytest.approx(30.0)
    need = flops.train_flops_per_record(cfg, 32000)
    assert read("sparse_step_mfu", run) == pytest.approx(
        100 * need * 4 / 197e12)
    g_flops, g_bytes = flops.grouped_matmul_per_step(cfg, 32000)
    assert read("grouped_matmul_roofline_pct", run) == pytest.approx(
        100 * max(g_flops / 197e12, g_bytes / 819e9) / 10e-3)
    a_flops, a_bytes = flops.attention_per_step(cfg)
    assert read("masked_attention_roofline_pct", run) == pytest.approx(
        100 * max(a_flops / 197e12, a_bytes / 819e9) / 30e-3)
    # the experts' float32 matrices are 3.6 GB a step (nine passes) and
    # the rows 1.6 GB: 6.4 ms at the chip's bandwidth, so 10 ms reads 64 %
    assert g_bytes / 819e9 > g_flops / 197e12
    assert 50 < read("grouped_matmul_roofline_pct", run) < 100
    assert read("masked_attention_roofline_pct", run) < 100


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


@pytest.mark.parametrize("name", SHARED)
def test_the_accepted_lists_do_not_name_the_cell(name):
    """A cell listed under a metric has to report it, and two of these
    read nothing in any cell since PR 26: the cell stays off all six."""
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    entry = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(entry) == 1 and CELL not in entry[0]["workloads"]

