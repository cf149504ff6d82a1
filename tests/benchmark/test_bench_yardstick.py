"""The yardstick's own arithmetic: FLOP and byte functions against hand
counts, the trace reduction on a trace built by hand, the comparison's
measures, and BENCHMARK.json against the files it names."""

import json
import os

import numpy as np
import pytest

from benchmark import correctness, harness, trace_reduce

BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


def cfg_of(name):
    return harness.load_json(os.path.join(harness.HERE, "configs",
                                          name + ".json"))


# ------------------------------------------------------------------ flops
def test_resnet50_flops_against_the_published_count():
    flops = harness.load_module("flops", "resnet50")
    cfg = cfg_of("resnet50")
    fwd = flops.forward_flops_per_record(cfg)
    # 4.09 GMACs forward at 224x224 is the figure every ResNet-50 table
    # carries (He et al. report 3.8 "GFLOPs" for the v1 stride layout)
    assert 4.0e9 < fwd / 2 < 4.2e9
    assert flops.train_flops_per_record(cfg) == 3 * fwd
    # by hand: the stem is 112*112 outputs x 7*7*3 x 64
    stem = 2.0 * 112 * 112 * 7 * 7 * 3 * 64
    assert flops._convs(cfg)[0] == (112, 7, 3, 64, 1)
    assert stem == 2.0 * 118013952
    # 25.5 M parameters, 53 convolutions
    n = sum(int(np.prod(s)) for s in flops.param_shapes(cfg))
    assert 25.4e6 < n < 25.7e6
    assert len(flops._convs(cfg)) == 53


def test_openai_gpt_flops_by_hand():
    flops = harness.load_module("flops", "openai-gpt")
    cfg = cfg_of("openai-gpt")
    d, f, t, layers = 768, 3072, 512, 12
    dense = 2 * d * 3 * d + 2 * d * d + 4 * d * f         # per token
    attn = 4 * d * (t + 1) / 2                              # causal mean
    want = layers * (dense + attn) * t + 2 * d * 2
    assert flops.forward_flops_per_record(cfg) == pytest.approx(want)
    # about 8.8 TFLOP a 32-sequence step
    step = flops.train_flops_per_record(cfg) * cfg["batch_size"]
    assert 8.5e12 < step < 9.2e12
    n = sum(int(np.prod(s)) for s in flops.param_shapes(cfg))
    assert 116e6 < n < 117.5e6
    a_flops, a_bytes = flops.attention_per_step(cfg)
    one = 2 * 32 * t * d * (t + 1) / 2
    assert a_flops == pytest.approx(layers * 6 * one)
    assert a_bytes == layers * 12 * 32 * t * d * 4


def test_optimizer_bytes_and_kernel_leaves():
    common = harness.load_module("flops", "common")
    # 1024 and 2048 elements go to the kernel; 1000 and 1536 do not
    shapes = [(1024,), (2, 1024), (1000,), (1536,), (8,)]
    assert common.kernel_leaf_elements(shapes) == 3072
    assert common.optimizer_bytes("sgd", 10) == 200
    assert common.optimizer_bytes("adam", 10) == 280
    peaks = harness.peaks_for("TPU v5 lite")
    assert common.roofline_seconds(197e12, 1.0, peaks) == (1.0, "compute")
    assert common.roofline_seconds(1.0, 819e9, peaks) == (1.0, "memory")


# ------------------------------------------------------------------ trace
def hand_trace():
    us = 1000
    ops = [("%while.1 = ...", 0, 100 * us),          # holds the next two
           ("%fusion.1 = f32[] fusion(...)", 10 * us, 30 * us),
           ("%custom-call.2 = ... _sgd_kernel", 50 * us, 20 * us),
           ("%fusion.1 = f32[] fusion(...)", 150 * us, 50 * us),
           ("%copy.3 = ...", 400 * us, 100 * us)]
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [("jit_epoch", 0, 500 * us)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                ("bench_boundary", 300 * us, 1 * us),
                ("other", 120 * us, 5 * us)]}]},
    ]


def test_trace_reduction_on_a_hand_built_trace():
    red = trace_reduce.reduce(hand_trace(), window_s=1e-3)
    # union: [0,100] + [150,200] + [400,500] microseconds
    assert red["busy_s"] == pytest.approx(250e-6)
    assert red["window_s"] == 1e-3 and red["devices"] == 1
    assert red["events"] == 5 and red["marks"] == 1
    # own time: the while keeps what its two children do not cover
    assert red["by_name"]["%while.1 = ..."] == pytest.approx(50e-6)
    assert red["by_name"]["%fusion.1 = f32[] fusion(...)"] == \
        pytest.approx(80e-6)
    assert trace_reduce.seconds_of(red, ["_sgd_kernel"]) == \
        pytest.approx(20e-6)
    assert trace_reduce.seconds_of(red, ["_adam_kernel"]) == 0
    # gaps: 100-150 (no boundary inside), 200-400 (the boundary at 300)
    assert red["gaps"] == [(200000, True), (50000, False)]
    out = trace_reduce.breakdown(red, "epoch_scan")
    assert out["idle_gaps"] == [["epoch_boundary", 200e-6],
                                ["inside_dispatch", 50e-6]]
    assert out["device_ops"][0] == ["copy", pytest.approx(100e-6)]
    assert out["device_ops"][1] == ["fusion", pytest.approx(80e-6)]
    assert ["tpu_custom_call", 0.0] not in out["device_ops"]
    assert trace_reduce.op_kind(
        '%closed_call.9 = f32[8,128] custom-call(f32[4] %x), '
        'custom_call_target="tpu_custom_call"') == "tpu_custom_call"
    assert trace_reduce.op_kind("%multiply_reduce_fusion.147 = (bf16[2])"
                                ) == "multiply_reduce_fusion"
    assert trace_reduce.breakdown(red, "per_step")["idle_gaps"][0][0] == \
        "step_boundary"


def test_trace_without_a_device_is_refused():
    with pytest.raises(ValueError):
        trace_reduce.reduce(hand_trace()[1:])
    empty = [{"name": "/device:TPU:0",
              "lines": [{"name": "XLA Ops", "events": []}]}]
    with pytest.raises(ValueError):
        trace_reduce.reduce(empty)


def test_two_devices_average_their_busy_time():
    planes = hand_trace()
    second = json.loads(json.dumps(planes[0]))
    second["name"] = "/device:TPU:1"
    second["lines"][0]["events"] = [["%fusion.9", 0, 50000]]
    red = trace_reduce.reduce(planes + [second])
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx((250e-6 + 50e-6) / 2)


# ------------------------------------------------------------- comparison
def test_worst_leaf_measures_the_gap_of_norms():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-6}
    got = {"a": 1.1, "b": 2.0, "c": 2e-6}
    rec = correctness.worst_leaf(got, ref)
    # c doubles, but is held against the median leaf's norm, not its own
    assert rec["leaf"] == "a" and rec["value"] == pytest.approx(0.1)
    assert rec["median_gap"] == pytest.approx(1e-6)
    assert correctness.worst_leaf({"a": 1.0}, ref)["value"] == float("inf")


def test_compare_and_judge():
    ref = {"loss": [2.0, 4.0, 6.0], "grad1_norm": {"w": 1.0, "k": 1e-9},
           "moment_norm": {"w": 1.0, "k": 1e-9},
           "dparam_norm": {"w": 1.0, "k": 1e-9}}
    program = {"loss": [(0, 3, 4.4)], "moment_norm": {"w": 1.0, "k": 1e-9},
               "dparam_norm": {"w": 0.0, "k": 1.0}}
    numbers = correctness.compare(program, ref)
    assert numbers["loss"]["value"] == pytest.approx(0.1)
    assert numbers["grad"]["value"] == 0
    # k has no gradient in the reference, so its change is not compared;
    # w did not move at all: a state returned unchanged reads 1
    assert numbers["dparam"]["value"] == 1.0 and \
        numbers["dparam"]["leaves"] == 1
    out = correctness.judge(numbers, {
        "loss": None, "grad": 0.5,
        "dparam": {"of": "median_gap", "limit": 0.5}})
    assert set(out["observed"]) == {"loss"}
    assert out["compared"]["grad"]["ok"]
    held = out["compared"]["dparam"]
    assert not held["ok"] and held["of"] == "median_gap"
    assert held["value"] == 1.0 and held["worst"] == 1.0   # one leaf
    nan = {"grad": {"value": float("nan")}}
    assert not correctness.judge(nan, {"grad": 1.0})["compared"]["grad"]["ok"]


# -------------------------------------------------------------- the files
def test_benchmark_json_names_files_that_exist():
    for c in BENCH["configs"]:
        cfg = harness.load_json(os.path.join(harness.ROOT, c["file"]))
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        for kind in ("configs", "flops", "reference"):
            assert os.path.exists(os.path.join(harness.HERE, kind,
                                               c["name"] + ".py"))
    cells = {w["name"] for w in BENCH["workloads"]}
    for w in BENCH["workloads"]:
        entry, cell, cfg = harness.load_cell(w["name"], BENCH)
        assert cell["config"] == w["config"] == cfg["name"]
        assert w["name"] == f'{w["config"]}.{w["traffic"]}'
        assert cell["engine"] in ("epoch_scan", "per_step")
        assert set(cell["limits"]) <= {"loss", "grad", "dparam", "state"}
    ends = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert hasattr(harness.load_module("metrics", m["name"]), "read")
        assert m["moves"] in ends and set(m["workloads"]) <= cells
    assert any("mfu" in m["name"].split("_") for m in BENCH["per_layer"])


def test_published_dropouts_are_the_only_reduction():
    cfg = cfg_of("openai-gpt")
    assert cfg["reduced"] == ["attn_pdrop", "embd_pdrop", "resid_pdrop"]
    assert (cfg["n_embd"], cfg["n_head"], cfg["n_layer"], cfg["n_positions"],
            cfg["vocab_size"], cfg["n_inner"]) == (768, 12, 12, 512, 40478,
                                                   3072)
    assert cfg_of("resnet50")["reduced"] == []
