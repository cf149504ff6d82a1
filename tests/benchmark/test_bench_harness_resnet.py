"""The ResNet cells: each cell's harness end to end at a toy size on the CPU: the last
line's keys, the engine the cell's file names, and `correct` coming out
false when the timed path is broken underneath."""

import json

import jax
import numpy as np
import pytest

from benchmark import harness

from bench_toy import TOY

CELLS = ["resnet50.train_hbm", "resnet50.train_stream"]
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(name, trace=False, seed=2 ** 31 + 7, limits=None, **kw):
    cell, cfg = TOY[name]
    cell = dict(cell, **({"limits": limits} if limits else {}))
    return harness.run_cell(name, seed, 1.0, trace, require_chip=False,
                            cell_override=cell, cfg_override=cfg, **kw)


@pytest.mark.parametrize("name", CELLS)
def test_cell_end_to_end(one_chip, name):
    line = run(name)
    assert LINE_KEYS <= set(line)
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) == {"train_records_per_s", "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"      # named, never hidden
    engine = harness.load_cell(name)[1]["engine"]
    assert list(line["info"]["engine"]) == ['{path="%s"}' % engine]
    assert {"loss", "grad", "dparam"} <= \
        set(line["compared"]) | set(line["observed"])
    for rec in line["compared"].values():
        assert np.isfinite(rec["value"]) and "limit" in rec
        assert rec["ok"] == (rec["value"] <= rec["limit"])
    json.dumps(line)


@pytest.mark.parametrize("name", ["resnet50.train_stream"])
def test_traced_run_reports_per_layer_metrics(one_chip, name):
    line = run(name, trace=True)
    bench = harness.load_json(harness.os.path.join(harness.ROOT,
                                                   "BENCHMARK.json"))
    listed = {m["name"] for m in bench["per_layer"]
              if name in m.get("workloads", [name])}
    assert set(line["metrics"]) <= listed
    # counts and host times are read anywhere; shares of a peak only on
    # a device that peaks.json knows, and a reader with nothing to read
    # returns nothing
    assert {"dispatches_per_step", "compile_s", "step_device_ms",
            "device_idle_pct"} <= set(line["metrics"])
    assert "step_mfu" not in line["metrics"]
    if name.endswith("train_stream"):
        assert "input_wait_ms_per_step" in line["metrics"]
        assert line["metrics"]["dispatches_per_step"]["value"] == 1.0
    else:
        assert line["metrics"]["dispatches_per_step"]["value"] == 1 / 2
    assert 0 < line["device"]["busy_s"]
    assert line["device"]["window_s"] > 0
    assert len(line["breakdown"]["device_ops"]) <= 10
    assert len(line["breakdown"]["idle_gaps"]) <= 10


def test_resnet_matches_reference_in_float32(one_chip, f32_program):
    """ResNet's gradients at random init are chaotic (the reference
    differs from itself by 5 % in direction under a permuted batch), so
    the norms are held loosely and the first loss tightly."""
    line = run("resnet50.train_stream",
               limits={"loss": 1.0, "grad": 0.05, "dparam": 1.0})
    assert line["compared"]["grad"]["ok"], line["compared"]
    first = [l for l in line["compared"]["loss"]["all"] if l[1] == "steps 0-1"]
    assert first and first[0][0] < 1e-4


