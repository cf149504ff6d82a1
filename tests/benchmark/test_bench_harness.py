"""Each cell's harness end to end at a toy size on the CPU: the last
line's keys, the engine the cell's file names, and `correct` coming out
false when the timed path is broken underneath."""

import json

import jax
import numpy as np
import pytest

from benchmark import harness

from bench_toy import TOY

CELLS = ["openai-gpt.finetune_hbm"]
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


@pytest.mark.parametrize("name", ["openai-gpt.finetune_hbm"])
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


def test_program_matches_reference_in_float32(one_chip, f32_program):
    """The GPT reference and the repo's model compute the same step:
    in float32 on the CPU they agree to rounding."""
    line = run("openai-gpt.finetune_hbm",
               limits={"loss": 1e-5, "grad": 1e-4, "dparam": 1e-4})
    assert line["correct"], line["compared"]


def _break_step(monkeypatch, fault):
    from analytics_zoo_tpu.parallel.trainer import DistributedTrainer
    sound = DistributedTrainer._step_core

    def broken(self, params, opt_state, state, batch, rng):
        if fault == "unchanged":
            _, _, new_state, loss = sound(self, params, opt_state, state,
                                          batch, rng)
            return params, opt_state, new_state, loss
        half = jax.tree_util.tree_map(
            lambda a: a[:a.shape[0] // 2], batch)
        return sound(self, params, opt_state, state, half, rng)

    monkeypatch.setattr(DistributedTrainer, "_step_core", broken)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_step_is_not_correct(one_chip, f32_program, monkeypatch,
                                    fault):
    """The timed path broken underneath: a step that returns its state
    unchanged, and half of the batch left out with the mean taken over
    the rest.  Each has to fail the comparison that a sound run passes
    (test_program_matches_reference_in_float32, same limits)."""
    _break_step(monkeypatch, fault)
    line = run("openai-gpt.finetune_hbm",
               limits={"loss": 1e-5, "grad": 1e-4, "dparam": 1e-4})
    assert line["correct"] is False
    failed = [k for k, rec in line["compared"].items() if not rec["ok"]]
    assert failed, line["compared"]
    if fault == "unchanged":
        assert "dparam" in failed


def test_plain_callable_trigger_drops_to_the_per_step_engine(one_chip):
    """Why the window's trigger subclasses MaxEpoch: the same deadline
    as a plain callable silently leaves the scan engine."""
    from analytics_zoo_tpu.feature.feature_set import FeatureSet
    from analytics_zoo_tpu.pipeline.api.keras.engine import Layer
    from analytics_zoo_tpu.pipeline.estimator import Estimator
    cell, cfg = TOY["openai-gpt.finetune_hbm"]
    _, cell0, cfg0 = harness.load_cell("openai-gpt.finetune_hbm")
    cfg = {**cfg0, **cfg}
    model_file = harness.load_module("configs", cfg["name"])
    from benchmark import data as data_lib
    x, y = data_lib.make_rows(model_file.input_spec(cfg),
                              {**cell0, **cell}, 3)

    def engines(trigger):
        Layer.reset_name_counters()
        model = model_file.build(cfg)
        before = harness.registry_snapshot()
        Estimator(model, optim_method=model.optim_method).train(
            FeatureSet.from_ndarrays(list(x), y), model.loss,
            end_trigger=trigger, batch_size=cfg["batch_size"])
        return harness.counter_delta(harness.registry_snapshot(), before,
                                     "train_steps_total")

    plan = {"seconds": 0.0, "follow_steps": None, "warmup_steps": 2}
    scan = engines(harness.make_trigger(plan))
    assert list(scan) == ['{path="epoch_scan"}']
    plain = engines(lambda ts: ts.iteration >= 4)
    assert list(plain) == ['{path="per_step"}']


def test_cpu_device_fails_without_a_result(capsys):
    """The command on a machine without the chip: non-zero, no line."""
    import subprocess
    import sys
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "resnet50.train_hbm", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=harness.ROOT, capture_output=True, text=True,
        env={**harness.os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(harness.BenchmarkError):
        harness.peaks_for("TPU v9 imaginary")
