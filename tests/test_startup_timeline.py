"""The start-up timeline (PR 35): the spans ``Estimator.train`` leaves
between its first line and its first finished step and at the state's
return, how they nest, that their self seconds account for the wall
time, the compile-stage counters fed by ``jax.monitoring``, the count
of train-program traces, the time-to-first-step gauge with its log
line, and what a disabled tracer leaves behind."""

import importlib.util
import logging
import os
import threading
import time

import jax
import numpy as np
import pytest

from analytics_zoo_tpu.compile import engine_jit
from analytics_zoo_tpu.observability import (
    diagnostics, get_registry, get_tracer, reset_registry)
from analytics_zoo_tpu.observability.tracing import (
    TRAIN_TIMELINE_SPANS, reset_tracer)


@pytest.fixture(autouse=True)
def fresh_observability():
    reset_registry()
    reset_tracer()
    diagnostics.install_compile_listener()
    yield
    reset_registry()
    reset_tracer()


def counters(family):
    """``{label value: value}`` of a one-label counter family."""
    out = {}
    for key, value in get_registry().snapshot()["counters"].items():
        if key.startswith(family + "{"):
            out[key[len(family):].split('"')[1]] = value
    return out


def _toy_model():
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.engine import Layer
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    Layer.reset_name_counters()
    m = Sequential()
    m.add(Dense(4, input_shape=(8,)))
    m.compile("adam", "mse")
    return m


def _train(engine, end_trigger=None, model=None):
    """A toy ``train()`` on ``engine`` from a fresh context: three
    epochs of four steps on the scan engine, twelve steps on the
    per-step one.  Returns the estimator and the clock's reading just
    before ``init_zoo_context``."""
    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.common.config import get_config
    from analytics_zoo_tpu.common.triggers import MaxEpoch, MaxIteration
    from analytics_zoo_tpu.common.zoo_context import reset_zoo_context
    from analytics_zoo_tpu.feature.feature_set import FeatureSet
    from analytics_zoo_tpu.pipeline.estimator import Estimator
    reset_zoo_context()
    get_config().set("observability.device_time_every", 4)
    t0 = time.perf_counter()
    init_zoo_context()
    rs = np.random.RandomState(0)
    x = rs.randn(256, 8).astype("float32")
    y = rs.randn(256, 4).astype("float32")
    m = model or _toy_model()
    est = Estimator(m, optim_method=m.optim_method)
    if end_trigger is None:
        end_trigger = MaxEpoch(3) if engine == "epoch_scan" \
            else MaxIteration(12)
    est.train(FeatureSet.from_ndarrays(x, y), "mse",
              end_trigger=end_trigger, batch_size=64)
    return est, t0


# what each engine's train() leaves of the table in
# docs/observability.md, by its parent; each exactly once
EDGES = {
    "epoch_scan": {
        "train_startup": None,
        "startup_init_variables": "train_startup",
        "startup_place_state": "train_startup",
        "startup_first_dispatch": None,
        "startup_cost_analysis": "train_epoch_scan",
        "train_return": None},
    "per_step": {
        "train_startup": None,
        "startup_init_variables": "train_startup",
        "startup_place_state": "train_startup",
        "startup_loader": "train_startup",
        "aot_warm_start": "train_startup",
        "startup_first_dispatch": None,
        "startup_cost_analysis": "train_step",
        "train_return": None},
}
DISPATCH = {"epoch_scan": "train_epoch_scan", "per_step": "train_step"}


@pytest.mark.parametrize("engine", sorted(EDGES))
def test_train_leaves_each_edge_span_once_under_its_parent(engine):
    _train(engine)
    events = [e for e in get_tracer().events() if e["ph"] == "X"]
    assert set(EDGES[engine]) <= set(TRAIN_TIMELINE_SPANS["main"])
    for name, parent in EDGES[engine].items():
        found = [e for e in events if e["name"] == name]
        assert len(found) == 1, (name, len(found))
        assert found[0]["parent"] == parent, found[0]
    # no span of the other engine's edge
    assert not {e["name"] for e in events} & (
        set().union(*EDGES.values()) - set(EDGES[engine]))
    # the first dispatch alone is under startup_first_dispatch (three
    # epochs, twelve steps), and the umbrella ends where it starts
    dispatches = [e for e in events if e["name"] == DISPATCH[engine]]
    assert len(dispatches) == (3 if engine == "epoch_scan" else 12)
    assert [e["parent"] for e in dispatches] == \
        ["startup_first_dispatch"] + [None] * (len(dispatches) - 1)
    umbrella, = [e for e in events if e["name"] == "train_startup"]
    first, = [e for e in events if e["name"] == "startup_first_dispatch"]
    assert umbrella["ts"] + umbrella["dur"] <= first["ts"]
    assert first["ts"] - (umbrella["ts"] + umbrella["dur"]) < 1e3  # us
    placed, = [e for e in events if e["name"] == "startup_place_state"]
    returned, = [e for e in events if e["name"] == "train_return"]
    assert placed["args"]["bytes"] == returned["args"]["bytes"] == \
        (8 * 4 + 4) * 4
    assert get_tracer().depth() == 0


@pytest.mark.parametrize("engine", sorted(EDGES))
def test_self_seconds_account_for_the_wall_time_to_the_first_boundary(
        engine):
    """From before ``init_zoo_context`` to the end trigger's first call
    after a step, the main thread's self seconds cover the wall time
    within 5 %: what they leave is the rows' and the estimator's
    construction and the boundary that is still open (the model is
    built before: in a fresh process that imports the layers)."""
    from analytics_zoo_tpu.common.triggers import MaxEpoch

    class AtFirstBoundary(MaxEpoch):
        seen = None

        def __call__(self, ts):
            if self.seen is None and ts.iteration > 0:
                self.seen = (time.perf_counter(), counters(
                    "span_self_seconds_total"))
            return super().__call__(ts)

    trigger = AtFirstBoundary(1)
    # a callable that is no MaxEpoch keeps the scan engines out
    _, t0 = _train(engine, model=_toy_model(),
                   end_trigger=trigger if engine == "epoch_scan"
                   else lambda ts: trigger(ts))
    t_boundary, self_seconds = trigger.seen
    # the prefetch thread's spans (the per-step engine's) are not the
    # main thread's time
    main = {name: s for name, s in self_seconds.items()
            if name in TRAIN_TIMELINE_SPANS["main"]}
    assert set(self_seconds) - set(main) <= {"data_assemble", "data_place"}
    assert sum(main.values()) == pytest.approx(t_boundary - t0, rel=0.05)


def test_an_early_failure_closes_the_umbrella():
    from analytics_zoo_tpu.feature.feature_set import FeatureSet
    from analytics_zoo_tpu.pipeline.estimator import Estimator
    m = _toy_model()
    est = Estimator(m, optim_method=m.optim_method)
    x = np.zeros((8, 8), "float32")
    with pytest.raises(ValueError, match="exceeds dataset size"):
        est.train(FeatureSet.from_ndarrays(x, x[:, :4]), "mse",
                  batch_size=64)
    tracer = get_tracer()
    assert tracer.depth() == 0
    assert [e["name"] for e in tracer.events()
            if e["parent"] is None] == ["train_startup"]
    assert "train_time_to_first_step_seconds" not in \
        get_registry().snapshot()["gauges"]


# ------------------------------------------- the compile-stage counters
def test_traces_of_a_known_jit_are_counted_exactly():
    def known_fn_of_this_test(x):
        return x * 2.0

    program = engine_jit(known_fn_of_this_test)
    name = "known_fn_of_this_test"
    program(np.ones(3, "float32"))
    program(np.ones(3, "float32"))
    assert counters("jax_traces_total")[name] == 1
    program(np.ones(4, "float32"))        # another shape: another trace
    assert counters("jax_traces_total")[name] == 2
    # a lowering carries the module's name: the same label
    assert counters("jax_lower_seconds_total")[name] > 0
    assert counters("jax_trace_seconds_total")[name] > 0
    plain = get_registry().snapshot()["counters"]
    assert plain["jax_backend_compiles_total"] >= 2
    assert plain["jax_backend_compile_seconds_total"] > 0
    assert plain["compile_cache_load_seconds_total"] == 0


@pytest.mark.parametrize("fun_name,label", [
    ("epoch", "epoch"), ("jit(epoch)", "epoch"), ("jit_epoch", "epoch"),
    ("jit(_where)", "_where"), (None, "?"), ("", "?")])
def test_a_trace_and_its_lowering_share_a_label(fun_name, label):
    assert diagnostics._fn_label(fun_name) == label


def test_a_stage_counts_less_the_stages_nested_in_it(monkeypatch):
    """JAX reports a stage at its end with its whole duration; the
    counters keep what the stages nested in it do not cover, so a
    program's trace is not counted again for every jitted function and
    kernel traced inside it."""
    now = [0.0]
    monkeypatch.setattr(diagnostics.time, "time", lambda: now[0])
    monkeypatch.setattr(diagnostics, "_stage_local", threading.local())
    trace = "/jax/core/compile/jaxpr_trace_duration"
    lower = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def ends(at, event, duration, fun_name):
        now[0] = at
        diagnostics._backend_compile_listener(event, duration,
                                              fun_name=fun_name)

    ends(5.0, trace, 2.0, "kernel")        # 3..5, inside the program's
    ends(8.0, trace, 1.0, "_where")        # 7..8, inside it too
    ends(10.0, trace, 9.0, "epoch")        # 1..10
    ends(14.0, trace, 0.5, "_where")       # 13.5..14, inside a lowering
    ends(15.0, lower, 4.0, "jit(epoch)")   # 11..15
    ends(20.0, trace, 5.0, "epoch")        # 15..20: claims none of them
    seconds = counters("jax_trace_seconds_total")
    assert seconds == {"kernel": 2.0, "_where": 1.5,
                       "epoch": pytest.approx(9.0 - 3.0 + 5.0)}
    assert counters("jax_lower_seconds_total") == {"epoch": 3.5}
    assert counters("jax_traces_total") == {
        "kernel": 1, "_where": 2, "epoch": 2}
    # the sum is the wall time under any stage
    assert sum(seconds.values()) + 3.5 == pytest.approx(9.0 + 4.0 + 5.0)


@pytest.mark.parametrize("engine,fn", [("epoch_scan", "epoch"),
                                       ("per_step", "train_step_at")])
def test_train_program_traces_are_the_traces_really_made(engine, fn,
                                                         monkeypatch):
    """The counter in the program's Python body against the runs of the
    step's body under it: the first call, and whatever the warm-start
    and the cost analysis had to trace again."""
    from analytics_zoo_tpu.parallel.trainer import DistributedTrainer
    step_checked, bodies = DistributedTrainer._step_checked, []

    def counted(self, *args):
        bodies.append(1)
        return step_checked(self, *args)
    monkeypatch.setattr(DistributedTrainer, "_step_checked", counted)
    _train(engine)
    mine = counters("train_program_traces_total")
    assert set(mine) == {engine}
    # every trace of the program runs the step's body once, and JAX
    # reports at least as many (an event for a trace it then answers
    # from a cache runs no body)
    assert mine[engine] == len(bodies) >= 1
    assert mine[engine] <= counters("jax_traces_total")[fn]
    # running the program traces nothing: a second estimator's train()
    # on the same model builds its own programs and counts again
    steps = get_registry().snapshot()["counters"][
        'train_steps_total{path="%s"}' % engine]
    assert steps == 12 and mine[engine] < steps


def test_chunked_dispatch_counts_under_its_own_path():
    from analytics_zoo_tpu.common.config import get_config
    get_config().set("train.hbm_cache_mb", 0)
    get_config().set("train.steps_per_dispatch", 2)
    _train("epoch_scan")
    mine = counters("train_program_traces_total")
    assert set(mine) == {"chunked"}
    assert mine["chunked"] == counters("jax_traces_total")["epoch"]


# ------------------------------------------------ the operator's reading
@pytest.mark.parametrize("engine", sorted(EDGES))
def test_time_to_first_step_is_set_once_with_its_log_line(engine, caplog):
    with caplog.at_level(logging.INFO, "analytics_zoo_tpu.estimator"):
        t0 = time.perf_counter()
        _train(engine)
        wall = time.perf_counter() - t0
    value = get_registry().snapshot()["gauges"][
        "train_time_to_first_step_seconds"]
    assert 0 < value < wall
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("time to first step")]
    assert len(lines) == 1            # three epochs, twelve steps: once
    head, pairs = lines[0].split(": ")
    assert float(head.split()[4]) == pytest.approx(value, abs=0.01)
    pairs = [p.rsplit(" ", 1) for p in pairs.split(", ")]
    seconds = [float(s) for _, s in pairs]
    assert seconds == sorted(seconds, reverse=True)
    names = [n for n, _ in pairs]
    assert DISPATCH[engine] in names and len(set(names)) == len(names)
    # self seconds of the thread's spans since the entry: within the
    # total, and nearly all of it (each pair is rounded to a hundredth
    # and so is the total)
    assert 0.9 * value < sum(seconds) <= value + 0.005 * (len(seconds) + 1)


def test_a_disabled_tracer_records_nothing_and_trains_the_same():
    enabled, _ = _train("epoch_scan")
    traces = counters("train_program_traces_total")
    reset_registry()
    reset_tracer()
    tracer = get_tracer()
    tracer.enabled = False
    disabled, _ = _train("epoch_scan")
    assert tracer.events() == [] and tracer.depth() == 0
    assert counters("span_seconds_total") == {}
    assert counters("spans_total") == {}
    # the counters that are no span's are fed as before
    assert counters("train_program_traces_total") == traces
    assert get_registry().snapshot()["gauges"][
        "train_time_to_first_step_seconds"] > 0
    for a, b in zip(jax.tree_util.tree_leaves(enabled.variables),
                    jax.tree_util.tree_leaves(disabled.variables)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_obs_report_prints_the_timeline_under_the_compile_table(tmp_path,
                                                                capsys):
    _train("epoch_scan")
    snap = str(tmp_path / "snap.jsonl")
    get_registry().write_jsonl(snap)
    spec = importlib.util.spec_from_file_location(
        "obs_report", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts", "obs_report.py"))
    obs_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(obs_report)
    assert obs_report.main([snap]) == 0
    out = capsys.readouterr().out
    assert out.index("compilation (") < out.index("start-up timeline (")
    for name in EDGES["epoch_scan"]:
        assert name in out
    assert "time to first step: " in out
    assert "compile stages (exact" in out
    assert "train program [epoch_scan]: traced 2 time(s)" in out
