"""The training timeline (PR 25): what the tracer records about a span
(parent, iteration, self time, the three span counters), the spans each
dispatch engine leaves behind, the finite flag's way to the host (a
value the step returns: no host callback in a default train program),
the names on device work, and ``dev/trace-summary``'s attribution of
idle gaps."""

import ast
import importlib.machinery
import importlib.util
import os
import re
import threading
import time

import numpy as np
import pytest

from analytics_zoo_tpu.observability import (
    get_registry, get_tracer, reset_registry)
from analytics_zoo_tpu.observability.tracing import (
    TRAIN_TIMELINE_SPANS, Tracer, iteration_args, reset_tracer)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def fresh_observability():
    reset_registry()
    reset_tracer()
    yield
    reset_registry()
    reset_tracer()


def span_counters():
    out = {}
    for key, value in get_registry().snapshot()["counters"].items():
        for family in ("span_seconds_total", "span_self_seconds_total",
                       "spans_total"):
            if key.startswith(family + "{"):
                name = key[len(family) + len('{name="'):-2]
                out.setdefault(name, {})[family] = value
    return out


# ------------------------------------------------------ what a span records
def _nested(tr):
    with tr.span("outer", iteration=3):
        with tr.span("inner", jax_annotation=True, iteration=3):
            pass


def _siblings(tr):
    with tr.span("outer", iteration=3):
        with tr.span("inner", iteration=3):
            pass
        with tr.span("inner", iteration=4):
            pass


def _cross_thread(tr):
    """A producer's span for batch 7 on another thread, the consumer's
    wait for it here: each thread has its own stack."""
    with tr.span("outer", iteration=7):
        t = threading.Thread(target=lambda: _span(tr, "inner", 7))
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()


def _span(tr, name, iteration):
    with tr.span(name, **iteration_args(iteration)):
        pass


@pytest.mark.parametrize("scenario,inner_parent,inner_count", [
    (_nested, "outer", 1), (_siblings, "outer", 2),
    (_cross_thread, None, 1)], ids=["nested", "siblings", "cross_thread"])
def test_events_keep_parent_and_iteration(scenario, inner_parent,
                                          inner_count):
    tr = Tracer()
    scenario(tr)
    events = tr.events()
    inner = [e for e in events if e["name"] == "inner"]
    outer = [e for e in events if e["name"] == "outer"]
    assert len(inner) == inner_count and len(outer) == 1
    assert outer[0]["parent"] is None
    assert all(e["parent"] == inner_parent for e in inner)
    assert inner[0]["args"]["iteration"] == outer[0]["args"]["iteration"]
    assert tr.depth() == 0 and tr.current_span() is None


def test_iteration_args():
    assert iteration_args(None, 5) == {}
    assert iteration_args(np.int32(3), 2) == {"iteration": 5}


def test_self_time_is_duration_less_children_and_counters_agree():
    tr = get_tracer()
    with tr.span("parent"):
        time.sleep(0.01)
        for _ in range(2):
            with tr.span("child"):
                time.sleep(0.01)
    tr.complete("epoch", time.perf_counter(), 1.0)     # never counted
    events = tr.events()
    ring = {}
    for e in events:
        ring.setdefault(e["name"], []).append(e["dur"] / 1e6)
    counters = span_counters()
    assert set(counters) == {"parent", "child"}
    for name in ("parent", "child"):
        assert counters[name]["spans_total"] == len(ring[name])
        assert counters[name]["span_seconds_total"] == \
            pytest.approx(sum(ring[name]), rel=1e-9)
    assert counters["child"]["span_self_seconds_total"] == \
        pytest.approx(sum(ring["child"]), rel=1e-9)
    assert counters["parent"]["span_self_seconds_total"] == \
        pytest.approx(ring["parent"][0] - sum(ring["child"]), rel=1e-6)
    assert counters["parent"]["span_self_seconds_total"] >= 0.009


def test_disabled_tracer_costs_one_attribute_test():
    tr = Tracer()
    tr.enabled = False
    first = tr.span("a", jax_annotation=True, iteration=1)
    assert first is tr.span("b")          # one shared no-op, no allocation
    with first as yielded:
        assert yielded is tr
    assert tr.events() == [] and span_counters() == {}


def test_a_raising_registry_cannot_break_a_span(monkeypatch):
    from analytics_zoo_tpu.observability import tracing

    def boom():
        raise RuntimeError("registry down")
    monkeypatch.setattr(tracing, "get_registry", boom)
    tr = Tracer()
    with tr.span("still_recorded"):
        pass
    assert [e["name"] for e in tr.events()] == ["still_recorded"]


# ------------------------------------------------- the engines' timelines
def _toy_model():
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.engine import Layer
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    Layer.reset_name_counters()
    m = Sequential()
    m.add(Dense(4, input_shape=(8,)))
    m.compile("adam", "mse")
    return m


def _train(engine):
    """Eight steps of a toy model on ``engine``; returns the steps each
    dispatch covers."""
    from analytics_zoo_tpu.common.config import get_config
    from analytics_zoo_tpu.common.triggers import MaxEpoch, MaxIteration
    from analytics_zoo_tpu.data import DataPipeline
    from analytics_zoo_tpu.feature.feature_set import FeatureSet
    from analytics_zoo_tpu.pipeline.estimator import Estimator
    rs = np.random.RandomState(0)
    x = rs.randn(256, 8).astype("float32")
    y = rs.randn(256, 4).astype("float32")
    cfg = get_config()
    cfg.set("observability.device_time_every", 4)
    m = _toy_model()
    est = Estimator(m, optim_method=m.optim_method)
    if engine == "per_step":
        with DataPipeline(x, y, batch_size=64, num_workers=2) as pipe:
            est.train(pipe, "mse", end_trigger=MaxIteration(8))
        return 1
    if engine == "chunked":
        cfg.set("train.hbm_cache_mb", 0)
        cfg.set("train.steps_per_dispatch", 2)
    est.train(FeatureSet.from_ndarrays(x, y), "mse",
              end_trigger=MaxEpoch(2), batch_size=64)
    return 2 if engine == "chunked" else 4


ENGINE_SPANS = {
    "epoch_scan": {"train_permute", "train_epoch_scan", "train_loss_sync",
                   "train_boundary"},
    "chunked": {"data_wait", "data_assemble", "data_place",
                "train_dispatch", "train_loss_sync", "train_boundary"},
    "per_step": {"data_build", "data_assemble", "data_place", "data_wait",
                 "train_step", "train_device_sync", "train_loss_sync",
                 "train_boundary", "aot_warm_start"},
}
DISPATCH_SPAN = {"epoch_scan": "train_epoch_scan",
                 "chunked": "train_dispatch", "per_step": "train_step"}


@pytest.mark.parametrize("engine", sorted(ENGINE_SPANS))
def test_engine_leaves_its_spans_with_sane_nesting(engine):
    stride = _train(engine)
    events = [e for e in get_tracer().events() if e["ph"] == "X"]
    names = {e["name"] for e in events}
    known = {n for group in TRAIN_TIMELINE_SPANS.values() for n in group}
    assert ENGINE_SPANS[engine] <= names
    assert names - {"epoch"} <= known

    # a parent is a span of that name on the same thread that encloses
    # the child in time
    for e in events:
        if e["parent"] is None:
            continue
        assert any(p["name"] == e["parent"] and p["tid"] == e["tid"]
                   and p["ts"] <= e["ts"]
                   and p["ts"] + p["dur"] >= e["ts"] + e["dur"]
                   for p in events), e
    for e in events:
        if e["name"] in ("train_loss_sync", "checkpoint_save", "eval"):
            assert e["parent"] in (None, "train_boundary")
        elif e["name"] == "train_device_sync":
            assert e["parent"] == "train_step"
        elif e["name"] == "startup_cost_analysis":
            assert e["parent"] == DISPATCH_SPAN[engine]
        elif e["name"] in TRAIN_TIMELINE_SPANS["main"]:
            # top level, but for what the job's first edge holds
            # (tests/test_startup_timeline.py has that nesting)
            assert e["parent"] in (None, "train_startup",
                                   "startup_first_dispatch"), e

    # the spans of one dispatch, on whatever thread, share `iteration`
    dispatch = [e for e in events if e["name"] == DISPATCH_SPAN[engine]]
    assert sorted(e["args"]["iteration"] for e in dispatch) == \
        list(range(0, 8, stride))
    assert all(e["args"]["steps"] == stride
               and e["args"]["path"] == engine for e in dispatch)
    shared = ENGINE_SPANS[engine] - {
        "aot_warm_start", "train_loss_sync", "train_device_sync",
        "train_permute"}
    for name in shared:
        seen = {e["args"]["iteration"] for e in events
                if e["name"] == name and "args" in e}
        assert set(range(0, 8, stride)) <= seen, name

    counters = span_counters()
    steps = get_registry().snapshot()["counters"][
        'train_steps_total{path="%s"}' % engine]
    assert steps == 8
    assert counters[DISPATCH_SPAN[engine]]["spans_total"] == 8 // stride
    # the default config runs no callback, so none leaves a span
    assert not [n for n in names if n.startswith("callback_")]
    if engine == "per_step":
        assert counters["train_step"]["spans_total"] == steps
        # draining the finite flags adds no sync: the spans in which the
        # host blocks are the parent's for these 8 steps (every 4th
        # dispatch; the loss at each of the two epochs' ends)
        assert counters["train_device_sync"]["spans_total"] == 2
        assert counters["train_loss_sync"]["spans_total"] == 2
        placed = [e for e in events if e["name"] == "data_place"]
        assert all(e["args"]["bytes"] == 64 * (8 + 4) * 4 for e in placed)
        assert get_registry().snapshot()["counters"][
            "data_h2d_bytes_total"] == 8 * 64 * (8 + 4) * 4


# ------------------------- the flag's host function, the callback left
def _finite_check():
    """The host function every drained flag goes through (main thread,
    no span of its own): two checked steps, one of them non-finite."""
    from analytics_zoo_tpu.observability.watchdog import (
        record_finite_checks)
    record_finite_checks(2, 1)
    return None


def _grad_norm():
    from analytics_zoo_tpu.parallel.trainer import _record_grad_norm
    _record_grad_norm(np.float32(2.0))
    return "callback_grad_norm"


@pytest.mark.parametrize("callback", [_finite_check, _grad_norm],
                         ids=["finite_check", "grad_norm"])
@pytest.mark.parametrize("registry", ["sound", "raising"])
def test_host_function_never_raises_and_a_callback_records_a_span(
        callback, registry, monkeypatch):
    if registry == "raising":
        from analytics_zoo_tpu.observability import (
            metrics, tracing, watchdog)
        from analytics_zoo_tpu.parallel import trainer

        def boom():
            raise RuntimeError("registry down")
        for module in (metrics, tracing, watchdog, trainer):
            monkeypatch.setattr(module, "get_registry", boom)
    name = callback()        # must not raise, wherever it runs
    if name is not None:
        assert name in [e["name"] for e in get_tracer().events()]
    if registry == "sound":
        if name is not None:
            assert span_counters()[name]["spans_total"] == 1
        else:
            counters = get_registry().snapshot()["counters"]
            assert counters["train_finite_checked_steps_total"] == 2
            assert counters['train_nonfinite_total{source="step"}'] == 1


# ------------------------------------- finiteness as a value the step returns
def _toy_trainer():
    from analytics_zoo_tpu.parallel.trainer import DistributedTrainer
    from analytics_zoo_tpu.pipeline.api.keras import objectives
    m = _toy_model()
    trainer = DistributedTrainer(m, objectives.get("mse"),
                                 optim_method=m.optim_method)
    variables = m.get_variables()
    params = trainer.place_params(variables["params"])
    state = trainer.replicate(variables["state"])
    return trainer, params, trainer.init_opt_state(params), state


def _compile_step_at(trainer, params, opt_state, state, rng):
    batch = trainer.put_batch((np.ones((64, 8), "float32"),
                               np.ones((64, 4), "float32")))
    return trainer._build_train_step(fold_rng=True).lower(
        params, opt_state, state, batch, rng, np.int32(0)).compile()


def _compile_epoch_scan(trainer, params, opt_state, state, rng):
    x, y = trainer.put_epoch_source(np.ones((256, 8), "float32"),
                                    np.ones((256, 4), "float32"))
    return trainer.epoch_scan_fn(4, 64).lower(
        params, opt_state, state, x, y, rng, np.int32(0)).compile()


@pytest.mark.parametrize("compile_program", [
    _compile_step_at, _compile_epoch_scan],
    ids=["train_step_at", "train_epoch_scan"])
@pytest.mark.parametrize("grad_norm", [False, True],
                         ids=["default", "grad_norm_on"])
def test_default_train_program_holds_no_host_callback(compile_program,
                                                      grad_norm):
    """With the default config (finite check on) the compiled program
    holds no host callback, so it keeps pjit's C++ dispatch path and
    the persistent caches take it; ``observability.grad_norm``, the
    opt-in that still rides a callback, brings one back (the control:
    this test can see a callback)."""
    import jax
    from analytics_zoo_tpu.common.config import get_config
    assert get_config().get("observability.check_finite") is True
    get_config().set("observability.grad_norm", grad_norm)
    trainer, params, opt_state, state = _toy_trainer()
    compiled = compile_program(trainer, params, opt_state, state,
                               jax.random.PRNGKey(0))
    assert compiled._executable.unsafe_call.has_host_callbacks is grad_norm
    # (a bare "callback" would also match this test's name in the
    # HLO's source metadata)
    calls = re.findall(r'custom_call_target="[^"]*callback[^"]*"',
                       compiled.as_text())
    assert bool(calls) is grad_norm, calls
    # the flag is the program's fifth output, beside the loss
    assert len(compiled.out_tree.children()) == 5


POISONED_FROM, POISONED_OF = 3, 8


def _poisoned_rows():
    """Eight unshuffled batches of 64; every row from batch 3 on has a
    NaN label."""
    rs = np.random.RandomState(0)
    x = rs.randn(64 * POISONED_OF, 8).astype("float32")
    y = rs.randn(64 * POISONED_OF, 4).astype("float32")
    y[64 * POISONED_FROM:] = np.nan
    return x, y


def _train_poisoned(engine):
    from analytics_zoo_tpu.common.config import get_config
    from analytics_zoo_tpu.common.triggers import MaxEpoch, MaxIteration
    from analytics_zoo_tpu.feature.feature_set import FeatureSet
    from analytics_zoo_tpu.pipeline.estimator import Estimator
    from analytics_zoo_tpu.pipeline.estimator.local_estimator import (
        LocalEstimator)
    x, y = _poisoned_rows()
    m = _toy_model()
    cfg = get_config()
    if engine == "local":
        LocalEstimator(m, "mse", m.optim_method).fit(
            FeatureSet.from_ndarrays(x, y, shuffle=False), None,
            batch_size=64, epochs=1)
        return
    est = Estimator(m, optim_method=m.optim_method)
    if engine == "chunked":
        cfg.set("train.hbm_cache_mb", 0)
        cfg.set("train.steps_per_dispatch", 2)
    end = MaxIteration(POISONED_OF) if engine == "per_step" \
        else MaxEpoch(1)
    est.train(FeatureSet.from_ndarrays(x, y, shuffle=False), "mse",
              end_trigger=end, batch_size=64)


@pytest.mark.parametrize("engine", ["per_step", "chunked", "epoch_scan",
                                    "local"])
def test_every_nonfinite_step_is_counted_once_before_train_returns(engine):
    """Rows poisoned from step k of n: exactly n - k non-finite steps,
    on every engine, counted by the time ``train`` / ``fit`` returns
    (the benchmark reads the counter for ``failed`` right after)."""
    _train_poisoned(engine)
    counters = get_registry().snapshot()["counters"]
    assert counters['train_nonfinite_total{source="step"}'] == \
        POISONED_OF - POISONED_FROM
    assert counters["train_finite_checked_steps_total"] == POISONED_OF


@pytest.mark.parametrize("engine", sorted(ENGINE_SPANS))
def test_every_dispatched_step_has_its_flag_read(engine):
    """``train_finite_checked_steps_total`` grows by what
    ``train_steps_total`` grows by: a flag that is computed and never
    read cannot go unnoticed."""
    _train(engine)
    counters = get_registry().snapshot()["counters"]
    assert counters["train_finite_checked_steps_total"] == \
        counters['train_steps_total{path="%s"}' % engine] == 8
    assert 'train_nonfinite_total{source="step"}' not in counters


# ------------------------------------------------------ names on device work
@pytest.mark.parametrize("module,sites", [
    ("fused.py", 3), ("pallas_attention.py", 4),
    ("grouped_matmul.py", 1)])
def test_every_pallas_call_site_passes_a_name(module, sites):
    path = os.path.join(REPO_ROOT, "analytics_zoo_tpu", "ops", module)
    with open(path) as f:
        tree = ast.parse(f.read())
    calls = [node for node in ast.walk(tree)
             if isinstance(node, ast.Call)
             and isinstance(node.func, ast.Attribute)
             and node.func.attr == "pallas_call"]
    assert len(calls) == sites
    for call in calls:
        assert "name" in {k.arg for k in call.keywords}, \
            f"{module}:{call.lineno} pallas_call without name="


def test_kernel_names_reach_the_jaxpr():
    """The toy kernels in interpret mode: the name each site passes is
    the one the traced program carries."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import fused
    from analytics_zoo_tpu.ops.pallas_attention import flash_attention

    def step(q, x, b):
        att = jax.grad(lambda q: jnp.sum(flash_attention(
            q, q, q, causal=True, block_q=8, block_k=8,
            interpret=True)))(q)
        gelu = fused.bias_gelu(x, b, interpret=True)
        norm = fused.layernorm_act(x, b, b, interpret=True)
        return att, gelu, norm

    jaxpr = jax.make_jaxpr(step)(
        jnp.ones((1, 1, 16, 8), jnp.float32),
        jnp.ones((8, 128), jnp.float32), jnp.ones((128,), jnp.float32))
    found = set()

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                found.add(eqn.params["name"])
            for value in eqn.params.values():
                inner = getattr(value, "jaxpr", value)
                if hasattr(inner, "eqns"):
                    walk(inner)
    walk(jaxpr.jaxpr)
    assert found == {"flash_attention_fwd", "flash_attention_bwd",
                     "bias_gelu", "layernorm_act"}


def test_step_scopes_reach_the_compiled_op_names():
    """``forward_loss``, ``finite_check`` and ``optimizer_update`` are
    in the compiled step's ``op_name`` metadata."""
    import re

    import jax

    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.parallel.trainer import DistributedTrainer
    from analytics_zoo_tpu.pipeline.api.keras import objectives
    init_zoo_context()
    m = _toy_model()
    trainer = DistributedTrainer(m, objectives.get("mse"),
                                 optim_method=m.optim_method)
    variables = m.get_variables()
    params = trainer.place_params(variables["params"])
    state = trainer.replicate(variables["state"])
    opt_state = trainer.init_opt_state(params)
    batch = trainer.put_batch((np.zeros((32, 8), np.float32),
                               np.zeros((32, 4), np.float32)))
    step = trainer._build_train_step(fold_rng=True)
    text = step.lower(params, opt_state, state, batch,
                      jax.random.PRNGKey(0), np.int32(0)).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("forward_loss", "finite_check", "optimizer_update"):
        assert any(f"/{scope}/" in n for n in op_names), scope


# ------------------------------------------------------- dev/trace-summary
def trace_summary():
    loader = importlib.machinery.SourceFileLoader(
        "trace_summary", os.path.join(REPO_ROOT, "dev", "trace-summary"))
    spec = importlib.util.spec_from_loader("trace_summary", loader)
    module = importlib.util.module_from_spec(spec)
    loader.exec_module(module)
    return module


def hand_trace():
    """A device busy 0-10 ms, 40-50 ms and 52-60 ms (gaps of 30 and
    2 ms), a main thread and a callback thread."""
    ms = 1_000_000
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ("%fusion.1", 0, 10 * ms), ("%while.2", 40 * ms, 10 * ms),
                ("%fusion.3", 42 * ms, 2 * ms),      # nested in the while
                ("%fused_sgd.4", 52 * ms, 8 * ms)]},
            {"name": "Steps", "events": [("step", 0, 60 * ms)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [
                ("train_step", 5 * ms, 50 * ms, {"iteration": 7}),
                ("train_device_sync", 20 * ms, 34 * ms, {"iteration": 7}),
                ("$trainer.py:400 _dispatch_instrumented", 0, 60 * ms, {}),
                ("train_boundary", 55 * ms, 1 * ms, {"iteration": 7})]},
            {"name": "callback", "events": [
                ("callback_grad_norm", 12 * ms, 1 * ms, {}),
                ("callback_grad_norm", 50 * ms + ms // 2, ms, {})]},
            {"name": "idle", "events": []}]},
    ]


def test_trace_summary_names_the_span_behind_each_gap():
    ts = trace_summary()
    spans = ts.span_roles()
    assert spans["train_step"] == "main"
    assert spans["data_build"] == "worker"
    report = ts.gap_report(hand_trace(), spans, top=10)
    assert [round(g["length_ms"]) for g in report["gaps"]] == [30, 2]
    long, short = report["gaps"]
    assert long["start_ms"] == 10
    # the sync covers 20 of the 30 ms: innermost of the two past half
    assert long["threads"]["main"] == {
        "span": "train_device_sync", "iteration": 7,
        "share": pytest.approx(2 / 3)}
    # the callback covers a thirtieth of it: the best there is
    assert long["threads"]["callback"]["span"] == "callback_grad_norm"
    assert long["threads"]["callback"]["share"] == pytest.approx(1 / 30)
    assert long["threads"]["callback"]["iteration"] is None
    assert short["threads"]["main"]["span"] == "train_device_sync"
    assert short["threads"]["callback"]["share"] == pytest.approx(0.5)
    assert "idle" not in report["roles"]
    assert report["roles"] == {"main": "main", "callback": "callback"}
    assert report["spans"]["main"]["train_step"] == {
        "count": 1, "seconds": pytest.approx(0.05)}
    text = ts.render(report)
    assert "train_device_sync iteration=7 (covers 67%)" in text
    assert "$trainer.py" not in text      # only the program's spans


def test_trace_summary_refuses_a_trace_without_device_work():
    ts = trace_summary()
    with pytest.raises(ValueError, match="no device plane"):
        ts.gap_report(hand_trace()[1:], ts.span_roles())
    empty = hand_trace()
    empty[0]["lines"][0]["events"] = []
    with pytest.raises(ValueError, match="no device operation"):
        ts.gap_report(empty, ts.span_roles())
