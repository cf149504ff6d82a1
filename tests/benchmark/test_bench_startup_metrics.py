"""The set-up metrics of PR 35 on a ``run`` built by hand: what each
reads of the registry as the window opens (absolute values: set-up
lies before the window), the one that reads growth inside it, and
``None`` where there is nothing to read (a program without the spans
and counters, as the parent of PR 35).  New cases of
``test_bench_program_metrics.py``'s two tests, in a file of their own:
a PR may add files to the benchmark's paths and edit none."""

import pytest

from analytics_zoo_tpu.observability.tracing import TRAIN_TIMELINE_SPANS
from benchmark import harness

from bench_toy import TOY

SETUP_S = 44.0
WINDOW_S = 20.0

# the registry as the window opens, scan engine: seconds by span name
SECONDS = {"startup_init_variables": 6.5,
           "train_startup": 14.0, "startup_place_state": 3.0,
           "train_permute": 0.75,
           "startup_first_dispatch": 12.0, "train_epoch_scan": 12.25,
           "startup_cost_analysis": 4.0, "train_loss_sync": 3.0,
           "train_boundary": 1.5, "data_place": 9.0}
SELF = {"startup_init_variables": 6.5,
        "train_startup": 4.25, "startup_place_state": 3.0,
        "train_permute": 0.75,
        "startup_first_dispatch": 0.0, "train_epoch_scan": 8.25,
        "startup_cost_analysis": 4.0, "train_loss_sync": 3.0,
        "train_boundary": 1.5,
        # another thread's: no part of the main thread's time
        "data_place": 9.0}
COUNTERS = {
    'jax_trace_seconds_total{fn="epoch"}': 5.0,
    'jax_trace_seconds_total{fn="_backward"}': 2.5,
    'jax_lower_seconds_total{fn="epoch"}': 1.25,
    'jax_traces_total{fn="epoch"}': 3.0,
    'train_program_traces_total{path="epoch_scan"}': 2.0,
    'train_program_traces_total{path="per_step"}': 7.0,
    "compile_cache_load_seconds_total": 1.5,
    "jax_backend_compile_seconds_total": 8.0,
    'train_steps_total{path="epoch_scan"}': 16.0,
}


def spans(kind, seconds):
    return {'%s{name="%s"}' % (kind, name): value
            for name, value in seconds.items()}


def run(program=True, returned=2.5):
    """A scan cell's ``run``: ``program`` False is the parent's, whose
    registry holds the steady state's spans alone."""
    edge = {n for n in SELF
            if n.startswith("startup_") or n == "train_startup"}
    before = {**spans("span_seconds_total", SECONDS),
              **spans("span_self_seconds_total", SELF), **COUNTERS}
    if not program:
        before = {k: v for k, v in before.items()
                  if not any('"%s"' % n in k for n in edge)
                  and not k.startswith(("jax_trace", "jax_lower",
                                        "train_program", "compile_cache"))}
    after = dict(before)
    after['train_steps_total{path="epoch_scan"}'] += 48.0
    after['span_seconds_total{name="train_epoch_scan"}'] += 0.5
    if program:
        after['span_seconds_total{name="train_return"}'] = returned
    return {"steps": 48, "setup_s": SETUP_S, "window_s": WINDOW_S,
            "before": {"counters": before}, "after": {"counters": after}}


def read(metric, r):
    return harness.load_module("metrics", metric).read(r)


EXPECTED = {
    # every fn's trace and lowering; the counts are another family
    "setup_trace_lower_s": 5.0 + 2.5 + 1.25,
    # the path that moved in the window, not the other engine's
    "train_program_traces": 2.0,
    "setup_cache_load_s": 1.5,
    "setup_state_s": 6.5 + 3.0,
    "setup_first_dispatch_s": 12.0,
    # less every MAIN-thread span's self seconds, data_place's left in
    "setup_outside_program_s": SETUP_S - (sum(SELF.values()) - 9.0),
    "train_return_pct_of_window": 100.0 * 2.5 / WINDOW_S,
}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_metric_reads_its_spans_or_counters(metric):
    assert read(metric, run()) == pytest.approx(EXPECTED[metric], rel=1e-9)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_metric_is_none_when_there_is_nothing_to_read(metric):
    """The parent of PR 35 has ``aot_warm_start``, ``train_permute`` and
    the steady state's spans, and none of the start-up timeline: nothing
    raises, the line leaves the metric out."""
    assert read(metric, run(program=False)) is None


def test_a_cold_run_reads_no_cache_load_as_zero_not_none():
    cold = run()
    cold["before"]["counters"]["compile_cache_load_seconds_total"] = 0.0
    assert read("setup_cache_load_s", cold) == 0.0


def test_the_per_step_engine_adds_its_warm_start_and_loader():
    stream = run()
    counters = stream["before"]["counters"]
    counters.update(spans("span_seconds_total", {
        "aot_warm_start": 7.0, "startup_loader": 0.75}))
    assert read("setup_first_dispatch_s", stream) == 12.0 + 7.0
    assert read("setup_state_s", stream) == 6.5 + 3.0 + 0.75


def test_outside_and_under_spans_add_up_to_setup_s():
    r = run()
    under = sum(v for k, v in r["before"]["counters"].items()
                if k.startswith("span_self_seconds_total")
                and k.split('"')[1] in TRAIN_TIMELINE_SPANS["main"])
    assert read("setup_outside_program_s", r) + under == \
        pytest.approx(SETUP_S, abs=1e-9)


def test_the_seven_metrics_list_all_six_cells():
    bench = harness.load_json(harness.os.path.join(harness.ROOT,
                                                   "BENCHMARK.json"))
    # the six cells of PR 35, by position: a later cell may be appended
    # to these lists or not, and this file need not change for it
    cells = [w["name"] for w in bench["workloads"]][:6]
    assert len(cells) == 6
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in EXPECTED:
        entry = listed[name]
        assert entry["workloads"][:6] == cells, name
        assert entry["layer"] == "estimator and dispatch"
        assert entry["better"] == "lower"
        assert harness.load_module("metrics", name).read
        if name == "train_return_pct_of_window":
            assert (entry["unit"], entry["moves"], entry["source"]) == \
                ("%", "train_records_per_s", "program_span")
        else:
            assert entry["moves"] == "setup_s"
            assert entry["unit"] == (
                "1" if name == "train_program_traces" else "s")
            assert entry["source"] in ("program_span", "program_counter")


@pytest.mark.parametrize("position", range(3))
def test_a_patched_cell_test_file_is_there_with_its_list(cells_own_tests,
                                                         position):
    """``tests/conftest.py`` adds the seven to the ``METRICS`` of the
    cells' own test files, found by name: a file renamed, or one whose
    list went, has to fail here."""
    import ast
    names, _ = cells_own_tests
    assert len(names) == 3
    path = harness.os.path.join(harness.os.path.dirname(__file__),
                                names[position] + ".py")
    with open(path) as f:
        tree = ast.parse(f.read())
    assigned = [t.id for node in tree.body if isinstance(node, ast.Assign)
                for t in node.targets if isinstance(t, ast.Name)]
    assert assigned.count("METRICS") == 1, path


def test_the_patch_appends_the_seven_or_fails_loudly(cells_own_tests):
    import types
    _, with_setup_metrics = cells_own_tests
    stand_in = types.ModuleType("test_bench_stand_in")
    stand_in.METRICS = ["its_own"]
    patched = with_setup_metrics(stand_in)
    assert patched[0] == "its_own" and stand_in.METRICS == ["its_own"]
    assert sorted(patched[1:]) == sorted(EXPECTED)
    del stand_in.METRICS
    with pytest.raises(AssertionError, match="no METRICS"):
        with_setup_metrics(stand_in)


def test_a_traced_run_reports_the_seven(one_chip):
    """The harness end to end at a toy size on the CPU: the program's
    start-up timeline reaches the line under the seven names."""
    name = "openai-gpt.finetune_hbm"
    cell, cfg = TOY[name]
    line = harness.run_cell(name, 2 ** 31 + 35, 1.0, True,
                            require_chip=False, cell_override=cell,
                            cfg_override=cfg)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(EXPECTED) <= set(got)
    assert got["train_program_traces"] >= 1
    assert got["setup_trace_lower_s"] > 0
    assert got["setup_cache_load_s"] == 0     # the suite runs cache off
    assert got["setup_state_s"] > 0
    # the train program's trace and lowering run inside its first
    # dispatch; its compile does too, but compile_s is every program's
    assert got["setup_first_dispatch_s"] > 0
    assert got["setup_outside_program_s"] > 0
    assert 0 < got["train_return_pct_of_window"] < 100
