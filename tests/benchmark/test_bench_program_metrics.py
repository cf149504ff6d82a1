"""The per-layer metrics of PR 25 on a ``run`` built by hand: what each
reads from the program's span counters or from the named events of the
device trace, and ``None`` where there is nothing to read (a program
without the counters or the names, as the parent of PR 25)."""

import pytest

from benchmark import harness

STEPS = 10
SGD = ('%fused_sgd.7 = (f32[18432,128]{1,0:T(8,128)S(1)}) custom-call('
       '%copy-done.1), custom_call_target="tpu_custom_call", '
       'metadata={op_name="jit(epoch)/while/body/optimizer_update/'
       'fused_sgd/pallas_call"}')
FLASH = ('%transpose_jvp_flash_attention_dq__.3 = f32[384,512,64] '
         'custom-call(%bitcast.9), custom_call_target="tpu_custom_call", '
         'metadata={op_name="jit(epoch)/while/body/forward_loss/'
         'transpose(jvp(flash_attention_dq))/pallas_call"}')
UNNAMED = ('%closed_call.12 = f32[384,512,64] custom-call(%bitcast.9), '
           'custom_call_target="tpu_custom_call", metadata={op_name='
           '"jit(epoch)/while/body/closed_call/pallas_call"}')


def counters(kind, **seconds):
    return {'%s{name="%s"}' % (kind, name): value
            for name, value in seconds.items()}


def run(by_name=None, steps=STEPS, **families):
    """``families``: ``seconds`` / ``self_seconds`` dicts by span name,
    on top of a snapshot in which every series stood at 1."""
    after, before = {}, {}
    for family, kind in (("seconds", "span_seconds_total"),
                         ("self_seconds", "span_self_seconds_total")):
        for key, moved in counters(kind, **families.get(family, {})).items():
            before[key] = 1.0
            after[key] = 1.0 + moved
    # a series that did not move in the window, and another family
    before['span_seconds_total{name="eval"}'] = 2.0
    after['span_seconds_total{name="eval"}'] = 2.0
    after['spans_total{name="train_step"}'] = 99.0
    return {"steps": steps, "before": {"counters": before},
            "after": {"counters": after},
            "trace": {"by_name": by_name or {}}}


def read(metric, r):
    return harness.load_module("metrics", metric).read(r)


HOST_RUN = dict(
    seconds={"train_step": 0.50, "train_device_sync": 0.30,
             "train_loss_sync": 0.10, "train_boundary": 0.05,
             "callback_finite_check": 0.004, "callback_grad_norm": 0.001,
             "data_place": 0.20, "data_build": 1.60, "data_wait": 0.3},
    self_seconds={"train_step": 0.20, "train_epoch_scan": 0.02,
                  "train_dispatch": 0.01, "train_device_sync": 0.30,
                  "train_boundary": 0.04, "train_permute": 0.01,
                  "data_place": 0.20})
DEVICE_RUN = {SGD: 0.002, FLASH: 0.5, UNNAMED: 0.9,
              "%debug_callback.3 = () custom-call(%x), custom_call_target="
              '"xla_ffi_python_cpu_callback"': 0.03,
              '%fusion.9 = f32[2] fusion(%y), metadata={op_name="jit(epoch)'
              '/while/body/optimizer_update/mul"}': 0.4}


@pytest.mark.parametrize("metric,expected", [
    # self seconds of the three dispatch spans; the nested sync left out
    ("host_dispatch_ms_per_step", 1e3 * (0.20 + 0.02 + 0.01) / STEPS),
    ("host_sync_ms_per_step", 1e3 * (0.30 + 0.10) / STEPS),
    ("boundary_host_ms_per_step", 1e3 * (0.04 + 0.01) / STEPS),
    ("callback_host_ms_per_step", 1e3 * (0.004 + 0.001) / STEPS),
    ("input_place_ms_per_step", 1e3 * 0.20 / STEPS),
    ("input_build_ms_per_step", 1e3 * 1.60 / STEPS),
    ("finite_check_device_ms_per_step", 1e3 * 0.03 / STEPS),
    # a kernel by its name, not every custom call and not its scope
    ("attention_kernel_ms_per_step", 1e3 * 0.5 / STEPS),
    ("optimizer_kernel_ms_per_step", 1e3 * 0.002 / STEPS),
])
def test_metric_reads_its_spans_or_events(metric, expected):
    assert read(metric, run(DEVICE_RUN, **HOST_RUN)) == \
        pytest.approx(expected, rel=1e-9)


@pytest.mark.parametrize("metric", [
    "host_dispatch_ms_per_step", "host_sync_ms_per_step",
    "boundary_host_ms_per_step", "callback_host_ms_per_step",
    "input_place_ms_per_step", "input_build_ms_per_step",
    "finite_check_device_ms_per_step", "attention_kernel_ms_per_step",
    "optimizer_kernel_ms_per_step"])
def test_metric_is_none_when_there_is_nothing_to_read(metric):
    """The parent of PR 25: no span counter, no kernel name, no finite
    check in the trace.  Nothing raises; the line leaves the metric
    out."""
    parent = run({UNNAMED: 0.9})
    assert read(metric, parent) is None
    assert read(metric, run(DEVICE_RUN, steps=0, **HOST_RUN)) is None


def test_every_new_metric_is_listed_with_its_cells():
    bench = harness.load_json(harness.os.path.join(harness.ROOT,
                                                   "BENCHMARK.json"))
    cells = [w["name"] for w in bench["workloads"]]
    listed = {m["name"]: m for m in bench["per_layer"]}
    want = {
        "host_dispatch_ms_per_step": cells, "host_sync_ms_per_step": cells,
        "boundary_host_ms_per_step": cells,
        "callback_host_ms_per_step": cells,
        "finite_check_device_ms_per_step": cells,
        "optimizer_kernel_ms_per_step": cells,
        "input_place_ms_per_step": ["resnet50.train_stream"],
        "input_build_ms_per_step": ["resnet50.train_stream"],
        "attention_kernel_ms_per_step": ["openai-gpt.finetune_hbm"]}
    for name, workloads in want.items():
        entry = listed[name]
        assert entry["workloads"] == workloads
        assert entry["better"] == "lower" and entry["unit"] == "ms"
        assert entry["moves"] == "train_records_per_s"
        assert harness.load_module("metrics", name).read
