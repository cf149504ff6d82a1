"""``train_return_gb_per_s``: the bytes the return's ``fetch_global``
fetched over the seconds of the ``train_return`` span, both as growth
between the window's two registry snapshots.  On a ``run`` built by
hand, with a checkpoint's bytes left out, on a program without the
counter (``None``), listed with the eight cells it was added with, and
read end to end by a traced toy run."""

import pytest

from benchmark import harness

from bench_toy import TOY

NAME = "train_return_gb_per_s"
RETURN = 'span_seconds_total{name="train_return"}'
OVERLAPPED = 'host_fetch_bytes_total{path="overlapped",site="train_return"}'
ALLGATHER = 'host_fetch_bytes_total{path="allgather",site="train_return"}'
SNAPSHOT = \
    'host_fetch_bytes_total{path="overlapped",site="checkpoint_save"}'


def run(after, before=None):
    return {"window_s": 20.0, "steps": 48,
            "before": {"counters": before or {}},
            "after": {"counters": after}}


def read(r):
    return harness.load_module("metrics", NAME).read(r)


def test_reads_every_paths_bytes_over_the_returns_seconds():
    before = {RETURN: 0.5, OVERLAPPED: 1.0e9,
              'span_seconds_total{name="train_epoch_scan"}': 3.0}
    after = {RETURN: 0.5 + 0.75, OVERLAPPED: 1.0e9 + 4.0e9,
             ALLGATHER: 0.5e9, SNAPSHOT: 9.0e9,
             'span_seconds_total{name="train_epoch_scan"}': 9.0,
             'span_seconds_total{name="checkpoint_save"}': 2.0}
    assert read(run(after, before)) == pytest.approx(4.5 / 0.75, rel=1e-12)


@pytest.mark.parametrize("after", [
    # the parent: the span, no counter
    {RETURN: 0.9},
    # the counter, and no return inside the window
    {OVERLAPPED: 2.0e9},
    # a checkpoint's bytes alone, none of them the return's
    {SNAPSHOT: 2.0e9, RETURN: 0.9},
    {},
])
def test_is_none_when_there_is_nothing_to_read(after):
    assert read(run(after)) is None


def test_is_listed_with_every_cell():
    bench = harness.load_json(harness.os.path.join(harness.ROOT,
                                                   "BENCHMARK.json"))
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    # the eight cells it was added with, by position: a later cell may
    # be appended to the list or not, and this file need not change
    cells = [w["name"] for w in bench["workloads"]][:8]
    assert len(cells) == 8 and entry["workloads"][:8] == cells
    assert (entry["unit"], entry["better"], entry["moves"],
            entry["layer"], entry["source"]) == (
        "GB/s", "higher", "train_records_per_s", "estimator and dispatch",
        "program_counter")


def test_a_traced_run_reports_the_rate(one_chip):
    name = "openai-gpt.finetune_hbm"
    cell, cfg = TOY[name]
    line = harness.run_cell(name, 2 ** 31 + 42, 1.0, True,
                            require_chip=False, cell_override=cell,
                            cfg_override=cfg)
    assert line["metrics"][NAME]["unit"] == "GB/s"
    assert line["metrics"][NAME]["value"] > 0
