"""The control kept alive at a toy size: the reference put in the
program's place in float8 has to read far above what a sound float32 run
reads, and so has each planted fault.  (The readings that the limits were
set from are the chip's, at the cells' own size: PERF.md.)"""

import pytest

from bench_toy import TOY
from benchmark import control

NAME = "openai-gpt.finetune_hbm"
# what the sound program reads against the reference in float32 on the
# CPU (test_program_matches_reference_in_float32 holds it to these)
SOUND = {"loss": 1e-5, "grad": 1e-4, "dparam": 1e-4}


@pytest.fixture(scope="module")
def readings():
    cell, cfg = TOY[NAME]
    return control.read_cell(
        NAME, 2 ** 31 + 11, ["round:fp8", "round:bf16", "fault:half_batch"],
        cell, cfg, require_chip=False)


def read(rec, key):
    """The reading the cells hold: the median leaf's gap for the
    per-leaf numbers, the widest gap for the loss."""
    return rec[key]["value" if key == "loss" else "median_gap"]


def test_float8_control_is_not_correct(readings):
    fp8 = readings["round:fp8"]
    for key in SOUND:
        assert read(fp8, key) > 30 * SOUND[key], key


def test_float8_reads_above_bfloat16(readings):
    """The control is the precision BELOW the configuration's: it has to
    stand clear of the configuration's own."""
    for key in SOUND:
        assert read(readings["round:fp8"], key) > \
            3 * read(readings["round:bf16"], key), key


def test_half_batch_fault_is_not_correct(readings):
    half = readings["fault:half_batch"]
    assert read(half, "grad") > 1000 * SOUND["grad"]
    assert read(half, "dparam") > 1000 * SOUND["dparam"]


def test_the_control_itself_refuses_the_cpu():
    from benchmark import harness
    with pytest.raises(harness.BenchmarkError):
        control.read_cell(NAME, 1, [], *TOY[NAME])
