#!/usr/bin/env python3
"""Read the control and the planted faults of a cell, on the chip, at
the cell's own size.  Not part of a benchmark run: this is how the
limits in ``workloads/<cell>.json`` were set (PERF.md gives the
readings), and ``tests/benchmark`` keeps it alive at a toy size.

    python benchmark/control.py --workload <cell> --seeds 1,2,3

For each seed the plain reference follows the cell's first steps, and
is then put in the program's place three ways: computed in the nearest
precision below the one the configuration states (``fp8`` for bfloat16),
in the configuration's own (``bf16``: what a sound program should read
like), and with half of each batch left out (``half_batch``).  Each
prints the numbers the comparison would read.  A state left unchanged
reads 1 in ``dparam`` by the measure's definition and needs no run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def stand_in(result, scan: bool):
    """A ``follow`` result laid out as the harness lays out what the
    program produced."""
    losses = result["loss"]
    k = len(losses)
    loss = [(0, k, sum(losses) / k)] if scan else \
        [(i, i + 1, v) for i, v in enumerate(losses)]
    return {"loss": loss, **{k: v for k, v in result.items()
                             if k.endswith("_norm")}}


def read_cell(name: str, seed: int, variants, cell_override=None,
              cfg_override=None, require_chip: bool = True):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax
    from benchmark import correctness, data as data_lib, harness
    entry, cell, cfg = harness.load_cell(name)
    cell = {**cell, **(cell_override or {})}
    cfg = {**cfg, **(cfg_override or {})}
    if require_chip and jax.devices()[0].platform != "tpu":
        raise harness.BenchmarkError("the control is read on the chip")
    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.common.config import get_config
    init_zoo_context()
    model_file = harness.load_module("configs", cfg["name"])
    reference = harness.load_module("reference", cfg["name"])
    batch = int(cfg["batch_size"])
    x, y = data_lib.make_rows(model_file.input_spec(cfg), cell, seed)
    source, step_rows, steps_per_epoch = data_lib.build_source(
        cell, x, y, batch, int(get_config().get("data.shuffle_seed")))
    scan = cell["engine"] == "epoch_scan"
    follow = steps_per_epoch if scan else int(cell["follow_steps"])
    moment_after = follow if scan else 1
    if hasattr(source, "close"):
        source.close()
    stages = cell.get("stages", [])

    def batches():
        for i in range(follow):
            rows = step_rows(i)
            yield (reference.prepare(cfg, stages,
                                     data_lib.take_rows(x, rows)), y[rows])

    t0 = time.perf_counter()
    ref = reference.follow(cfg, seed, batches(), moment_after)
    out = {"seed": seed, "reference_s": time.perf_counter() - t0}
    for variant in variants:
        kind, _, arg = variant.partition(":")
        kwargs = {"rounding": arg} if kind == "round" else {"fault": arg}
        got = reference.follow(cfg, seed, batches(), moment_after, **kwargs)
        numbers = correctness.compare(stand_in(got, scan), ref)
        out[variant] = {k: {kk: vv for kk, vv in v.items() if kk != "all"}
                        for k, v in numbers.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--variants",
                    default="round:fp8,round:bf16,fault:half_batch")
    args = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    for seed in args.seeds.split(","):
        rec = read_cell(args.workload, int(seed), args.variants.split(","))
        rec["device"] = {"platform": dev.platform, "kind": dev.device_kind}
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
