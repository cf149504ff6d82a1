"""The one general generator: a cell's rows from its file's parameters
and ``--seed``.

A cell's file (``workloads/<cell>.json``) says how many rows the data
set holds, how they are stored (``dtype``), and through which input
layer of the program they go (``source``: ``feature_set`` or
``data_pipeline``, with ``num_workers`` and ``stages``).  What one
record is (an image, a token sequence) comes from the configuration's
``input_spec``.  Every row differs; the same seed gives the same rows.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def make_rows(spec: Dict, cell: Dict, seed: int):
    """``(x, y)`` host arrays: ``x`` an array or a tuple of arrays with
    ``rows`` leading, ``y`` int32 labels of shape ``(rows, 1)``."""
    rng = np.random.Generator(np.random.PCG64(seed))
    n = int(cell["rows"])
    labels = rng.integers(0, spec["classes"], size=(n, 1), dtype=np.int32)
    if spec["kind"] == "image":
        shape = (n,) + tuple(spec["shape"])
        if cell.get("dtype", "float32") == "uint8":
            x = rng.integers(0, 256, size=shape, dtype=np.uint8)
        else:
            # zero mean, unit variance: what a normalising input
            # pipeline hands over
            x = rng.standard_normal(shape, dtype=np.float32)
        return x, labels
    if spec["kind"] == "tokens":
        t = spec["seq_len"]
        tokens = rng.integers(0, spec["vocab"], size=(n, t), dtype=np.int32)
        # positions are the last seq_len rows of the shared table
        positions = np.broadcast_to(
            spec["vocab"] + np.arange(t, dtype=np.int32), (n, t)).copy()
        return (tokens, positions), labels
    raise ValueError(f"unknown record kind {spec['kind']!r}")


# ------------------------------------------------------------ host stages
def flip_normalize(stage: Dict):
    """uint8 HWC images -> horizontally flipped float32, normalised."""
    mean, std = np.float32(stage["mean"]), np.float32(stage["std"])

    def fn(batch):
        x, y = batch
        return ((x[:, :, ::-1, :].astype(np.float32) - mean) / std, y)
    return fn


STAGES = {"flip_normalize": flip_normalize}


def take_rows(x, idx):
    if isinstance(x, tuple):
        return tuple(a[idx] for a in x)
    return x[idx]


def build_source(cell: Dict, x, y, batch_size: int, shuffle_seed: int):
    """The program's input layer over the rows, and a function giving
    the row indices of 0-based training step ``i`` of epoch 0 (the
    program's own deterministic order, read from its sampler: the
    reference is handed the same rows in the same order)."""
    kind = cell["source"]
    if kind == "feature_set":
        from analytics_zoo_tpu.feature.feature_set import FeatureSet
        xs = list(x) if isinstance(x, tuple) else x
        fs = FeatureSet.from_ndarrays(xs, y, shuffle=True,
                                      seed=shuffle_seed)
        perm = np.asarray(fs._epoch_perm(0))

        def step_rows(i: int) -> np.ndarray:
            return perm[i * batch_size:(i + 1) * batch_size]
        return fs, step_rows, len(perm) // batch_size
    if kind == "data_pipeline":
        from analytics_zoo_tpu.data import DataPipeline
        pipe = DataPipeline(x, y, batch_size=batch_size, shuffle=True,
                            seed=shuffle_seed,
                            num_workers=int(cell.get("num_workers", 0)))
        for stage in cell.get("stages", []):
            pipe = pipe.map(STAGES[stage["kind"]](stage))
        sampler = pipe.sampler

        def step_rows(i: int) -> np.ndarray:
            return np.asarray(sampler.batch_indices(0, i)[0])
        return pipe, step_rows, pipe.num_batches
    raise ValueError(f"unknown source kind {kind!r}")


def check_order(step_rows, steps: int, rows: int) -> None:
    """The order read from the program has to deliver distinct rows of
    the data set: anything else is not an epoch."""
    seen = np.concatenate([step_rows(i) for i in range(steps)])
    if len(np.unique(seen)) != len(seen) or seen.min() < 0 \
            or seen.max() >= rows:
        raise RuntimeError("the input layer's order repeats or leaves "
                           "the data set")
