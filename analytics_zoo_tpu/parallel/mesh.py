"""Device-mesh construction and sharding helpers.

The reference's distribution fabric is Spark: ``Engine.init`` discovers
node/core counts and ``AllReduceParameter`` partitions the parameter
vector across Spark block managers (SURVEY.md §2.4).  TPU-natively the
fabric is a ``jax.sharding.Mesh``: ICI links inside a slice, DCN between
slices, with XLA inserting collectives from sharding annotations.

Axis convention (outer → inner, fastest collectives innermost):

- ``data``  : pure data parallelism (gradient psum) — the reference's
              only training parallelism (wp-bigdl.md:113-171).
- ``fsdp``  : optional parameter/optimizer sharding (ZeRO-style) —
              a new TPU-native capability.
- ``model`` : tensor parallelism for wide layers.
- ``seq``   : sequence/context parallelism (ring attention).
- ``pipe``  : pipeline parallelism (GPipe microbatch schedule over
              ppermute — parallel/pipeline.py).
- ``expert``: expert parallelism for MoE layers (all_to_all token
              routing).

A 1-chip mesh is simply shape ``{"data": 1}`` — every code path is
written against the mesh so that single-chip and pod runs share code.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
FSDP_AXIS = "fsdp"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
PIPE_AXIS = "pipe"
EXPERT_AXIS = "expert"

ALL_AXES = (DATA_AXIS, FSDP_AXIS, MODEL_AXIS, SEQ_AXIS, PIPE_AXIS,
            EXPERT_AXIS)


def create_mesh(shape: Optional[Dict[str, int]] = None,
                devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a Mesh from an axis→size dict.

    ``shape=None`` puts every device on the ``data`` axis (matching the
    reference's pure-DP posture).  Axes with size 1 are still created so
    sharding specs can always name them.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    n = len(devices)
    if shape is None:
        shape = {DATA_AXIS: n}
    # Fill in implicit axes with size 1, preserving canonical order.
    sizes = {ax: int(shape.get(ax, 1)) for ax in ALL_AXES}
    # Allow a -1 wildcard on one axis.
    wild = [ax for ax, s in sizes.items() if s == -1]
    if len(wild) > 1:
        raise ValueError("at most one mesh axis may be -1")
    fixed = math.prod(s for s in sizes.values() if s != -1)
    if wild:
        if n % fixed != 0:
            raise ValueError(
                f"cannot infer {wild[0]}: {n} devices not divisible by {fixed}")
        sizes[wild[0]] = n // fixed
    total = math.prod(sizes.values())
    if total != n:
        raise ValueError(
            f"mesh shape {sizes} needs {total} devices, have {n}")
    dims = [sizes[ax] for ax in ALL_AXES]
    if devices and devices[0].platform == "tpu" and n > 1:
        # On real TPUs mesh_utils lays devices out so inner mesh axes
        # ride ICI and the outermost (data) axis spans DCN/slices — a
        # plain reshape can put a model axis across slice boundaries
        # and turn every tensor-parallel collective into a DCN hop.  A
        # layout mesh_utils refuses raises: never a quiet row-major.
        from jax.experimental import mesh_utils
        arr = mesh_utils.create_device_mesh(
            dims, devices=devices, allow_split_physical_axes=True)
    else:
        arr = np.array(devices).reshape(dims)
    return Mesh(arr, ALL_AXES)


def data_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Shard dim 0 across data(+fsdp) axes; replicate the rest.

    Batches are split over every data-parallel device, the way the
    reference splits an RDD's partitions across executors.
    """
    spec = [None] * ndim
    spec[0] = (DATA_AXIS, FSDP_AXIS)
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_shardings(mesh: Mesh, batch_pytree):
    """Per-leaf data shardings for an arbitrary batch pytree."""
    return jax.tree_util.tree_map(
        lambda x: data_sharding(mesh, np.ndim(x)), batch_pytree)


def fsdp_shardings(mesh: Mesh, params, min_size: int = 2 ** 12):
    """ZeRO-style sharding spec for a parameter pytree.

    Each large-enough leaf is sharded along its largest dimension that
    divides the fsdp axis size; small leaves replicate.  This is the
    TPU-native answer to the reference's *partitioned*
    ``AllReduceParameter`` (the parameter vector chunked across nodes,
    Topology.scala:1126-1128) — except here the optimizer update also
    runs sharded and XLA handles the gather.
    """
    axis = mesh.shape[FSDP_AXIS]

    def leaf_spec(x):
        if axis == 1 or x.size < min_size:
            return NamedSharding(mesh, P())
        dims = list(np.argsort(x.shape)[::-1])
        for d in dims:
            if x.shape[d] % axis == 0:
                spec = [None] * x.ndim
                spec[d] = FSDP_AXIS
                return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map(leaf_spec, params)


def local_batch_size(mesh: Mesh, batch_size: int) -> int:
    """Per-device rows for ``batch_size``.

    Single-host: ``batch_size`` is the global batch.  Multi-host:
    ``batch_size`` is the PER-HOST batch (each process contributes its
    own slice of the global batch), so it must tile this host's share
    of the data-parallel degree.
    """
    dp = mesh.shape[DATA_AXIS] * mesh.shape[FSDP_AXIS]
    nproc = jax.process_count()
    if nproc > 1 and dp % nproc == 0:
        dp = dp // nproc
    if batch_size % dp != 0:
        raise ValueError(
            f"batch {batch_size} not divisible by data-parallel "
            f"degree {dp}" + (" (per-host)" if nproc > 1 else ""))
    return batch_size // dp


def data_split_across_hosts(mesh: Mesh) -> bool:
    """True when the data axes divide across processes (each host feeds
    its own slice of the global batch); False means every host must
    feed IDENTICAL replicated batches.  The single source of truth for
    the host-splitting rule used by put_batch / epoch_scan_fn /
    benchmarks."""
    dp = mesh.shape[DATA_AXIS] * mesh.shape[FSDP_AXIS]
    nproc = jax.process_count()
    return nproc > 1 and dp % nproc == 0 and dp >= nproc


def global_batch_rows(mesh: Mesh, batch_size: int) -> int:
    """Rows of the GLOBAL batch for a per-host ``batch_size`` (equal to
    ``batch_size`` whenever hosts replicate instead of splitting)."""
    return batch_size * (jax.process_count()
                         if data_split_across_hosts(mesh) else 1)


def fetch_global(tree):
    """Bring a (possibly cross-process-sharded) pytree to host numpy.

    ``jax.device_get`` refuses arrays whose shards live on other
    processes' devices (e.g. fsdp-sharded params on a multi-host mesh);
    those leaves go through ``process_allgather`` instead — a
    collective, so EVERY process must call this together (the reference
    analogue is InternalDistriOptimizer.getModel pulling the
    AllReduceParameter chunks back to the driver, Topology.scala:1549).
    """
    def fetch(leaf):
        if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
            from jax.experimental import multihost_utils
            return multihost_utils.process_allgather(leaf, tiled=True)
        return jax.device_get(leaf)

    return jax.tree_util.tree_map(fetch, tree)
