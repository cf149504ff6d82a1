"""Context initialisation — the ``NNContext`` equivalent.

Reference behavior (NNContext.scala:133-149 ``initNNContext``): create or
fetch the SparkContext with zoo conf defaults, set MKL env vars per
engine type, version-check, then ``Engine.init`` discovers the node and
core topology.  TPU-natively the "engine" is JAX/XLA and the topology is
the device mesh, so ``init_zoo_context``:

1. resolves the layered config (``ZooConfig``),
2. initialises ``jax.distributed`` when a multi-host environment is
   detected (the Engine.init analogue),
3. builds the default ``jax.sharding.Mesh`` (ICI×DCN axes),
4. applies numeric policy (matmul precision, default dtypes).

Like the reference, it is idempotent: repeated calls return the live
context (``getOrCreate`` semantics).
"""

from __future__ import annotations

import logging
import os
from typing import Any, Dict, Optional

import jax

from analytics_zoo_tpu.common.config import ZooConfig, set_config
from analytics_zoo_tpu.parallel import mesh as mesh_lib

log = logging.getLogger("analytics_zoo_tpu")


class ZooContext:
    """Live runtime context: config + mesh + process topology."""

    def __init__(self, config: ZooConfig, mesh):
        self.config = config
        self.mesh = mesh
        self.process_index = jax.process_index()
        self.process_count = jax.process_count()
        self.local_devices = jax.local_devices()
        self.devices = jax.devices()

    @property
    def num_devices(self) -> int:
        return len(self.devices)

    @property
    def is_coordinator(self) -> bool:
        return self.process_index == 0

    def __repr__(self):
        return (f"ZooContext(devices={self.num_devices}, "
                f"processes={self.process_count}, "
                f"mesh={dict(self.mesh.shape)})")


_context: Optional[ZooContext] = None


def init_zoo_context(conf: Optional[Dict[str, Any]] = None,
                     conf_file: Optional[str] = None,
                     mesh_shape: Optional[Dict[str, int]] = None,
                     name: str = "Analytics Zoo TPU") -> ZooContext:
    """Create (or return) the global context.

    Mirrors ``init_nncontext`` (pyzoo nncontext.py:104): conf may carry
    any dotted config key; ``mesh_shape`` is an axis→size dict, e.g.
    ``{"data": 8}`` or ``{"data": -1, "model": 4}``.
    """
    global _context
    if _context is not None:
        return _context

    # the compile-stage clocks (docs/observability.md, "The start-up
    # timeline") run from here on, so that they hold a model's
    # initialisation and the caller's own programs, not only what
    # compiles after the first trainer is built
    from analytics_zoo_tpu.observability.diagnostics import (
        install_compile_listener)
    install_compile_listener()

    # Programmatic sets made BEFORE context init (get_config().set)
    # carry over; explicit init conf wins on conflicts.
    from analytics_zoo_tpu.common import config as config_mod
    prior = getattr(config_mod._global_config, "_programmatic", None) \
        if config_mod._global_config is not None else None
    merged = {**(prior or {}), **(conf or {})}
    config = ZooConfig(conf_file=conf_file, overrides=merged or None)
    set_config(config)

    logging.basicConfig(level=getattr(logging, str(config.get("log.level")),
                                      logging.INFO))

    # Multi-host bring-up (the Engine.init role): only when the standard
    # coordinator env is present and more than one process is declared.
    n_proc = int(os.environ.get("ZOO_TPU_NUM_PROCESSES", "1"))
    if n_proc > 1 and os.environ.get("ZOO_TPU_COORDINATOR"):
        jax.distributed.initialize(
            coordinator_address=os.environ["ZOO_TPU_COORDINATOR"],
            num_processes=n_proc,
            process_id=int(os.environ.get("ZOO_TPU_PROCESS_ID", "0")),
        )

    jax.config.update("jax_default_matmul_precision",
                      str(config.get("dtype.matmul_precision")))

    if mesh_shape is None:
        raw = config.get("mesh.shape")
        if raw and raw != "auto":
            mesh_shape = {}
            for part in str(raw).split(","):
                ax, s = part.split(":")
                mesh_shape[ax.strip()] = int(s)
    mesh = mesh_lib.create_mesh(mesh_shape)

    _context = ZooContext(config, mesh)

    # Cluster observability plane: when the launcher handed us a run
    # dir, stamp this worker's immutable host/process_index labels on
    # the registry, start its metrics endpoint on the injected port,
    # and (host 0) attach the cluster aggregator.  Best-effort — a
    # broken metrics port must never stop training.
    if os.environ.get("ZOO_TPU_RUN_DIR"):
        try:
            from analytics_zoo_tpu.observability.aggregator import (
                init_worker_observability)
            init_worker_observability(
                process_index=_context.process_index)
        except Exception:
            log.exception("cluster observability bring-up failed")

    log.info("%s initialised: %r", name, _context)
    return _context


def get_zoo_context() -> ZooContext:
    """Return the live context, initialising with defaults if needed."""
    if _context is None:
        return init_zoo_context()
    return _context


def reset_zoo_context() -> None:
    """Drop the global context (test helper)."""
    global _context
    _context = None
