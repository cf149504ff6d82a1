"""Shared by the benchmark's tests: the harness driven at toy sizes on
the suite's CPU devices, with the kernels steered to the Pallas branch in
interpret mode BY THE TEST (the program has no such option)."""

import os
import re
import sys

import jax
import pytest
from jax.experimental import pallas as pl

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

@pytest.fixture
def one_chip(monkeypatch):
    """A one-chip process on the suite's 8 virtual devices: a one-device
    context mesh, the kernel suite on its Pallas branch, every kernel
    interpreted, and the trace reduction pointed at the CPU's plane."""
    from analytics_zoo_tpu.common import zoo_context
    from analytics_zoo_tpu.common.config import get_config
    from analytics_zoo_tpu.ops import fused
    from analytics_zoo_tpu.parallel import mesh as mesh_lib
    from benchmark import trace_reduce
    mesh = mesh_lib.create_mesh({"data": 1}, devices=jax.devices()[:1])
    monkeypatch.setattr(zoo_context, "_context",
                        zoo_context.ZooContext(get_config(), mesh))
    monkeypatch.setattr(fused, "pallas_supported", lambda: True)
    monkeypatch.setattr(
        fused, "_use_pallas", lambda: fused._mode() in ("auto", "pallas"))
    compiled_call = pl.pallas_call

    def interpreted(*args, **kwargs):
        kwargs["interpret"] = True
        return compiled_call(*args, **kwargs)
    monkeypatch.setattr(pl, "pallas_call", interpreted)
    monkeypatch.setattr(trace_reduce, "DEVICE_PLANE",
                        re.compile(r"^/host:CPU$"))
    monkeypatch.setattr(trace_reduce, "OPS_LINE", None)


@pytest.fixture
def f32_program():
    """The program in full float32, so that it and the float32 reference
    compute the same thing and a tight comparison means something."""
    from analytics_zoo_tpu.ops import dtypes
    old = dtypes.get_policy()
    dtypes.set_policy(param_dtype="float32", compute_dtype="float32")
    yield
    dtypes.restore_policy(old)
