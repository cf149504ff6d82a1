"""Seconds of set-up in which the host traced Python to jaxprs or
lowered jaxprs to MLIR modules, every function of the process: the
program's ``jax_trace_seconds_total{fn}`` and
``jax_lower_seconds_total{fn}``, which hold each stage LESS the stages
nested in it, so their sum is wall time (a train program's trace holds
the traces of the jitted functions and kernels called in it)."""

from benchmark.metrics._startup import family_at_open


def read(run):
    trace = family_at_open(run, "jax_trace_seconds_total")
    lower = family_at_open(run, "jax_lower_seconds_total")
    if not trace and not lower:
        return None
    return sum(trace.values()) + sum(lower.values())
