"""Seconds in the XLA backend compile during set-up, persistent-cache
lookups included, as ``jax.monitoring`` reports them."""


def read(run):
    return run["compile_s"] or None
