"""Seconds of set-up reading executables from JAX's persistent
compilation cache (``compile_cache_load_seconds_total``): the part of
``compile_s`` that is a read from disk (a warm checkout) and not a
compile (a cold one, where this reads 0)."""

from benchmark.metrics._startup import family_at_open


def read(run):
    return family_at_open(run, "compile_cache_load_seconds_total").get("")
