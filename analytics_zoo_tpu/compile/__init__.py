"""``engine_jit``: the one place a compiled program is built
(docs/aot-compile.md)."""

from analytics_zoo_tpu.compile.engine import (  # noqa: F401
    EngineJit, engine_jit)
