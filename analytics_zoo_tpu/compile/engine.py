"""engine_jit — ``jax.jit`` with a name: the one place every compiled
program in ``analytics_zoo_tpu/`` is built (zoolint COMPILE011 holds
every other module to it).

Dispatch is jit's own, and so are both caches: the in-process one that
``CompileMonitor`` reads for its accounting, and the persistent one on
disk (``analytics_zoo_tpu._place_compile_cache``;
``JAX_COMPILATION_CACHE_DIR`` places it from outside).  What the
chokepoint adds is ``key_hint``, the program's name, and :meth:`warm`,
which pays a program's compile before its first request or step.  See
docs/aot-compile.md.
"""

from __future__ import annotations

import logging
from typing import Optional

import jax

log = logging.getLogger("analytics_zoo_tpu.compile")

_UNSPECIFIED = object()


class EngineJit:
    """A jitted function that knows its name.  Attributes it does not
    have (``lower``, ``eval_shape``, ``_cache_size``, ...) are the
    jitted function's."""

    def __init__(self, fn, *, static_argnums=(), donate_argnums=(),
                 in_shardings=_UNSPECIFIED,
                 out_shardings=_UNSPECIFIED,
                 key_hint: Optional[str] = None):
        kwargs = {"static_argnums": static_argnums,
                  "donate_argnums": donate_argnums}
        if in_shardings is not _UNSPECIFIED:
            kwargs["in_shardings"] = in_shardings
        if out_shardings is not _UNSPECIFIED:
            kwargs["out_shardings"] = out_shardings
        self._jit = jax.jit(fn, **kwargs)
        self.key_hint = key_hint or getattr(fn, "__qualname__", None) \
            or getattr(fn, "__name__", None) or "fn"

    def __getattr__(self, item):
        return getattr(self._jit, item)

    def __call__(self, *args):
        return self._jit(*args)

    def warm(self, *args) -> bool:
        """Compile the program for these arguments without running it:
        ``args`` are concrete arrays or ``jax.ShapeDtypeStruct``s
        (with their shardings for a sharded program); nothing is
        donated.  jit finds the executable again at the first call of
        the same signature, which then fires no backend compile
        (``tests/test_engine_jit.py`` holds that).  False when the
        program could not be compiled for them: the first call then
        compiles, or raises what this swallowed."""
        try:
            self._jit.lower(*args).compile()
        except Exception:   # noqa: BLE001 — warming is best-effort
            log.debug("engine_jit %r: warm failed; the first call "
                      "compiles", self.key_hint, exc_info=True)
            return False
        return True


def engine_jit(fn, *, static_argnums=(), donate_argnums=(),
               in_shardings=_UNSPECIFIED, out_shardings=_UNSPECIFIED,
               key_hint: Optional[str] = None) -> EngineJit:
    """``jax.jit(fn, static_argnums=..., donate_argnums=...,
    in_shardings=..., out_shardings=...)`` through the platform's
    chokepoint; ``key_hint`` names the program."""
    return EngineJit(fn, static_argnums=static_argnums,
                     donate_argnums=donate_argnums,
                     in_shardings=in_shardings,
                     out_shardings=out_shardings, key_hint=key_hint)
