"""Expert-layer load, read where the host already waits.

``DroplessMoE`` counts the rows it routes to each expert it holds (and,
in one more slot, to all the others) in its non-trained layer state,
which rides the train program's carry like BatchNorm's moving
statistics: no host callback, no further output of the program.  At the
syncs the train loop has anyway (every host read of a loss, the end of
``train``) ``MoeStatsReader.read`` fetches the few integers of each
layer, under a ``moe_stats_read`` span, and publishes what was routed
since its last read:

* ``moe_rows_routed_total{layer, held}`` — assignments (token, expert)
  routed to the experts this chip holds (``held="1"``: the rows the
  grouped products processed) and to the absent ones (``held="0"``);
* ``moe_expert_load_max_over_mean{layer}`` — over the held experts, the
  busiest one's rows over the mean, for the interval since the last
  read (1.0 is an even split).
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from analytics_zoo_tpu.observability.metrics import get_registry
from analytics_zoo_tpu.observability.tracing import get_tracer

STATE_KEY = "rows_routed"


class MoeStatsReader:
    """The expert layers of one model and the counts last read."""

    def __init__(self, model, state):
        # the layers whose state holds the counts: a ``DroplessMoE``, or
        # a decoder layer that holds one and carries its state
        self.layers = [l.name for l in getattr(model, "layers", ())
                       if STATE_KEY in (state.get(l.name) or {})]
        self._last: Dict[str, np.ndarray] = {}
        if not self.layers:
            return
        reg = get_registry()
        self._rows = reg.counter(
            "moe_rows_routed_total",
            "assignments routed to held (1) and absent (0) experts",
            labels=("layer", "held"))
        self._load = reg.gauge(
            "moe_expert_load_max_over_mean",
            "busiest held expert's rows over the mean, last interval",
            labels=("layer",))
        self._last = self._fetch(state)

    def _fetch(self, state) -> Dict[str, np.ndarray]:
        import jax
        host = jax.device_get({n: state[n][STATE_KEY] for n in self.layers})
        # the counts are int32 that wrap; differences are taken mod 2**32
        return {n: np.asarray(a).astype(np.uint32) for n, a in host.items()}

    def read(self, state, iteration: Optional[int] = None) -> None:
        """Publish what was routed since the last read.  ``state`` must
        be ready (call after a sync on the dispatch that produced it)."""
        if not self.layers:
            return
        with get_tracer().span("moe_stats_read", jax_annotation=True,
                               iteration=iteration):
            now = self._fetch(state)
            for name, rows in now.items():
                delta = (rows - self._last[name]).astype(np.int64)
                if (delta >= 2 ** 31).any():
                    continue      # a restored state: count on from it
                held = delta[:-1]
                self._rows.labels(name, "1").inc(float(held.sum()))
                self._rows.labels(name, "0").inc(float(delta[-1]))
                if held.sum() > 0:
                    self._load.labels(name).set(
                        float(held.max() / held.mean()))
            self._last = now
