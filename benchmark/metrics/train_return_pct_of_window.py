"""Share of the window ``Estimator.train`` spent handing the trained
state back (``train_return``: ``fetch_global`` of the parameters and
the state, ``model.set_variables``), after the last step: growth of
``span_seconds_total{name="train_return"}`` between the snapshots over
``run["window_s"]``.  Read it beside ``device_idle_pct``: the device
has nothing to run meanwhile."""

from benchmark.harness import counter_delta


def read(run):
    moved = counter_delta(run["after"], run["before"],
                          "span_seconds_total").get('{name="train_return"}')
    return None if moved is None else 100.0 * moved / run["window_s"]
