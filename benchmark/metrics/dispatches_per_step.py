"""Train-program dispatches per training step in the window, from the
program's ``train_steps_total{path}`` and the boundaries the trigger
saw: one dispatch a step on ``per_step``, one an epoch on
``epoch_scan``.  Exact."""

from benchmark.harness import counter_delta


def read(run):
    moved = counter_delta(run["after"], run["before"], "train_steps_total")
    steps = sum(moved.values())
    if not steps:
        return None
    dispatches = sum(v for k, v in moved.items() if "per_step" in k)
    if any("per_step" not in k for k in moved):
        dispatches += run["boundaries"]
    return dispatches / steps
