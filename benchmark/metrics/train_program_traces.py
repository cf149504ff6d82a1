"""Times the train program's Python body was traced before the window
opened: ``train_program_traces_total{path}`` for the engine whose
``train_steps_total{path}`` moved in the window.  Counted in the body
itself, so exact: 1 is the floor (the first call), and a warm-start or
a cost analysis whose signature jit has not traced yet adds one each."""

from benchmark.harness import counter_delta
from benchmark.metrics._startup import family_at_open


def read(run):
    engines = counter_delta(run["after"], run["before"], "train_steps_total")
    traces = family_at_open(run, "train_program_traces_total")
    found = [traces[path] for path in engines if path in traces]
    return sum(found) if found else None
