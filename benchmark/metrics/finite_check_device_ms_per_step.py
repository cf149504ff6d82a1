"""Device milliseconds a step inside the finite check's host callback:
the own time of the events named ``debug_callback`` (the device holds
the step there until the host has taken the flag)."""

from benchmark.trace_reduce import seconds_of


def read(run):
    if not run["steps"]:
        return None
    seconds = seconds_of(run["trace"], ["debug_callback"])
    return 1e3 * seconds / run["steps"] if seconds else None
