"""Device milliseconds a training step took: the union of the device's
operation intervals in the traced window, per step."""


def read(run):
    if not run["steps"]:
        return None
    return 1e3 * run["trace"]["busy_s"] / run["steps"]
