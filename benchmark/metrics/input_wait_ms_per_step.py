"""Host milliseconds a step waited for its batch: the growth of the
program's ``data_batch_wait_seconds`` histogram over the window, per
step.  Only the ``DataPipeline`` path feeds that histogram."""

from benchmark.harness import histogram_delta


def read(run):
    waited = histogram_delta(run["after"], run["before"],
                             "data_batch_wait_seconds")
    if not waited["count"] or not run["steps"]:
        return None
    return 1e3 * waited["sum"] / run["steps"]
