"""Seconds of set-up on the model's state and the first batch: every
leaf initialised in ``get_variables()`` (``startup_init_variables``:
the harness then lays the seed's weights over them),
``Estimator.train`` placing parameters and state on the mesh and
building the optimizer's state (``startup_place_state``), and the warm
batch built on the main thread where a ``DataPipeline`` feeds the job
(``startup_loader``; a scan cell's rows go to HBM in milliseconds,
under no span of their own)."""

from benchmark.metrics._startup import span_seconds_at_open


def read(run):
    return span_seconds_at_open(
        run, ("startup_init_variables", "startup_place_state",
              "startup_loader"),
        needs="startup_place_state")
