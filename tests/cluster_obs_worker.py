"""Worker for the cluster-observability multiprocess test.

Launched (4x) by tests/test_cluster_observability.py via ``ZooCluster``
with a ``run_dir`` — the launcher's simulate-N-hosts mode (pattern of
tests/distributed_fit_worker.py) but WITHOUT the jax.distributed
handshake: the observability plane is deliberately decoupled from the
collective fabric, so a worker only needs the launcher's env contract
(ZOO_TPU_RUN_DIR / PROCESS_ID / METRICS_PORT / CLOCK_ANCHOR) to join
the plane.  That keeps this tier-1-safe: no coordinator rendezvous, no
gloo, no compiles.

Each worker:
  * brings up its run-dir slot + /metrics endpoint
    (``init_worker_observability`` — host 0 also gets the
    ClusterAggregator, so ITS endpoint serves /metrics/cluster),
  * records deterministic per-step wall/barrier metrics — the worker
    at STRAGGLER_PID is deliberately slowed (3x step time, ~zero
    barrier wait; the others wait out the skew),
  * emits a couple of trace spans, flushes its snapshot, then parks
    until the parent drops ``run_dir/stop`` (so the parent can scrape
    the LIVE federated view first).
"""

import os
import sys
import time

# a host worker unless the parent says otherwise
os.environ.setdefault("JAX_PLATFORMS", "cpu")

STRAGGLER_PID = 2
FAST_STEP_S = 0.01
SLOW_STEP_S = 0.03
STEPS = 50


def main():
    pid = int(os.environ["ZOO_TPU_PROCESS_ID"])
    run_dir = os.environ["ZOO_TPU_RUN_DIR"]

    from analytics_zoo_tpu.observability import (
        flush_worker_observability, get_registry, get_tracer,
        init_worker_observability)
    wdir = init_worker_observability(process_index=pid)
    assert wdir and os.path.isdir(wdir), wdir

    reg = get_registry()
    # immutable identity: a second, conflicting set must raise
    try:
        reg.set_const_labels(process_index=str(pid + 1))
    except ValueError:
        pass
    else:
        raise AssertionError("const labels were not immutable")

    step_s = SLOW_STEP_S if pid == STRAGGLER_PID else FAST_STEP_S
    barrier_s = 0.0 if pid == STRAGGLER_PID \
        else (SLOW_STEP_S - FAST_STEP_S)
    steps = reg.counter("train_steps_total", "train steps dispatched",
                        labels=("path",))
    lat = reg.histogram("train_step_latency_seconds",
                        "host wall time per dispatched train step",
                        labels=("path",))
    barrier = reg.histogram(
        "train_barrier_wait_seconds",
        "sampled cross-host barrier wait after a train step")
    reg.gauge("train_prefetch_queue_depth", "prefetch depth").set(pid)
    reg.counter("collective_bytes_total", "estimated collective bytes",
                labels=("op",)).labels("psum_grads").inc(
                    STEPS * 1_000_000.0)
    if pid == 0:
        reg.gauge("pipeline_bubble_fraction",
                  "GPipe fill/drain bubble").set(0.25)
    tracer = get_tracer()
    for _ in range(STEPS):
        with tracer.span("train_step", worker=pid):
            pass   # synthetic: the recorded VALUES carry the skew
        steps.labels("per_step").inc()
        lat.labels("per_step").observe(step_s)
        barrier.observe(barrier_s)
    flush_worker_observability()

    # stay scrapeable until the parent has exercised /metrics/cluster
    stop = os.path.join(run_dir, "stop")
    deadline = time.time() + 60.0
    while not os.path.exists(stop) and time.time() < deadline:
        time.sleep(0.05)
    print(f"cluster obs worker {pid} done", flush=True)


if __name__ == "__main__":
    main()
    sys.exit(0)
