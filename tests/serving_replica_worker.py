"""Serving replica worker for the supervisor fleet tests.

Launched (Nx) by ``tests/test_serving_resilience.py`` through a
:class:`~analytics_zoo_tpu.serving.supervisor.ServingSupervisor`
worker factory.  It runs the REAL ``ClusterServing`` loop (consumer
group, PEL reclaim, quarantine, breaker, /healthz, heartbeats, drain)
against the test's ``BrokerServer``, but with a pure-numpy model so a
replica spawn costs an import, not a compile:

* a record whose values exceed ``1e8`` is POISON — the model
  ``os._exit(11)``\\ s, the process-killing payload class (segfault /
  OOM inside predict) that in-process chaos cannot express;
* scripted chaos (``ZOO_TPU_CHAOS``, e.g. a ``kill`` at
  ``serving.predict`` step 0) rides the normal env contract and is
  parsed by ``active_chaos()`` inside the serving loop;
* ``--start-delay`` staggers replica bring-up so a test can guarantee
  WHICH replica owns the first batch.
"""

import argparse
import os
import sys
import time

# a host worker unless the parent says otherwise
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

POISON_THRESHOLD = 1e8
POISON_EXIT_CODE = 11


class PoisonSensitiveModel:
    """Numpy stand-in for an InferenceModel whose predict DIES on the
    magic poison payload (the crash class the quarantine exists for).
    ``predict_delay`` simulates device time per batch so autoscaler
    tests can build sustained queue pressure against a fast model."""

    def __init__(self, predict_delay: float = 0.0):
        self.predict_delay = float(predict_delay)

    def predict(self, x, batch_size=None):
        x = np.asarray(x, dtype=np.float32)
        if np.any(np.abs(x) > POISON_THRESHOLD):
            os._exit(POISON_EXIT_CODE)
        if self.predict_delay > 0:
            time.sleep(self.predict_delay)
        return np.tile(np.arange(4, dtype=np.float32), (len(x), 1))


def main(argv=None) -> int:
    # a TERM before the serve loop exists (mid-import, mid start
    # delay) has nothing in flight to drain: exit 0 immediately.
    # ClusterServing.install_signal_handlers() replaces this with the
    # graceful-drain handler once there is something to drain.
    import signal
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(0))
    p = argparse.ArgumentParser()
    p.add_argument("--redis-url", required=True)
    p.add_argument("--consumer-group", default="serving")
    p.add_argument("--consumer-name", required=True)
    p.add_argument("--batch-size", type=int, default=4)
    p.add_argument("--poison-max-attempts", type=int, default=2)
    p.add_argument("--reclaim-min-idle-ms", type=int, default=300)
    p.add_argument("--request-deadline-ms", type=int, default=0)
    p.add_argument("--healthz-max-queue", type=int, default=0)
    # breaker-failures=0 builds the DELIBERATELY BROKEN fleet the
    # loadgen teeth test runs: a raw (breaker-less) broker connection
    # never reconnects after a transport failure, so a broker outage
    # wedges the replica forever — exactly the defect the SLO verdict
    # must catch
    p.add_argument("--breaker-failures", type=int, default=None)
    p.add_argument("--breaker-cooldown-s", type=float, default=None)
    p.add_argument("--start-delay", type=float, default=0.0)
    p.add_argument("--predict-delay", type=float, default=0.0)
    args = p.parse_args(argv)

    if args.start_delay > 0:
        time.sleep(args.start_delay)

    from analytics_zoo_tpu.serving.server import (
        ClusterServing, ServingConfig)
    cfg = ServingConfig(
        redis_url=args.redis_url,
        batch_size=args.batch_size,
        consumer_group=args.consumer_group,
        consumer_name=args.consumer_name,
        poison_max_attempts=args.poison_max_attempts,
        reclaim_min_idle_ms=args.reclaim_min_idle_ms,
        request_deadline_ms=args.request_deadline_ms,
        healthz_max_queue=args.healthz_max_queue or None,
        breaker_failures=args.breaker_failures,
        breaker_cooldown_s=args.breaker_cooldown_s,
        metrics_port=0,               # /healthz on an ephemeral port,
        metrics_host="127.0.0.1")     # published via the port file
    serving = ClusterServing(
        PoisonSensitiveModel(predict_delay=args.predict_delay), cfg)
    serving.install_signal_handlers()     # SIGTERM -> graceful drain
    serving.run(poll_ms=50)
    return 0


if __name__ == "__main__":
    sys.exit(main())
