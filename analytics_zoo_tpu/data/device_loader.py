"""DeviceLoader — double-buffered host→device feeding.

Reference: the prefetch queue bolted onto ``DistributedTrainer``
(``Trainer.prefetch``, the MTSampleToMiniBatch analogue), promoted to a
first-class pipeline component: a background thread pulls host batches
from a :class:`DataPipeline` (a PURE read — no position movement),
places them on device (``put_fn`` — ``DistributedTrainer.put_batch``
when training on a mesh, sharded ``jax.device_put`` otherwise) and
keeps ``depth`` batches in flight, so H2D transfer overlaps device
compute.  The loader feeds the existing
``train_prefetch_queue_depth`` gauge (PR 1) and commits the pipeline
position ONLY as batches are handed to the caller — the property that
makes a mid-epoch checkpoint exact even with batches in flight.
"""

from __future__ import annotations

from typing import Any, Callable, Iterator, Optional

import jax

from analytics_zoo_tpu.data.pipeline import DataPipeline
from analytics_zoo_tpu.data.stages import (
    PrefetchIterator, pull_with_wait_spans)
from analytics_zoo_tpu.observability import get_registry
from analytics_zoo_tpu.observability.diagnostics import (
    step_attribution_histogram)
from analytics_zoo_tpu.resilience.chaos import (
    SITE_DATA_BATCH, active_chaos)


def _default_put(batch):
    """Sharded single-host placement: shard on the data axis of the
    current mesh when one exists, else plain device_put."""
    try:
        from analytics_zoo_tpu.common.zoo_context import get_zoo_context
        from analytics_zoo_tpu.parallel import mesh as mesh_lib
        mesh = get_zoo_context().mesh
    except Exception:
        return jax.device_put(batch)
    import numpy as np

    dp = mesh.shape[mesh_lib.DATA_AXIS] * mesh.shape[mesh_lib.FSDP_AXIS]

    def put(a):
        if a is None:
            return None
        if np.ndim(a) == 0 or np.shape(a)[0] % dp != 0:
            return jax.device_put(a, mesh_lib.replicated(mesh))
        return jax.device_put(
            a, mesh_lib.data_sharding(mesh, np.ndim(a)))

    return jax.tree_util.tree_map(put, batch,
                                  is_leaf=lambda v: v is None)


class DeviceLoader:
    """Iterate a pipeline's epochs as DEVICE-resident batches.

    ``depth=2`` is classic double buffering: batch ``k+1`` transfers
    while batch ``k`` computes.  Deeper helps only when host batch
    assembly is burstier than the step time.
    """

    def __init__(self, pipeline: DataPipeline,
                 put_fn: Optional[Callable] = None,
                 depth: Optional[int] = None):
        if depth is None:
            from analytics_zoo_tpu.common.config import get_config
            depth = int(get_config().get("data.prefetch"))
        self.pipeline = pipeline
        self.put_fn = put_fn if put_fn is not None else _default_put
        self.depth = max(int(depth), 0)
        self._m_depth = get_registry().gauge(
            "train_prefetch_queue_depth",
            "device-placed batches waiting in the prefetch queue")
        # step-time attribution: the loader is the training loop's
        # data_wait producer on the DataPipeline path
        self._m_wait = step_attribution_histogram().labels("data_wait")

    def epoch(self, iteration: Optional[int] = None) -> Iterator[Any]:
        """Yield device batches for the pipeline's current epoch from
        its current step; the pipeline position commits per yielded
        batch (exact-resume contract) and rolls to the next epoch at
        the end.  ``iteration`` is the training step the first batch
        feeds: the builders', the placer's and this consumer's spans
        for one batch then carry the same ``iteration``."""
        pipe = self.pipeline
        epoch, start = pipe.epoch, pipe.step

        def place(pair):
            step, batch = pair
            return step, self.put_fn(batch)

        host_batches = pipe.iter_epoch(epoch, start, iteration)
        if self.depth <= 0:   # synchronous fallback
            placed: Iterator = map(place, host_batches)
        else:
            placed = PrefetchIterator(
                host_batches, self.depth, fn=place,
                on_depth=self._m_depth.set, iteration=iteration)
        chaos = active_chaos()
        try:
            for (step, batch), wait in pull_with_wait_spans(
                    placed, iteration):
                if chaos is not None:
                    # fault-injection site, keyed on the pipeline's
                    # epoch step index, tripped BEFORE the position
                    # commits: an injected input-side failure never
                    # skips the batch it interrupted
                    chaos.trip(SITE_DATA_BATCH, step)
                # feed the pipeline's own batch counter / wait
                # histogram — device-fed consumption is still pipeline
                # consumption — plus the step-attribution data_wait
                # component the diagnostics report reads
                pipe._m["wait"].observe(wait)
                self._m_wait.observe(wait)
                pipe._m["batches"].inc()
                pipe.commit(epoch, step + 1)
                yield batch
        finally:
            # a consumer stopping mid-epoch (end trigger, retry
            # restore, exception) must release the prefetch thread and
            # the device batches it buffered — without this they stay
            # pinned in HBM for the life of the process.  The host read
            # is closed only once that thread has ended (a read that
            # stopped mid-epoch drops the pipeline's build stream); a
            # thread that outlives the join may still be inside it, so
            # its stream is dropped from here instead, never shared
            if isinstance(placed, PrefetchIterator) and not placed.close():
                pipe.drop_stream()
            else:
                host_batches.close()

    def __iter__(self) -> Iterator[Any]:
        return self.epoch()
