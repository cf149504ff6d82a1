"""DataPipeline — deterministic, checkpointable, sharded input engine.

Reference: the FeatureSet/DataSet layer feeding the distributed
optimizer (SURVEY L1/L2), rebuilt Grain-style: a random-access
:class:`~analytics_zoo_tpu.data.source.Source`, a pure-function
:class:`~analytics_zoo_tpu.data.sampler.IndexSampler`, composable host
stages, and an explicit ``(epoch, step)`` POSITION that
``state_dict()``/``load_state_dict()`` checkpoint — so a restored run
resumes on the exact next batch instead of replaying the epoch.

Determinism contract:

* same ``(source order, seed)`` => identical batch stream, across runs
  and across processes;
* shard ``h`` of ``S`` sees rows ``[h*B:(h+1)*B]`` of every global
  batch — concatenating all shards' step-``k`` batches reproduces the
  unsharded step-``k`` batch exactly;
* the position advances ONLY when a batch is handed to the consumer
  (``__iter__`` / ``DeviceLoader``), never when a worker merely built
  it ahead — so a checkpoint taken between steps is exact even with
  prefetch in flight.

The position is intentionally NOT buried in a live iterator:
``iter_epoch`` is a pure read (resumable from any ``(epoch, step)``),
``commit`` moves the position, and the consuming loop decides when.

With workers, the pull-ahead is ONE ordered stream of builds over
``(epoch, step)`` positions that runs on past an epoch's last step, so
the workers start the next epoch while the consumer finishes this one
(:class:`_BuildStream`).  A stream is picked up again only where its
next position is exactly the one asked for; otherwise it is dropped.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Iterator, Optional, Sequence, Tuple

import numpy as np

from analytics_zoo_tpu.data.sampler import IndexSampler
from analytics_zoo_tpu.data.source import Source, as_source
from analytics_zoo_tpu.data.stages import (
    MapStage, Stage, TransformStage, WorkerPool, run_stages)
from analytics_zoo_tpu.observability import get_registry, get_tracer
from analytics_zoo_tpu.observability.tracing import iteration_args

STATE_VERSION = 1


def _pipeline_metrics(name: str):
    reg = get_registry()
    return {
        "batches": reg.counter(
            "data_batches_total",
            "host batches produced by the data pipeline",
            labels=("pipeline",)).labels(name),
        "wait": reg.histogram(
            "data_batch_wait_seconds",
            "consumer wait for the next host batch (0 ≈ the workers "
            "are keeping up)", labels=("pipeline",)).labels(name),
        "qdepth": reg.gauge(
            "data_worker_queue_depth",
            "batches built ahead by the pipeline worker pool",
            labels=("pipeline",)).labels(name),
        **{outcome: reg.counter(
            "data_builds_carried_total",
            "batch builds carried past the reader's position: "
            "outcome=used, submitted before the epoch's read began and "
            "handed out by it; outcome=dropped, cancelled or discarded "
            "with their stream", labels=("pipeline", "outcome")
        ).labels(name, outcome) for outcome in ("used", "dropped")},
    }


class _BuildStream:
    """One ordered pull-ahead of batch builds over ``(epoch, step)``
    positions, running on past each epoch's last step into the next.

    Up to ``2 x workers`` builds are in flight, as in
    ``WorkerPool.imap``.  One reader takes from it at a time (the
    pipeline lends it out); ``drop`` may come from any thread and
    cancels every build still queued.
    """

    def __init__(self, pipe: "DataPipeline", epoch: int, step: int,
                 iteration: Optional[int]):
        self.pool = pipe._pool
        self.pos = (epoch, step)   # the next position handed out
        self.dropped = False
        self._pipe = pipe
        self._depth = 2 * self.pool.workers
        self._items = self._positions(epoch, step, iteration)
        self._inflight: deque = deque()   # [epoch, step, future, carried]
        self._lock = threading.Lock()

    def _positions(self, epoch, step, iteration):
        sampler, k = self._pipe.sampler, 0
        while True:
            for s, sel, mask in sampler.iter_epoch(epoch, step):
                yield epoch, s, (sel, mask), \
                    None if iteration is None else iteration + k
                k += 1
            epoch, step = epoch + 1, 0

    def fill(self) -> None:
        with self._lock:
            while not self.dropped and len(self._inflight) < self._depth:
                epoch, step, sel_mask, it = next(self._items)
                self._inflight.append([epoch, step, self.pool.submit(
                    self._pipe._build_batch, sel_mask, it), False])

    def resume(self) -> None:
        """A new read picks the stream up: what is in flight now was
        submitted before it began, so is carried."""
        with self._lock:
            for entry in self._inflight:
                entry[3] = True

    def take(self) -> Tuple[int, Any]:
        self.fill()
        with self._lock:
            if self.dropped:
                raise RuntimeError(
                    f"data pipeline {self._pipe.name!r}: its build "
                    "stream was dropped during a read")
            self._pipe._m["qdepth"].set(len(self._inflight))
            epoch, step, future, carried = self._inflight.popleft()
            self.pos = (epoch, step + 1) \
                if step + 1 < self._pipe.num_batches else (epoch + 1, 0)
        batch = future.result()
        if carried:
            self._pipe._m["used"].inc()
        return step, batch

    def drop(self) -> None:
        with self._lock:
            if self.dropped:
                return
            self.dropped = True
            pending, self._inflight = self._inflight, deque()
        for entry in pending:
            entry[2].cancel()
        self._pipe._m["dropped"].inc(len(pending))
        self._pipe._m["qdepth"].set(0)


class DataPipeline:
    """Deterministic sharded batch pipeline over a random-access source.

    Args:
        source: a :class:`Source`, or arrays/pytrees (coerced via
            :class:`ArraySource`; pass ``y=...`` for labels).
        batch_size: rows PER SHARD per step.
        shuffle / seed: deterministic per-epoch shuffling.
        shard_index / shard_count: this host's shard — defaults to
            ``jax.process_index()/process_count()`` so the same script
            shards itself per host.
        remainder: ``"drop"`` (training) or ``"pad"`` (a masked short
            tail batch; the mask is appended to the batch tuple).
        stages: host-side :class:`Stage` chain applied to each batch.
        num_workers: >0 builds batches in a thread pool, ``num_workers``
            wide, pulling ahead of the consumer (ordered — parallelism
            never reorders the stream).
    """

    def __init__(self, source, y=None, *, batch_size: int = 32,
                 shuffle: bool = True, seed: Optional[int] = None,
                 shard_index: Optional[int] = None,
                 shard_count: Optional[int] = None,
                 remainder: str = "drop",
                 stages: Sequence[Stage] = (),
                 num_workers: int = 0,
                 name: str = "train"):
        self.source: Source = as_source(source, y)
        self.sampler = IndexSampler(
            len(self.source), batch_size, shuffle=shuffle, seed=seed,
            shard_index=shard_index, shard_count=shard_count,
            remainder=remainder)
        self.stages = list(stages)
        self.num_workers = int(num_workers)
        self.name = name
        self._epoch = 0
        self._step = 0   # next batch to hand out
        self._pool: Optional[WorkerPool] = None
        # the build stream between reads, and those lent to a read
        self._stream: Optional[_BuildStream] = None
        self._lent: set = set()
        self._stream_lock = threading.Lock()
        self._m = _pipeline_metrics(name)

    # ------------------------------------------------------------ geometry
    @property
    def batch_size(self) -> int:
        return self.sampler.batch_size

    @property
    def num_batches(self) -> int:
        """Steps per epoch (identical on every shard)."""
        return self.sampler.num_batches

    @property
    def size(self) -> int:
        """Records in the underlying source (pre-shard)."""
        return len(self.source)

    @property
    def seed(self) -> int:
        return self.sampler.seed

    @property
    def shuffle(self) -> bool:
        return self.sampler.shuffle

    # ----------------------------------------------------------- builders
    def _derive(self, extra_stage: Stage) -> "DataPipeline":
        return DataPipeline(
            self.source, batch_size=self.sampler.batch_size,
            shuffle=self.sampler.shuffle, seed=self.sampler.seed,
            shard_index=self.sampler.shard_index,
            shard_count=self.sampler.shard_count,
            remainder=self.sampler.remainder,
            stages=self.stages + [extra_stage],
            num_workers=self.num_workers, name=self.name)

    def map(self, fn: Callable, per_leaf: bool = False) -> "DataPipeline":
        """Append a batch-level map stage (``fn(batch) -> batch``)."""
        return self._derive(MapStage(fn, per_leaf=per_leaf))

    def transform(self, preprocessing) -> "DataPipeline":
        """Append a Preprocessing / callable over the X half — the
        ``FeatureSet.transform`` migration hook."""
        return self._derive(TransformStage(preprocessing))

    __rshift__ = transform

    def workers(self, num_workers: int) -> "DataPipeline":
        """Set the stage worker-pool width (chainable)."""
        self.num_workers = int(num_workers)
        return self

    # ------------------------------------------------------- batch assembly
    def _build_batch(self, sel_mask: Tuple[np.ndarray, np.ndarray],
                     iteration: Optional[int] = None):
        """Gather and run the stages: one ``data_build`` span a batch,
        on whichever thread builds it."""
        sel, mask = sel_mask
        with get_tracer().span("data_build", jax_annotation=True,
                               **iteration_args(iteration)):
            batch = run_stages(self.source.gather(sel), self.stages)
        if self.sampler.remainder == "pad":
            if isinstance(batch, tuple):
                return batch + (mask,)
            return (batch, mask)
        return batch

    def batch_at(self, epoch: int, step: int):
        """The batch at ``(epoch, step)``, built on the calling thread:
        a pure read that leaves the workers and their stream alone."""
        return self._build_batch(self.sampler.batch_indices(epoch, step))

    def iter_epoch(self, epoch: int, start_step: int = 0,
                   iteration: Optional[int] = None
                   ) -> Iterator[Tuple[int, Any]]:
        """Pure read of ``(step, batch)`` pairs for one epoch — does
        NOT move the pipeline position (``commit`` does).  Resumable
        from any step; with ``num_workers`` the batches are assembled
        in the pool, ordered, by the pipeline's build stream: a read
        that reaches the epoch's end leaves the stream building the
        next epoch, for a read that starts exactly there.
        ``iteration`` is the training step that ``start_step``'s batch
        feeds, for the builders' spans."""
        if self.num_workers <= 0:
            for step, sel, mask in self.sampler.iter_epoch(
                    epoch, start_step):
                yield step, self._build_batch(
                    (sel, mask),
                    None if iteration is None
                    else iteration + step - start_step)
            return
        if start_step >= self.num_batches:
            return
        stream = self._lend_stream(epoch, start_step, iteration)
        completed = False
        try:
            while stream.pos[0] == epoch:
                yield stream.take()
            completed = True
        finally:
            self._release_stream(stream, completed)

    def _lend_stream(self, epoch: int, step: int,
                     iteration: Optional[int]) -> _BuildStream:
        """The stream between reads if its next position is
        ``(epoch, step)``; else a new one from there."""
        with self._stream_lock:
            if self._pool is None:
                self._pool = WorkerPool(self.num_workers,
                                        name=f"data-{self.name}")
            stream, self._stream = self._stream, None
            if stream is not None and stream.pos == (epoch, step):
                stream.resume()
            else:
                if stream is not None:
                    stream.drop()
                stream = _BuildStream(self, epoch, step, iteration)
            self._lent.add(stream)
        return stream

    def _release_stream(self, stream: _BuildStream,
                        completed: bool) -> None:
        """Back from a read.  A read that reached its epoch's end keeps
        the stream, topped up with the next epoch's builds; a read that
        stopped before it (its reader took builds it may never commit)
        drops it."""
        if completed:
            stream.fill()   # still lent: a drop_stream() meanwhile reaches it
        with self._stream_lock:
            self._lent.discard(stream)
            if completed and not stream.dropped \
                    and stream.pool is self._pool:
                stream, self._stream = self._stream, stream
        if stream is not None:   # the one released, or the one it replaced
            stream.drop()

    def drop_stream(self) -> None:
        """Cancel the builds carried past the position: the stream kept
        between reads and any lent to a read.  ``Estimator.train`` calls
        it on its way out, so builds of an epoch that will never run do
        not hold the workers."""
        with self._stream_lock:
            streams = [*self._lent, self._stream]
            self._stream = None
        for stream in streams:
            if stream is not None:
                stream.drop()

    # ------------------------------------------------------------ position
    @property
    def epoch(self) -> int:
        return self._epoch

    @property
    def step(self) -> int:
        return self._step

    def commit(self, epoch: int, step: int) -> None:
        """Move the position to ``(epoch, step)`` = the next batch to
        deliver; rolls into the next epoch at epoch end."""
        if step >= self.num_batches:
            epoch, step = epoch + 1, 0
        self._epoch, self._step = int(epoch), int(step)

    # ----------------------------------------------------------- iteration
    def __iter__(self) -> Iterator[Any]:
        """Yield the REMAINING batches of the current epoch, committing
        the position as each batch is handed out; at epoch end the
        position rolls to ``(epoch+1, 0)``.  ``for batch in pipeline:``
        therefore consumes exactly one (rest-of-)epoch per loop."""
        epoch = self._epoch
        batches = self.iter_epoch(epoch, self._step)
        try:
            t0 = time.perf_counter()
            for step, batch in batches:
                self._m["wait"].observe(time.perf_counter() - t0)
                self._m["batches"].inc()
                self.commit(epoch, step + 1)
                yield batch
                t0 = time.perf_counter()
        finally:
            batches.close()

    def __len__(self) -> int:
        return self.num_batches

    # -------------------------------------------------------- checkpointing
    def state_dict(self) -> Dict[str, Any]:
        """Position + the stream-identity fingerprint.  Restoring this
        into a pipeline with the same fingerprint resumes the exact
        batch stream at the exact next batch."""
        s = self.sampler
        return {
            "version": STATE_VERSION,
            "epoch": self._epoch,
            "step": self._step,
            "seed": s.seed,
            "shuffle": s.shuffle,
            "batch_size": s.batch_size,
            "shard_index": s.shard_index,
            "shard_count": s.shard_count,
            "num_records": s.num_records,
        }

    def load_state_dict(self, state: Dict[str, Any],
                        strict: bool = True) -> None:
        """Restore the position.  ``strict`` verifies the fingerprint —
        a checkpoint taken with a different seed/batch/shard geometry
        describes a DIFFERENT batch stream, and resuming it silently
        would skip and replay samples."""
        if int(state.get("version", 0)) != STATE_VERSION:
            raise ValueError(
                f"data pipeline state version "
                f"{state.get('version')!r} != {STATE_VERSION}")
        if strict:
            s = self.sampler
            mine = {"seed": s.seed, "shuffle": s.shuffle,
                    "batch_size": s.batch_size,
                    "shard_count": s.shard_count,
                    "num_records": s.num_records}
            diffs = {k: (state.get(k), v) for k, v in mine.items()
                     if int(state.get(k, v)) != int(v)}
            if diffs:
                raise ValueError(
                    "data pipeline state does not match this pipeline "
                    f"(checkpointed vs current): {diffs}; pass "
                    "strict=False to restore the position anyway")
        self._epoch = int(state["epoch"])
        self._step = int(state["step"])
        if self._step >= self.num_batches:
            self._epoch, self._step = self._epoch + 1, 0
        # the kept stream builds for where the position was
        with self._stream_lock:
            stream, self._stream = self._stream, None
        if stream is not None:
            stream.drop()

    # ------------------------------------------------------------- cleanup
    def close(self) -> None:
        self.drop_stream()
        with self._stream_lock:
            pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown()

    def __enter__(self) -> "DataPipeline":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()
