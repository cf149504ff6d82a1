"""The data/ input-pipeline engine: determinism, sharding, resume.

The contracts under test (docs/data.md):
  * same seed => bit-identical batch stream across runs AND across
    host-shard counts (shard recomposition);
  * state_dict at step k => the resumed stream is exactly batches
    k+1... — demonstrated end-to-end by an Estimator run checkpointed
    MID-epoch whose resumed final params are bit-identical to an
    uninterrupted run's;
  * corruption in a TFRecord source fails loudly with a byte offset.
"""

import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from analytics_zoo_tpu.data import (
    ArraySource, DataPipeline, DeviceLoader, IndexSampler,
    TFRecordSource, from_feature_set, pad_to_batch)
from analytics_zoo_tpu.feature.tfrecord import (
    CorruptRecordError, index_tfrecord, make_example, write_tfrecord)


def _xy(n=100, width=4, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, width).astype(np.float32)
    y = np.arange(n, dtype=np.int64).reshape(n, 1)
    return x, y


def _pipe(n=100, batch_size=10, **kw):
    x, y = _xy(n)
    kw.setdefault("seed", 5)
    kw.setdefault("name", "test")
    return DataPipeline(x, y, batch_size=batch_size, **kw)


# ---------------------------------------------------------------- sampler
class TestIndexSampler:
    def test_pure_function_of_epoch_step(self):
        s = IndexSampler(100, 10, seed=3, shard_index=0, shard_count=1)
        a, _ = s.batch_indices(2, 4)
        b, _ = s.batch_indices(2, 4)
        np.testing.assert_array_equal(a, b)

    def test_epochs_reshuffle_deterministically(self):
        s = IndexSampler(100, 10, seed=3, shard_index=0, shard_count=1)
        e1 = np.concatenate([s.batch_indices(1, k)[0] for k in range(10)])
        e2 = np.concatenate([s.batch_indices(2, k)[0] for k in range(10)])
        assert not np.array_equal(e1, e2)
        assert sorted(e1) == sorted(e2) == list(range(100))

    def test_shards_partition_each_global_batch(self):
        g = IndexSampler(96, 12, seed=9, shard_index=0, shard_count=1)
        parts = [IndexSampler(96, 4, seed=9, shard_index=i,
                              shard_count=3) for i in range(3)]
        assert g.num_batches == parts[0].num_batches == 8
        for step in range(8):
            whole, _ = g.batch_indices(0, step)
            np.testing.assert_array_equal(
                whole, np.concatenate(
                    [p.batch_indices(0, step)[0] for p in parts]))

    def test_drop_remainder(self):
        s = IndexSampler(25, 10, seed=1, shard_index=0, shard_count=1)
        assert s.num_batches == 2   # 5 trailing rows dropped

    def test_pad_remainder_masks_tail(self):
        s = IndexSampler(25, 10, seed=1, shard_index=0, shard_count=1,
                         remainder="pad")
        assert s.num_batches == 3
        sel, mask = s.batch_indices(0, 2)
        assert len(sel) == 10
        np.testing.assert_array_equal(mask, [1] * 5 + [0] * 5)

    def test_too_small_for_one_global_batch_raises(self):
        with pytest.raises(ValueError, match="cannot fill"):
            IndexSampler(7, 8, shard_index=0, shard_count=1)


# --------------------------------------------------------------- pipeline
class TestPipelineDeterminism:
    def test_same_seed_identical_stream_across_runs(self):
        for (a, ya), (b, yb) in zip(_pipe(), _pipe()):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(ya, yb)

    def test_different_seed_different_stream(self):
        a0 = next(iter(_pipe(seed=5)))[0]
        b0 = next(iter(_pipe(seed=6)))[0]
        assert not np.array_equal(a0, b0)

    def test_shard_recomposition_matches_unsharded(self):
        full = _pipe(n=96, batch_size=12)
        shards = [_pipe(n=96, batch_size=6, shard_index=i, shard_count=2)
                  for i in range(2)]
        for (gx, gy), (ax, ay), (bx, by) in zip(full, *shards):
            np.testing.assert_array_equal(gx, np.concatenate([ax, bx]))
            np.testing.assert_array_equal(gy, np.concatenate([ay, by]))

    def test_worker_pool_keeps_order(self):
        serial = _pipe().map(lambda b: (b[0] * 3, b[1]))
        pooled = _pipe(num_workers=4).map(lambda b: (b[0] * 3, b[1]))
        try:
            for (a, _), (b, _) in zip(serial, pooled):
                np.testing.assert_array_equal(a, b)
        finally:
            pooled.close()

    def test_epoch_rollover_position(self):
        p = _pipe()
        assert (p.epoch, p.step) == (0, 0)
        list(p)
        assert (p.epoch, p.step) == (1, 0)
        list(p)
        assert (p.epoch, p.step) == (2, 0)


class TestPipelineResume:
    def test_resume_yields_exact_next_batches(self):
        p = _pipe()
        it = iter(p)
        for _ in range(4):
            next(it)
        state = p.state_dict()
        assert (state["epoch"], state["step"]) == (0, 4)

        q = _pipe()
        q.load_state_dict(state)
        rest_orig = [b for b in it]
        rest_resumed = [b for b in q]
        assert len(rest_orig) == len(rest_resumed) == 6
        for (a, ya), (b, yb) in zip(rest_orig, rest_resumed):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(ya, yb)

    def test_fingerprint_mismatch_raises(self):
        state = _pipe(seed=5).state_dict()
        other = _pipe(seed=6)
        with pytest.raises(ValueError, match="does not match"):
            other.load_state_dict(state)
        other.load_state_dict(state, strict=False)   # position only

    def test_state_at_epoch_end_rolls_over(self):
        p = _pipe()
        state = p.state_dict()
        state["step"] = p.num_batches   # saved exactly at epoch end
        q = _pipe()
        q.load_state_dict(state)
        assert (q.epoch, q.step) == (1, 0)


class TestStagesAndSources:
    def test_transform_applies_to_x_only(self):
        p = _pipe().transform(lambda x: x + 100.0)
        bx, by = next(iter(p))
        assert bx.min() >= 90.0
        assert by.max() < 100   # labels untouched

    def test_pad_to_batch(self):
        out = pad_to_batch(np.ones((3, 2), np.float32), 5)
        assert out.shape == (5, 2)
        np.testing.assert_array_equal(out[3:], 0)

    def test_npy_like_array_source_single_input(self):
        src = ArraySource(np.arange(12, dtype=np.float32))
        p = DataPipeline(src, batch_size=4, shuffle=False, name="sx")
        bx, by = next(iter(p))
        np.testing.assert_array_equal(bx, [0, 1, 2, 3])
        assert by is None

    def test_pad_remainder_pipeline_appends_mask(self):
        p = DataPipeline(np.arange(10, dtype=np.float32),
                         batch_size=4, shuffle=False, remainder="pad",
                         name="padp")
        batches = list(p)
        assert len(batches) == 3
        *_, mask = batches[-1]
        np.testing.assert_array_equal(mask, [1, 1, 0, 0])


class TestTFRecordSource:
    def _write(self, tmp_path, n=12):
        path = str(tmp_path / "part-0.tfrecord")
        write_tfrecord(path, [
            make_example({"v": np.array([i], np.int64)})
            for i in range(n)])
        return path

    def test_random_access_and_pipeline(self, tmp_path):
        path = self._write(tmp_path)
        src = TFRecordSource(path)
        assert len(src) == 12
        assert src[9]["v"][0] == 9
        p = DataPipeline(src, batch_size=3, shuffle=False, name="tfr")
        first = next(iter(p))
        np.testing.assert_array_equal(first["v"].ravel(), [0, 1, 2])
        src.close()

    def test_shuffled_epochs_are_deterministic(self, tmp_path):
        path = self._write(tmp_path)
        mk = lambda: DataPipeline(TFRecordSource(path), batch_size=4,
                                  seed=2, name="tfr2")
        s1 = [b["v"].ravel().tolist() for b in mk()]
        s2 = [b["v"].ravel().tolist() for b in mk()]
        assert s1 == s2

    def test_index_offsets_match_frames(self, tmp_path):
        path = self._write(tmp_path, n=3)
        idx = list(index_tfrecord(path))
        assert len(idx) == 3
        assert idx[0][0] == 0
        # frames are contiguous: offset_{i+1} = offset_i + 12+len+4
        for (o1, l1), (o2, _l2) in zip(idx, idx[1:]):
            assert o2 == o1 + 12 + l1 + 4


class TestCorruptRecords:
    def test_truncated_payload_reports_offset(self, tmp_path):
        path = str(tmp_path / "t.tfrecord")
        write_tfrecord(path, [b"aaaa", b"bbbb"])
        good = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.truncate(good - 3)   # cut into record 2's payload crc
        with pytest.raises(CorruptRecordError) as ei:
            list(index_tfrecord(path))
        assert ei.value.offset == 12 + 4 + 4   # start of frame 2
        assert "truncated" in str(ei.value)

    def test_corrupt_length_never_trusted(self, tmp_path):
        # a corrupt length field must be caught by its crc BEFORE the
        # reader tries to consume length bytes — even with payload crc
        # checking off
        path = str(tmp_path / "t.tfrecord")
        write_tfrecord(path, [b"payload"])
        raw = bytearray(open(path, "rb").read())
        raw[0] ^= 0xFF   # corrupt the low length byte
        open(path, "wb").write(bytes(raw))
        from analytics_zoo_tpu.feature.tfrecord import read_tfrecord
        with pytest.raises(CorruptRecordError, match="length crc"):
            list(read_tfrecord(path, check_crc=False))

    def test_zero_length_records_roundtrip(self, tmp_path):
        path = str(tmp_path / "z.tfrecord")
        write_tfrecord(path, [b"", b"x", b""])
        from analytics_zoo_tpu.feature.tfrecord import read_tfrecord
        assert list(read_tfrecord(path)) == [b"", b"x", b""]
        assert [l for _o, l in index_tfrecord(path)] == [0, 1, 0]


# ---------------------------------------------------------- device loader
class TestDeviceLoader:
    def test_batches_land_on_device_and_commit(self):
        p = _pipe()
        loader = DeviceLoader(p, depth=2)
        n = 0
        for bx, by in loader:
            assert isinstance(bx, jax.Array)
            n += 1
        assert n == 10
        assert (p.epoch, p.step) == (1, 0)

    def test_matches_host_stream(self):
        host = [b[0] for b in _pipe()]
        dev = [np.asarray(b[0]) for b in DeviceLoader(_pipe(), depth=2)]
        for h, d in zip(host, dev):
            np.testing.assert_array_equal(h, d)


# ------------------------------------------- the epoch-spanning stream
def _carried(name, outcome):
    from analytics_zoo_tpu.observability import get_registry
    return get_registry().counter(
        "data_builds_carried_total", labels=("pipeline", "outcome")
    ).labels(name, outcome).value


def _rows_key(y):
    return tuple(int(v) for v in np.ravel(y))


class TestCarriedBuildStream:
    """With workers, one ordered stream of builds runs on past an
    epoch's end; a read picks it up only at exactly its next position."""

    def test_carried_epochs_match_fresh_reads_bit_for_bit(self):
        def fn(b):
            return np.sin(b[0]) * np.float32(3.0), b[1]
        carried = _pipe(n=200, num_workers=4, name="carry-bits").map(fn)
        fresh = _pipe(n=200, num_workers=4, name="carry-fresh").map(fn)
        used = _carried("carry-bits", "used")
        try:
            for epoch in range(3):
                fresh.drop_stream()   # a new stream for every epoch
                want = list(fresh.iter_epoch(epoch))
                got = list(carried)
                assert len(got) == len(want) == 20
                for (gx, gy), (_, (wx, wy)) in zip(got, want):
                    assert gx.tobytes() == wx.tobytes()
                    assert gy.tobytes() == wy.tobytes()
            # 2 x 4 builds of each next epoch were carried, twice
            assert _carried("carry-bits", "used") - used == 16
        finally:
            carried.close()
            fresh.close()

    def test_next_epoch_builds_start_before_last_batch_taken(self):
        starts = {}

        def record(b):
            starts.setdefault(_rows_key(b[1]), time.perf_counter())
            return b
        p = _pipe(n=200, num_workers=4, name="carry-early").map(record)
        used = _carried("carry-early", "used")
        try:
            for _ in p:
                time.sleep(0.02)   # a consumer slower than the builds
                t_last = time.perf_counter()
            first = _rows_key(p.sampler.batch_indices(1, 0)[0])
            assert starts[first] < t_last
            assert _carried("carry-early", "used") == used
            list(p)
            assert _carried("carry-early", "used") - used == 8
        finally:
            p.close()

    def test_load_state_dict_elsewhere_drops_carried_builds(self):
        fn = lambda b: (b[0] * 2.0, b[1])   # noqa: E731
        p = _pipe(num_workers=4, name="carry-seek").map(fn)
        serial = _pipe().map(fn)
        dropped = _carried("carry-seek", "dropped")
        used = _carried("carry-seek", "used")
        try:
            list(p)   # epoch 0; 8 builds of epoch 1 carried
            state = p.state_dict()
            state["epoch"], state["step"] = 2, 3
            p.load_state_dict(state)
            assert _carried("carry-seek", "dropped") - dropped == 8
            serial.load_state_dict(state)
            got, want = list(p), list(serial)
            assert len(got) == len(want) == 7
            for (gx, gy), (wx, wy) in zip(got, want):
                np.testing.assert_array_equal(gx, wx)
                np.testing.assert_array_equal(gy, wy)
            assert _carried("carry-seek", "used") == used
        finally:
            p.close()

    @pytest.mark.parametrize("stop", [5, 19])
    def test_mid_epoch_loader_stop_drops_and_resumes_exactly(self, stop):
        """At step 5 the stream is lent to the loader's read when the
        consumer stops; at step 19 that read has already reached the
        epoch's end and kept it, one position past the consumer's."""
        name = f"carry-stop-{stop}"
        p = _pipe(n=200, num_workers=4, name=name)
        serial = _pipe(n=200)
        loader = DeviceLoader(p, depth=2)
        dropped = _carried(name, "dropped")
        try:
            assert len(list(loader.epoch())) == 20
            batches = loader.epoch()
            for _ in range(stop):
                next(batches)
            deadline = time.monotonic() + 10
            while stop == 19 and p._stream is None \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            batches.close()   # the end trigger fired mid-epoch
            assert (p.epoch, p.step) == (1, stop)
            got = [np.asarray(b[0]) for b in loader.epoch()]
            assert _carried(name, "dropped") - dropped >= 1
            want = [bx for _, (bx, _by) in serial.iter_epoch(1, stop)]
            assert len(got) == len(want) == 20 - stop
            for g, w in zip(got, want):
                np.testing.assert_array_equal(g, w)
            assert (p.epoch, p.step) == (2, 0)
        finally:
            p.close()

    def test_close_runs_no_queued_build_of_the_dropped_stream(self):
        import threading
        gate, started = threading.Event(), []
        q = _pipe()
        epoch1 = {_rows_key(q.sampler.batch_indices(1, k)[0])
                  for k in range(q.num_batches)}

        def hold(b):
            key = _rows_key(b[1])
            if key in epoch1:
                started.append(key)
                gate.wait(10)
            return b
        p = _pipe(num_workers=2, name="carry-close").map(hold)
        dropped = _carried("carry-close", "dropped")
        try:
            list(p)   # epoch 0: two of epoch 1 run (held), two queue
            deadline = time.monotonic() + 10
            while len(started) < 2 and time.monotonic() < deadline:
                time.sleep(0.01)
            pool = p._pool
            p.close()
            assert _carried("carry-close", "dropped") - dropped == 4
        finally:
            gate.set()
        pool.shutdown(wait=True)
        assert len(started) == 2

    def test_reads_stay_exact_under_concurrent_drops(self):
        """More workers than cores, a short switch interval, and a
        second thread dropping the stream while reads take from it: a
        read either hands out the batch of its position or stops, and
        the next read resumes there."""
        import threading
        fn = lambda b: (b[0] + 1.0, b[1])   # noqa: E731
        p = _pipe(n=400, num_workers=16, name="carry-stress").map(fn)
        serial = _pipe(n=400).map(fn)
        want = {(e, k): b for e in range(4)
                for k, (_, b) in enumerate(serial.iter_epoch(e))}
        done, taken = threading.Event(), []

        def dropper():
            while not done.is_set():
                p.drop_stream()
                time.sleep(0.001)
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        thread = threading.Thread(target=dropper, daemon=True)
        thread.start()
        try:
            deadline = time.monotonic() + 60
            while p.epoch < 4 and time.monotonic() < deadline:
                try:
                    for bx, by in p:
                        at = (p.epoch, p.step - 1) if p.step \
                            else (p.epoch - 1, p.num_batches - 1)
                        np.testing.assert_array_equal(bx, want[at][0])
                        np.testing.assert_array_equal(by, want[at][1])
                        taken.append(at)
                except RuntimeError as e:   # dropped during the read
                    assert "dropped" in str(e)
        finally:
            done.set()
            thread.join(timeout=10)
            sys.setswitchinterval(interval)
            p.close()
        assert not thread.is_alive()
        assert taken == sorted(want)


# ------------------------------------------------- training integration
def _problem(n=160):
    rs = np.random.RandomState(0)
    x = rs.randn(n, 6).astype(np.float32)
    w = rs.randn(6, 1).astype(np.float32)
    return x, (x @ w).astype(np.float32)


def _model():
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.engine import Layer
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense, Dropout
    Layer.reset_name_counters()
    m = Sequential()
    m.add(Dense(8, activation="relu", input_shape=(6,)))
    m.add(Dropout(0.25))   # consumes rng every step: data/rng drift shows
    m.add(Dense(1))
    return m


class TestEstimatorIntegration:
    def test_mid_epoch_checkpoint_resumes_on_exact_next_batch(
            self, tmp_path):
        """The acceptance demo: interrupt at step 13 of 10-step epochs
        (mid-epoch 2), restore into a FRESH estimator + pipeline, and
        the final params are bit-identical to an uninterrupted run —
        only possible if the resumed run consumed exactly batches
        14..20 (a replayed or skipped batch changes the SGD trajectory
        immediately)."""
        from analytics_zoo_tpu.common.triggers import (
            MaxEpoch, MaxIteration, SeveralIteration)
        from analytics_zoo_tpu.pipeline.api.keras.optimizers import SGD
        from analytics_zoo_tpu.pipeline.estimator import Estimator

        x, y = _problem()
        mk_pipe = lambda: DataPipeline(x, y, batch_size=16, seed=11,
                                       name="resume")

        ref = Estimator(_model(), optim_method=SGD(learning_rate=0.05))
        ref.train(mk_pipe(), "mse", end_trigger=MaxEpoch(2))
        assert ref.train_state.iteration == 20

        d = str(tmp_path / "ckpt")
        half = Estimator(_model(), optim_method=SGD(learning_rate=0.05),
                         model_dir=d)
        p_half = mk_pipe()
        half.train(p_half, "mse", end_trigger=MaxIteration(13),
                   checkpoint_trigger=SeveralIteration(1))
        assert half.train_state.iteration == 13
        assert (p_half.epoch, p_half.step) == (1, 3)   # mid-epoch

        resumed = Estimator(_model(),
                            optim_method=SGD(learning_rate=0.05),
                            model_dir=d)
        p_res = mk_pipe()
        resumed.train(p_res, "mse", end_trigger=MaxEpoch(2),
                      checkpoint_trigger=SeveralIteration(1))
        assert resumed.train_state.iteration == 20
        assert (p_res.epoch, p_res.step) == (2, 0)

        for a, b in zip(
                jax.tree_util.tree_leaves(ref.variables["params"]),
                jax.tree_util.tree_leaves(resumed.variables["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_pipeline_and_feature_set_shim_both_train(self):
        from analytics_zoo_tpu.common.triggers import MaxEpoch
        from analytics_zoo_tpu.feature.feature_set import FeatureSet
        from analytics_zoo_tpu.pipeline.api.keras.optimizers import SGD
        from analytics_zoo_tpu.pipeline.estimator import Estimator

        x, y = _problem()
        fs = FeatureSet.from_ndarrays(x, y, seed=11)
        est = Estimator(_model(), optim_method=SGD(learning_rate=0.05))
        est.train(from_feature_set(fs, batch_size=16), "mse",
                  end_trigger=MaxEpoch(1))
        assert est.train_state.iteration == 10
        assert np.isfinite(est.train_state.last_loss)

    def test_validation_pipeline_needs_pad(self):
        from analytics_zoo_tpu.pipeline.estimator.estimator import (
            eval_batches)
        with pytest.raises(ValueError, match="remainder='pad'"):
            next(eval_batches(_pipe(), 10))

    def test_validation_via_pad_pipeline_matches_feature_set(self):
        from analytics_zoo_tpu.feature.feature_set import FeatureSet
        from analytics_zoo_tpu.pipeline.api.keras.metrics import MAE
        from analytics_zoo_tpu.pipeline.api.keras.optimizers import SGD
        from analytics_zoo_tpu.pipeline.estimator import Estimator

        x, y = _problem(96)
        m = _model()
        m.init()
        est = Estimator(m, optim_method=SGD(learning_rate=0.05))
        fs_scores = est.evaluate(
            FeatureSet.from_ndarrays(x, y, shuffle=False),
            validation_method=[MAE()], batch_size=20)
        pipe = DataPipeline(x, y, batch_size=20, shuffle=False,
                            remainder="pad", name="val")
        pipe_scores = est.evaluate(pipe, validation_method=[MAE()],
                                   batch_size=20)
        assert fs_scores.keys() == pipe_scores.keys()
        for k in fs_scores:
            np.testing.assert_allclose(fs_scores[k], pipe_scores[k],
                                       rtol=1e-5)

    def test_local_estimator_accepts_pipeline(self):
        from analytics_zoo_tpu.pipeline.estimator.local_estimator import (
            LocalEstimator)
        x, y = _problem()
        est = LocalEstimator(_model(), "mse", "sgd")
        est.fit(DataPipeline(x, y, batch_size=16, seed=3, name="local"),
                None, epochs=2)
        assert len(est.history) == 2
        assert np.isfinite(est.history[-1]["loss"])

    def test_keras_fit_accepts_pipeline(self):
        x, y = _problem()
        m = _model()
        m.compile(optimizer="sgd", loss="mse")
        m.fit(DataPipeline(x, y, batch_size=16, seed=3, name="keras"),
              nb_epoch=1)


# ------------------------------------------------------------- CI wrapper
def test_check_determinism_script():
    """The CI smoke script is itself tier-1: a shuffle/shard order
    regression fails this test, not just a nightly job."""
    script = os.path.join(os.path.dirname(__file__), os.pardir,
                          "scripts", "check_determinism.py")
    proc = subprocess.run(
        [sys.executable, script], capture_output=True, text=True,
        timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert '"ok": true' in proc.stdout
