"""Serving engine v2 tests — the transport/batcher/executor split.

Acceptance (ISSUE 10):

* continuous batcher: with a scripted arrival queue the engine
  dispatches PARTIAL bucket batches the moment the executor frees
  (deterministic event-order assertions, no wall-clock ratios), and a
  lone request is served within ``batch_max_wait_ms``;
* every bucket size is AOT-warmed, so a post-warm-up run records zero
  recompiles (CompileMonitor's backend-compile listener + the
  engine's AOT signature census);
* multi-model: one worker serves two registered endpoints (distinct
  models) over BOTH transports with per-endpoint metrics, correct
  routing, and exactly-once Redis semantics preserved under a
  mid-batch kill;
* the deduplicated ``dead_letter`` helper.
"""

import threading
import time

import numpy as np
import pytest

from analytics_zoo_tpu.serving.client import (
    InputQueue, OutputQueue, ServingHttpClient, ServingHttpError)
from analytics_zoo_tpu.serving.engine import (
    Request, ServingEngine, default_buckets)
from analytics_zoo_tpu.serving.engine.executor import parse_buckets
from analytics_zoo_tpu.serving.redis_client import EmbeddedBroker
from analytics_zoo_tpu.serving.server import (
    DEAD_LETTER_STREAM, ClusterServing, ServingConfig)


def _req(uri="u", endpoint="default", shape=(3,)):
    return Request(endpoint=endpoint, uri=uri,
                   data=np.zeros(shape, np.float32))


class GateModel:
    """Duck-typed model whose predict can be held closed — the
    executor-busy window every batcher test scripts against."""

    def __init__(self, classes=4):
        self.classes = classes
        self.gate = threading.Event()
        self.gate.set()
        self.entered = threading.Event()
        self.calls = []          # padded batch length per call

    def predict(self, x, batch_size=None):
        self.entered.set()
        assert self.gate.wait(20), "gate never opened"
        self.calls.append(len(x))
        return np.tile(np.arange(self.classes, dtype=np.float32),
                       (len(x), 1))


class TestBuckets:
    def test_default_ladder(self):
        assert default_buckets(32) == (1, 2, 4, 8, 16, 32)
        assert default_buckets(4) == (1, 2, 4)
        assert default_buckets(1) == (1,)
        assert default_buckets(6) == (1, 2, 4, 6)

    def test_parse_spec(self):
        assert parse_buckets("1,4,16", 16) == (1, 4, 16)
        # capped at batch_size, which is always present
        assert parse_buckets("1,4,64", 16) == (1, 4, 16)
        assert parse_buckets(None, 8) == (1, 2, 4, 8)
        assert parse_buckets([2, 2, 8], 8) == (2, 8)


class TestContinuousBatcher:
    def _engine(self, model, max_wait_ms, batch_size=4, **kw):
        eng = ServingEngine(max_wait_ms=max_wait_ms)
        eng.register("default", model, top_n=1,
                     batch_size=batch_size, **kw)
        eng.start()
        return eng

    def test_partial_bucket_dispatched_the_moment_executor_frees(self):
        """The continuous-batching property, by event order: requests
        that arrive WHILE the executor is busy are dispatched as a
        partial bucket immediately on free — even though
        batch_max_wait_ms is 10s, which a fill-waiting batcher would
        burn waiting for two more co-riders."""
        model = GateModel()
        eng = self._engine(model, max_wait_ms=10_000)
        try:
            model.gate.clear()
            # a full bucket dispatches immediately (no fill wait)
            first = [_req(f"a{i}") for i in range(4)]
            eng.submit(first)
            assert model.entered.wait(10)     # executor busy on it
            # two singles arrive mid-predict: they queue
            r1, r2 = _req("b0"), _req("b1")
            eng.submit([r1])
            eng.submit([r2])
            assert not r1.done and not r2.done
            model.gate.set()                  # executor frees NOW
            # bounded completion wait ≪ max_wait_ms proves the
            # dispatch happened on the free edge, not on the timer
            assert r1.wait(5) and r2.wait(5)
            assert r1.error is None and r2.error is None
            for r in first:
                assert r.wait(5) and r.error is None
            # call 1: the full bucket of 4; call 2: the two mid-
            # predict arrivals co-batched and padded to bucket 2
            assert model.calls == [4, 2]
        finally:
            eng.stop()

    def test_lone_request_served_within_max_wait(self):
        model = GateModel()
        eng = self._engine(model, max_wait_ms=100)
        try:
            result = eng.predict("default",
                                 np.zeros(3, np.float32),
                                 timeout_s=20)
            assert result and result[0][0] in range(4)
            # a lone request rides the SMALLEST bucket, not batch_size
            assert model.calls == [1]
        finally:
            eng.stop()

    def test_max_wait_zero_dispatches_immediately(self):
        model = GateModel()
        eng = self._engine(model, max_wait_ms=0)
        try:
            r = _req()
            eng.submit([r])
            assert r.wait(5) and r.error is None
            assert model.calls == [1]
        finally:
            eng.stop()

    def test_fill_wait_ends_on_bucket_full_not_on_timer(self):
        """On the empty-queue edge the batcher MAY wait for co-riders
        — but a filled largest bucket ends the wait instantly: four
        quick singles complete in a bounded few seconds against a 10s
        max_wait, composed into ONE full batch."""
        model = GateModel()
        eng = self._engine(model, max_wait_ms=10_000)
        try:
            reqs = [_req(f"c{i}") for i in range(4)]
            for r in reqs:
                eng.submit([r])
            for r in reqs:
                assert r.wait(5), "fill-wait did not end on full"
                assert r.error is None
            assert model.calls == [4]
        finally:
            eng.stop()

    def test_weighted_round_robin_across_endpoints(self):
        order = []

        class NamedModel:
            def __init__(self, name, gate):
                self.name, self.gate = name, gate

            def predict(self, x, batch_size=None):
                assert self.gate.wait(20)
                order.append(self.name)
                return np.zeros((len(x), 4), np.float32)

        gate = threading.Event()
        eng = ServingEngine(max_wait_ms=0)
        eng.register("a", NamedModel("a", gate), weight=2,
                     batch_size=4)
        eng.register("b", NamedModel("b", gate), weight=1,
                     batch_size=4)
        eng.start()
        try:
            # first group starts executing (blocked on the gate)...
            groups = [[_req(f"a0-{i}", endpoint="a")
                       for i in range(4)]]
            eng.submit(groups[0])
            # ...while full-bucket groups pile up on both endpoints
            # (full buckets so no two groups merge into one batch)
            for g in range(1, 5):
                groups.append([_req(f"a{g}-{i}", endpoint="a")
                               for i in range(4)])
                eng.submit(groups[-1])
            bgroups = [[_req(f"b{g}-{i}", endpoint="b")
                        for i in range(4)] for g in range(2)]
            for g in bgroups:
                eng.submit(g)
            gate.set()
            for g in groups + bgroups:
                for r in g:
                    assert r.wait(10) and r.error is None
            # weight-2 'a' gets two batches per 'b' batch; nobody
            # starves (deterministic credit scheduler)
            assert order == ["a", "a", "b", "a", "a", "b", "a"]
        finally:
            eng.stop()

    def test_unknown_endpoint_fails_fast(self):
        eng = ServingEngine()
        eng.register("default", GateModel())
        eng.start()
        try:
            with pytest.raises(KeyError, match="unknown serving"):
                eng.predict("nope", np.zeros(3, np.float32),
                            timeout_s=5)
        finally:
            eng.stop()

    def test_mismatched_shape_groups_never_share_a_batch(self):
        """Two groups with different record shapes cannot np.stack
        together: each rides its own batch and BOTH succeed."""
        model = GateModel()
        eng = self._engine(model, max_wait_ms=0)
        try:
            model.gate.clear()
            blocker = [_req("x0")]
            eng.submit(blocker)          # occupy the executor
            assert model.entered.wait(10)
            g1 = [_req(f"s3-{i}", shape=(3,)) for i in range(2)]
            g2 = [_req(f"s5-{i}", shape=(5,)) for i in range(2)]
            eng.submit(g1)
            eng.submit(g2)
            model.gate.set()
            for r in blocker + g1 + g2:
                assert r.wait(10) and r.error is None, r.uri
            # blocker alone, then the two same-shape groups each in
            # their own batch
            assert model.calls == [1, 2, 2]
        finally:
            eng.stop()

    def test_model_error_fails_exactly_its_own_batch(self):
        class FlakyModel(GateModel):
            def predict(self, x, batch_size=None):
                if len(x) == 2:          # the poisoned group's bucket
                    raise ValueError("boom")
                return super().predict(x, batch_size)

        model = FlakyModel()
        eng = self._engine(model, max_wait_ms=0)
        try:
            model.gate.clear()
            blocker = [_req("x0")]
            eng.submit(blocker)
            assert model.entered.wait(10)
            bad = [_req(f"bad-{i}", shape=(3,)) for i in range(2)]
            good = [_req(f"good-{i}", shape=(5,)) for i in range(4)]
            eng.submit(bad)
            eng.submit(good)
            model.gate.set()
            for r in bad:
                assert r.wait(10)
                assert isinstance(r.error, ValueError)
            for r in blocker + good:
                assert r.wait(10) and r.error is None
        finally:
            eng.stop()


class TestBucketWarmZeroRecompiles:
    """ISSUE 10 acceptance: after warm_start() every bucket is AOT-
    ready, so serving across ALL fill levels records zero backend
    compiles (the CompileMonitor-installed jax.monitoring listener)
    and mints zero new AOT signatures."""

    def _classifier(self):
        from analytics_zoo_tpu.pipeline.api.keras import Sequential
        from analytics_zoo_tpu.pipeline.api.keras.layers import (
            Dense, GlobalAveragePooling2D)
        m = Sequential()
        m.add(GlobalAveragePooling2D(input_shape=(8, 8, 3)))
        m.add(Dense(4))
        m.init()
        return m

    def test_post_warm_traffic_never_compiles(self):
        from analytics_zoo_tpu.observability import get_registry
        from analytics_zoo_tpu.observability.diagnostics import (
            get_compile_monitor)
        from analytics_zoo_tpu.pipeline.inference import InferenceModel
        get_compile_monitor()       # backend-compile listener active
        im = InferenceModel().load_zoo(self._classifier())
        broker = EmbeddedBroker()
        serving = ClusterServing(
            im, ServingConfig(batch_size=4, top_n=2,
                              input_shape=(8, 8, 3)),
            broker=broker)
        try:
            # the full ladder (1, 2, 4) is compiled here
            assert serving.warm_start() is True
            compiles = get_registry().counter(
                "jax_backend_compiles_total",
                "XLA backend compilations (jax.monitoring)")
            before = compiles.value
            inq = InputQueue(broker=broker)
            outq = OutputQueue(broker=broker)
            rs = np.random.RandomState(0)
            n = 0
            # every fill level: 1 (bucket 1), 2 (2), 3 (padded to 4),
            # 4 (4) — the scripted arrival queue
            for fill in (1, 2, 3, 4):
                for i in range(fill):
                    inq.enqueue(f"f{fill}-{i}",
                                rs.randn(8, 8, 3).astype(np.float32))
                    n += 1
                while serving.run_once(block_ms=10):
                    pass
            assert serving.total_records == n
            for fill in (1, 2, 3, 4):
                for i in range(fill):
                    assert outq.query(f"f{fill}-{i}") is not None
            # zero recompiles after warm-up: a request in every bucket
            # and no new backend compile event
            assert compiles.value == before
        finally:
            serving.close()


class ArgmaxLastModel:
    """Deterministic routing witness: top-1 class is always 3."""

    def predict(self, x, batch_size=None):
        return np.tile(np.arange(4, dtype=np.float32), (len(x), 1))


class ArgmaxFirstModel:
    """Deterministic routing witness: top-1 class is always 0."""

    def predict(self, x, batch_size=None):
        return np.tile(np.arange(4, 0, -1, dtype=np.float32),
                       (len(x), 1))


class _SimulatedReplicaDeath(BaseException):
    """Escapes ``except Exception`` the way a process kill escapes the
    worker: the batch stays un-acked in the PEL."""


class TestMultiModelAcceptance:
    def test_two_endpoints_both_transports_exactly_once_under_kill(
            self):
        """One worker, two registered endpoints (distinct models),
        Redis + HTTP transports, per-endpoint metrics — and the Redis
        exactly-once contract survives a mid-batch kill: the dying
        worker's un-acked batch is PEL-reclaimed by a peer and every
        record gets exactly one visible, correctly-routed result."""
        from analytics_zoo_tpu.observability import get_registry
        broker = EmbeddedBroker()

        class DiesOnFirstBatch(ArgmaxLastModel):
            def __init__(self):
                self.calls = 0

            def predict(self, x, batch_size=None):
                self.calls += 1
                if self.calls == 1:
                    raise _SimulatedReplicaDeath("killed mid-batch")
                return super().predict(x, batch_size)

        w1 = ClusterServing(
            DiesOnFirstBatch(),
            ServingConfig(batch_size=4, top_n=1,
                          consumer_group="serve",
                          consumer_name="w1"),
            broker=broker)
        w1.register_endpoint("beta", ArgmaxFirstModel())
        inq = InputQueue(broker=broker)
        n_alpha = n_beta = 4
        for i in range(n_alpha):
            inq.enqueue(f"alpha-{i}", np.zeros(3, np.float32))
        for i in range(n_beta):
            inq.enqueue(f"beta-{i}", np.zeros(3, np.float32),
                        endpoint="beta")

        def _run_until_death():
            try:
                w1.run(poll_ms=5)
            except _SimulatedReplicaDeath:
                pass
        t = threading.Thread(target=_run_until_death)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        # the kill left un-acked records in the PEL, not lost
        pend = broker._groups[("serving_stream", "serve")]["pending"]
        assert len(pend) >= 4

        # the surviving replica: same two endpoints, healthy models,
        # plus the HTTP fast path
        w2 = ClusterServing(
            ArgmaxLastModel(),
            ServingConfig(batch_size=4, top_n=1,
                          consumer_group="serve",
                          consumer_name="w2",
                          reclaim_min_idle_ms=0,
                          http_port=0, metrics_host="127.0.0.1"),
            broker=broker)
        w2.register_endpoint("beta", ArgmaxFirstModel())
        try:
            deadline = time.time() + 30
            total = n_alpha + n_beta
            while (w1.total_records + w2.total_records) < total \
                    and time.time() < deadline:
                if w2.run_once(block_ms=10) == 0:
                    w2._reclaim_stale(min_idle_ms=0)
            outq = OutputQueue(broker=broker)
            # correct routing: alpha → class 3, beta → class 0
            for i in range(n_alpha):
                res = outq.query(f"alpha-{i}")
                assert res is not None, f"alpha-{i} lost"
                assert res[0][0] == 3, res
            for i in range(n_beta):
                res = outq.query(f"beta-{i}")
                assert res is not None, f"beta-{i} lost"
                assert res[0][0] == 0, res
            # exactly-once-visible: every record served once, PEL empty
            assert w1.total_records + w2.total_records == total
            assert not broker._groups[("serving_stream",
                                       "serve")]["pending"]

            # ---- HTTP fast path against the same engine ------------
            http = ServingHttpClient(
                f"http://127.0.0.1:{w2.http_transport.port}")
            alpha = http.predict_http("default",
                                      np.zeros(3, np.float32))
            assert alpha["value"][0][0] == 3
            beta = http.predict_http("beta", np.zeros(3, np.float32))
            assert beta["value"][0][0] == 0
            eps = http.endpoints()
            assert set(eps) == {"default", "beta"}
            with pytest.raises(ServingHttpError) as ei:
                http.predict_http("gamma", np.zeros(3, np.float32))
            assert ei.value.status == 404

            # ---- per-endpoint metrics ------------------------------
            fam = get_registry().counter(
                "serving_endpoint_requests_total",
                "requests submitted per serving endpoint",
                labels=("endpoint",))
            assert fam.labels("default").value >= n_alpha + 1
            assert fam.labels("beta").value >= n_beta + 1
        finally:
            w2.close()
            w1.close()


class TestHttpTransport:
    def test_bad_payload_and_timeout_statuses(self):
        eng = ServingEngine()
        model = GateModel()
        eng.register("default", model)
        eng.start()
        from analytics_zoo_tpu.serving.engine.transport import (
            HttpTransport)
        tr = HttpTransport(eng, port=0, timeout_s=0.3)
        try:
            code, doc = tr.handle_predict("default", b"not json")
            assert code == 400 and "error" in doc
            code, doc = tr.handle_predict("default", b'{"x": 1}')
            assert code == 400
            code, doc = tr.handle_predict("nope", b'{"data": [1.0]}')
            assert code == 404 and doc["endpoints"] == ["default"]
            model.gate.clear()            # wedge the executor
            code, doc = tr.handle_predict(
                "default", b'{"data": [1.0, 2.0, 3.0]}')
            assert code == 504
            model.gate.set()
        finally:
            tr.stop()
            eng.stop()

    def test_http_client_connection_retries_are_bounded(self):
        # nothing listens on this port: connection-class errors retry
        # with bounded backoff then re-raise (the query_meta contract)
        from urllib.error import URLError
        client = ServingHttpClient("http://127.0.0.1:9", retries=2)
        t0 = time.monotonic()
        with pytest.raises((URLError, OSError)):
            client.predict_http("default", [1.0, 2.0],
                                timeout_s=0.5)
        assert time.monotonic() - t0 < 30.0


class TestDeadLetterHelper:
    def _serving(self, broker=None):
        return ClusterServing(
            ArgmaxLastModel(), ServingConfig(batch_size=2),
            broker=broker or EmbeddedBroker())

    def test_entry_fields_and_reason_counter(self):
        from analytics_zoo_tpu.observability import get_registry
        broker = EmbeddedBroker()
        s = self._serving(broker)
        try:
            fam = get_registry().counter(
                "serving_dead_letter_total",
                "records written to the serving_dead_letter stream, "
                "by reason", labels=("reason",))
            before = fam.labels("shed").value
            assert s.dead_letter(
                "shed", uri="u1", request_id="r1", cause="deadline",
                error=TimeoutError("too old"),
                extra={"age_ms": "512"}) is True
            entries = broker.xread(DEAD_LETTER_STREAM, "0-0")
            assert len(entries) == 1
            fields = {k: v.decode() if isinstance(v, bytes) else v
                      for k, v in entries[0][1].items()}
            assert fields["reason"] == "shed"
            assert fields["uri"] == "u1"
            assert fields["request_id"] == "r1"
            assert fields["cause"] == "deadline"
            assert fields["age_ms"] == "512"
            assert "TimeoutError" in fields["error"]
            assert fam.labels("shed").value == before + 1
        finally:
            s.close()

    def test_broker_failure_is_absorbed(self):
        class DeadBroker(EmbeddedBroker):
            def xadd(self, stream, fields):
                raise ConnectionError("broker down")

        # constructing against a dead broker: breaker-wrapped ops
        # absorb bring-up trouble; dead_letter must return False, not
        # raise
        s = ClusterServing(ArgmaxLastModel(),
                           ServingConfig(batch_size=2,
                                         breaker_failures=0),
                           broker=DeadBroker())
        try:
            assert s.dead_letter("poison", uri="u",
                                 extra={"deliveries": "3"}) is False
        finally:
            s.close()

    def test_all_three_reasons_flow_through_the_helper(self):
        """The three historical inline writers (write_abandoned /
        shed / poison) now share dead_letter(): drive each path and
        check its labeled count moved."""
        from analytics_zoo_tpu.observability import get_registry
        fam = get_registry().counter(
            "serving_dead_letter_total",
            "records written to the serving_dead_letter stream, by "
            "reason", labels=("reason",))
        broker = EmbeddedBroker()
        s = ClusterServing(
            ArgmaxLastModel(),
            ServingConfig(batch_size=2, consumer_group="serve",
                          request_deadline_ms=50,
                          result_write_retries=1),
            broker=broker)
        try:
            before = {r: fam.labels(r).value
                      for r in ("shed", "poison", "write_abandoned")}
            # shed: an entry whose stream-id ms half is ancient
            old_id = f"{int(time.time() * 1000) - 60_000}-1"
            kept = s._shed_expired([(old_id, {"uri": b"old-1"})])
            assert kept == []
            # poison: quarantine directly
            s._quarantine("1-1", {"uri": b"p-1"}, deliveries=2)
            # write_abandoned: result write against a broken hset
            orig = broker.hset
            broker.hset = lambda *a, **k: (_ for _ in ()).throw(
                ConnectionError("down"))
            assert s._write_result("w-1", "[]", retries=1) is False
            broker.hset = orig
            for reason in ("shed", "poison", "write_abandoned"):
                assert fam.labels(reason).value == before[reason] + 1, \
                    reason
            reasons = set()
            for _eid, fields in broker.xread(DEAD_LETTER_STREAM,
                                             "0-0"):
                r = fields["reason"]
                reasons.add(r.decode() if isinstance(r, bytes) else r)
            assert reasons == {"shed", "poison", "write_abandoned"}
        finally:
            s.close()
