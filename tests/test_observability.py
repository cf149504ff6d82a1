"""Observability layer: registry math, exposition format, span
nesting, device telemetry, the /metrics endpoint, and end-to-end
instrumentation of training + serving."""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from analytics_zoo_tpu.observability import (
    MetricsRegistry, Tracer, get_registry, get_tracer,
    sample_device_telemetry, start_metrics_server)


# ------------------------------------------------------------- registry
class TestRegistry:
    def test_counter_math_and_labels(self):
        reg = MetricsRegistry()
        c = reg.counter("reqs_total", "requests", labels=("route",))
        c.labels("/a").inc()
        c.labels("/a").inc(2.5)
        c.labels("/b").inc()
        assert c.labels("/a").value == 3.5
        assert c.labels("/b").value == 1.0
        with pytest.raises(ValueError):
            c.labels("/a").inc(-1)

    def test_gauge_set_inc_dec(self):
        reg = MetricsRegistry()
        g = reg.gauge("depth", "queue depth")
        g.set(5)
        g.inc(2)
        g.dec(3)
        assert g.value == 4.0

    def test_histogram_buckets_cumulative(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", "latency", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.1, 0.5, 5.0, 50.0):
            h.observe(v)
        child = h.labels()
        # le is INCLUSIVE: 0.1 lands in the 0.1 bucket
        assert child.cumulative() == [2, 3, 4]
        assert child.count == 5
        assert child.sum == pytest.approx(55.65)

    def test_get_or_create_is_idempotent_but_typed(self):
        reg = MetricsRegistry()
        a = reg.counter("x_total", "x")
        b = reg.counter("x_total", "x")
        assert a is b
        with pytest.raises(ValueError):
            reg.gauge("x_total", "x")
        with pytest.raises(ValueError):
            reg.counter("x_total", "x", labels=("l",))

    def test_label_free_families_present_at_zero(self):
        reg = MetricsRegistry()
        reg.counter("errs_total", "errors")
        reg.histogram("lat_seconds", "latency", buckets=(1.0,))
        reg.counter("by_route_total", "routed", labels=("route",))
        text = reg.prometheus_text()
        # a scrape BEFORE the first sample must show label-free series
        # (rate()/absent() alerting), but no phantom labeled children
        assert "errs_total 0" in text
        assert "lat_seconds_count 0" in text
        assert "by_route_total{" not in text

    def test_histogram_bucket_mismatch_raises(self):
        reg = MetricsRegistry()
        a = reg.histogram("h_seconds", "h", buckets=(1.0, 2.0))
        assert reg.histogram("h_seconds", "h", buckets=(2.0, 1.0)) is a
        with pytest.raises(ValueError, match="buckets"):
            reg.histogram("h_seconds", "h", buckets=(1.0, 3.0))

    def test_prometheus_exposition_golden(self):
        reg = MetricsRegistry()
        reg.counter("served_total", "records served",
                    labels=("worker",)).labels("w0").inc(3)
        reg.gauge("fill_ratio", "batch fill").set(0.75)
        h = reg.histogram("lat_seconds", "latency",
                          buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(7.0)
        text = reg.prometheus_text()
        expected = "\n".join([
            "# HELP fill_ratio batch fill",
            "# TYPE fill_ratio gauge",
            "fill_ratio 0.75",
            "# HELP lat_seconds latency",
            "# TYPE lat_seconds histogram",
            'lat_seconds_bucket{le="0.1"} 1',
            'lat_seconds_bucket{le="1"} 1',
            'lat_seconds_bucket{le="+Inf"} 2',
            "lat_seconds_sum 7.05",
            "lat_seconds_count 2",
            "# HELP served_total records served",
            "# TYPE served_total counter",
            'served_total{worker="w0"} 3',
        ]) + "\n"
        assert text == expected

    def test_label_value_escaping(self):
        reg = MetricsRegistry()
        reg.counter("c_total", "c", labels=("k",)).labels(
            'a"b\\c\nd').inc()
        text = reg.prometheus_text()
        assert r'c_total{k="a\"b\\c\nd"} 1' in text

    def test_snapshot_and_jsonl(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("n_total", "n").inc(2)
        reg.histogram("h", "h").observe(0.5)
        snap = reg.snapshot()
        assert snap["counters"]["n_total"] == 2.0
        assert snap["histograms"]["h"]["count"] == 1
        p = str(tmp_path / "metrics.jsonl")
        reg.write_jsonl(p)
        reg.write_jsonl(p)
        lines = open(p).read().strip().splitlines()
        assert len(lines) == 2
        rec = json.loads(lines[0])
        assert rec["metrics"]["counters"]["n_total"] == 2.0

    def test_thread_safety_under_contention(self):
        reg = MetricsRegistry()
        c = reg.counter("hits_total", "hits")

        def work():
            for _ in range(1000):
                c.inc()

        threads = [threading.Thread(target=work) for _ in range(8)]
        [t.start() for t in threads]
        [t.join() for t in threads]
        assert c.value == 8000


# --------------------------------------------------------------- tracer
class TestTracer:
    def test_span_nesting_and_order(self):
        tr = Tracer()
        with tr.span("outer"):
            assert tr.current_span() == "outer"
            with tr.span("inner", k=1):
                assert tr.depth() == 2
        events = tr.events()
        # inner completes (and records) before outer
        assert [e["name"] for e in events] == ["inner", "outer"]
        inner, outer = events
        assert inner["ph"] == "X" and inner["args"] == {"k": 1}
        # containment: inner's window sits inside outer's
        assert outer["ts"] <= inner["ts"]
        assert (inner["ts"] + inner["dur"]
                <= outer["ts"] + outer["dur"] + 1.0)

    def test_spans_are_per_thread(self):
        tr = Tracer()
        seen = []
        # barrier keeps all four threads alive inside their spans at
        # once: nesting state must not leak across threads, and the os
        # must not recycle thread ids mid-test
        barrier = threading.Barrier(4)

        def work(name):
            with tr.span(name):
                barrier.wait(timeout=10)
                seen.append(tr.current_span())

        threads = [threading.Thread(target=work, args=(f"t{i}",))
                   for i in range(4)]
        [t.start() for t in threads]
        [t.join() for t in threads]
        assert sorted(seen) == ["t0", "t1", "t2", "t3"]
        tids = {e["tid"] for e in tr.events()}
        assert len(tids) == 4

    def test_export_chrome_trace(self, tmp_path):
        tr = Tracer()
        with tr.span("work", step=3):
            pass
        tr.complete("epoch", 0.0, 1.0, epoch=1)
        tr.instant("marker")
        path = tr.export_chrome_trace(str(tmp_path / "trace.json"))
        doc = json.load(open(path))
        names = [e["name"] for e in doc["traceEvents"]]
        assert names == ["work", "epoch", "marker"]
        assert doc["traceEvents"][1]["dur"] == pytest.approx(1e6)

    def test_ring_buffer_bounds_memory(self):
        tr = Tracer(max_events=10)
        for i in range(100):
            with tr.span(f"s{i}"):
                pass
        assert len(tr.events()) == 10

    def test_disabled_tracer_records_nothing(self):
        tr = Tracer()
        tr.enabled = False
        with tr.span("x"):
            pass
        assert tr.events() == []


# ------------------------------------------------------------ telemetry
def test_device_telemetry_sets_gauges():
    reg = MetricsRegistry()
    sampled = sample_device_telemetry(reg)
    # CPU backend has no memory_stats, but the live-array census is
    # backend-independent
    assert "jax_live_arrays" in sampled
    assert "jax_live_arrays" in reg.prometheus_text()


def test_telemetry_sampler_is_restartable():
    from analytics_zoo_tpu.observability import TelemetrySampler
    reg = MetricsRegistry()
    s = TelemetrySampler(interval_s=60.0, registry=reg)
    s.start()
    s.stop()
    reg2 = MetricsRegistry()
    s.registry = reg2
    s.start()   # must sample again, not exit immediately
    for _ in range(100):
        if "jax_live_arrays" in reg2.prometheus_text():
            break
        import time
        time.sleep(0.05)
    s.stop()
    assert "jax_live_arrays" in reg2.prometheus_text()


# ------------------------------------------------------- /metrics server
class TestMetricsServer:
    def test_endpoint_smoke(self):
        reg = MetricsRegistry()
        reg.counter("pings_total", "pings").inc(7)
        tr = Tracer()
        with tr.span("op"):
            pass
        srv = start_metrics_server(port=0, registry=reg, tracer=tr)
        try:
            base = f"http://127.0.0.1:{srv.port}"
            text = urllib.request.urlopen(base + "/metrics").read()
            assert b"pings_total 7" in text
            snap = json.load(urllib.request.urlopen(
                base + "/metrics.json"))
            assert snap["counters"]["pings_total"] == 7.0
            trace = json.load(urllib.request.urlopen(base + "/trace"))
            assert trace["traceEvents"][0]["name"] == "op"
            assert urllib.request.urlopen(
                base + "/healthz").read() == b"ok"
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(base + "/nope")
        finally:
            srv.stop()

    def test_stop_releases_port(self):
        srv = start_metrics_server(port=0, registry=MetricsRegistry())
        port = srv.port
        srv.stop()
        # rebinding the exact port must succeed after stop
        srv2 = start_metrics_server(port=port,
                                    registry=MetricsRegistry())
        assert srv2.port == port
        srv2.stop()


# --------------------------------------------- training instrumentation
def _toy_problem(n=256, d=8):
    rs = np.random.RandomState(0)
    return (rs.randn(n, d).astype(np.float32),
            rs.randn(n, 1).astype(np.float32))


def _toy_model():
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    m = Sequential()
    m.add(Dense(1, input_shape=(8,)))
    m.compile(optimizer="sgd", loss="mse")
    return m


class TestTrainingInstrumentation:
    def test_train_produces_spans_and_step_metrics(self, tmp_path):
        from analytics_zoo_tpu.common.triggers import MaxIteration
        from analytics_zoo_tpu.feature.feature_set import FeatureSet
        from analytics_zoo_tpu.pipeline.estimator import Estimator
        x, y = _toy_problem()
        reg = get_registry()
        steps_before = reg.counter(
            "train_steps_total", "train steps dispatched",
            labels=("path",)).labels("per_step").value
        get_tracer().clear()
        m = _toy_model()
        est = Estimator(m, optim_method=m.optim_method)
        # MaxIteration end-trigger forces the per-step engine
        est.train(FeatureSet.from_ndarrays(x, y), "mse",
                  end_trigger=MaxIteration(6), batch_size=64)
        steps = reg.counter(
            "train_steps_total", "train steps dispatched",
            labels=("path",)).labels("per_step").value
        assert steps - steps_before == 6
        hist = reg.histogram(
            "train_step_latency_seconds", "", labels=("path",)
        ).labels("per_step")
        assert hist.count >= 6
        # acceptance: the exported Chrome trace holds per-step
        # train_step spans
        path = get_tracer().export_chrome_trace(
            str(tmp_path / "train_trace.json"))
        doc = json.load(open(path))
        names = [e["name"] for e in doc["traceEvents"]]
        assert names.count("train_step") >= 6

    def test_retry_path_increments_restore_counter(self, tmp_path):
        from analytics_zoo_tpu.common.triggers import MaxEpoch
        from analytics_zoo_tpu.feature.feature_set import FeatureSet
        from analytics_zoo_tpu.pipeline.estimator import Estimator
        x, y = _toy_problem()
        reg = get_registry()

        def counter(name):
            return reg.counter(name, "").value

        class FailsOnEpoch1(FeatureSet):
            """Raises once at the start of epoch 1 (a subclass, so the
            estimator stays on the per-step engine — the failure-retry
            loop's domain)."""
            fails = [1]

            def epoch_batches(self, epoch, batch_size, train=True):
                if train and epoch in self.fails:
                    self.fails.remove(epoch)
                    raise RuntimeError("synthetic mid-training failure")
                return super().epoch_batches(epoch, batch_size,
                                             train=train)

        before = {k: counter(k) for k in
                  ("checkpoint_save_total", "checkpoint_restore_total",
                   "train_retry_total")}
        ds = FailsOnEpoch1.from_ndarrays(x, y)
        m = _toy_model()
        est = Estimator(m, optim_method=m.optim_method,
                        model_dir=str(tmp_path))
        est.train(ds, "mse", end_trigger=MaxEpoch(3), batch_size=64)
        assert est.train_state.epoch == 3
        assert counter("checkpoint_save_total") - \
            before["checkpoint_save_total"] >= 2
        assert counter("train_retry_total") - \
            before["train_retry_total"] == 1
        # acceptance: the failure-retry path restored from snapshot
        assert counter("checkpoint_restore_total") - \
            before["checkpoint_restore_total"] >= 1

    def test_grad_norm_gauge_optin(self):
        from analytics_zoo_tpu.common.config import get_config
        from analytics_zoo_tpu.common.triggers import MaxIteration
        from analytics_zoo_tpu.feature.feature_set import FeatureSet
        from analytics_zoo_tpu.pipeline.estimator import Estimator
        get_config().set("observability.grad_norm", True)
        x, y = _toy_problem()
        m = _toy_model()
        est = Estimator(m, optim_method=m.optim_method)
        est.train(FeatureSet.from_ndarrays(x, y), "mse",
                  end_trigger=MaxIteration(2), batch_size=64)
        g = get_registry().gauge("train_grad_norm")
        assert g.value > 0.0


# -------------------------------------------- serving /metrics endpoint
class TestServingMetrics:
    def test_metrics_endpoint_on_running_engine(self):
        from analytics_zoo_tpu.observability import reset_registry
        from analytics_zoo_tpu.pipeline.inference import InferenceModel
        from analytics_zoo_tpu.serving.client import (
            InputQueue, OutputQueue)
        from analytics_zoo_tpu.serving.redis_client import EmbeddedBroker
        from analytics_zoo_tpu.serving.server import (
            ClusterServing, ServingConfig)
        from analytics_zoo_tpu.pipeline.api.keras import Sequential
        from analytics_zoo_tpu.pipeline.api.keras.layers import (
            Dense, Flatten)
        # the registry is process-global and serving counters are
        # cumulative: any earlier in-process test that served records
        # leaves serving_records_total > 0, failing the fresh-worker
        # zero assertion below depending on file selection/order.
        # This test is about a FRESH worker's exposition, so give it a
        # fresh registry.
        reset_registry()
        m = Sequential()
        m.add(Flatten(input_shape=(8, 8, 3)))
        m.add(Dense(4))
        m.init()
        im = InferenceModel().load_zoo(m)
        broker = EmbeddedBroker()
        serving = ClusterServing(
            im, ServingConfig(batch_size=4, top_n=2, metrics_port=0),
            broker=broker)
        try:
            assert serving.metrics_server is not None
            port = serving.metrics_server.port
            # a freshly started worker (zero records served) must
            # already expose its series
            fresh = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics").read().decode()
            assert "serving_request_latency_seconds_bucket" in fresh
            assert "serving_records_total 0" in fresh
            inq = InputQueue(broker=broker)
            outq = OutputQueue(broker=broker)
            rs = np.random.RandomState(0)
            for i in range(6):   # 4 + a half-full batch of 2
                inq.enqueue(f"r-{i}",
                            rs.randn(8, 8, 3).astype(np.float32))
            served = 0
            while served < 6:
                n = serving.run_once(block_ms=10)
                if n == 0:
                    break
                served += n
            assert served == 6
            assert outq.query("r-5") is not None
            text = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics").read().decode()
            # acceptance: latency histogram buckets + fill ratio gauge
            assert "serving_request_latency_seconds_bucket" in text
            assert 'le="+Inf"' in text
            assert "serving_batch_fill_ratio 0.5" in text
            assert "serving_records_total" in text
            assert "serving_queue_depth" in text
            for line in text.splitlines():
                if line.startswith(
                        "serving_request_latency_seconds_count"):
                    assert float(line.split()[-1]) >= 6
                    break
            else:
                pytest.fail("latency histogram count line missing")
        finally:
            serving.close()

    def test_close_is_idempotent_and_engine_reusable(self):
        from analytics_zoo_tpu.pipeline.inference import InferenceModel
        from analytics_zoo_tpu.serving.client import InputQueue
        from analytics_zoo_tpu.serving.redis_client import EmbeddedBroker
        from analytics_zoo_tpu.serving.server import (
            ClusterServing, ServingConfig)
        from analytics_zoo_tpu.pipeline.api.keras import Sequential
        from analytics_zoo_tpu.pipeline.api.keras.layers import (
            Dense, Flatten)
        import tempfile
        m = Sequential()
        m.add(Flatten(input_shape=(4, 4, 1)))
        m.add(Dense(2))
        m.init()
        im = InferenceModel().load_zoo(m)
        broker = EmbeddedBroker()
        with tempfile.TemporaryDirectory() as d:
            serving = ClusterServing(
                im, ServingConfig(batch_size=2, log_dir=d),
                broker=broker)
            inq = InputQueue(broker=broker)
            inq.enqueue("a", np.zeros((4, 4, 1), np.float32))
            serving.run_once(block_ms=10)
            serving.close()
            serving.close()   # idempotent
            assert serving.summary.closed
            # summaries reopen on write: serving again still records
            inq.enqueue("b", np.zeros((4, 4, 1), np.float32))
            serving.run_once(block_ms=10)
            assert not serving.summary.closed
            serving.close()


# ----------------------------------------------------- summary lifecycle
class TestSummaryLifecycle:
    def test_context_manager_and_idempotent_close(self, tmp_path):
        from analytics_zoo_tpu.utils.summary import TrainSummary
        with TrainSummary(str(tmp_path), "app") as ts:
            ts.add_scalar("Loss", 1.0, 1)
        assert ts.closed
        ts.close()   # second close is a no-op
        # reopen-on-write: the writer keeps working after close
        ts.add_scalar("Loss", 0.5, 2)
        assert not ts.closed
        assert ts.read_scalar("Loss") == [(1, 1.0), (2, 0.5)]
        ts.close()

    def test_estimator_train_closes_summaries(self, tmp_path):
        from analytics_zoo_tpu.common.triggers import MaxIteration
        from analytics_zoo_tpu.feature.feature_set import FeatureSet
        from analytics_zoo_tpu.pipeline.estimator import Estimator
        x, y = _toy_problem()
        m = _toy_model()
        est = Estimator(m, optim_method=m.optim_method)
        est.set_tensorboard(str(tmp_path), "app")
        est.train(FeatureSet.from_ndarrays(x, y), "mse",
                  end_trigger=MaxIteration(25), batch_size=64)
        assert est._train_summary.closed
        assert est._val_summary.closed
        # loss was sampled at the iteration-20 crossing before close
        assert est._train_summary.read_scalar("Loss")

    def test_summary_mirrors_to_registry(self, tmp_path):
        from analytics_zoo_tpu.utils.summary import ValidationSummary
        vs = ValidationSummary(str(tmp_path), "app")
        vs.add_scalar("mae", 0.25, 7)
        vs.close()
        g = get_registry().gauge("summary_scalar", "",
                                 labels=("kind", "tag"))
        assert g.labels("validation", "mae").value == 0.25
