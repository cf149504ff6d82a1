"""Training-health diagnostics: CompileMonitor recompile detection,
watchdog NaN/plateau/stall/divergence handling (including the
checkpoint_and_halt policy end-to-end with a restorable checkpoint),
step-time attribution + MFU gauges, the serving readiness probe, the
stale-telemetry marker, and the obs_report CLI."""

import json
import sys
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])

from analytics_zoo_tpu.observability import get_registry
from analytics_zoo_tpu.observability.diagnostics import CompileMonitor
from analytics_zoo_tpu.observability.metrics import MetricsRegistry
from analytics_zoo_tpu.observability.watchdog import (
    PendingFiniteFlags, TrainingHalted, TrainingWatchdog,
    record_finite_checks, set_active_watchdog)


# -------------------------------------------------------- CompileMonitor
class TestCompileMonitor:
    def test_counts_compiles_and_detects_churn(self):
        reg = MetricsRegistry()
        mon = CompileMonitor(warmup_calls=2, registry=reg)
        fn = mon.wrap("tstep", jax.jit(lambda a: (a * 3.0).sum()))
        for _ in range(5):
            float(fn(jnp.ones((16, 4))))
        st = mon.stats("tstep")
        assert st["compiles"] == 1
        assert st["recompiles_after_warmup"] == 0
        assert st["compile_seconds"] > 0
        # cost analysis populated the FLOPs gauge
        assert st["flops"] and st["flops"] > 0
        text = reg.prometheus_text()
        assert 'jax_compiles_total{fn="tstep"} 1' in text
        assert 'train_step_flops{fn="tstep"}' in text

        # a NEW abstract signature after the warmup is churn
        float(fn(jnp.ones((32, 4))))
        st = mon.stats("tstep")
        assert st["compiles"] == 2
        assert st["recompiles_after_warmup"] == 1
        assert 'jax_recompiles_total{fn="tstep"} 1' \
            in reg.prometheus_text()

    def test_dtype_change_is_a_new_signature(self):
        mon = CompileMonitor(warmup_calls=10, registry=MetricsRegistry())
        fn = mon.wrap("dt", jax.jit(lambda a: a.sum()))
        fn(jnp.ones((4,), jnp.float32))
        fn(jnp.ones((4,), jnp.int32))
        assert mon.stats("dt")["compiles"] == 2

    def test_wrapper_forwards_aot_attributes(self):
        # benchmarks.compiled_flops calls .lower() on the wrapped fn
        mon = CompileMonitor(warmup_calls=2, registry=MetricsRegistry())
        fn = mon.wrap("aot", jax.jit(lambda a: a * 2))
        lowered = fn.lower(jnp.ones((4, 4)))
        assert lowered.compile() is not None

    def test_churn_detected_at_the_first_drifting_call(self):
        # however long the steady run before it: nothing is sampled
        mon = CompileMonitor(warmup_calls=2, registry=MetricsRegistry())
        fn = mon.wrap("stable", jax.jit(lambda a: a.sum()))
        for _ in range(50):
            fn(jnp.ones((4,)))
        fn(jnp.ones((8,)))
        assert mon.stats("stable")["recompiles_after_warmup"] == 1

    def test_steady_calls_compute_no_signature(self, monkeypatch):
        """N same-signature calls: ONE compile, and no dispatch after
        it walks the arguments (the signature is the warning's text,
        built only when jit's cache has grown)."""
        from analytics_zoo_tpu.observability import diagnostics
        mon = CompileMonitor(warmup_calls=2, registry=MetricsRegistry())
        fn = mon.wrap("steady", jax.jit(
            lambda p, x: jax.tree_util.tree_map(
                lambda a: a + x.sum(), p)))
        p = {"w": jnp.ones((4,)), "b": jnp.ones((2,))}
        p = fn(p, jnp.ones((8,)))

        walks = []

        def walked(args):
            walks.append(args)
            raise AssertionError("a steady dispatch walked its "
                                 "arguments")
        monkeypatch.setattr(diagnostics, "abstract_signature", walked)
        for _ in range(40):
            p = fn(p, jnp.ones((8,)))
        assert walks == []
        assert mon.stats("steady")["compiles"] == 1
        assert mon.stats("steady")["recompiles_after_warmup"] == 0

    def test_sharding_only_recompile_is_counted(self):
        """Unchanged shapes and dtypes under a changed input sharding
        compile a new program (ROADMAP D12: a 45 s ResNet-50 recompile
        went uncounted): it counts, and after the warm-up it is
        churn."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        reg = MetricsRegistry()
        mon = CompileMonitor(warmup_calls=2, registry=reg)
        fn = mon.wrap("resharded", jax.jit(lambda a: a * 2))
        mesh = Mesh(np.array(jax.devices()).reshape(8,), ("data",))
        x = np.arange(16, dtype=np.float32)
        split = jax.device_put(x, NamedSharding(mesh, P("data")))
        whole = jax.device_put(x, NamedSharding(mesh, P()))
        for _ in range(3):
            fn(split)
        assert mon.stats("resharded")["compiles"] == 1
        out = fn(whole)
        assert np.asarray(out).tolist() == (x * 2).tolist()
        st = mon.stats("resharded")
        assert st["compiles"] == 2
        assert st["recompiles_after_warmup"] == 1
        text = reg.prometheus_text()
        assert 'jax_compiles_total{fn="resharded"} 2' in text
        assert 'jax_recompiles_total{fn="resharded"} 1' in text

    def test_churn_warning_reads_donated_buffers(self, caplog):
        """The warning's text is built AFTER the call, from arguments
        the call has donated: a deleted array still has its shape and
        dtype."""
        import logging
        mon = CompileMonitor(warmup_calls=1, registry=MetricsRegistry())
        fn = mon.wrap("donating", jax.jit(
            lambda p, x: jax.tree_util.tree_map(
                lambda a: a + x.sum(), p), donate_argnums=(0,)))
        p = fn({"w": jnp.ones((4,))}, jnp.ones((8,)))
        gone = p
        with caplog.at_level(logging.WARNING,
                             logger="analytics_zoo_tpu.observability"):
            p = fn(gone, jnp.ones((16,)))     # churn: x drifted
        assert gone["w"].is_deleted()
        assert np.asarray(p["w"]).tolist() == [25.0] * 4
        assert mon.stats("donating")["recompiles_after_warmup"] == 1
        churn = [r.getMessage() for r in caplog.records
                 if "recompilation churn" in r.getMessage()]
        assert len(churn) == 1
        assert "((4,), 'float32')" in churn[0]
        assert "((16,), 'float32')" in churn[0]

    def test_fresh_wrapper_restarts_warmup(self):
        # churn state is per built program: a rebuilt trainer must not
        # inherit another's warmup budget
        mon = CompileMonitor(warmup_calls=1, registry=MetricsRegistry())
        a = mon.wrap("shared", jax.jit(lambda v: v + 1))
        a(jnp.ones((4,)))
        a(jnp.ones((8,)))   # churn on wrapper a
        b = mon.wrap("shared", jax.jit(lambda v: v + 1))
        b(jnp.ones((16,)))  # first call of wrapper b: warmup, not churn
        assert mon.stats("shared")["recompiles_after_warmup"] == 1


# --------------------------------------------------------- watchdog unit
class TestWatchdog:
    def test_plateau_detected_over_sliding_window(self):
        reg = MetricsRegistry()
        wd = TrainingWatchdog(policy="warn", window=4, min_delta=1e-3,
                              stall_timeout_s=0, registry=reg)
        wd.observe_loss(1.0)
        wd.observe_loss(0.5)          # improvement
        for _ in range(4):
            wd.observe_loss(0.5)      # flat
        assert wd.poll() is None      # warn policy never halts
        snap = reg.snapshot()
        assert snap["counters"]['watchdog_events_total{kind="plateau"}'] \
            == 1.0

    def test_plateau_rearms_once_per_window(self):
        reg = MetricsRegistry()
        wd = TrainingWatchdog(policy="warn", window=3, min_delta=1e-3,
                              registry=reg)
        wd.observe_loss(1.0)
        for _ in range(7):            # 2 full flat windows + 1
            wd.observe_loss(1.0)
        wd.poll()
        assert reg.snapshot()["counters"][
            'watchdog_events_total{kind="plateau"}'] == 2.0

    def test_divergence_fires_and_halts_under_policy(self):
        reg = MetricsRegistry()
        wd = TrainingWatchdog(policy="checkpoint_and_halt", window=50,
                              divergence=5.0, registry=reg)
        wd.observe_loss(1.0)
        wd.observe_loss(100.0)        # 99 > 5 * max(|1|, 1)
        issue = wd.poll()
        assert issue is not None and issue["kind"] == "divergence"
        assert wd.halted()

    def test_stall_flagged_with_fake_clock(self):
        t = [0.0]
        reg = MetricsRegistry()
        wd = TrainingWatchdog(policy="warn", stall_timeout_s=30.0,
                              clock=lambda: t[0], registry=reg)
        wd.beat()
        t[0] = 20.0
        assert not wd.check_stall()   # within deadline
        t[0] = 55.0
        assert wd.check_stall()       # 55s idle > 30s deadline
        assert not wd.check_stall()   # once per stall episode
        snap = reg.snapshot()
        assert snap["counters"]['watchdog_events_total{kind="stall"}'] \
            == 1.0
        assert reg.snapshot()["gauges"]["train_health_status"] >= 1
        wd.beat()                     # loop resumed: episode over
        t[0] = 100.0
        assert wd.check_stall()       # a SECOND stall is re-detected
        assert reg.snapshot()["counters"][
            'watchdog_events_total{kind="stall"}'] == 2.0

    def test_read_flags_route_to_active_watchdog(self):
        reg = MetricsRegistry()
        wd = TrainingWatchdog(policy="checkpoint_and_halt", registry=reg)
        prev = set_active_watchdog(wd)
        try:
            checked = get_registry().snapshot()["counters"].get(
                "train_finite_checked_steps_total", 0.0)
            record_finite_checks(4, 0)        # four finite steps: no-op
            assert wd.poll() is None
            record_finite_checks(3, 2)        # two NaN/Inf steps of three
            issue = wd.poll()
            assert issue is not None and issue["kind"] == "nonfinite"
            assert reg.snapshot()["counters"][
                'train_nonfinite_total{source="step"}'] == 2.0
            # the checked steps are counted in the shared registry
            assert get_registry().snapshot()["counters"][
                "train_finite_checked_steps_total"] == checked + 7.0
        finally:
            set_active_watchdog(prev)

    def test_pending_flags_are_read_at_drain_and_bounded(self):
        """A step's flag (bool: finite?) and a scan's count of
        non-finite steps are both held unread; drain reports each step
        once, without a watchdog through the bare counter; a caller
        that never drains reads its oldest flag past MAX_PENDING; a
        check that is off (None) holds nothing."""
        def read():
            snap = get_registry().snapshot()["counters"]
            return (snap.get("train_finite_checked_steps_total", 0.0),
                    snap.get('train_nonfinite_total{source="step"}', 0.0))
        start = read()

        def counters():
            return tuple(now - was for now, was in zip(read(), start))
        prev = set_active_watchdog(None)
        try:
            flags = PendingFiniteFlags()
            flags.keep(None)
            flags.keep(jnp.bool_(True))
            flags.keep(jnp.bool_(False))
            flags.keep(jnp.int32(3), steps=5)      # a scan of 5, 3 bad
            assert counters() == (0.0, 0.0)        # nothing read yet
            flags.drain()
            assert counters() == (7.0, 4.0)
            flags.drain()                          # nothing pending
            assert counters() == (7.0, 4.0)
            for _ in range(PendingFiniteFlags.MAX_PENDING):
                flags.keep(np.bool_(False))
            assert counters() == (7.0, 4.0)
            flags.keep(np.bool_(True))             # one too many
            assert counters() == (8.0, 5.0)        # the OLDEST was read
            flags.drain()
            assert counters() == (8.0 + PendingFiniteFlags.MAX_PENDING,
                                  4.0 + PendingFiniteFlags.MAX_PENDING)
        finally:
            set_active_watchdog(prev)

    def test_bad_policy_rejected(self):
        with pytest.raises(ValueError, match="policy"):
            TrainingWatchdog(policy="explode",
                             registry=MetricsRegistry())


# ------------------------------------------------- estimator integration
def _toy_model():
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    m = Sequential()
    m.add(Dense(1, input_shape=(8,)))
    m.compile(optimizer="sgd", loss="mse")
    return m


def _toy_data(n=512, poison_from=None):
    rs = np.random.RandomState(0)
    x = rs.randn(n, 8).astype(np.float32)
    y = rs.randn(n, 1).astype(np.float32)
    if poison_from is not None:
        y[poison_from:poison_from + 64] = np.nan
    return x, y


class TestEstimatorWatchdog:
    def test_nan_loss_checkpoint_and_halt_with_restorable_ckpt(
            self, tmp_path):
        from analytics_zoo_tpu.common.config import get_config
        from analytics_zoo_tpu.common.triggers import MaxIteration
        from analytics_zoo_tpu.feature.feature_set import FeatureSet
        from analytics_zoo_tpu.pipeline.estimator import Estimator
        get_config().set("observability.watchdog_policy",
                         "checkpoint_and_halt")
        x, y = _toy_data(poison_from=128)
        m = _toy_model()
        est = Estimator(m, optim_method=m.optim_method,
                        model_dir=str(tmp_path))
        with pytest.raises(TrainingHalted) as err:
            # MaxIteration end trigger keeps the per-step engine; the
            # loss goes NaN within epoch 0 and MUST halt well before
            # the trigger would end training
            est.train(FeatureSet.from_ndarrays(x, y), "mse",
                      end_trigger=MaxIteration(200), batch_size=64)
        assert err.value.issue["kind"] == "nonfinite"
        assert est.train_state.iteration < 200
        halt_iter = est.train_state.iteration
        # the halt snapshot goes to model_dir/halt/ so it can NEVER
        # shadow a good periodic snapshot on a later restore_latest
        halt_dir = tmp_path / "halt"
        assert any(p.name.startswith("snapshot.")
                   for p in halt_dir.iterdir())
        snap = get_registry().snapshot()
        assert any(k.startswith("train_nonfinite_total")
                   for k in snap["counters"])
        assert snap["gauges"]["train_health_status"] == 2.0

        # ... and it is LOADABLE: a fresh estimator pointed at the
        # halt directory resumes from it (restore counter moves,
        # training continues from the halt iteration, warn policy)
        get_config().set("observability.watchdog_policy", "warn")
        before = get_registry().counter(
            "checkpoint_restore_total", "").value
        x2, y2 = _toy_data()          # clean data
        # fresh name counters so the rebuilt model's layer names match
        # the checkpoint's (same-process rebuild shifts auto-names)
        from analytics_zoo_tpu.pipeline.api.keras.engine import Layer
        Layer.reset_name_counters()
        m2 = _toy_model()
        est2 = Estimator(m2, optim_method=m2.optim_method,
                         model_dir=str(halt_dir))
        est2.train(FeatureSet.from_ndarrays(x2, y2), "mse",
                   end_trigger=MaxIteration(halt_iter + 8),
                   batch_size=64)
        assert get_registry().counter(
            "checkpoint_restore_total", "").value == before + 1
        assert est2.train_state.iteration >= halt_iter + 8

    def test_nan_with_warn_policy_keeps_training(self):
        from analytics_zoo_tpu.common.triggers import MaxIteration
        from analytics_zoo_tpu.feature.feature_set import FeatureSet
        from analytics_zoo_tpu.pipeline.estimator import Estimator
        x, y = _toy_data(n=256, poison_from=0)
        m = _toy_model()
        est = Estimator(m, optim_method=m.optim_method)
        # default policy is warn: the run completes despite the NaN
        est.train(FeatureSet.from_ndarrays(x, y), "mse",
                  end_trigger=MaxIteration(25), batch_size=64)
        assert est.train_state.iteration == 25
        snap = get_registry().snapshot()
        assert any(k.startswith("train_nonfinite_total") and v > 0
                   for k, v in snap["counters"].items())

    def test_local_estimator_halts_on_nan(self):
        from analytics_zoo_tpu.common.config import get_config
        from analytics_zoo_tpu.pipeline.estimator.local_estimator import (
            LocalEstimator)
        get_config().set("observability.watchdog_policy",
                         "checkpoint_and_halt")
        x, y = _toy_data(n=256, poison_from=0)
        m = _toy_model()
        le = LocalEstimator(m, "mse", m.optim_method)
        with pytest.raises(TrainingHalted):
            le.fit(x, y, batch_size=64, epochs=8)


# ------------------------------------------- attribution + MFU end-to-end
class TestStepAttribution:
    def test_attribution_and_mfu_on_metrics(self):
        from analytics_zoo_tpu.common.config import get_config
        from analytics_zoo_tpu.common.triggers import MaxIteration
        from analytics_zoo_tpu.feature.feature_set import FeatureSet
        from analytics_zoo_tpu.pipeline.estimator import Estimator
        get_config().set("observability.device_time_every", 2)
        # CPU has no known peak: the override makes MFU computable on
        # the tier-1 run (acceptance: /metrics exposes an MFU value)
        get_config().set("observability.peak_flops", 1e9)
        x, y = _toy_data()
        m = _toy_model()
        est = Estimator(m, optim_method=m.optim_method)
        est.train(FeatureSet.from_ndarrays(x, y), "mse",
                  end_trigger=MaxIteration(8), batch_size=64)
        reg = get_registry()
        snap = reg.snapshot()
        hist = snap["histograms"]
        assert hist[
            'train_step_time_seconds{component="data_wait"}']["count"] \
            >= 8
        assert hist[
            'train_step_time_seconds{component="host_dispatch"}'][
            "count"] >= 8
        # device bracket sampled every 2nd step
        assert hist[
            'train_step_time_seconds{component="device"}']["count"] >= 4
        assert snap["counters"]['jax_compiles_total{fn="train_step"}'] \
            >= 1
        assert sum(v for k, v in snap["counters"].items()
                   if k.startswith("jax_compile_seconds_total")) > 0
        assert snap["gauges"]["train_mfu"] > 0
        # ... and all of it shows on the exposition endpoint directly
        text = reg.prometheus_text()
        assert "train_step_time_seconds_bucket" in text
        assert "train_mfu" in text
        assert "jax_compiles_total" in text

    def test_local_estimator_attribution_and_mfu(self):
        from analytics_zoo_tpu.common.config import get_config
        from analytics_zoo_tpu.pipeline.estimator.local_estimator import (
            LocalEstimator)
        get_config().set("observability.device_time_every", 2)
        get_config().set("observability.peak_flops", 1e9)
        reg = get_registry()
        hist = reg.histogram("train_step_time_seconds", "",
                             labels=("component",))
        before = {c: hist.labels(c).count
                  for c in ("data_wait", "host_dispatch", "device")}
        x, y = _toy_data(n=256)
        m = _toy_model()
        LocalEstimator(m, "mse", m.optim_method).fit(
            x, y, batch_size=64, epochs=2)   # 8 steps
        assert hist.labels("data_wait").count - before["data_wait"] == 8
        assert hist.labels("host_dispatch").count \
            - before["host_dispatch"] == 8
        assert hist.labels("device").count - before["device"] == 4
        assert reg.snapshot()["gauges"]["train_mfu"] > 0

    def test_device_loader_feeds_data_wait(self):
        from analytics_zoo_tpu.data import DataPipeline, DeviceLoader
        reg = get_registry()
        before = reg.histogram(
            "train_step_time_seconds", "", labels=("component",)
        ).labels("data_wait").count
        rs = np.random.RandomState(0)
        pipe = DataPipeline(rs.randn(64, 4).astype(np.float32),
                            rs.randn(64, 1).astype(np.float32),
                            batch_size=16, name="diag-loader")
        for _ in DeviceLoader(pipe, depth=2):
            pass
        after = reg.histogram(
            "train_step_time_seconds", "", labels=("component",)
        ).labels("data_wait").count
        assert after - before == 4
        pipe.close()


# ------------------------------------------------- serving readiness
class TestServingReadiness:
    def _engine(self, **cfg_kw):
        from analytics_zoo_tpu.pipeline.api.keras import Sequential
        from analytics_zoo_tpu.pipeline.api.keras.layers import (
            Dense, Flatten)
        from analytics_zoo_tpu.pipeline.inference import InferenceModel
        from analytics_zoo_tpu.serving.redis_client import EmbeddedBroker
        from analytics_zoo_tpu.serving.server import (
            ClusterServing, ServingConfig)
        m = Sequential()
        m.add(Flatten(input_shape=(4, 4, 1)))
        m.add(Dense(2))
        m.init()
        im = InferenceModel().load_zoo(m)
        return ClusterServing(
            im, ServingConfig(batch_size=2, metrics_port=0, **cfg_kw),
            broker=EmbeddedBroker())

    def test_healthz_flips_503_on_queue_depth(self):
        serving = self._engine(healthz_max_queue=3)
        try:
            url = (f"http://127.0.0.1:{serving.metrics_server.port}"
                   "/healthz")
            body = json.load(urllib.request.urlopen(url))
            assert body == {"ready": True}
            # the readiness probe reads THIS instance's observed
            # backlog (not the shared registry gauge, which another
            # still-draining serving instance in the same process
            # could stomp between the set and the probe — the old
            # contention flake)
            serving._note_backlog(10)     # backlog beyond threshold
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(url)
            assert err.value.code == 503
            reason = json.load(err.value)
            assert reason["ready"] is False
            assert reason["reason"] == "queue_depth"
            assert reason["queue_depth"] == 10
            serving._note_backlog(0)      # drains -> ready again
            assert json.load(urllib.request.urlopen(url))["ready"]
        finally:
            serving.close()

    def test_healthz_flips_503_on_error_rate(self):
        serving = self._engine(healthz_max_error_rate=0.25)
        try:
            url = (f"http://127.0.0.1:{serving.metrics_server.port}"
                   "/healthz")
            serving._recent_outcomes.extend([1] * 5 + [0] * 5)
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(url)
            assert err.value.code == 503
            assert json.load(err.value)["reason"] == "error_rate"
        finally:
            serving.close()

    def test_yaml_parses_readiness_thresholds(self, tmp_path):
        from analytics_zoo_tpu.serving.server import ServingConfig
        p = tmp_path / "config.yaml"
        p.write_text(
            "model:\n  builder: x:y\n"
            "data:\n  src: localhost:6379\n"
            "params:\n  batch_size: 8\n  healthz_max_queue: 500\n"
            "  healthz_max_error_rate: 0.1\n")
        cfg = ServingConfig.from_yaml(str(p))
        assert cfg.healthz_max_queue == 500
        assert cfg.healthz_max_error_rate == 0.1


# ----------------------------------------------- telemetry stale marker
def test_telemetry_stale_marker_on_midrun_failure(monkeypatch):
    from analytics_zoo_tpu.observability import telemetry

    class FlakyDev:
        id = "diag-flaky-0"

        def __init__(self):
            self.ok = True

        def memory_stats(self):
            if not self.ok:
                raise RuntimeError("backend lost memory_stats")
            return {"bytes_in_use": 123, "bytes_limit": 1000}

    dev = FlakyDev()
    monkeypatch.setattr(jax, "local_devices", lambda: [dev])
    reg = MetricsRegistry()
    sampled = telemetry.sample_device_telemetry(reg)
    assert sampled['device_bytes_in_use{diag-flaky-0}'] == 123.0
    dev.ok = False
    # must not raise; the last-good gauges stay, stale marker set
    sampled = telemetry.sample_device_telemetry(reg)
    assert sampled['device_telemetry_stale{diag-flaky-0}'] == 1.0
    snap = reg.snapshot()
    assert snap["gauges"][
        'device_bytes_in_use{device="diag-flaky-0"}'] == 123.0
    assert snap["gauges"][
        'device_telemetry_stale{device="diag-flaky-0"}'] == 1.0
    dev.ok = True
    telemetry.sample_device_telemetry(reg)
    assert reg.snapshot()["gauges"][
        'device_telemetry_stale{device="diag-flaky-0"}'] == 0.0


# ------------------------------------------------------ obs_report CLI
class TestObsReport:
    def _snapshot_file(self, tmp_path, tput=100.0):
        reg = MetricsRegistry()
        reg.gauge("train_throughput_samples_per_sec", "t").set(tput)
        reg.gauge("train_mfu", "m").set(0.41)
        h = reg.histogram("train_step_time_seconds", "a",
                          labels=("component",))
        for comp, v in (("data_wait", 0.001), ("host_dispatch", 0.004),
                        ("device", 0.02)):
            for _ in range(10):
                h.labels(comp).observe(v)
        reg.counter("jax_compiles_total", "c",
                    labels=("fn",)).labels("train_step").inc(2)
        reg.counter("jax_compile_seconds_total", "s",
                    labels=("fn",)).labels("train_step").inc(3.5)
        reg.counter("watchdog_events_total", "w",
                    labels=("kind",)).labels("plateau").inc()
        path = tmp_path / f"snap_{tput}.jsonl"
        reg.write_jsonl(str(path))
        return str(path)

    def test_report_renders_from_registry_jsonl(self, tmp_path, capsys):
        obs_report = _load_obs_report()
        snap = self._snapshot_file(tmp_path)
        rc = obs_report.main([snap])
        out = capsys.readouterr().out
        assert rc == 0
        assert "step-time attribution" in out
        assert "data_wait" in out and "device" in out
        assert "MFU: 41.0%" in out
        assert "compilation" in out
        assert "watchdog events [kind=\"plateau\"]: 1" in out

    def test_report_renders_bench_metrics_shape(self, tmp_path, capsys):
        obs_report = _load_obs_report()
        reg = MetricsRegistry()
        reg.gauge("train_mfu", "m").set(0.2)
        bench_like = {"ncf": {"recorded_unix": 1, "mfu": 0.2,
                              "metrics": reg.snapshot()}}
        p = tmp_path / "bench_metrics.json"
        p.write_text(json.dumps(bench_like))
        rc = obs_report.main([str(p), "--workload", "ncf"])
        assert rc == 0
        assert "ncf" in capsys.readouterr().out

    def test_diff_gates_every_workload_in_bench_metrics(self, tmp_path,
                                                        capsys):
        # regression hides in the alphabetically-LAST workload: the
        # gate must still catch it (every shared workload is diffed)
        obs_report = _load_obs_report()

        def snap(tput):
            reg = MetricsRegistry()
            reg.gauge("train_throughput_samples_per_sec",
                      "t").set(tput)
            return {"metrics": reg.snapshot()}

        cur = tmp_path / "cur.json"
        base = tmp_path / "base.json"
        cur.write_text(json.dumps({"aa": snap(100.0),
                                   "zz": snap(50.0)}))
        base.write_text(json.dumps({"aa": snap(100.0),
                                    "zz": snap(200.0)}))
        rc = obs_report.main([str(cur), "--diff", str(base)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "REGRESSION" in out

    def test_diff_gates_on_throughput_regression(self, tmp_path,
                                                 capsys):
        obs_report = _load_obs_report()
        base = self._snapshot_file(tmp_path, tput=200.0)
        cur = self._snapshot_file(tmp_path, tput=100.0)
        rc = obs_report.main([cur, "--diff", base])
        out = capsys.readouterr().out
        assert rc == 1
        assert "REGRESSION" in out
        # self-diff is clean
        assert obs_report.main([cur, "--diff", cur]) == 0


def _load_obs_report():
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "obs_report.py")
    spec = importlib.util.spec_from_file_location("obs_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------- bench --compare
def test_bench_compare_against_baseline(tmp_path, monkeypatch, capsys):
    import bench
    artifact = tmp_path / "bench_results.json"
    artifact.write_text(json.dumps({"results": [
        {"metric": "ncf_movielens1m_train_throughput", "value": 80.0},
        {"metric": "cluster_serving_throughput", "value": 500.0},
    ]}))
    monkeypatch.setattr(bench, "ARTIFACT_PATH", str(artifact))
    base = tmp_path / "BASELINE.json"
    # flat {metric: value} map form
    base.write_text(json.dumps(
        {"ncf_movielens1m_train_throughput": 100.0,
         "cluster_serving_throughput": 400.0}))
    rc = bench._compare_against_baseline(str(base), threshold=0.10)
    line = json.loads(capsys.readouterr().out.strip())
    assert rc == 1
    assert line["ok"] is False
    assert line["regressions"][0]["metric"] == \
        "ncf_movielens1m_train_throughput"
    # within threshold -> clean
    base.write_text(json.dumps(
        {"ncf_movielens1m_train_throughput": 85.0}))
    rc = bench._compare_against_baseline(str(base), threshold=0.10)
    assert rc == 0
    assert json.loads(capsys.readouterr().out.strip())["ok"] is True
    # a baseline metric the current artifact doesn't have must be
    # reported as skipped, NOT gate the exit code (single-workload
    # rerun vs full-run baseline)
    base.write_text(json.dumps(
        {"ncf_movielens1m_train_throughput": 85.0,
         "resnet50_imagenet_train_throughput": 999.0}))
    rc = bench._compare_against_baseline(str(base), threshold=0.10)
    line = json.loads(capsys.readouterr().out.strip())
    assert rc == 0 and line["ok"] is True
    assert line["skipped"][0]["metric"] == \
        "resnet50_imagenet_train_throughput"


def test_bench_derive_health_fields():
    import bench
    snap = {"gauges": {"train_mfu": 0.37},
            "counters": {
                'jax_compile_seconds_total{fn="train_step"}': 2.5,
                'jax_compile_seconds_total{fn="train_epoch_scan"}': 1.5,
                'jax_compiles_total{fn="train_step"}': 2.0,
                'jax_recompiles_total{fn="train_step"}': 1.0,
                "jax_backend_compile_seconds_total": 3.25,
            }}
    out = bench._derive_health_fields(snap)
    assert out["mfu"] == 0.37
    assert out["compile_seconds_total"] == 4.0
    assert out["backend_compile_seconds_total"] == 3.25
    assert out["compiles_total"] == 2
    assert out["recompiles_after_warmup"] == 1
