"""Token-level continuous batching tests (ISSUE 12).

* ``Seq2seq.infer`` early exit: the ``lax.while_loop`` decode stops
  the moment every sequence emitted EOS — a batch finishing at step 1
  pays 1 iteration, not ``max_seq_len`` — with the masked output
  contract bit-identical to the historical scan + host-mask path.
* The decode slot pool: admit/retire/backfill sequencing with the
  EOS-freed slot reused the SAME scheduler iteration, per-request
  token budgets, zero post-warm recompiles across every fill level
  (``jax_backend_compiles_total`` delta 0 over the AOT-warmed
  ``(batch_bucket, state_bucket)`` ladder), and pool recovery after a
  failed iteration.
* Iteration-level scheduling beats whole-sequence decode by device
  STEP COUNT on mixed-length traffic (the deterministic half of the
  bench claim — wall-clock lives in ``bench.py serving_generative``).
* Redis transport: generative groups keep exactly-once/poison
  semantics — a replica dying mid-decode leaves its batch un-acked in
  the PEL for a peer to reclaim, every sequence exactly-once visible.
* HTTP fast path: chunked per-token streaming ``/generate`` +
  ``ServingHttpClient.generate`` with the bounded retry contract.
* The PR 8 acceptance: a second process over a warm compile cache
  deserializes the decode-step executable (>=1 hit, zero post-warm
  compiles, identical tokens).
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from analytics_zoo_tpu.models.seq2seq import Seq2seq
from analytics_zoo_tpu.observability import get_registry
from analytics_zoo_tpu.serving.client import (
    InputQueue, OutputQueue, ServingHttpClient, ServingHttpError)
from analytics_zoo_tpu.serving.engine import Request, ServingEngine
from analytics_zoo_tpu.serving.redis_client import EmbeddedBroker
from analytics_zoo_tpu.serving.server import (
    ClusterServing, ServingConfig)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

START, STOP = 0, 9


class CountdownModel:
    """Deterministic generative duck model (the Seq2seq decode
    contract as real jax programs, so engine_jit/AOT/recompile
    accounting is exercised for real): a sequence whose first encoder
    token is ``s`` emits ``s, s+1, ..., STOP`` — per-request lengths
    controlled by the input, which is what the admit/retire tests
    need."""

    def decode_params(self):
        import jax.numpy as jnp
        return {"w": jnp.zeros(())}

    def prefill(self, params, enc_ids):
        import jax.numpy as jnp
        h = jnp.zeros((enc_ids.shape[0], 4), jnp.float32)
        h = h.at[:, 0].set(enc_ids[:, 0].astype(jnp.float32))
        return ((h, h * 0.0),)

    def decode_step(self, params, tok, carries):
        import jax.numpy as jnp
        (h, c), = carries
        first = h[:, 0].astype(jnp.int32)
        nxt = jnp.where(tok == START, first, tok + 1)
        return nxt, ((h, c),)

    def initial_carries(self, batch):
        import jax.numpy as jnp
        z = jnp.zeros((batch, 4), jnp.float32)
        return ((z, z),)


def _expected(first_tok: int):
    return list(range(first_tok, STOP + 1))


def _gen_engine(slots=4, max_seq_len=16, **kw):
    eng = ServingEngine(**kw)
    ep = eng.register_generative(
        "gen", CountdownModel(), enc_len=3, start_sign=START,
        stop_sign=STOP, max_seq_len=max_seq_len, slots=slots)
    eng.start()
    return eng, ep


def _req(first_tok, uri=None, **kw):
    return Request(endpoint="gen", uri=uri or f"u{first_tok}",
                   data=np.array([first_tok, 0, 0], np.int32), **kw)


# =============================================== Seq2seq early exit
class TestSeq2seqEarlyExit:
    def _model(self):
        m = Seq2seq(vocab_size=10, embed_dim=8, hidden_sizes=(16,))
        m.init()
        return m

    def test_early_exit_bit_identical_to_scan_mask(self):
        m = self._model()
        src = np.random.RandomState(0).randint(2, 10, (4, 6))
        naive = m.infer(src, start_sign=1, max_seq_len=7, stop_sign=2,
                        early_exit=False)
        fast, steps = m.infer(src, start_sign=1, max_seq_len=7,
                              stop_sign=2, return_steps=True)
        assert np.array_equal(naive, fast)
        assert 1 <= steps <= 7

    def test_all_stopped_batch_exits_early(self):
        """A batch that finishes at step 1 pays 1 decode iteration,
        not max_seq_len — the device-program early exit (satellite:
        no more post-hoc host masking paying the full scan)."""
        m = self._model()
        # generator-bias surgery: argmax is ALWAYS the stop token
        p = m.get_variables()["params"]
        p[m.generator.name]["bias"] = \
            p[m.generator.name]["bias"].at[2].set(1e6)
        src = np.random.RandomState(1).randint(2, 10, (4, 6))
        out, steps = m.infer(src, start_sign=1, max_seq_len=30,
                             stop_sign=2, return_steps=True)
        assert steps == 1
        assert (out == 2).all()          # masked contract intact
        naive = m.infer(src, start_sign=1, max_seq_len=30,
                        stop_sign=2, early_exit=False)
        assert np.array_equal(out, naive)

    def test_no_stop_sign_keeps_whole_scan(self):
        m = self._model()
        src = np.random.RandomState(2).randint(2, 10, (2, 5))
        out, steps = m.infer(src, start_sign=1, max_seq_len=6,
                             return_steps=True)
        assert out.shape == (2, 6) and steps == 6


# ==================================================== slot pool
class TestDecodeSlotPool:
    def test_admit_retire_backfill_and_results(self):
        """8 mixed-length sequences through a 4-slot pool: every
        result correct, and at least one EOS-freed slot is reused by
        a backfilled sequence in the SAME scheduler iteration."""
        eng, ep = _gen_engine(slots=4)
        try:
            firsts = [5, 6, 7, 8, 5, 6, 7, 8]
            reqs = [_req(f, uri=f"u{i}") for i, f in enumerate(firsts)]
            eng.submit_wait(reqs, timeout_s=60)
            for r, f in zip(reqs, firsts):
                assert r.error is None, (r.uri, r.error)
                assert r.result == _expected(f), (r.uri, r.result)
            # same-iteration reuse: a retire (iteration k, slot s)
            # matched by an admit (k, s)
            retired = set(ep.pool.retire_log)
            assert any(entry in retired
                       for entry in ep.pool.admit_log), (
                ep.pool.admit_log, ep.pool.retire_log)
            assert ep.pool.active_count == 0
            assert ep.pool.admitted_total == 8
        finally:
            eng.stop()

    def test_iteration_scheduling_beats_whole_sequence_step_count(
            self):
        """The deterministic half of the bench claim: on mixed-length
        traffic the scheduler executes >=2x fewer device decode steps
        than request-granularity whole-sequence decode (which pays
        max_seq_len per batch, padding included)."""
        max_len = 16
        eng, ep = _gen_engine(slots=4, max_seq_len=max_len)
        try:
            # lengths 2..5 tokens; naive = ceil(12/4) batches * 16
            firsts = [8, 7, 6, 5] * 3
            reqs = [_req(f, uri=f"m{i}") for i, f in enumerate(firsts)]
            eng.submit_wait(reqs, timeout_s=60)
            assert all(r.error is None for r in reqs)
            naive_steps = (len(firsts) // 4) * max_len
            assert ep.pool.iterations * 2 <= naive_steps, (
                ep.pool.iterations, naive_steps)
        finally:
            eng.stop()

    def test_per_request_max_tokens(self):
        eng, ep = _gen_engine(slots=2)
        try:
            capped = _req(3, uri="capped", max_tokens=2)
            free = _req(8, uri="free")
            eng.submit_wait([capped, free], timeout_s=60)
            assert capped.result == [3, 4]          # budget cut
            assert free.result == _expected(8)      # EOS cut
        finally:
            eng.stop()

    def test_generative_request_breaks_stateless_fill_wait(self):
        """A sequence arriving while a stateless peer holds the
        idle-edge fill-wait must not sit behind the co-rider timer:
        bounded completion far under the 10s max_wait proves the wait
        broke on the generative arrival (event order, no ratios)."""
        eng = ServingEngine(max_wait_ms=10_000)

        class Stateless:
            def predict(self, x, batch_size=None):
                return np.zeros((len(x), 4), np.float32)

        eng.register("plain", Stateless(), batch_size=4)
        eng.register_generative(
            "gen", CountdownModel(), enc_len=3, start_sign=START,
            stop_sign=STOP, max_seq_len=16, slots=4)
        eng.start()
        try:
            plain = Request(endpoint="plain", uri="p",
                            data=np.zeros(3, np.float32))
            eng.submit([plain])          # enters the idle-edge wait
            time.sleep(0.1)
            gen = _req(7, uri="g")
            eng.submit([gen])
            assert gen.wait(5), "first token sat behind the timer"
            assert gen.error is None and gen.result == _expected(7)
            assert plain.wait(5) and plain.error is None
        finally:
            eng.stop()

    def test_streaming_callback_order(self):
        eng, ep = _gen_engine(slots=2)
        try:
            seen = []
            r = _req(6, on_token=lambda i, t: seen.append((i, t)))
            eng.submit_wait([r], timeout_s=60)
            assert r.result == _expected(6)
            assert seen == list(enumerate(_expected(6)))
        finally:
            eng.stop()

    def test_zero_recompiles_across_all_fill_levels(self):
        """After ``warm()`` every (batch_bucket, state_bucket) rung of
        the step AND prefill programs is compiled: traffic at every
        occupancy, so a request in every bucket, fires no backend
        compile."""
        from analytics_zoo_tpu.observability.diagnostics import (
            get_compile_monitor)
        get_compile_monitor()     # backend-compile listener active
        eng, ep = _gen_engine(slots=4)
        try:
            # ladder (1, 2, 4) x (step, prefill) = 6 programs
            assert ep.warm() == 6
            compiles = get_registry().counter(
                "jax_backend_compiles_total",
                "XLA backend compilations (jax.monitoring)")
            before = compiles.value
            # every fill level 1..4 (3 pads to bucket 4)
            for fill in (1, 2, 3, 4):
                reqs = [_req(5 + i % 4, uri=f"f{fill}-{i}")
                        for i in range(fill)]
                eng.submit_wait(reqs, timeout_s=60)
                assert all(r.error is None for r in reqs)
            assert compiles.value == before
        finally:
            eng.stop()

    def test_failed_prefill_consumes_exactly_its_batch(self):
        """A deterministically-poison admission group is failed AND
        consumed — re-queueing it would fail every future iteration
        forever — while later traffic serves normally."""
        eng, ep = _gen_engine(slots=2)
        try:
            orig = ep.pool._prefill
            calls = {"n": 0}

            def bomb(*args):
                calls["n"] += 1
                if calls["n"] == 1:
                    raise ValueError("prefill boom")
                return orig(*args)

            ep.pool._prefill = bomb
            bad = _req(5, uri="bad")
            eng.submit_wait([bad], timeout_s=60)
            assert isinstance(bad.error, ValueError)
            good = _req(7, uri="good")
            eng.submit_wait([good], timeout_s=60)
            assert good.error is None and good.result == _expected(7)
            assert len(ep.pool._free) == 2      # no leaked slots
        finally:
            eng.stop()

    def test_abandoned_request_swept_without_decoding(self):
        """A transport that timed a sequence out already answered its
        client: the scheduler retires the slot instead of decoding
        tokens nobody reads."""
        from analytics_zoo_tpu.serving.engine.decode import (
            GenerativeEndpoint)
        ep = GenerativeEndpoint(
            "gen", CountdownModel(), enc_len=3, start_sign=START,
            stop_sign=STOP, max_seq_len=16, slots=2)
        gone, live = _req(3, uri="gone"), _req(8, uri="live")
        ep.pool.admit([gone, live])
        gone.fail(TimeoutError("client gave up"))
        while ep.pool.active_count:
            assert ep.pool.step_once() <= 1   # only 'live' decodes
        assert live.result == _expected(8)
        assert gone.result is None            # never decoded
        assert len(ep.pool._free) == 2

    def test_failed_iteration_fails_active_and_pool_recovers(self):
        """A model Exception mid-iteration fails exactly the active
        sequences (their state shared the fused step program), the
        batcher thread survives, and fresh traffic is served on a
        reset pool."""
        eng, ep = _gen_engine(slots=2)
        try:
            orig = ep.pool._step
            calls = {"n": 0}

            def bomb(*args):
                calls["n"] += 1
                if calls["n"] == 1:
                    raise ValueError("decode boom")
                return orig(*args)

            ep.pool._step = bomb
            bad = [_req(5, uri="bad-0"), _req(6, uri="bad-1")]
            eng.submit_wait(bad, timeout_s=60)
            for r in bad:
                assert isinstance(r.error, ValueError), r.error
            assert ep.pool.active_count == 0
            good = _req(7, uri="good")
            eng.submit_wait([good], timeout_s=60)
            assert good.error is None
            assert good.result == _expected(7)
        finally:
            eng.stop()


# ================================== Redis transport: exactly-once
class _SimulatedReplicaDeath(BaseException):
    """Escapes ``except Exception`` the way a process kill escapes
    the worker: the batch stays un-acked in the PEL."""


class TestGenerativeRedisExactlyOnce:
    def test_mid_decode_kill_reclaimed_exactly_once(self):
        """A worker dying mid-decode leaves its generative group
        un-acked; a peer reclaims it and every sequence gets exactly
        one visible result — the stateless PEL contract preserved for
        generative groups (satellite 3)."""
        broker = EmbeddedBroker()
        w1 = ClusterServing(
            None,
            ServingConfig(batch_size=4, consumer_group="serve",
                          consumer_name="w1"),
            broker=broker)
        ep1 = w1.register_generative_endpoint(
            "gen", CountdownModel(), enc_len=3, start_sign=START,
            stop_sign=STOP, max_seq_len=16)
        orig = ep1.pool._step
        calls = {"n": 0}

        def dies(*args):
            calls["n"] += 1
            if calls["n"] == 1:
                raise _SimulatedReplicaDeath("killed mid-decode")
            return orig(*args)

        ep1.pool._step = dies
        inq = InputQueue(broker=broker)
        firsts = [5, 6, 7, 8]
        for i, f in enumerate(firsts):
            inq.enqueue(f"g{i}", np.array([f, 0, 0], np.int32),
                        endpoint="gen")

        def _run_until_death():
            try:
                w1.run(poll_ms=5)
            except _SimulatedReplicaDeath:
                pass
        t = threading.Thread(target=_run_until_death)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        pend = broker._groups[("serving_stream", "serve")]["pending"]
        assert len(pend) == 4        # un-acked, not lost

        w2 = ClusterServing(
            None,
            ServingConfig(batch_size=4, consumer_group="serve",
                          consumer_name="w2",
                          reclaim_min_idle_ms=0),
            broker=broker)
        w2.register_generative_endpoint(
            "gen", CountdownModel(), enc_len=3, start_sign=START,
            stop_sign=STOP, max_seq_len=16)
        try:
            deadline = time.time() + 30
            while (w1.total_records + w2.total_records) < 4 \
                    and time.time() < deadline:
                if w2.run_once(block_ms=10) == 0:
                    w2._reclaim_stale(min_idle_ms=0)
            outq = OutputQueue(broker=broker)
            for i, f in enumerate(firsts):
                res = outq.query(f"g{i}")
                assert res == _expected(f), (i, res)
            assert w1.total_records + w2.total_records == 4
            assert not broker._groups[("serving_stream",
                                       "serve")]["pending"]
        finally:
            w2.close()
            w1.close()

    def test_max_tokens_field_rides_the_stream(self):
        broker = EmbeddedBroker()
        s = ClusterServing(None, ServingConfig(batch_size=4),
                           broker=broker)
        s.register_generative_endpoint(
            "gen", CountdownModel(), enc_len=3, start_sign=START,
            stop_sign=STOP, max_seq_len=16)
        try:
            inq = InputQueue(broker=broker)
            inq.enqueue("capped", np.array([3, 0, 0], np.int32),
                        endpoint="gen", max_tokens=2)
            inq.enqueue("full", np.array([8, 0, 0], np.int32),
                        endpoint="gen")
            served = 0
            deadline = time.time() + 30
            while served < 2 and time.time() < deadline:
                served += s.run_once(block_ms=10)
            outq = OutputQueue(broker=broker)
            assert outq.query("capped") == [3, 4]
            assert outq.query("full") == _expected(8)
        finally:
            s.close()


# ======================================= HTTP streaming fast path
class TestGenerativeHttpStreaming:
    def _serving(self):
        eng, ep = _gen_engine(slots=4)

        class Stateless:
            def predict(self, x, batch_size=None):
                return np.zeros((len(x), 4), np.float32)

        eng.register("plain", Stateless(), batch_size=2)
        from analytics_zoo_tpu.serving.engine.transport import (
            HttpTransport)
        tr = HttpTransport(eng, port=0).start()
        return eng, ep, tr

    def test_streams_tokens_then_done(self):
        eng, ep, tr = self._serving()
        try:
            client = ServingHttpClient(f"http://127.0.0.1:{tr.port}")
            seen = []
            doc = client.generate(
                "gen", [6, 0, 0],
                on_token=lambda i, t: seen.append((i, t)))
            assert doc["tokens"] == _expected(6)
            assert seen == list(enumerate(_expected(6)))
            assert doc["endpoint"] == "gen" and doc["request_id"]
            capped = client.generate("gen", [3, 0, 0], max_tokens=3)
            assert capped["tokens"] == [3, 4, 5]
        finally:
            tr.stop()
            eng.stop()

    def test_status_contract(self):
        eng, ep, tr = self._serving()
        try:
            client = ServingHttpClient(f"http://127.0.0.1:{tr.port}")
            with pytest.raises(ServingHttpError) as ei:
                client.generate("nope", [1, 2, 3])
            assert ei.value.status == 404
            # generate against a stateless endpoint is a 400, with a
            # pointer at the right route
            with pytest.raises(ServingHttpError) as ei:
                client.generate("plain", [1, 2, 3])
            assert ei.value.status == 400
            assert "/predict/plain" in str(ei.value)
            # endpoints listing advertises the generative shape
            eps = client.endpoints()
            assert eps["gen"]["generative"] is True
            assert eps["gen"]["slots"] == 4
            assert "generative" not in eps["plain"]
        finally:
            tr.stop()
            eng.stop()

    def test_client_disconnect_mid_stream_frees_slot(self):
        """A client hanging up mid-stream fails its request, so the
        scheduler's abandoned-sweep retires the slot instead of
        decoding to max_seq_len for nobody."""
        import json as _json

        from analytics_zoo_tpu.serving.engine.transport import (
            HttpTransport)
        eng, ep = _gen_engine(slots=2, max_seq_len=10_000)
        tr = HttpTransport(eng, port=0)    # no socket: direct handler

        class DropsAfterFirstToken:
            def _respond(self, code, doc):
                raise AssertionError(f"unexpected status {code}")

            def start_stream(self, code=200):
                pass

            def stream_line(self, doc):
                if "token" in doc:
                    raise BrokenPipeError("client gone")

            def end_stream(self):
                pass

        try:
            # start token far from STOP: without the sweep this
            # sequence would decode for thousands of iterations
            body = _json.dumps(
                {"data": [100, 0, 0], "dtype": "int32"}).encode()
            tr.handle_generate("gen", body, DropsAfterFirstToken())
            deadline = time.monotonic() + 10
            while ep.pool.active_count and time.monotonic() < deadline:
                time.sleep(0.02)
            assert ep.pool.active_count == 0, \
                "disconnected stream still holds its slot"
            assert len(ep.pool._free) == 2
        finally:
            eng.stop()

    def test_connection_retries_are_bounded(self):
        # nothing listens here: connection-class errors retry with
        # bounded backoff then re-raise (the predict_http contract)
        from urllib.error import URLError
        client = ServingHttpClient("http://127.0.0.1:9", retries=2)
        t0 = time.monotonic()
        with pytest.raises((URLError, OSError)):
            client.generate("gen", [1, 2, 3], timeout_s=0.5)
        assert time.monotonic() - t0 < 30.0


# =============================== compile-cache second-process warm
class TestDecodeCacheWarmStart:
    def _run(self, cache_dir):
        env = dict(os.environ)
        env.pop("ZOO_TPU_RUN_DIR", None)
        env["PYTHONPATH"] = REPO_ROOT + os.pathsep + \
            env.get("PYTHONPATH", "")
        r = subprocess.run(
            [sys.executable,
             os.path.join(REPO_ROOT, "tests",
                          "generative_cache_worker.py"),
             cache_dir],
            capture_output=True, text=True, timeout=420, env=env,
            cwd=REPO_ROOT)
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    def test_second_process_warm_loads_decode_step(self, tmp_path):
        """ISSUE 12 acceptance: the decode-step executables round-trip
        JAX's persistent compilation cache — a second process reads
        them (the six of the ladder at least, and misses nothing),
        records zero post-warm backend compiles at any fill level, and
        emits identical tokens."""
        cache_dir = str(tmp_path / "gen-cache")
        cold = self._run(cache_dir)
        assert cold["warmed_programs"] == 6
        assert cold["cache_hits"] == 0
        assert cold["cache_misses"] >= 6
        assert len(os.listdir(cache_dir)) >= 6
        assert cold["post_warm_compiles"] == 0
        warm = self._run(cache_dir)
        assert warm["warmed_programs"] == 6
        assert warm["cache_hits"] == cold["cache_misses"]
        assert warm["cache_misses"] == 0
        assert warm["post_warm_compiles"] == 0
        assert warm["tokens_digest"] == cold["tokens_digest"]
