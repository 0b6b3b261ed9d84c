"""Serving subsystem: registry, dynamic batching scheduler, admission
control, continuous batching, HTTP front end, metrics.

Concurrency edge cases the ISSUE names: mixed-shape bucketing under N
submitting threads, deadline expiry mid-queue, load-shed under
saturation, graceful drain completing in-flight work, continuous-
batching slot-reuse parity vs a sequential decode — plus the
acceptance end-to-end: >= 100 concurrent mixed predict+generate
requests with zero lost/duplicated responses, outputs equal to direct
single-request model calls, and metrics showing >1 average batch
occupancy.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu import chaos
from deeplearning4j_tpu.nn.conf import updaters
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                               EmbeddingSequenceLayer,
                                               OutputLayer,
                                               RnnOutputLayer,
                                               TransformerEncoderLayer)
from deeplearning4j_tpu.serving import (BatchScheduler,
                                        CircuitBreaker,
                                        CircuitOpenError,
                                        ContinuousBatcher,
                                        DeadlineExceededError,
                                        ModelNotFoundError,
                                        ModelRegistry, ModelServer,
                                        QueueFullError,
                                        ServerClosedError,
                                        ServingMetrics)


class EchoModel:
    """Records every served batch shape; output = 2 * input."""

    def __init__(self, delay=0.0):
        self.delay = delay
        self.shapes = []
        self._lock = threading.Lock()

    def output(self, x):
        x = np.asarray(x)
        with self._lock:
            self.shapes.append(x.shape)
        if self.delay:
            time.sleep(self.delay)
        return x * 2.0


class PoisonModel(EchoModel):
    """Fails any batch containing a NaN row."""

    def output(self, x):
        x = np.asarray(x)
        if np.isnan(x).any():
            raise ValueError("poison row")
        return super().output(x)


def _mlp(seed=0):
    conf = (NeuralNetConfiguration.builder().set_seed(seed)
            .updater(updaters.adam(0.01)).list()
            .layer(DenseLayer(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=3))
            .set_input_type(InputType.feed_forward(4)).build())
    return MultiLayerNetwork(conf).init()


def _assert_each_caller_got_its_own_row(results, direct):
    """The scheduler's contract is "each caller gets its own row", not
    bit identity across batch shapes: a row served from a padded
    power-of-two batch and the same row at batch 1 differ by an ulp
    in the CPU's matmul (and on the TPU, PERF.md section 6, PR 21)."""
    for i, got in enumerate(results):
        np.testing.assert_allclose(got, direct[i], rtol=1e-6, atol=0)
        gaps = [float(np.abs(got - d).max()) for d in direct]
        # nearer its own direct output than any other caller's, by
        # orders of magnitude: the inputs are distinct random rows
        assert min(gaps[:i] + gaps[i + 1:]) > 1e3 * gaps[i], (i, gaps)


LM_V, LM_CAP = 13, 32


def _lm(seed=0):
    conf = (NeuralNetConfiguration.builder().set_seed(seed)
            .updater(updaters.adam(1e-3)).list()
            .layer(EmbeddingSequenceLayer(n_in=LM_V, n_out=16))
            .layer(TransformerEncoderLayer(n_heads=2, causal=True))
            .layer(RnnOutputLayer(n_out=LM_V, loss="mcxent"))
            .set_input_type(InputType.recurrent(LM_V, LM_CAP)).build())
    return MultiLayerNetwork(conf).init()


# ---------------------------------------------------------------------------
# satellite: ParallelInference backpressure semantics
# ---------------------------------------------------------------------------

class TestParallelInferenceBackpressure:
    def test_queue_full_fails_fast(self):
        from deeplearning4j_tpu.parallel.inference import (
            ParallelInference, QueueFullError as PIQueueFull)
        assert PIQueueFull is QueueFullError   # one typed error
        model = EchoModel(delay=0.2)
        pi = ParallelInference(model, max_batch_size=2, queue_limit=1,
                               wait_ms=1.0)

        def quiet_call():
            try:                     # shutdown may fail these; fine
                pi.output(np.ones((1, 4)))
            except RuntimeError:
                pass

        try:
            # head request occupies the collector inside the slow
            # model call; then fill the 1-deep queue and overflow it
            threading.Thread(target=quiet_call, daemon=True).start()
            time.sleep(0.05)
            filler = threading.Thread(target=quiet_call, daemon=True)
            filler.start()
            time.sleep(0.05)
            t0 = time.monotonic()
            with pytest.raises(QueueFullError):
                pi.output(np.ones((1, 4)))
            # fail-FAST: no blocking until the queue drains
            assert time.monotonic() - t0 < 0.15
        finally:
            pi.shutdown()

    def test_per_item_error_propagation(self):
        """A poison request in a coalesced batch fails only its own
        caller; neighbours still get results."""
        model = PoisonModel()
        pi = None
        from deeplearning4j_tpu.parallel.inference import (
            ParallelInference)
        pi = ParallelInference(model, max_batch_size=8, queue_limit=16,
                               wait_ms=20.0)
        results, errors = {}, {}

        def call(i, x):
            try:
                results[i] = pi.output(x)
            except BaseException as e:
                errors[i] = e

        bad = np.full((1, 4), np.nan)
        good = [np.full((1, 4), float(i)) for i in range(4)]
        threads = [threading.Thread(target=call, args=(0, bad))]
        threads += [threading.Thread(target=call, args=(i + 1, g))
                    for i, g in enumerate(good)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        pi.shutdown()
        assert isinstance(errors[0], ValueError)
        for i in range(1, 5):
            np.testing.assert_array_equal(results[i],
                                          good[i - 1] * 2.0)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

class TestModelRegistry:
    def test_versioned_swap(self):
        reg = ModelRegistry()
        a, b = EchoModel(), EchoModel()
        assert reg.register("m", a) == 1
        assert reg.get("m") is a
        assert reg.register("m", b) == 2
        assert reg.get("m") is b            # swap-in: default moved
        assert reg.get("m", version=1) is a  # old version addressable
        reg.unregister("m", version=2)
        assert reg.get("m") is a             # swap-out: rolls back
        listing = reg.models()
        assert listing[0]["name"] == "m"
        assert listing[0]["serving_default"] == 1

    def test_not_found(self):
        reg = ModelRegistry()
        with pytest.raises(ModelNotFoundError):
            reg.get("nope")
        reg.register("m", EchoModel())
        with pytest.raises(ModelNotFoundError):
            reg.get("m", version=9)
        with pytest.raises(ModelNotFoundError):
            reg.unregister("nope")


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

class TestBatchScheduler:
    def test_mixed_shape_buckets(self):
        """N threads submit two incompatible trailing shapes at once:
        every response matches its own request, and every coalesced
        device call is shape-uniform."""
        model = EchoModel()
        s = BatchScheduler(model, max_batch_size=16, queue_limit=64,
                           wait_ms=10.0)
        results = {}

        def call(i):
            width = 3 if i % 2 == 0 else 5
            x = np.full((1, width), float(i), np.float32)
            results[i] = (x, s.predict(x))

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        s.shutdown()
        for i, (x, out) in results.items():
            np.testing.assert_array_equal(out, x * 2.0)
        assert len(results) == 24
        # pow2-padded, shape-uniform batches only
        assert all(shape[0] in (1, 2, 4, 8, 16)
                   and shape[1] in (3, 5) for shape in model.shapes)
        # under simultaneous load the batcher actually coalesced
        assert any(shape[0] > 1 for shape in model.shapes)

    def test_multi_row_requests_respect_max_batch(self):
        """Two 20-row requests under max_batch_size=32 must not
        coalesce into one 40-row (pow2 -> 64) device call."""
        model = EchoModel()
        s = BatchScheduler(model, max_batch_size=32, queue_limit=64,
                           wait_ms=20.0)
        rs = [s.submit(np.full((20, 4), float(i), np.float32))
              for i in range(2)]
        outs = [s.wait(r) for r in rs]
        s.shutdown()
        for i, o in enumerate(outs):
            np.testing.assert_array_equal(o, np.full((20, 4), 2.0 * i))
        assert all(shape[0] <= 32 for shape in model.shapes)

    def test_submit_after_shutdown_never_hangs(self):
        s = BatchScheduler(EchoModel(), max_batch_size=4,
                           queue_limit=16, wait_ms=1.0)
        s.shutdown()
        with pytest.raises(ServerClosedError):
            s.predict(np.ones((1, 4), np.float32))

    def test_deadline_expiry_mid_queue(self):
        """A request whose deadline lapses while an earlier batch
        hogs the model gets DeadlineExceededError, not service."""
        model = EchoModel(delay=0.3)
        s = BatchScheduler(model, max_batch_size=4, queue_limit=16,
                           wait_ms=1.0)
        first = s.submit(np.ones((1, 4), np.float32))
        time.sleep(0.05)              # collector is inside the sleep
        doomed = s.submit(np.ones((1, 4), np.float32), timeout=0.05)
        with pytest.raises(DeadlineExceededError):
            s.wait(doomed)
        np.testing.assert_array_equal(s.wait(first), np.ones((1, 4)) * 2)
        assert s.metrics.endpoint("predict").expired >= 1
        s.shutdown()

    def test_load_shed_under_saturation(self):
        model = EchoModel(delay=0.2)
        s = BatchScheduler(model, max_batch_size=2, queue_limit=2,
                           wait_ms=1.0, name="predict")
        held = [s.submit(np.ones((1, 4), np.float32))]
        time.sleep(0.05)              # head request occupies the model
        shed = 0
        for _ in range(8):
            try:
                held.append(s.submit(np.ones((1, 4), np.float32)))
            except QueueFullError:
                shed += 1
        assert shed >= 1              # saturation rejected, not blocked
        snap = s.metrics.snapshot()
        assert snap["endpoints"]["predict"]["shed"] == shed
        for r in held:                # admitted work still completes
            np.testing.assert_array_equal(s.wait(r),
                                          np.ones((1, 4)) * 2)
        s.shutdown()

    def test_graceful_drain_completes_in_flight(self):
        model = EchoModel(delay=0.05)
        s = BatchScheduler(model, max_batch_size=4, queue_limit=64,
                           wait_ms=5.0)
        handles = [s.submit(np.full((1, 4), float(i), np.float32))
                   for i in range(12)]
        assert s.drain(timeout=10.0)
        with pytest.raises(ServerClosedError):
            s.submit(np.ones((1, 4), np.float32))
        for i, r in enumerate(handles):
            np.testing.assert_array_equal(s.wait(r),
                                          np.full((1, 4), 2.0 * i))

    def test_real_model_batched_equals_direct(self):
        net = _mlp()
        s = BatchScheduler(net, max_batch_size=8, wait_ms=5.0)
        rng = np.random.default_rng(0)
        xs = rng.normal(0, 1, (10, 1, 4)).astype(np.float32)
        direct = [np.asarray(net.output(x)) for x in xs]
        results = {}

        def call(i):
            results[i] = s.predict(xs[i])

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(10)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        s.shutdown()
        _assert_each_caller_got_its_own_row(
            [results[i] for i in range(10)], direct)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

class TestServingMetrics:
    def test_histogram_quantiles(self):
        from deeplearning4j_tpu.serving.metrics import LatencyHistogram
        h = LatencyHistogram()
        for ms in range(1, 101):      # 1..100 ms uniform
            h.record(ms / 1e3)
        snap = h.snapshot()
        assert snap["count"] == 100
        # log-bucketed interpolation: coarse but ordered and in-range
        assert 0 < snap["p50_ms"] < snap["p95_ms"] <= snap["p99_ms"]
        assert 25 <= snap["p50_ms"] <= 80
        assert snap["p99_ms"] <= 160

    def test_publish_to_stats_storage(self):
        from deeplearning4j_tpu.ui.stats import InMemoryStatsStorage
        m = ServingMetrics()
        ep = m.endpoint("predict")
        ep.observe(0.004)
        ep.observe(0.006)
        storage = InMemoryStatsStorage()
        m.publish_to(storage, session_id="serving")
        m.publish_to(storage, session_id="serving")
        ups = storage.get_all_updates("serving")
        assert len(ups) == 2
        assert ups[-1].iteration == 2
        assert ups[-1].score == 2.0          # request count
        assert ups[-1].duration_ms > 0       # p50 latency


# ---------------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------------

class TestContinuousBatching:
    def test_slot_reuse_parity_vs_sequential(self):
        """More requests than slots, submitted all at once: slot
        recycling + mid-flight admission must produce exactly the ids
        a sequential one-at-a-time decode of the same prompts does."""
        net = _lm()
        prompts = [np.array([1, 2, 3]), np.array([4, 5]),
                   np.array([6]), np.array([7, 8, 9, 10]),
                   np.array([2, 9]), np.array([3])]
        cb = ContinuousBatcher(net, slots=2, capacity=LM_CAP,
                               queue_limit=16)
        handles = [cb.submit(p, 6) for p in prompts]
        got = [cb.wait(h) for h in handles]
        occupancy = cb.metrics.snapshot()["batching"]["generate"]
        assert cb.drain()
        seq = ContinuousBatcher(net, slots=2, capacity=LM_CAP)
        ref = [seq.generate(p, 6) for p in prompts]
        assert seq.drain()
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(a, b)
        # 6 requests through 2 slots forced reuse, and slots overlapped
        assert occupancy["avg_batch_size"] > 1
        assert occupancy["max_batch_size_seen"] == 2

    def test_matches_streaming_session_generate(self):
        """Greedy continuous-batched decode == the in-process
        session.generate contract for the same prompt."""
        net = _lm()
        sess = net.streaming_session(capacity=LM_CAP, batch=1)
        ref = np.asarray(sess.generate(
            np.array([[1, 2, 3]], np.float32), 5))[0]
        cb = ContinuousBatcher(net, slots=3, capacity=LM_CAP)
        got = cb.generate(np.array([1, 2, 3]), 5)
        assert cb.drain()
        np.testing.assert_array_equal(got, ref)

    def test_admission_control(self):
        net = _lm()
        cb = ContinuousBatcher(net, slots=1, capacity=LM_CAP,
                               queue_limit=2)
        with pytest.raises(ValueError):
            cb.submit(np.arange(1, 5), LM_CAP)   # over capacity
        with pytest.raises(ValueError):
            cb.submit(np.array([]), 4)           # empty prompt
        with pytest.raises(ValueError):
            cb.submit(np.array([1]), 0)          # zero tokens
        with pytest.raises(ValueError):
            cb.submit(np.array([1]), -3)         # negative tokens
        cb.generate(np.array([1, 2]), 2)         # warm the compile
        # occupy the single slot with a long decode, then saturate the
        # 2-deep queue: at least one of the burst must be shed
        handles = [cb.submit(np.array([1, 2]), LM_CAP - 2)]
        lengths = [LM_CAP - 2]
        shed = 0
        for _ in range(8):
            try:
                handles.append(cb.submit(np.array([1, 2]), 4))
                lengths.append(4)
            except QueueFullError:
                shed += 1
        assert shed >= 1
        for h, n in zip(handles, lengths):       # admitted work lands
            assert len(cb.wait(h)) == n
        assert cb.drain()
        with pytest.raises(ServerClosedError):
            cb.submit(np.array([1]), 2)

    def test_deadline_expires_while_slots_busy(self):
        """A queued generate whose deadline lapses while every slot
        is occupied fails with DeadlineExceededError; the occupying
        request is unaffected."""
        net = _lm()
        cb = ContinuousBatcher(net, slots=1, capacity=LM_CAP)
        cb.generate(np.array([1, 2]), 2)          # warm the compile
        long = cb.submit(np.array([1, 2]), LM_CAP - 2)
        # already-lapsed deadline: on a fast host the warm LM can
        # finish `long`'s whole decode inside any small positive
        # timeout, racing the slot free against the expiry — the
        # invariant under test (expired while queued => never
        # served) must not depend on decode speed
        doomed = cb.submit(np.array([1, 2]), 4, timeout=-0.001)
        with pytest.raises(DeadlineExceededError):
            cb.wait(doomed)
        assert len(cb.wait(long)) == LM_CAP - 2
        assert cb.metrics.endpoint(cb.name).expired >= 1
        assert cb.drain()

    def test_reinit_states_recovers_session(self):
        """After a failed (donated) device step the batcher rebuilds
        the session carries: reinit must restore a bitwise-fresh
        session."""
        net = _lm()
        sess = net.slot_streaming_session(capacity=LM_CAP, slots=2)
        x = np.full((2, 1, 1), 3.0, np.float32)
        act = np.array([True, True])
        h1 = np.asarray(sess.step_slots(x, act))
        np.asarray(sess.step_slots(x, act))   # advance positions
        sess.reinit_states()
        assert (sess.slot_pos == 0).all()
        h2 = np.asarray(sess.step_slots(x, act))
        np.testing.assert_array_equal(h1, h2)

    def test_rejects_running_statistic_layers(self):
        from deeplearning4j_tpu.nn.conf.layers import GlobalPoolingLayer
        conf = (NeuralNetConfiguration.builder().set_seed(0)
                .updater(updaters.adam(1e-3)).list()
                .layer(EmbeddingSequenceLayer(n_in=LM_V, n_out=8))
                .layer(GlobalPoolingLayer())
                .layer(OutputLayer(n_out=3))
                .set_input_type(InputType.recurrent(LM_V, 8)).build())
        net = MultiLayerNetwork(conf).init()
        with pytest.raises(ValueError, match="running statistic"):
            net.slot_streaming_session(capacity=8, slots=2)


# ---------------------------------------------------------------------------
# satellite: deadline-expired work is NEVER served late
# ---------------------------------------------------------------------------

class RecordingModel(EchoModel):
    """Also records every batch's CONTENT, so a test can prove a
    payload never reached the device."""

    def __init__(self, delay=0.0):
        super().__init__(delay)
        self.batches = []

    def output(self, x):
        with self._lock:
            self.batches.append(np.array(x))
        return super().output(x)


@pytest.mark.chaos
class TestDeadlineNeverServedLate:
    def test_scheduler_expired_payload_never_reaches_device(self):
        model = RecordingModel(delay=0.25)
        s = BatchScheduler(model, max_batch_size=4, queue_limit=16,
                           wait_ms=1.0, name="predict")
        first = s.submit(np.ones((1, 4), np.float32))
        time.sleep(0.05)          # collector is inside the sleep
        doomed = s.submit(np.full((1, 4), 7.0, np.float32),
                          timeout=0.05)
        with pytest.raises(DeadlineExceededError):
            s.wait(doomed)
        np.testing.assert_array_equal(s.wait(first),
                                      np.ones((1, 4)) * 2)
        assert s.drain()
        # the expired payload (marker 7.0) was in no device call
        assert not any((b == 7.0).any() for b in model.batches)
        # and the expiry landed on the canonical counter
        c = s.metrics.registry.get("serving_deadline_expired_total",
                                   labels={"endpoint": "predict"})
        assert c is not None and c.value >= 1

    def test_batcher_expired_prompt_never_starts_decoding(self):
        net = _lm()
        cb = ContinuousBatcher(net, slots=1, capacity=LM_CAP,
                               name="generate")
        cb.generate(np.array([1, 2]), 2)          # warm the compile
        long = cb.submit(np.array([1, 2]), LM_CAP - 2)
        # lapsed-at-submit deadline (see
        # test_deadline_expires_while_slots_busy: the expiry must
        # not race the warm decode freeing the slot)
        doomed = cb.submit(np.array([3, 4]), 4, timeout=-0.001)
        with pytest.raises(DeadlineExceededError):
            cb.wait(doomed)
        assert len(cb.wait(long)) == LM_CAP - 2
        c = cb.metrics.registry.get("serving_deadline_expired_total",
                                    labels={"endpoint": "generate"})
        assert c is not None and c.value >= 1
        assert cb.drain()


# ---------------------------------------------------------------------------
# circuit breaker e2e: crash-looping backend opens, probes, closes
# ---------------------------------------------------------------------------

@pytest.mark.chaos
class TestCircuitBreakerE2E:
    @pytest.fixture(autouse=True)
    def _clean_injector(self):
        yield
        chaos.uninstall()

    def test_open_half_open_close(self):
        """Three injected worker crashes open the circuit within the
        window; admission sheds with CircuitOpenError; after the
        cooldown the half-open probe succeeds (faults exhausted) and
        the circuit closes."""
        chaos.install({"faults": [{"site": "serving.worker.step",
                                   "kind": "crash", "p": 1.0,
                                   "max_fires": 3}]}, seed=1)
        br = CircuitBreaker(failure_threshold=3, window_s=10.0,
                            cooldown_s=0.2, half_open_max=1)
        s = BatchScheduler(EchoModel(), max_batch_size=4,
                           queue_limit=16, wait_ms=1.0, breaker=br,
                           name="predict")
        for _ in range(3):
            with pytest.raises(chaos.SimulatedCrashError):
                s.predict(np.ones((1, 4), np.float32))
        # breaker trip happens on the worker thread; wait for it
        for _ in range(200):
            if br.state == "open":
                break
            time.sleep(0.005)
        assert br.state == "open"
        with pytest.raises(CircuitOpenError):
            s.submit(np.ones((1, 4), np.float32))
        crashes = s.metrics.registry.get(
            "serving_worker_crashes_total",
            labels={"endpoint": "predict"})
        assert crashes.value == 3
        time.sleep(0.25)                   # cooldown -> half-open
        # the restarted worker serves the probe; success closes
        out = s.predict(np.ones((1, 4), np.float32))
        np.testing.assert_array_equal(out, np.ones((1, 4)) * 2)
        assert br.state == "closed"
        # fully recovered: subsequent traffic flows
        out = s.predict(np.full((1, 4), 3.0, np.float32))
        np.testing.assert_array_equal(out, np.full((1, 4), 6.0))
        s.shutdown()

    def test_worker_crash_fails_only_inflight_batch(self):
        """One injected crash fails the in-flight waiters with the
        crash error, the restarted worker serves later traffic, and
        the circuit (threshold 3) never opens for a single crash."""
        chaos.install({"faults": [{"site": "serving.worker.step",
                                   "kind": "crash", "at": [1]}]},
                      seed=1)
        s = BatchScheduler(EchoModel(), max_batch_size=4,
                           queue_limit=16, wait_ms=1.0,
                           breaker=CircuitBreaker(failure_threshold=3),
                           name="predict")
        with pytest.raises(chaos.SimulatedCrashError):
            s.predict(np.ones((1, 4), np.float32))
        out = s.predict(np.full((1, 4), 2.0, np.float32))
        np.testing.assert_array_equal(out, np.full((1, 4), 4.0))
        assert s.breaker.state == "closed"
        s.shutdown()

    def test_batcher_crash_spares_pending_requests(self):
        """A worker crash fails only the streams mid-decode; an
        admitted-but-unslotted (pending) request survives and is
        served by the restarted loop."""
        chaos.install({"faults": [{"site": "serving.worker.step",
                                   "kind": "crash", "at": [3]}]},
                      seed=1)
        net = _lm()
        cb = ContinuousBatcher(
            net, slots=1, capacity=LM_CAP,
            breaker=CircuitBreaker(failure_threshold=5))
        first = cb.submit(np.array([1, 2, 3]), 4)   # crashes at hit 3
        second = cb.submit(np.array([4, 5]), 3)     # pending
        with pytest.raises(chaos.SimulatedCrashError):
            cb.wait(first)
        assert len(cb.wait(second)) == 3            # restarted loop
        assert cb.breaker.state == "closed"
        assert cb.drain()

    def test_poison_fault_fails_greedy_request_loudly(self):
        """A poisoned device step (NaN logits) must fail the affected
        greedy request with a typed per-slot error — never stream
        token 0 with a success status — and must not kill the
        worker: the next request decodes normally."""
        # prompt [1,2,3]: steps 1-2 prefill (outputs discarded), step
        # 3 samples the first token — poison THAT step
        chaos.install({"faults": [{"site": "serving.worker.step",
                                   "kind": "poison", "at": [3]}]},
                      seed=1)
        net = _lm()
        cb = ContinuousBatcher(net, slots=2, capacity=LM_CAP)
        with pytest.raises(ValueError, match="non-finite"):
            cb.generate(np.array([1, 2, 3]), 4)
        out = cb.generate(np.array([1, 2, 3]), 4)
        assert len(out) == 4
        assert cb.breaker.state == "closed"    # per-slot, not a crash
        assert cb.drain()

    def test_healthz_and_metrics_report_open_circuit(self):
        reg = ModelRegistry()
        reg.register("iris", _mlp())
        srv = ModelServer(reg, port=0, wait_ms=2.0).start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            body, code = _post(base, "/v1/predict",
                               {"model": "iris",
                                "inputs": [[1, 2, 3, 4]]})
            assert code == 200
            body, _ = _get(base, "/healthz")
            assert body["status"] == "ok"
            srv._schedulers[("iris", 1)].breaker.force_open()
            body, _ = _get(base, "/healthz")
            assert body["status"] == "degraded"
            assert body["circuits"] == {"predict/iris/v1": "open"}
            # the circuit_state gauge reaches Prometheus scrapers
            import urllib.request
            with urllib.request.urlopen(
                    base + "/metrics?format=prometheus") as resp:
                text = resp.read().decode()
            assert ('circuit_state{endpoint="predict/iris/v1"} 2'
                    in text)
            # an open circuit sheds over HTTP as 503
            _, code = _post(base, "/v1/predict",
                            {"model": "iris",
                             "inputs": [[1, 2, 3, 4]]})
            assert code == 503
        finally:
            srv.stop(drain=True, timeout=10.0)


# ---------------------------------------------------------------------------
# acceptance end-to-end: >=100 concurrent mixed predict + generate
# ---------------------------------------------------------------------------

class TestServingEndToEnd:
    def test_hundred_concurrent_mixed_requests(self):
        net = _mlp()
        lm = _lm()
        metrics = ServingMetrics()
        sched = BatchScheduler(net, max_batch_size=16, queue_limit=256,
                               wait_ms=5.0, metrics=metrics,
                               name="predict")
        cb = ContinuousBatcher(lm, slots=4, capacity=LM_CAP,
                               queue_limit=256, metrics=metrics,
                               name="generate")
        rng = np.random.default_rng(0)
        n_predict, n_generate = 64, 40
        xs = rng.normal(0, 1, (n_predict, 1, 4)).astype(np.float32)
        direct = [np.asarray(net.output(x)) for x in xs]
        prompts = [rng.integers(1, LM_V, size=rng.integers(1, 5))
                   for _ in range(n_generate)]
        seq_ref = ContinuousBatcher(lm, slots=4, capacity=LM_CAP)
        gen_ref = [seq_ref.generate(p, 5) for p in prompts]
        assert seq_ref.drain()

        results = {}
        errors = {}
        barrier = threading.Barrier(n_predict + n_generate)

        def predict(i):
            try:
                barrier.wait(timeout=30)
                results[("p", i)] = sched.predict(xs[i])
            except BaseException as e:
                errors[("p", i)] = e

        def generate(i):
            try:
                barrier.wait(timeout=30)
                results[("g", i)] = cb.generate(prompts[i], 5)
            except BaseException as e:
                errors[("g", i)] = e

        threads = ([threading.Thread(target=predict, args=(i,))
                    for i in range(n_predict)]
                   + [threading.Thread(target=generate, args=(i,))
                      for i in range(n_generate)])
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        # zero lost or duplicated responses
        assert len(results) == n_predict + n_generate
        # every caller got its own row of the direct model's outputs
        _assert_each_caller_got_its_own_row(
            [results[("p", i)] for i in range(n_predict)], direct)
        for i in range(n_generate):
            np.testing.assert_array_equal(results[("g", i)],
                                          gen_ref[i])
        # metrics: real coalescing happened on both paths
        snap = metrics.snapshot()
        assert snap["batching"]["predict"]["avg_batch_size"] > 1
        assert snap["batching"]["generate"]["avg_batch_size"] > 1
        assert snap["endpoints"]["predict"]["requests"] == n_predict
        assert snap["endpoints"]["generate"]["requests"] == n_generate
        assert sched.drain()
        assert cb.drain()


# ---------------------------------------------------------------------------
# HTTP front end
# ---------------------------------------------------------------------------

def _post(base, path, body):
    req = urllib.request.Request(
        base + path, json.dumps(body).encode(),
        {"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req) as resp:
            return json.loads(resp.read()), resp.status
    except urllib.error.HTTPError as e:
        return json.loads(e.read()), e.code


def _get(base, path):
    try:
        with urllib.request.urlopen(base + path) as resp:
            return json.loads(resp.read()), resp.status
    except urllib.error.HTTPError as e:
        return json.loads(e.read()), e.code


class TestModelServer:
    @pytest.fixture()
    def server(self):
        reg = ModelRegistry()
        reg.register("iris", _mlp())
        reg.register("lm", _lm())
        srv = ModelServer(reg, port=0, slots=2, capacity=LM_CAP,
                          wait_ms=2.0).start()
        yield srv
        srv.stop(drain=True, timeout=10.0)

    def test_endpoints(self, server):
        base = f"http://127.0.0.1:{server.port}"
        body, code = _get(base, "/healthz")
        assert (code, body["status"]) == (200, "ok")
        body, _ = _get(base, "/v1/models")
        assert {m["name"] for m in body["models"]} == {"iris", "lm"}
        x = [[0.1, 0.2, 0.3, 0.4]]
        body, code = _post(base, "/v1/predict",
                           {"model": "iris", "inputs": x})
        assert code == 200 and body["model_version"] == 1
        direct = np.asarray(server.registry.get("iris").output(
            np.asarray(x, np.float32)))
        np.testing.assert_array_equal(
            np.asarray(body["outputs"], np.float32),
            direct.astype(np.float32))
        body, code = _post(base, "/v1/generate",
                           {"model": "lm", "prompt": [1, 2, 3],
                            "n_tokens": 4})
        assert code == 200 and len(body["ids"]) == 4
        body, code = _get(base, "/metrics")
        assert code == 200
        assert body["endpoints"]["predict/iris/v1"]["requests"] == 1

    def test_error_mapping(self, server):
        base = f"http://127.0.0.1:{server.port}"
        _, code = _post(base, "/v1/predict",
                        {"model": "ghost", "inputs": [[1]]})
        assert code == 404
        _, code = _post(base, "/v1/predict", {"inputs": [[1]]})
        assert code == 400
        _, code = _post(base, "/v1/predict",
                        {"model": "iris", "version": 7,
                         "inputs": [[1, 2, 3, 4]]})
        assert code == 404
        _, code = _get(base, "/nope")
        assert code == 404
        _, code = _post(base, "/v1/generate",
                        {"model": "lm", "prompt": [1, 2],
                         "n_tokens": 0})
        assert code == 400

    def test_draining_returns_503(self, server):
        base = f"http://127.0.0.1:{server.port}"
        server._draining.set()
        body, code = _get(base, "/healthz")
        assert body["status"] == "draining"
        _, code = _post(base, "/v1/predict",
                        {"model": "iris", "inputs": [[1, 2, 3, 4]]})
        assert code == 503
        server._draining.clear()
        _, code = _post(base, "/v1/predict",
                        {"model": "iris", "inputs": [[1, 2, 3, 4]]})
        assert code == 200

    def test_version_swap_in(self, server):
        base = f"http://127.0.0.1:{server.port}"
        server.registry.register("iris", _mlp(seed=9))
        body, code = _post(base, "/v1/predict",
                           {"model": "iris",
                            "inputs": [[1, 2, 3, 4]]})
        assert code == 200 and body["model_version"] == 2
        body, code = _post(base, "/v1/predict",
                           {"model": "iris", "version": 1,
                            "inputs": [[1, 2, 3, 4]]})
        assert code == 200 and body["model_version"] == 1
        # swap-out releases the old version's collector thread AND
        # its /metrics gauge (a leaked gauge pins the backend+model)
        assert ("iris", 1) in server._schedulers
        assert server.evict_model("iris", version=1)
        assert ("iris", 1) not in server._schedulers
        assert ("iris", 2) in server._schedulers
        gauges = server.metrics.snapshot()["gauges"]
        assert "predict/iris/v1_queue_depth" not in gauges
        assert "predict/iris/v2_queue_depth" in gauges


# ---------------------------------------------------------------------------
# CLI wiring
# ---------------------------------------------------------------------------

class TestServeCLI:
    def test_serve_help_in_process(self, capsys):
        from deeplearning4j_tpu.cli import main
        with pytest.raises(SystemExit) as ei:
            main(["serve", "--help"])
        assert ei.value.code == 0
        out = capsys.readouterr().out
        for flag in ("--model", "--queue-limit", "--slots",
                     "--capacity", "--max-batch-size"):
            assert flag in out

    @pytest.mark.slow
    def test_serve_help_subprocess(self):
        import os
        import subprocess
        import sys
        r = subprocess.run(
            [sys.executable, "-m", "deeplearning4j_tpu", "serve",
             "--help"],
            capture_output=True, timeout=240,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert r.returncode == 0, r.stderr.decode()
        assert b"--queue-limit" in r.stdout


class TestWaitLeakGuard:
    """GL008 regression (ISSUE 14): a request the shutdown sweep
    never saw must not strand its caller in wait() forever — once
    the worker thread is gone, wait()'s heartbeat delivers the typed
    shutdown error itself."""

    def test_request_leaked_past_sweep_fails_typed(self):
        s = BatchScheduler(EchoModel(), max_batch_size=4,
                           queue_limit=8)
        s.shutdown(drain=False)      # worker exits; sweep has run
        from deeplearning4j_tpu.serving.lifecycle import BaseRequest
        r = BaseRequest(deadline=None)    # leaked: no sweep saw it
        t0 = time.monotonic()
        with pytest.raises(ServerClosedError) as ei:
            s.wait(r)
        # one heartbeat (~1s), not forever — and the 503 is priced
        assert time.monotonic() - t0 < 10.0
        assert ei.value.retry_after_s is not None

    def test_normal_completion_still_instant(self):
        s = BatchScheduler(EchoModel(), max_batch_size=4,
                           queue_limit=8, wait_ms=1.0)
        out = s.predict(np.ones((1, 3), np.float32))
        np.testing.assert_array_equal(
            out, 2.0 * np.ones((1, 3), np.float32))
        s.shutdown()

    def test_draining_503_carries_retry_hint(self):
        # GL010 regression: the admission-path ServerClosedError
        # ships a priced Retry-After hint
        s = BatchScheduler(EchoModel(), max_batch_size=4,
                           queue_limit=8)
        s.shutdown(drain=False)
        with pytest.raises(ServerClosedError) as ei:
            s.submit(np.ones((1, 3), np.float32))
        assert ei.value.retry_after_s is not None
