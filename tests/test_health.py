"""Training-health monitor, flight recorder, alerting (ISSUE 3).

Covers: the fused in-step finite check (trips within one step, ONE
device→host transfer per step, no recompile storm), warn/raise/
rollback policies (rollback restores the last finite checkpoint via
ElasticTrainer and continues), host-side sliding-window detectors,
flight-recorder bundles that load standalone, declarative alerts,
/healthz degradation, the UI health panel + hardened POST endpoints,
StatsReport round-trip goldens, CheckpointListener pruning, and the
stale-metric-name doc lint.
"""

import dataclasses
import json
import logging
import os
import sys
import types
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.data.iterators import ListDataSetIterator
from deeplearning4j_tpu.observability.alerts import (AlertManager,
                                                     AlertRule)
from deeplearning4j_tpu.observability.flight_recorder import (
    FlightRecorder, install, uninstall)
from deeplearning4j_tpu.observability.health import (
    BIT_LOSS, HealthMonitor, TrainingDivergedError)
from deeplearning4j_tpu.observability.registry import MetricsRegistry
from deeplearning4j_tpu.observability.tracing import Tracer
from deeplearning4j_tpu.train.fault_tolerance import ElasticTrainer
from deeplearning4j_tpu.train.listeners import (
    CheckpointListener, is_checkpoint_protected, protect_checkpoint,
    unprotect_checkpoint)
from deeplearning4j_tpu.ui.stats import (FileStatsStorage,
                                         InMemoryStatsStorage,
                                         StatsReport)

from fixtures import (make_batches, poison_batch, poison_params,
                      tiny_classifier)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _post(url, data: bytes, headers=None):
    req = urllib.request.Request(url, data=data,
                                 headers=headers or {},
                                 method="POST")
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.read().decode()


# ---------------------------------------------------------------------------
# fused device-plane monitor
# ---------------------------------------------------------------------------

class TestFusedHealthMonitor:
    def test_trips_within_one_step_of_poison(self):
        net = tiny_classifier()
        mon = HealthMonitor(policy="raise")
        net.add_listeners(mon)
        batches = poison_batch(make_batches(6), 3)
        with pytest.raises(TrainingDivergedError):
            net.fit(ListDataSetIterator(batches))
        # the poisoned batch is ordinal 3 → the monitor must trip at
        # iteration 3 exactly (within one step, not "eventually")
        assert mon.anomalies[-1]["kind"] == "non_finite"
        assert mon.anomalies[-1]["iteration"] == 3
        assert mon.tripped and mon.status()["status"] == "diverged"

    def test_one_transfer_per_step_no_recompile(self):
        """The acceptance contract: the fused check costs ONE fetch
        per step (counted by the monitor — it never walks leaves) and
        does not churn the jit cache (asserted by a raising compile
        observer listening around the live steps)."""
        from deeplearning4j_tpu.observability.compile_watch import (
            GlobalCompileStats)
        net = tiny_classifier()
        mon = HealthMonitor(policy="warn")
        net.add_listeners(mon)
        batches = make_batches(3)
        net.fit(ListDataSetIterator(batches))        # compile once
        assert net._health_enabled and net._last_health is not None
        more = make_batches(5, seed=1)
        stats = GlobalCompileStats(registry=MetricsRegistry(),
                                   storm_threshold=2,
                                   on_storm="raise").install()
        before, steps = mon.device_fetches, net.iteration_count
        try:
            net.fit(ListDataSetIterator(more), epochs=2)
        finally:
            stats.uninstall()
        # 10 more steps: all jit-cache hits, nothing traced, lowered
        # or compiled by any function
        assert net.iteration_count - steps == 10
        assert stats.by_function() == {}
        assert stats.summary()["backend_compiles"] == 0
        # exactly one health fetch per step
        assert mon.device_fetches - before == 10

    def test_warn_policy_continues(self, caplog):
        net = tiny_classifier()
        mon = HealthMonitor(policy="warn")
        net.add_listeners(mon)
        batches = poison_batch(make_batches(5), 1)
        with caplog.at_level(logging.WARNING, "deeplearning4j_tpu"):
            net.fit(ListDataSetIterator(batches))
        assert net.iteration_count == 5        # training went on
        assert any(a["kind"] == "non_finite" for a in mon.anomalies)
        assert any("non-finite" in r.message for r in caplog.records)
        assert mon.status()["status"] == "warning"

    def test_poisoned_params_trip(self):
        net = tiny_classifier()
        mon = HealthMonitor(policy="raise")
        net.add_listeners(mon)
        net.fit(ListDataSetIterator(make_batches(1)))
        poison_params(net, layer=0)
        with pytest.raises(TrainingDivergedError):
            net.fit(ListDataSetIterator(make_batches(1, seed=2)))
        assert mon.anomalies[-1]["kind"] == "non_finite"

    def test_no_monitor_means_no_health_outputs(self):
        net = tiny_classifier()
        net.fit(ListDataSetIterator(make_batches(2)))
        assert net._health_enabled is False
        assert net._last_health is None

    def test_graph_executor_trips(self):
        from deeplearning4j_tpu import (ComputationGraph,
                                        NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.conf import updaters
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                                       OutputLayer)
        g = (NeuralNetConfiguration.builder()
             .set_seed(0).updater(updaters.adam(0.01))
             .graph_builder()
             .add_inputs("in")
             .add_layer("d", DenseLayer(n_out=8, activation="relu"),
                        "in")
             .add_layer("out", OutputLayer(n_out=3), "d")
             .set_outputs("out")
             .set_input_types(InputType.feed_forward(4))
             .build())
        net = ComputationGraph(g).init()
        mon = HealthMonitor(policy="raise")
        net.add_listeners(mon)
        batches = poison_batch(make_batches(4), 2)
        with pytest.raises(TrainingDivergedError):
            net.fit(batches)
        assert mon.anomalies[-1]["iteration"] == 2


# ---------------------------------------------------------------------------
# host-plane sliding-window detectors
# ---------------------------------------------------------------------------

def _dummy_model(health_vec=None):
    m = types.SimpleNamespace()
    if health_vec is not None:
        m._last_health = np.asarray(health_vec, np.float32)
    return m


class TestHostDetectors:
    def test_loss_divergence_raises(self):
        mon = HealthMonitor(policy="raise", divergence_factor=4.0,
                            divergence_patience=3)
        m = _dummy_model()
        mon.iteration_done(m, 0, 1.0, 8)
        with pytest.raises(TrainingDivergedError) as ei:
            for i in range(1, 10):
                mon.iteration_done(m, i, 50.0, 8)
        assert ei.value.anomaly["kind"] == "loss_divergence"
        assert not ei.value.rollback

    def test_loss_plateau_warns(self, caplog):
        mon = HealthMonitor(policy="raise", plateau_window=5)
        m = _dummy_model()
        with caplog.at_level(logging.WARNING, "deeplearning4j_tpu"):
            for i in range(8):       # identical loss → zero span
                mon.iteration_done(m, i, 0.5, 8)
        assert any(a["kind"] == "loss_plateau" for a in mon.anomalies)
        # plateau is advisory: the hard policy did NOT apply
        assert not mon.tripped

    def test_grad_explosion_from_device_vector(self):
        mon = HealthMonitor(policy="raise", grad_explosion=100.0)
        m = _dummy_model([0.0, 0.5, 1e6, 0.1, 1.0])
        with pytest.raises(TrainingDivergedError) as ei:
            mon.iteration_done(m, 0, 0.5, 8)
        assert ei.value.anomaly["kind"] == "grad_explosion"

    def test_grad_vanish_warns_after_patience(self, caplog):
        mon = HealthMonitor(policy="raise", grad_vanish=1e-8,
                            vanish_patience=3)
        with caplog.at_level(logging.WARNING, "deeplearning4j_tpu"):
            for i in range(5):
                m = _dummy_model([0.0, 0.5, 1e-12, 0.1, 1.0])
                mon.iteration_done(m, i, 0.5, 8)
        assert any(a["kind"] == "grad_vanish" for a in mon.anomalies)

    def test_update_ratio_detector_and_stamping(self):
        inner = InMemoryStatsStorage()
        mon = HealthMonitor(policy="warn", ratio_patience=2,
                            storage=inner)
        # give the monitor device-plane context to stamp with
        mon.iteration_done(_dummy_model([0.0, 0.4, 2.5, 0.01, 7.0]),
                           0, 0.4, 8)
        for i in range(1, 4):
            r = StatsReport(session_id="s", worker_id="w",
                            iteration=i, timestamp=float(i),
                            score=0.4,
                            update_ratios={"0": 0.9})   # way over 1e-1
            mon.put_update(r)
        assert any(a["kind"] == "update_ratio" for a in mon.anomalies)
        # forwarded to the wrapped storage, stamped with health fields
        ups = inner.get_all_updates("s")
        assert len(ups) == 3
        assert ups[-1].gradient_norm == pytest.approx(2.5)
        assert ups[-1].param_norm == pytest.approx(7.0)
        assert ups[-1].health.get("finite_bits") == 0

    def test_fallback_without_fused_vector(self):
        mon = HealthMonitor(policy="raise")
        with pytest.raises(TrainingDivergedError) as ei:
            mon.iteration_done(_dummy_model(), 7, float("nan"), 8)
        assert ei.value.anomaly["value"] == BIT_LOSS

    def test_trip_heals_after_clean_steps(self):
        """A rolled-back-and-recovered run must not stay 'diverged'
        on the dashboard forever."""
        mon = HealthMonitor(policy="rollback", heal_after=5)
        m = _dummy_model()
        with pytest.raises(TrainingDivergedError):
            mon.iteration_done(m, 0, float("nan"), 8)
        assert mon.status()["status"] == "diverged"
        for i in range(1, 4):
            mon.iteration_done(m, i, 0.5, 8)
        assert mon.status()["status"] == "diverged"   # not yet healed
        for i in range(4, 8):
            mon.iteration_done(m, i, 0.5, 8)
        assert mon.status()["status"] == "ok"
        assert mon.status()["anomaly_count"] == 1     # history kept

    def test_dead_activation_detector(self):
        net = tiny_classifier()
        mon = HealthMonitor(policy="warn", check_activations_every=1,
                            dead_threshold=0.5)
        net.add_listeners(mon)
        net.fit(ListDataSetIterator(make_batches(2)))
        # kill the hidden layer: ReLU of large negative bias is 0
        import jax.numpy as jnp
        p = net.params[0]
        p["b"] = jnp.full_like(p["b"], -100.0)
        p["W"] = jnp.zeros_like(p["W"])
        net.fit(ListDataSetIterator(make_batches(2, seed=3)))
        assert any(a["kind"] == "dead_activations"
                   for a in mon.anomalies)
        assert mon.last["dead_fraction"]["0"] == 1.0


# ---------------------------------------------------------------------------
# rollback policy through ElasticTrainer
# ---------------------------------------------------------------------------

class TestRollbackPolicy:
    def test_rollback_restores_and_continues(self, tmp_path):
        net = tiny_classifier()
        mon = HealthMonitor(policy="rollback")
        net.add_listeners(mon)
        batches = poison_batch(make_batches(8), 5)
        tr = ElasticTrainer(net, str(tmp_path), save_every=2,
                            keep=3, lr_drop_on_rollback=0.5)
        tr.fit(batches, epochs=1)
        assert tr.total_rollbacks == 1
        assert (0, 5) in tr._skip            # poison batch skipped
        # restored + continued: every param finite, epoch completed
        assert all(np.isfinite(np.asarray(p)).all()
                   for lp in net.params for p in lp.values())
        assert tr._epoch == 1
        # 8 batches, 1 skipped → 7 trained iterations
        assert net.iteration_count == 7
        # the optional LR drop applied
        assert net.conf.conf.updater_cfg["lr"] == pytest.approx(0.005)

    def test_raise_policy_propagates_out_of_trainer(self, tmp_path):
        net = tiny_classifier()
        net.add_listeners(HealthMonitor(policy="raise"))
        batches = poison_batch(make_batches(4), 1)
        tr = ElasticTrainer(net, str(tmp_path), save_every=2)
        with pytest.raises(TrainingDivergedError):
            tr.fit(batches, epochs=1)

    def test_trainer_checkpoints_are_protected(self, tmp_path):
        net = tiny_classifier()
        tr = ElasticTrainer(net, str(tmp_path), save_every=2)
        tr.fit(make_batches(4), epochs=1)
        latest = tr.latest_checkpoint()
        assert latest is not None
        assert is_checkpoint_protected(latest)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_ring_is_bounded(self):
        rec = FlightRecorder(capacity=10, capture_spans=False)
        for i in range(100):
            rec.record("tick", i=i)
        evs = rec.events()
        assert len(evs) == 10
        assert evs[-1]["i"] == 99 and evs[0]["i"] == 90
        assert rec.total_events == 100

    def test_bundle_loads_standalone(self, tmp_path):
        tracer = Tracer(enabled=True)
        rec = FlightRecorder(capacity=100, out_dir=str(tmp_path),
                             tracer=tracer, registry=MetricsRegistry())
        with tracer.span("train_step"):
            pass
        rec.record("anomaly", detector="test")
        rec.put_update(StatsReport(session_id="s", worker_id="w",
                                   iteration=1, timestamp=1.0,
                                   score=0.5))
        bundle = rec.dump("unit_test")
        assert bundle and os.path.isdir(bundle)
        # JSONL parses line by line
        with open(os.path.join(bundle, "events.jsonl")) as f:
            events = [json.loads(line) for line in f]
        kinds = {e["kind"] for e in events}
        assert {"span", "anomaly", "stats_report"} <= kinds
        # Chrome trace opens
        with open(os.path.join(bundle, "trace.json")) as f:
            tracedoc = json.load(f)
        assert any(e["name"] == "train_step"
                   for e in tracedoc["traceEvents"])
        # env snapshot present with device info
        with open(os.path.join(bundle, "env.json")) as f:
            env = json.load(f)
        assert "devices" in env and env["devices"]
        assert "python" in env
        with open(os.path.join(bundle, "MANIFEST.json")) as f:
            man = json.load(f)
        assert man["reason"] == "unit_test"
        assert "events.jsonl" in man["files"]

    def test_debounce(self, tmp_path):
        rec = FlightRecorder(out_dir=str(tmp_path),
                             capture_spans=False,
                             min_dump_interval_s=3600.0)
        assert rec.dump("a", force=False) is not None
        assert rec.dump("b", force=False) is None     # debounced
        assert rec.dump("c", force=True) is not None  # forced

    def test_aborted_fit_leaves_bundle(self, tmp_path):
        """The acceptance case: an aborted run leaves a standalone
        post-mortem bundle via the executors' crash hook."""
        rec = install(FlightRecorder(out_dir=str(tmp_path),
                                     capture_spans=False,
                                     min_dump_interval_s=0.0))
        try:
            net = tiny_classifier()

            class Bomb:
                def on_epoch_start(self, model):
                    pass

                def on_epoch_end(self, model):
                    pass

                def iteration_done(self, model, it, score, bs):
                    if it == 2:
                        raise RuntimeError("sim device fault")

            net.add_listeners(Bomb())
            with pytest.raises(RuntimeError, match="sim device"):
                net.fit(ListDataSetIterator(make_batches(5)))
        finally:
            uninstall()
        bundles = [d for d in os.listdir(tmp_path)
                   if d.startswith("postmortem-")]
        assert len(bundles) == 1
        bundle = os.path.join(tmp_path, bundles[0])
        with open(os.path.join(bundle, "events.jsonl")) as f:
            events = [json.loads(line) for line in f]
        exc = [e for e in events if e["kind"] == "exception"]
        assert exc and "sim device fault" in exc[0]["error"]
        assert exc[0]["iteration"] == 2
        assert any(e["kind"] == "metrics" for e in events)

    def test_monitor_feeds_recorder(self, tmp_path):
        rec = FlightRecorder(out_dir=str(tmp_path),
                             capture_spans=False,
                             min_dump_interval_s=0.0)
        mon = HealthMonitor(policy="warn", recorder=rec)
        batches = poison_batch(make_batches(3), 1)
        net = tiny_classifier()
        net.add_listeners(mon)
        net.fit(ListDataSetIterator(batches))
        anomalies = [e for e in rec.events()
                     if e["kind"] == "anomaly"]
        assert anomalies and anomalies[0]["iteration"] == 1
        # anomaly triggered a (debounced-at-0) dump
        assert rec.dumps


# ---------------------------------------------------------------------------
# alerts
# ---------------------------------------------------------------------------

class TestAlerts:
    def _manager(self, rules, t0=0.0):
        reg = MetricsRegistry()
        clock = {"t": t0}
        am = AlertManager(reg, rules=rules,
                          clock=lambda: clock["t"])
        return reg, am, clock

    def test_gauge_rule_fires_and_resolves(self):
        reg, am, clock = self._manager(
            [AlertRule(name="deep_queue", metric="q_depth",
                       threshold=5.0)])
        g = reg.gauge("q_depth")
        g.set(2.0)
        assert am.evaluate() == [] and am.firing() == []
        g.set(9.0)
        changes = am.evaluate()
        assert [c["event"] for c in changes] == ["fire"]
        assert am.firing()[0]["name"] == "deep_queue"
        assert am.firing()[0]["value"] == 9.0
        g.set(1.0)
        changes = am.evaluate()
        assert [c["event"] for c in changes] == ["resolve"]
        assert am.firing() == []

    def test_for_duration_semantics(self):
        reg, am, clock = self._manager(
            [AlertRule(name="slow", metric="g", threshold=1.0,
                       for_seconds=10.0)])
        reg.gauge("g").set(5.0)
        assert am.evaluate() == []          # pending, not firing
        clock["t"] = 5.0
        assert am.evaluate() == []
        clock["t"] = 11.0
        assert [c["event"] for c in am.evaluate()] == ["fire"]

    def test_blip_resets_for_duration(self):
        reg, am, clock = self._manager(
            [AlertRule(name="slow", metric="g", threshold=1.0,
                       for_seconds=10.0)])
        g = reg.gauge("g")
        g.set(5.0)
        am.evaluate()
        clock["t"] = 8.0
        g.set(0.0)
        am.evaluate()                        # condition broke
        g.set(5.0)
        clock["t"] = 12.0
        assert am.evaluate() == []           # pending restarted at 12
        clock["t"] = 23.0
        assert [c["event"] for c in am.evaluate()] == ["fire"]

    def test_debounce_suppresses_refire(self):
        reg, am, clock = self._manager(
            [AlertRule(name="flappy", metric="g", threshold=1.0,
                       debounce_seconds=30.0)])
        g = reg.gauge("g")
        g.set(5.0)
        assert [c["event"] for c in am.evaluate()] == ["fire"]
        g.set(0.0)
        clock["t"] = 1.0
        am.evaluate()                        # resolve at t=1
        g.set(5.0)
        clock["t"] = 10.0
        assert am.evaluate() == []           # inside debounce window
        clock["t"] = 40.0
        assert [c["event"] for c in am.evaluate()] == ["fire"]

    def test_histogram_quantile_rule(self):
        reg, am, clock = self._manager(
            [AlertRule(name="p99_high", metric="lat",
                       threshold=0.5, quantile=0.99)])
        h = reg.histogram("lat", buckets=[0.1, 1.0, 10.0])
        for _ in range(100):
            h.record(5.0)                    # p99 ≈ 5s
        assert [c["event"] for c in am.evaluate()] == ["fire"]
        assert am.firing()[0]["value"] > 0.5

    def test_missing_metric_does_not_fire(self):
        _reg, am, _clock = self._manager(
            [AlertRule(name="ghost", metric="nope", threshold=1.0)])
        assert am.evaluate() == [] and am.firing() == []

    def test_callbacks_and_counter(self):
        fired = []
        reg = MetricsRegistry()
        am = AlertManager(reg, on_fire=fired.append)
        am.add_rule(AlertRule(name="r", metric="g", threshold=1.0))
        reg.gauge("g").set(2.0)
        am.evaluate()
        assert fired and fired[0]["name"] == "r"
        assert reg.get("alerts_fired_total").value == 1.0
        assert reg.get("alerts_firing").value() == 1.0

    def test_restart_cannot_orphan_previous_loop(self):
        # stop() then an immediate start() must not revive the OLD
        # evaluation loop: each generation owns its own stop event,
        # so the old loop's event stays set even after a restart
        # clears the way for a new one (a shared event that start()
        # cleared could be cleared before the old loop observed it)
        import threading as _t
        am = AlertManager(MetricsRegistry())
        am.start(interval_s=30.0)
        t1, e1 = am._thread, am._stop
        am.stop()
        assert not t1.is_alive()
        am.start(interval_s=30.0)
        try:
            assert am._stop is not e1 and e1.is_set()
            assert isinstance(am._thread, _t.Thread)
            assert am._thread is not t1 and am._thread.is_alive()
        finally:
            am.stop()
        assert am._thread is None

    def test_bad_rule_rejected(self):
        with pytest.raises(ValueError):
            AlertRule(name="x", metric="m", threshold=1.0, op="~")
        with pytest.raises(ValueError):
            AlertRule(name="x", metric="m", threshold=1.0,
                      quantile=2.0)


# ---------------------------------------------------------------------------
# /healthz degradation (live server)
# ---------------------------------------------------------------------------

class TestHealthzDegraded:
    def test_healthz_flips_degraded_under_firing_alert(self):
        from deeplearning4j_tpu.serving.http import ModelServer
        from deeplearning4j_tpu.serving.metrics import ServingMetrics
        metrics = ServingMetrics()
        am = AlertManager(metrics.registry, rules=[
            AlertRule(name="queue_backlog", metric="backlog",
                      threshold=100.0, severity="critical",
                      description="admission queue too deep")])
        server = ModelServer(metrics=metrics, alerts=am).start()
        try:
            base = f"http://127.0.0.1:{server.port}"
            _, body = _get(base + "/healthz")
            assert json.loads(body)["status"] == "ok"
            # blow the metric up → next probe reports degraded
            metrics.registry.gauge("backlog").set(500.0)
            _, body = _get(base + "/healthz")
            doc = json.loads(body)
            assert doc["status"] == "degraded"
            assert doc["alerts"][0]["name"] == "queue_backlog"
            assert doc["alerts"][0]["severity"] == "critical"
            # recovery flips it back
            metrics.registry.gauge("backlog").set(0.0)
            _, body = _get(base + "/healthz")
            assert json.loads(body)["status"] == "ok"
        finally:
            server.stop(drain=False)


# ---------------------------------------------------------------------------
# UI server: health panel + hardened endpoints
# ---------------------------------------------------------------------------

class TestUIServerHealthAndHardening:
    def _server(self, **kw):
        from deeplearning4j_tpu.ui.server import UIServer
        s = UIServer(port=0, **kw)
        s.start()
        return s

    def test_remote_post_roundtrips_health_fields(self):
        s = self._server()
        try:
            base = f"http://127.0.0.1:{s.port}"
            report = StatsReport(
                session_id="s1", worker_id="w0", iteration=3,
                timestamp=1.5, score=0.25, gradient_norm=2.5,
                update_norm=0.01, param_norm=9.0,
                health={"finite_bits": 0})
            code, doc = _post(base + "/api/remote",
                              report.to_json().encode())
            assert code == 200 and doc == {"ok": True}
            _, body = _get(base + "/api/updates?session=s1")
            ups = json.loads(body)
            assert ups[0]["gradient_norm"] == 2.5
            assert ups[0]["health"] == {"finite_bits": 0}
        finally:
            s.stop()

    def test_malformed_post_is_400_json(self):
        s = self._server()
        try:
            base = f"http://127.0.0.1:{s.port}"
            code, doc = _post(base + "/api/remote", b"{not json!")
            assert code == 400 and "bad request" in doc["error"]
            # missing required StatsReport fields → still a 400
            code, doc = _post(base + "/api/remote", b'{"score": 1}')
            assert code == 400
            # non-object tsne payload → 400
            code, doc = _post(base + "/api/tsne", b"[1, 2, 3]")
            assert code == 400
        finally:
            s.stop()

    def test_oversized_post_is_400_with_bound(self):
        s = self._server(max_body_bytes=64)
        try:
            base = f"http://127.0.0.1:{s.port}"
            payload = b'{"x": "' + b"a" * 500 + b'"}'
            code, doc = _post(base + "/api/remote", payload)
            assert code == 400
            assert "too large" in doc["error"]
        finally:
            s.stop()

    def test_api_health_panel(self):
        s = self._server()
        try:
            reg = MetricsRegistry()
            am = AlertManager(reg, rules=[
                AlertRule(name="loss_stuck", metric="g",
                          threshold=1.0)])
            mon = HealthMonitor(policy="warn")
            # trip one advisory anomaly
            mon.iteration_done(_dummy_model(), 4, float("nan"), 8)
            s.attach_health(monitor=mon, alerts=am)
            base = f"http://127.0.0.1:{s.port}"
            _, body = _get(base + "/api/health")
            doc = json.loads(body)
            assert doc["status"] == "degraded"     # warning-level
            assert doc["monitor"]["anomaly_count"] == 1
            reg.gauge("g").set(5.0)
            _, body = _get(base + "/api/health")
            doc = json.loads(body)
            assert doc["alerts"][0]["name"] == "loss_stuck"
            # the dashboard page carries the panel
            _, page = _get(base + "/")
            assert "Training health" in page
            assert "/api/health" in page
        finally:
            s.stop()

    def test_api_health_empty_is_ok(self):
        s = self._server()
        try:
            _, body = _get(f"http://127.0.0.1:{s.port}/api/health")
            doc = json.loads(body)
            assert doc == {"status": "ok", "alerts": [],
                           "monitor": None}
        finally:
            s.stop()


# ---------------------------------------------------------------------------
# StatsReport round-trip golden
# ---------------------------------------------------------------------------

class TestStatsReportRoundTrip:
    # every field with a non-default sentinel; the coverage assert
    # below makes adding a StatsReport field without updating this
    # golden a test failure (that is how fields stop being silently
    # dropped)
    _GOLDEN = dict(
        session_id="sess", worker_id="w7", iteration=42,
        timestamp=123.25, score=0.625,
        param_mean_magnitudes={"0_W": 0.5},
        gradient_mean_magnitudes={"0_W": 0.25},
        update_mean_magnitudes={"0": 0.125},
        update_ratios={"0": 1e-3},
        learning_rate=0.01,
        histograms={"param/0_W": {"min": -1.0, "max": 1.0,
                                  "counts": [1, 2, 3]}},
        activation_images={"conv0": "aGVsbG8="},
        duration_ms=12.5, samples_per_sec=800.0,
        memory_bytes=1024,
        profile={"data_wait_ms": 1.5, "mfu": 0.42},
        gradient_norm=3.5, update_norm=0.007, param_norm=11.0,
        health={"finite_bits": 0, "worst_dead_fraction": 0.125},
    )

    def test_golden_covers_every_field(self):
        assert set(self._GOLDEN) == {
            f.name for f in dataclasses.fields(StatsReport)}

    def test_file_storage_roundtrips_every_field(self, tmp_path):
        path = str(tmp_path / "stats.jsonl")
        FileStatsStorage(path).put_update(StatsReport(**self._GOLDEN))
        loaded = FileStatsStorage(path).get_latest_update("sess")
        assert dataclasses.asdict(loaded) == \
            dataclasses.asdict(StatsReport(**self._GOLDEN))

    def test_from_json_tolerates_unknown_fields(self):
        d = dict(self._GOLDEN)
        d["some_future_field"] = {"x": 1}
        r = StatsReport.from_json(json.dumps(d))
        assert r.iteration == 42 and r.health["finite_bits"] == 0

    def test_from_json_rejects_non_object(self):
        with pytest.raises(ValueError):
            StatsReport.from_json("[1, 2]")


# ---------------------------------------------------------------------------
# CheckpointListener pruning
# ---------------------------------------------------------------------------

class TestCheckpointPruning:
    def test_keep_last_prunes_oldest(self, tmp_path):
        net = tiny_classifier()
        lst = CheckpointListener(str(tmp_path),
                                 save_every_n_iterations=1,
                                 keep_last=2)
        for it in range(1, 6):
            lst.iteration_done(net, it, 0.5, 8)
        files = sorted(os.listdir(tmp_path))
        assert files == ["checkpoint_4.zip", "checkpoint_5.zip"]

    def test_protected_checkpoint_survives_pruning(self, tmp_path):
        net = tiny_classifier()
        lst = CheckpointListener(str(tmp_path),
                                 save_every_n_iterations=1,
                                 keep_last=1)
        lst.iteration_done(net, 1, 0.5, 8)
        protected = os.path.join(str(tmp_path), "checkpoint_1.zip")
        protect_checkpoint(protected)
        try:
            for it in range(2, 5):
                lst.iteration_done(net, it, 0.5, 8)
            files = sorted(os.listdir(tmp_path))
            # the protected file survived; the unprotected middle
            # ones were pruned down to keep_last
            assert "checkpoint_1.zip" in files
            assert files == ["checkpoint_1.zip", "checkpoint_4.zip"]
        finally:
            unprotect_checkpoint(protected)


# ---------------------------------------------------------------------------
# stale-metric-name lint
# ---------------------------------------------------------------------------

class TestMetricNameLint:
    def _mod(self):
        # ported to graftlint rule GL005 (ISSUE 6); the
        # check_perf_claims.py shim keeps the same API and is covered
        # in tests/test_graftlint.py
        sys.path.insert(0, REPO)
        try:
            from tools.graftlint.rules import gl005_literal_drift
        finally:
            sys.path.pop(0)
        return gl005_literal_drift

    def _fake_repo(self, tmp_path, doc_text):
        pkg = tmp_path / "deeplearning4j_tpu"
        pkg.mkdir()
        (pkg / "m.py").write_text(
            'C = registry.counter("foo_requests_total")\n'
            'G = metrics.register_gauge(f"{name}_queue_depth", fn)\n')
        (tmp_path / "BENCH_DETAIL.json").write_text("{}")
        (tmp_path / "README.md").write_text(doc_text)
        return str(tmp_path)

    def test_cited_existing_metric_passes(self, tmp_path):
        mod = self._mod()
        repo = self._fake_repo(
            tmp_path, "alert on `foo_requests_total` and "
                      "`predict_v1_queue_depth`.\n")
        assert mod.check(repo) == []

    def test_stale_metric_fails(self, tmp_path):
        mod = self._mod()
        repo = self._fake_repo(
            tmp_path, "alert on `foo_requests_total` and the "
                      "renamed `bar_bogus_total`.\n")
        errors = mod.check(repo)
        assert len(errors) == 1 and "bar_bogus_total" in errors[0]

    def test_committed_docs_have_no_stale_metrics(self):
        mod = self._mod()
        assert mod.check_metric_names(REPO) == []


# ---------------------------------------------------------------------------
# CLI surface
# ---------------------------------------------------------------------------

class TestCLI:
    def test_help_mentions_new_flags(self, capsys):
        from deeplearning4j_tpu.cli import main
        with pytest.raises(SystemExit):
            main(["--help"])
        assert "--flight-record" in capsys.readouterr().out
        with pytest.raises(SystemExit):
            main(["train", "--help"])
        out = capsys.readouterr().out
        assert "--health" in out and "rollback" in out

    def test_flight_record_bundle_on_cli_run(self, tmp_path,
                                             capsys):
        from deeplearning4j_tpu.cli import main
        from deeplearning4j_tpu.observability import flight_recorder
        from deeplearning4j_tpu.util.model_serializer import (
            write_model)
        mpath = str(tmp_path / "m.zip")
        write_model(tiny_classifier(), mpath)
        out_dir = str(tmp_path / "fr")
        os.makedirs(out_dir)
        try:
            main(["--flight-record", out_dir, "summary",
                  "--model", mpath])
        finally:
            flight_recorder.uninstall()
            from deeplearning4j_tpu.observability.tracing import (
                trace)
            trace.disable()
            trace.clear()
        bundles = [d for d in os.listdir(out_dir)
                   if d.startswith("postmortem-")]
        assert len(bundles) == 1
        with open(os.path.join(out_dir, bundles[0],
                               "MANIFEST.json")) as f:
            assert json.load(f)["reason"] == "exit"
