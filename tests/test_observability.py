"""Observability subsystem: tracing, registry, recompile watchdog,
step profiler, perf-claims lint.

The ISSUE-2 acceptance surface: a test induces a recompile storm and
the watchdog reports it with shapes; Chrome-trace export round-trips
(valid JSON, nested spans, monotonic ts); Prometheus exposition is
golden-tested; the disabled tracer's span path allocates nothing; the
committed docs pass the N.Nx-claims lint.
"""

import json
import logging
import os
import sys
import threading
import time
import tracemalloc

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

class TestTracing:
    def _tracer(self):
        from deeplearning4j_tpu.observability.tracing import Tracer
        return Tracer(enabled=True)

    def test_nested_spans_recorded(self):
        t = self._tracer()
        with t.span("outer"):
            time.sleep(0.002)
            with t.span("inner", {"k": 7}):
                time.sleep(0.001)
        evs = {e["name"]: e for e in t.events()}
        assert set(evs) == {"outer", "inner"}
        assert evs["inner"]["depth"] == 1
        assert evs["outer"]["depth"] == 0
        assert evs["inner"]["args"] == {"k": 7}
        # child interval nests inside the parent's
        o, i = evs["outer"], evs["inner"]
        assert o["ts_us"] <= i["ts_us"]
        assert (i["ts_us"] + i["dur_us"]
                <= o["ts_us"] + o["dur_us"] + 1e-3)

    def test_chrome_trace_round_trip(self, tmp_path):
        t = self._tracer()
        for k in range(3):
            with t.span(f"step{k}"):
                with t.span("sub"):
                    pass
        path = str(tmp_path / "trace.json")
        n = t.export_chrome_trace(path)
        assert n == 6
        with open(path) as f:
            doc = json.load(f)          # valid JSON
        evs = doc["traceEvents"]
        assert all(e["ph"] == "X" for e in evs)
        assert all({"name", "pid", "tid", "ts", "dur"} <= set(e)
                   for e in evs)
        # ts monotonic per emission order within a thread for the
        # top-level steps
        steps = [e for e in evs if e["name"].startswith("step")]
        ts = [e["ts"] for e in steps]
        assert ts == sorted(ts)

    def test_jsonl_streaming(self, tmp_path):
        from deeplearning4j_tpu.observability.tracing import Tracer
        path = str(tmp_path / "spans.jsonl")
        t = Tracer()
        t.enable(jsonl_path=path)
        with t.span("a"):
            pass
        t.disable()
        lines = [json.loads(l) for l in open(path)]
        assert len(lines) == 1 and lines[0]["name"] == "a"

    def test_disabled_span_is_shared_noop(self):
        from deeplearning4j_tpu.observability.tracing import Tracer
        t = Tracer(enabled=False)
        s1, s2 = t.span("x"), t.span("y")
        assert s1 is s2                 # the no-op singleton
        with s1:
            pass
        assert t.events() == []

    def test_disabled_hot_path_allocates_nothing(self):
        """The fit loops call span() every iteration unconditionally;
        disabled tracing must not grow memory."""
        from deeplearning4j_tpu.observability.tracing import Tracer
        t = Tracer(enabled=False)
        for _ in range(100):            # warm any lazy caches
            with t.span("warm"):
                pass
        tracemalloc.start()
        base, _ = tracemalloc.get_traced_memory()
        for _ in range(5000):
            with t.span("hot"):
                pass
        cur, _ = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        assert cur - base < 512, (
            f"disabled span path retained {cur - base} bytes over "
            "5000 iterations")

    def test_thread_safety_and_buffer_limit(self):
        from deeplearning4j_tpu.observability.tracing import Tracer
        t = Tracer(enabled=True, buffer_limit=50)

        def worker():
            for _ in range(40):
                with t.span("w"):
                    pass

        threads = [threading.Thread(target=worker) for _ in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert len(t.events()) == 50
        assert t.dropped == 4 * 40 - 50


# ---------------------------------------------------------------------------
# metrics registry / prometheus
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_get_or_create_identity(self):
        from deeplearning4j_tpu.observability.registry import (
            MetricsRegistry)
        r = MetricsRegistry()
        a = r.counter("x_total", labels={"k": "v"})
        b = r.counter("x_total", labels={"k": "v"})
        c = r.counter("x_total", labels={"k": "w"})
        assert a is b and a is not c
        with pytest.raises(TypeError):
            r.gauge("x_total", labels={"k": "v"})

    def test_counter_monotonic(self):
        from deeplearning4j_tpu.observability.registry import (
            MetricsRegistry)
        c = MetricsRegistry().counter("c_total")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_histogram_quantiles(self):
        from deeplearning4j_tpu.observability.registry import (
            MetricsRegistry)
        h = MetricsRegistry().histogram("h", buckets=[1, 2, 4, 8])
        for v in (0.5, 1.5, 3, 3, 7):
            h.record(v)
        snap = h.snapshot()
        assert snap["count"] == 5
        assert snap["sum"] == pytest.approx(15.0)
        assert 1 < h.quantile(0.5) <= 4

    def test_prometheus_exposition_golden(self):
        """Exact exposition for a small fixed registry — the format a
        Prometheus scraper parses."""
        from deeplearning4j_tpu.observability.registry import (
            MetricsRegistry)
        r = MetricsRegistry()
        c = r.counter("requests_total", help="total requests",
                      labels={"endpoint": "predict"})
        c.inc(3)
        r.gauge("queue_depth", fn=lambda: 2)
        h = r.histogram("lat_seconds", buckets=[0.1, 1.0])
        h.record(0.05)
        h.record(0.5)
        h.record(5.0)
        assert r.prometheus_text() == (
            "# HELP requests_total total requests\n"
            "# TYPE requests_total counter\n"
            'requests_total{endpoint="predict"} 3\n'
            "# TYPE queue_depth gauge\n"
            "queue_depth 2\n"
            "# TYPE lat_seconds histogram\n"
            'lat_seconds_bucket{le="0.1"} 1\n'
            'lat_seconds_bucket{le="1"} 2\n'
            'lat_seconds_bucket{le="+Inf"} 3\n'
            "lat_seconds_sum 5.55\n"
            "lat_seconds_count 3\n")

    def test_label_escaping_and_name_sanitizing(self):
        from deeplearning4j_tpu.observability.registry import (
            MetricsRegistry)
        r = MetricsRegistry()
        r.gauge("serving_gauge",
                labels={"name": 'predict/iris/v1"x'}).set(1)
        text = r.prometheus_text()
        assert 'name="predict/iris/v1\\"x"' in text
        c = r.counter("bad name-with/chars")
        c.inc()
        assert "bad_name_with_chars 1" in r.prometheus_text()

    def test_dead_gauge_callback_skipped(self):
        from deeplearning4j_tpu.observability.registry import (
            MetricsRegistry)
        r = MetricsRegistry()
        r.gauge("dead", fn=lambda: 1 / 0)
        r.counter("ok_total").inc()
        text = r.prometheus_text()
        assert "ok_total 1" in text
        assert "\ndead " not in text


# ---------------------------------------------------------------------------
# recompile watchdog
# ---------------------------------------------------------------------------

@pytest.fixture
def listening():
    """A ``GlobalCompileStats`` of the test's own (registry, storm
    policy) listening to ``jax.monitoring`` for the test's length."""
    from deeplearning4j_tpu.observability.compile_watch import (
        GlobalCompileStats)
    from deeplearning4j_tpu.observability.registry import (
        MetricsRegistry)
    made = []

    def make(**policy):
        made.append(GlobalCompileStats(registry=MetricsRegistry(),
                                       **policy).install())
        return made[-1]

    yield make
    for stats in made:
        stats.uninstall()


class TestCompileWatch:
    def test_hit_miss_accounting(self, listening):
        """The per-function table: a call served from the jit's own
        executable cache fires nothing, a new shape is one more
        compile of that function."""
        import jax
        import jax.numpy as jnp
        stats = listening()

        def dbl(x):
            return x * 2

        f = jax.jit(dbl)
        f(jnp.ones(3))
        f(jnp.ones(3))
        f(jnp.ones(3))
        row = stats.by_function()["dbl"]
        assert row["compiles"] + row["loads"] == 1
        f(jnp.ones(5))                  # new shape: compile
        row = stats.by_function()["dbl"]
        assert row["compiles"] + row["loads"] == 2
        assert row["trace_s"] > 0 and row["lower_s"] > 0
        assert row["compile_s"] > 0
        s = stats.summary()
        assert s["backend_compiles"] >= 2
        assert s["cold_compiles"] + s["persistent_cache_hits"] == \
            s["backend_compiles"]

    def test_storm_tripwire_fires_on_shape_churn(self, listening):
        """The shape-churn bug class: a fresh batch shape every call
        recompiling forever. The trip-wire must fire out of the call
        that compiled AND name the function."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.observability.compile_watch import (
            RecompileStormError)
        listening(storm_threshold=4, storm_window_s=60.0,
                  on_storm="raise")

        def churny(x):
            return x + 1

        f = jax.jit(churny)
        with pytest.raises(RecompileStormError) as ei:
            for n in range(2, 40):
                # numpy's: ``jnp.ones`` compiles too, a shape a call
                f(np.ones(n, np.float32))   # every call a new shape
        msg = str(ei.value)
        assert "'churny'" in msg and "4 times" in msg
        assert len(ei.value.events) == 4

    def test_storm_warn_mode_does_not_raise(self, listening, caplog):
        import jax
        import jax.numpy as jnp
        stats = listening(storm_threshold=2, storm_window_s=60.0,
                          on_storm="warn")

        def warned(x):
            return x + 1

        f = jax.jit(warned)
        with caplog.at_level(logging.WARNING, "deeplearning4j_tpu"):
            for n in range(2, 8):
                f(np.ones(n, np.float32))
        row = stats.by_function()["warned"]
        assert row["compiles"] + row["loads"] == 6
        # a report every second compile, not one a compile
        assert sum("'warned' compiled 2 times" in r.message
                   for r in caplog.records) == 3

    def test_stable_shapes_never_trip(self, listening):
        import jax
        import jax.numpy as jnp
        stats = listening(storm_threshold=2, on_storm="raise")

        def stable(x):
            return x + 1

        f = jax.jit(stable)
        for _ in range(50):
            f(np.ones(4, np.float32))
        row = stats.by_function()["stable"]
        assert row["compiles"] + row["loads"] == 1

    def test_compiles_under_a_setup_span_are_expected(self, listening):
        """The storm rule leaves the set-up alone: what compiles
        under an open span of the stats' timeline does not count."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.observability.tracing import Tracer
        timeline = Tracer(enabled=True, annotate=False)
        stats = listening(timeline=timeline, storm_threshold=2,
                          on_storm="raise")

        def warming(x):
            return x - 1

        f = jax.jit(warming)
        with timeline.span("setup/warm_programs"):
            for n in range(2, 8):
                f(np.ones(n, np.float32))
        assert stats.by_function()["warming"]["compiles"] + \
            stats.by_function()["warming"]["loads"] == 6

    def test_global_stats_counts_backend_compiles(self):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.observability.compile_watch import (
            install_global_watch)
        stats = install_global_watch()
        before = stats.mark()
        # a fresh lambda with a fresh shape forces a real compile
        jax.jit(lambda x: x * 3.5 + 0.25)(jnp.ones(17))
        delta = stats.summary(since=before)
        assert delta["backend_compiles"] >= 1
        assert delta["compile_secs"] > 0


# ---------------------------------------------------------------------------
# step profiler
# ---------------------------------------------------------------------------

def _mlp():
    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                                   OutputLayer)
    conf = (NeuralNetConfiguration.builder().set_seed(0)
            .updater(updaters.adam(1e-2)).list()
            .layer(DenseLayer(n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=3, loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    return MultiLayerNetwork(conf).init()


class TestStepProfiler:
    def _fit(self, listener, iterations=9):
        from deeplearning4j_tpu.data.dataset import DataSet
        net = _mlp()
        net.set_listeners(listener)
        rng = np.random.default_rng(0)
        x = rng.normal(0, 1, (iterations * 8, 4)).astype("float32")
        y = np.eye(3, dtype="float32")[
            rng.integers(0, 3, iterations * 8)]
        net.fit(DataSet(x, y), batch_size=8)
        return net

    def test_decomposition_report(self):
        from deeplearning4j_tpu.observability.step_profile import (
            ProfilerListener)
        p = ProfilerListener(frequency=2, report=False)
        self._fit(p)
        assert p.reports, "profiler produced no reports"
        rep = p.reports[-1]
        assert {"steps_per_sec", "samples_per_sec", "step_ms",
                "data_wait_ms", "dispatch_ms",
                "device_fence_ms"} <= set(rep)
        assert rep["steps_per_sec"] > 0
        assert rep["samples_per_sec"] > 0
        assert rep["data_wait_ms"] >= 0
        assert rep["dispatch_ms"] > 0
        # phases cannot exceed the step wall they decompose
        assert rep["data_wait_ms"] + rep["dispatch_ms"] \
            <= rep["step_ms"] * 1.5

    def test_mfu_none_on_cpu(self):
        from deeplearning4j_tpu.observability.step_profile import (
            ProfilerListener)
        p = ProfilerListener(frequency=2, flops_per_sample=1e6,
                             report=False)
        self._fit(p)
        assert all(r["mfu"] is None for r in p.reports)

    def test_mfu_accounting(self):
        from deeplearning4j_tpu.observability.step_profile import (
            model_flops_utilization, peak_flops_for_kind)
        assert peak_flops_for_kind("TPU v5 lite chip") == 197e12
        assert peak_flops_for_kind("Zen CPU") is None
        mfu = model_flops_utilization(4.09e9, 1458.1, True, 197e12)
        assert mfu == pytest.approx(0.0908, abs=2e-3)
        assert model_flops_utilization(1, 1, True, None) is None

    def test_reports_flow_into_stats_storage(self):
        from deeplearning4j_tpu.observability.step_profile import (
            ProfilerListener)
        from deeplearning4j_tpu.ui.stats import InMemoryStatsStorage
        storage = InMemoryStatsStorage()
        p = ProfilerListener(frequency=2, storage=storage,
                             session_id="prof", report=False)
        self._fit(p)
        reports = storage.get_all_updates("prof")
        assert reports
        assert reports[-1].profile["dispatch_ms"] > 0
        assert reports[-1].samples_per_sec > 0

    def test_stats_report_profile_round_trips_json(self):
        from deeplearning4j_tpu.ui.stats import StatsReport
        r = StatsReport(session_id="s", worker_id="w", iteration=1,
                        timestamp=0.0, score=1.0,
                        profile={"dispatch_ms": 1.5})
        back = StatsReport.from_json(r.to_json())
        assert back.profile == {"dispatch_ms": 1.5}

    def test_fit_emits_spans_when_tracing(self):
        from deeplearning4j_tpu.data.dataset import DataSet
        from deeplearning4j_tpu.observability.tracing import trace
        net = _mlp()
        rng = np.random.default_rng(0)
        x = rng.normal(0, 1, (16, 4)).astype("float32")
        y = np.eye(3, dtype="float32")[rng.integers(0, 3, 16)]
        trace.clear()
        trace.enable()
        try:
            net.fit(DataSet(x, y), batch_size=8)
        finally:
            trace.disable()
        names = {e["name"] for e in trace.events()}
        assert {"epoch", "data_wait", "train_step",
                "listeners"} <= names

    def test_graph_fit_emits_spans_and_timing(self):
        from deeplearning4j_tpu import (ComputationGraph,
                                        NeuralNetConfiguration)
        from deeplearning4j_tpu.data.dataset import DataSet
        from deeplearning4j_tpu.nn.conf import updaters
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                                       OutputLayer)
        from deeplearning4j_tpu.observability.tracing import trace
        conf = (NeuralNetConfiguration.builder().set_seed(0)
                .updater(updaters.adam(1e-2)).graph_builder()
                .add_inputs("in")
                .add_layer("d", DenseLayer(n_out=8,
                                           activation="relu"), "in")
                .add_layer("out", OutputLayer(n_out=3, loss="mcxent"),
                           "d")
                .set_outputs("out")
                .set_input_types(InputType.feed_forward(4)).build())
        net = ComputationGraph(conf).init()
        rng = np.random.default_rng(0)
        x = rng.normal(0, 1, (8, 4)).astype("float32")
        y = np.eye(3, dtype="float32")[rng.integers(0, 3, 8)]
        trace.clear()
        trace.enable()
        try:
            net.fit(DataSet(x, y))
        finally:
            trace.disable()
        names = {e["name"] for e in trace.events()}
        assert {"epoch", "data_wait", "train_step"} <= names
        assert net._step_timing is not None
        assert len(net._step_timing) == 2


# ---------------------------------------------------------------------------
# serving integration: registry-backed metrics + /metrics prometheus
# ---------------------------------------------------------------------------

class TestServingRegistryIntegration:
    def test_serving_metrics_prometheus_text(self):
        from deeplearning4j_tpu.serving.metrics import ServingMetrics
        m = ServingMetrics()
        ep = m.endpoint("predict")
        ep.observe(0.01)
        ep.count_shed()
        m.occupancy("predict", 32).record(8)
        m.register_gauge("predict_queue_depth", lambda: 3)
        text = m.prometheus_text()
        assert ('serving_requests_total{endpoint="predict"} 1'
                in text)
        assert 'serving_shed_total{endpoint="predict"} 1' in text
        assert ('serving_batch_items_total{endpoint="predict"} 8'
                in text)
        assert ('serving_gauge{name="predict_queue_depth"} 3'
                in text)
        assert "serving_latency_seconds_bucket" in text
        # JSON snapshot is unchanged by the re-base
        snap = m.snapshot()
        assert snap["endpoints"]["predict"]["requests"] == 1
        assert snap["endpoints"]["predict"]["shed"] == 1
        assert snap["batching"]["predict"]["avg_batch_size"] == 8.0

    def test_shared_registry_merges_same_endpoint(self):
        # two ServingMetrics on ONE registry (the process-wide pipe)
        # creating the same endpoint must merge instruments, not
        # raise on the histogram registration
        from deeplearning4j_tpu.observability.registry import (
            MetricsRegistry)
        from deeplearning4j_tpu.serving.metrics import ServingMetrics
        reg = MetricsRegistry()
        a = ServingMetrics(registry=reg).endpoint("predict")
        b = ServingMetrics(registry=reg).endpoint("predict")
        a.observe(0.01)
        b.observe(0.02)
        assert a.requests == 2 and b.requests == 2
        assert a.latency is b.latency

    def test_unregister_gauge_removes_exposition(self):
        from deeplearning4j_tpu.serving.metrics import ServingMetrics
        m = ServingMetrics()
        m.register_gauge("g", lambda: 1)
        assert 'serving_gauge{name="g"}' in m.prometheus_text()
        m.unregister_gauge("g")
        assert 'serving_gauge{name="g"}' not in m.prometheus_text()

    def test_model_server_metrics_content_negotiation(self):
        import urllib.request

        from deeplearning4j_tpu.serving.http import ModelServer
        server = ModelServer(port=0).start()
        try:
            base = f"http://127.0.0.1:{server.port}"
            # default (no Accept): JSON, the pre-observability contract
            with urllib.request.urlopen(base + "/metrics") as r:
                assert "application/json" in r.headers["Content-Type"]
                json.loads(r.read().decode())
            # prometheus via Accept (what a scraper sends)
            req = urllib.request.Request(
                base + "/metrics",
                headers={"Accept": "text/plain;version=0.0.4"})
            with urllib.request.urlopen(req) as r:
                assert "text/plain" in r.headers["Content-Type"]
                body = r.read().decode()
            assert body == "" or body.startswith("#")
            # prometheus via query param
            with urllib.request.urlopen(
                    base + "/metrics?format=prometheus") as r:
                assert "text/plain" in r.headers["Content-Type"]
        finally:
            server.stop(drain=False)

    def test_parallel_inference_counters_on_shared_registry(self):
        from deeplearning4j_tpu.observability.registry import (
            MetricsRegistry)
        from deeplearning4j_tpu.parallel.inference import (
            InferenceMode, ParallelInference)
        from deeplearning4j_tpu.serving.metrics import ServingMetrics

        class _Model:
            def output(self, x):
                return np.asarray(x)

        reg = MetricsRegistry()
        m = ServingMetrics(registry=reg)
        pi = ParallelInference(_Model(),
                               mode=InferenceMode.SEQUENTIAL,
                               metrics=m)
        gname = pi._gauge_name
        assert f'serving_gauge{{name="{gname}"}}' \
            in reg.prometheus_text()
        pi.shutdown()
        assert f'serving_gauge{{name="{gname}"}}' \
            not in reg.prometheus_text()


# ---------------------------------------------------------------------------
# CLI --trace
# ---------------------------------------------------------------------------

class TestCliTrace:
    def test_trace_flag_writes_chrome_trace(self, tmp_path):
        import subprocess

        from deeplearning4j_tpu.util.model_serializer import write_model
        model_path = str(tmp_path / "m.zip")
        write_model(_mlp(), model_path)
        trace_path = str(tmp_path / "t.json")
        r = subprocess.run(
            [sys.executable, "-m", "deeplearning4j_tpu",
             "--trace", trace_path, "summary", "--model", model_path],
            capture_output=True, text=True, timeout=240,
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO)
        assert r.returncode == 0, r.stderr[-2000:]
        assert "trace written" in r.stdout
        with open(trace_path) as f:
            doc = json.load(f)
        assert "traceEvents" in doc


# ---------------------------------------------------------------------------
# perf-claims lint
# ---------------------------------------------------------------------------

class TestPerfClaimsLint:
    def _mod(self):
        # ported to graftlint rule GL005 (ISSUE 6); the same checks
        # also run through tools/check_perf_claims.py, which is now a
        # thin deprecation shim over this module (shim covered in
        # tests/test_graftlint.py)
        sys.path.insert(0, REPO)
        try:
            from tools.graftlint.rules import gl005_literal_drift
        finally:
            sys.path.pop(0)
        return gl005_literal_drift

    def test_committed_docs_pass(self):
        mod = self._mod()
        errors = mod.check(REPO)
        assert errors == [], "\n".join(errors)

    def test_unmeasured_claim_fails(self, tmp_path):
        mod = self._mod()
        (tmp_path / "BENCH_DETAIL.json").write_text(json.dumps(
            {"configs": [{"value": 100.0, "unit": "u",
                          "vs_baseline": 1.3}]}))
        (tmp_path / "README.md").write_text(
            "ours is 9.7x faster than everything\n")
        errors = mod.check(str(tmp_path))
        assert len(errors) == 1 and "9.7x" in errors[0]

    def test_measured_claim_and_target_exempt(self, tmp_path):
        mod = self._mod()
        (tmp_path / "BENCH_DETAIL.json").write_text(json.dumps(
            {"configs": [{"value": 200.0, "unit": "u",
                          "vs_baseline": 1.31},
                         {"value": 100.0, "unit": "u"}]}))
        (tmp_path / "README.md").write_text(
            "measured 1.3x vs baseline\n"
            "derived 2.0x between configs\n"
            "goal (target: 0.7x) is exempt\n")
        assert mod.check(str(tmp_path)) == []
