"""The set-up timeline (ISSUE 50): ``observability.tracing.startup``
holds ``setup/*`` spans from ``init()`` to each step program's first
call and the ``xla/*`` spans ``compile_watch.GlobalCompileStats``
writes for every trace, lowering and compile; nothing on a steady step
records there and nothing of it reaches the hot-path ``trace``; the
stats tell a load from the persistent cache from a cold compile and
name what compiled; the benchmark's five ``setup_*`` readers over a
hand-made timeline; ``GET /debug/startup`` over live HTTP."""

import json
import os
import sys
import urllib.request

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import spec                      # noqa: E402
from deeplearning4j_tpu.observability import tracing    # noqa: E402
from deeplearning4j_tpu.observability.compile_watch import (  # noqa: E402
    GlobalCompileStats, SteadyStateCompileError, install_global_watch)
from deeplearning4j_tpu.observability.registry import (  # noqa: E402
    MetricsRegistry)
from deeplearning4j_tpu.observability.tracing import (  # noqa: E402
    Tracer, startup, trace)

S = 1_000_000_000


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
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    return MultiLayerNetwork(conf)


def _graph():
    from deeplearning4j_tpu import (ComputationGraph,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                                   OutputLayer)
    conf = (NeuralNetConfiguration.builder().set_seed(0)
            .updater(updaters.adam(1e-2)).graph_builder()
            .add_inputs("in")
            .set_input_types(InputType.feed_forward(4))
            .add_layer("hidden", DenseLayer(n_out=16, activation="relu"),
                       "in")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                          loss="mcxent"), "hidden")
            .set_outputs("out").build())
    return ComputationGraph(conf)


def _batches(n, rows=8):
    from deeplearning4j_tpu.data.dataset import DataSet
    rng = np.random.default_rng(1)
    return [DataSet(rng.normal(0, 1, (rows, 4)).astype("float32"),
                    np.eye(3, dtype="float32")[rng.integers(0, 3, rows)])
            for _ in range(n)]


LM_V, LM_CAP, CHUNK_T = 13, 32, 2


def _lm():
    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (
        EmbeddingSequenceLayer, RnnOutputLayer, TransformerEncoderLayer)
    conf = (NeuralNetConfiguration.builder().set_seed(0)
            .updater(updaters.adam(1e-3)).list()
            .layer(EmbeddingSequenceLayer(n_in=LM_V, n_out=16))
            .layer(TransformerEncoderLayer(n_heads=2, causal=True))
            .layer(RnnOutputLayer(n_out=LM_V, loss="mcxent"))
            .set_input_type(InputType.recurrent(LM_V, LM_CAP)).build())
    return MultiLayerNetwork(conf).init()


@pytest.fixture
def timeline():
    """``since()``: what ``startup`` has recorded since the test
    began, with the process's compile observer listening."""
    install_global_watch()
    head = startup.export_since(0, limit=0)["head"]
    return lambda: [e for e in startup.events() if e["seq"] > head]


def _tree(events):
    """``[(name, parent's name or None), ...]`` in recorded order."""
    by_id = {e["span_id"]: e for e in events}
    return [(e["name"],
             by_id[e["parent_id"]]["name"] if e.get("parent_id") in by_id
             else None) for e in events]


def _named(events, name):
    return [e for e in events if e["name"] == name]


def _inside(child, parent):
    c0, p0 = child["t_ns"], parent["t_ns"]
    return (p0 <= c0 and c0 + child["dur_us"] * 1e3
            <= p0 + parent["dur_us"] * 1e3 + 1)


# ---------------------------------------------------------------------------
# the spans of a training start
# ---------------------------------------------------------------------------

EXECUTORS = pytest.mark.parametrize("make", [_mlp, _graph],
                                   ids=["multilayer", "graph"])


class TestTrainingStart:
    @EXECUTORS
    def test_init_and_first_step(self, timeline, make):
        """``init()`` is one ``setup/init`` with the optimizer's state
        as its child; the first ``fit`` step is one ``setup/program``
        around the train step's trace, lowering and compile; the
        second step adds nothing."""
        import jax
        net = make().init()
        first, second = _batches(2)
        net.fit(first)
        events = timeline()
        init, = _named(events, "setup/init")
        opt, = _named(events, "setup/init/optimizer")
        assert opt["parent_id"] == init["span_id"] and _inside(opt, init)
        assert init["args"]["layers"] == 2
        assert init["args"]["param_bytes"] == sum(
            x.nbytes for x in jax.tree_util.tree_leaves(net.params))
        assert opt["args"]["state_bytes"] == sum(
            x.nbytes for x in jax.tree_util.tree_leaves(net.opt_state))
        assert "parent_id" not in init
        program, = _named(events, "setup/program")
        assert program["args"] == {"program": "train_step"}
        assert "parent_id" not in program
        kids = [e for e in events
                if e.get("parent_id") == program["span_id"]]
        assert [k["name"] for k in kids] == [
            "xla/trace", "xla/lower", "xla/compile"]
        assert {k["args"]["fun_name"] for k in kids} == {"train_step"}
        assert all(_inside(k, program) for k in kids)
        assert kids[2]["args"]["cache"] in ("hit", "miss", "off")
        # every other xla/* event of the start is an eager op of init
        # (none where an earlier test's network compiled them all)
        assert {parent for name, parent in _tree(events)
                if name.startswith("xla/")} <= {
                    "setup/init", "setup/init/optimizer",
                    "setup/program"}
        net.fit(second)
        assert len(timeline()) == len(events)

    @EXECUTORS
    def test_a_hundred_steady_steps_record_nothing(self, timeline, make):
        net = make().init()
        net.fit(_batches(1)[0])
        from deeplearning4j_tpu.data.iterators import (
            ListDataSetIterator)
        before = len(timeline())
        net.fit(ListDataSetIterator(_batches(25)), epochs=4)
        assert net.iteration_count == 101
        assert len(timeline()) == before

    def test_fused_window_is_a_program_of_its_own(self, timeline):
        from deeplearning4j_tpu.data.iterators import (
            ListDataSetIterator)
        net = _mlp().init()
        net.fit(ListDataSetIterator(_batches(8)),
                steps_per_device_call=4)
        programs = [e["args"]["program"]
                    for e in _named(timeline(), "setup/program")]
        assert programs == ["train_step_fused/k=4"]
        fused, = _named(timeline(), "setup/program")
        assert [e["name"] for e in timeline()
                if e.get("parent_id") == fused["span_id"]] == [
                    "xla/trace", "xla/lower", "xla/compile"]

    def test_aot_warmup_names_its_programs(self, timeline):
        net = _mlp().init()
        built = net.warmup(_batches(1)[0], steps_per_device_call=2)
        assert set(built) == {"train_step", "kstep_2"}
        programs = _named(timeline(), "setup/program")
        assert [e["args"]["program"] for e in programs] == [
            "train_step", "train_step_fused/k=2"]
        for p in programs:
            compiles = [e for e in timeline()
                        if e.get("parent_id") == p["span_id"]
                        and e["name"] == "xla/compile"]
            assert len(compiles) == 1

    def test_the_hot_tracer_holds_nothing_of_the_set_up(self, timeline):
        """``trace.events()`` with the tracer on from before ``init``:
        a reader that takes the earliest ``t_ns`` as the traced part's
        start (the benchmark's clock fit) must find no set-up span
        there."""
        trace.clear()
        trace.enable()
        try:
            net = _mlp().init()
            for ds in _batches(3):
                net.fit(ds)
            hot = trace.events()
        finally:
            trace.disable()
            trace.clear()
        assert {"step", "train_step", "enqueue"} <= {
            e["name"] for e in hot}
        assert not [e["name"] for e in hot
                    if e["name"].startswith(("setup/", "xla/"))]
        assert _named(timeline(), "setup/program")

    def test_chrome_export_can_carry_the_set_up(self, timeline, tmp_path):
        _mlp().init()
        t = Tracer(enabled=True, annotate=False)
        with t.span("hot"):
            pass
        path = tmp_path / "trace.json"
        n = t.export_chrome_trace(str(path), also=(startup,))
        with open(path) as f:
            out = json.load(f)["traceEvents"]
        assert n == len(out) == 1 + len(startup.events())
        init = [e for e in out if e["name"] == "setup/init"][-1]
        want = _named(timeline(), "setup/init")[-1]
        assert init["ts"] == (want["t_ns"] - t.origin_ns) / 1e3
        assert init["args"]["layers"] == 2


# ---------------------------------------------------------------------------
# the spans of a serving start
# ---------------------------------------------------------------------------

class TestServingStart:
    @pytest.fixture(autouse=True)
    def _two_tokens_a_chunk(self, monkeypatch):
        from deeplearning4j_tpu.serving import continuous
        monkeypatch.setattr(continuous, "CHUNK_ROWS", 2 * CHUNK_T)
        monkeypatch.setattr(continuous, "WIDE_CHUNK_ROWS", 2 * CHUNK_T)

    def test_first_request_then_steady_steps(self, timeline):
        """A paged batcher's start: ``setup/session`` as it is built,
        ``setup/warm_programs`` at the first step with one
        ``setup/program`` a width, each around its own trace, lowering
        and compile. A hundred steps later the timeline is as it
        was."""
        from deeplearning4j_tpu.serving.continuous import (
            ContinuousBatcher)
        cb = ContinuousBatcher(_lm(), slots=2, capacity=LM_CAP,
                               queue_limit=16)
        try:
            assert cb._chunk_t == CHUNK_T
            assert len(cb.generate(np.array([1, 2, 3]), 4)) == 4
            events = timeline()
            session, = _named(events, "setup/session")
            assert session["args"]["slots"] == 2
            assert session["args"]["capacity"] == LM_CAP
            assert session["args"]["pool_bytes"] > 0
            warm, = _named(events, "setup/warm_programs")
            assert warm["args"] == {"widths": [CHUNK_T, 1]}
            programs = _named(events, "setup/program")
            assert [p["args"]["program"] for p in programs] == [
                f"paged_step_ids/t={CHUNK_T}", "paged_step_ids/t=1"]
            for p in programs:
                assert p["parent_id"] == warm["span_id"]
                assert _inside(p, warm)
                kids = [e for e in events
                        if e.get("parent_id") == p["span_id"]]
                assert [k["name"] for k in kids] == [
                    "xla/trace", "xla/lower", "xla/compile"]
                assert {k["args"]["fun_name"] for k in kids} == {
                    "step_ids"}
            steps = lambda: sum(
                v for k, v in cb.metrics.registry.snapshot().items()
                if k.startswith("serving_batches_total"))
            before, n0 = len(events), steps()
            handles = [cb.submit(np.array([1 + i, 2, 3 + i]), 27)
                       for i in range(8)]
            assert all(len(cb.wait(h)) == 27 for h in handles)
            assert steps() - n0 >= 100
            assert len(timeline()) == before
        finally:
            cb.shutdown()

    def test_debug_startup_over_http(self, timeline):
        from deeplearning4j_tpu.serving import ModelRegistry, ModelServer
        reg = ModelRegistry()
        reg.register("lm", _lm())
        srv = ModelServer(reg, port=0, slots=2, capacity=LM_CAP,
                          wait_ms=2.0).start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            req = urllib.request.Request(
                base + "/v1/generate", json.dumps(
                    {"model": "lm", "prompt": [1, 2, 3],
                     "n_tokens": 3}).encode(),
                {"Content-Type": "application/json"})
            with urllib.request.urlopen(req) as resp:
                assert resp.status == 200
            with urllib.request.urlopen(base + "/debug/startup") as resp:
                body = json.loads(resp.read())
        finally:
            srv.stop(drain=True, timeout=10.0)
        mine = {e["seq"] for e in timeline()}
        events = [e for e in body["events"] if e["seq"] in mine]
        batcher, = _named(events, "setup/batcher")
        assert batcher["args"] == {"model": "lm"}
        session, = _named(events, "setup/session")
        assert session["parent_id"] == batcher["span_id"]
        assert {e["args"]["program"]
                for e in _named(events, "setup/program")} == {
                    f"paged_step_ids/t={CHUNK_T}", "paged_step_ids/t=1"}
        row = body["by_function"]["step_ids"]
        assert row["compiles"] + row["loads"] >= 2
        assert row["trace_s"] > 0 and row["lower_s"] > 0
        assert body["compiles"]["backend_compiles"] >= 2
        assert body["dropped"] == startup.dropped


# ---------------------------------------------------------------------------
# the compile observer
# ---------------------------------------------------------------------------

@pytest.fixture
def listening():
    """A ``GlobalCompileStats`` of the test's own with a timeline of
    its own, listening for the test's length."""
    made = []

    def make(**kw):
        kw.setdefault("timeline", Tracer(enabled=True, annotate=False))
        made.append(GlobalCompileStats(registry=MetricsRegistry(),
                                       **kw).install())
        return made[-1]

    yield make
    for stats in made:
        stats.uninstall()


@pytest.fixture
def cache_dir(tmp_path):
    """jax's persistent compile cache in a directory of the test's
    own, every executable kept; as it was afterwards."""
    import jax
    from jax.experimental.compilation_cache import (
        compilation_cache as cc)
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_enable_compilation_cache")
    before = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()
    yield tmp_path
    for k, v in before.items():
        jax.config.update(k, v)
    cc.reset_cache()


def _twice():
    """Two function objects of one text: jax's in-memory caches know
    them apart, the persistent cache's key does not."""
    import jax.numpy as jnp

    def make():
        def cold_then_warm(x):
            return jnp.tanh(x) * 3 + 1
        return cold_then_warm

    return make(), make()


class TestCompileObserver:
    def test_cold_then_warm(self, listening, cache_dir):
        """What jax 0.9.0 fires on a persistent-cache hit: the backend
        compile event WITH the retrieval inside it. The stats count
        both as ``backend_compiles`` / ``compile_secs`` (as before)
        and tell them apart."""
        import jax
        stats = listening()
        cold, warm = _twice()
        x = np.ones((7, 5), np.float32)
        jax.jit(cold)(x)
        s1 = stats.summary()
        assert s1["backend_compiles"] == s1["cold_compiles"] == 1
        assert s1["cache_requests"] == 1
        assert s1["persistent_cache_hits"] == 0
        assert s1["cache_hit"] is False
        assert s1["cache_load_secs"] == 0
        assert any(cache_dir.iterdir())
        mark = stats.mark()
        jax.jit(warm)(x)
        s2 = stats.summary(mark)
        assert s2["backend_compiles"] == 1 and s2["cold_compiles"] == 0
        assert s2["cold_compile_secs"] == 0
        assert s2["persistent_cache_hits"] == s2["cache_requests"] == 1
        assert s2["cache_hit"] is True
        assert stats.cache_hit is False        # the process as a whole
        total = stats.summary()
        assert total["backend_compiles"] == 2
        assert total["compile_secs"] >= total["cold_compile_secs"] > 0
        compiles = _named(stats.timeline.events(), "xla/compile")
        assert [c["args"]["cache"] for c in compiles] == ["miss", "hit"]
        assert compiles[0]["args"]["load_s"] == 0
        assert 0 < compiles[1]["args"]["load_s"] \
            <= compiles[1]["dur_us"] / 1e6
        row = stats.by_function()["cold_then_warm"]
        assert (row["compiles"], row["loads"]) == (1, 1)
        assert row["load_s"] == compiles[1]["args"]["load_s"]
        # the cache saves neither the trace nor the lowering
        assert len(_named(stats.timeline.events(), "xla/trace")) == 2
        assert len(_named(stats.timeline.events(), "xla/lower")) == 2

    def test_cache_off(self, listening, cache_dir):
        import jax
        from jax.experimental.compilation_cache import (
            compilation_cache as cc)
        # jax decides once a process whether it uses the cache, until
        # someone resets it (the fixture does, again, afterwards)
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        stats = listening()

        def uncached(x):
            return x * 5 - 2

        jax.jit(uncached)(np.ones(3, np.float32))
        s = stats.summary()
        assert s["cache_requests"] == 0 and s["cache_hit"] is None
        assert s["backend_compiles"] == s["cold_compiles"] == 1
        compile_, = _named(stats.timeline.events(), "xla/compile")
        assert compile_["args"] == {"fun_name": "uncached",
                                    "cache": "off", "load_s": 0.0}

    def test_nothing_compiled_is_no_evidence(self):
        stats = GlobalCompileStats(registry=MetricsRegistry())
        assert stats.cache_hit is None
        assert stats.summary()["cache_hit"] is None
        assert stats.by_function() == {}

    def test_nested_jits_do_not_double(self, listening):
        """A jitted function traced inside another's trace fires its
        own duration inside the outer one's: only the outermost is
        counted and written."""
        import jax
        import jax.numpy as jnp
        stats = listening()

        @jax.jit
        def inner_fn(x):
            return jnp.tanh(x) @ x

        @jax.jit
        def outer_fn(x):
            return inner_fn(x) + inner_fn(x * 2)

        outer_fn(np.ones((8, 8), np.float32))
        events = stats.timeline.events()
        assert [(e["name"], e["args"]["fun_name"]) for e in events] == [
            ("xla/trace", "outer_fn"), ("xla/lower", "outer_fn"),
            ("xla/compile", "outer_fn")]
        assert set(stats.by_function()) == {"outer_fn"}
        row, s = stats.by_function()["outer_fn"], stats.summary()
        assert s["trace_secs"] == round(row["trace_s"], 3)
        assert s["lower_secs"] == round(row["lower_s"], 3)
        assert row["trace_s"] == pytest.approx(
            events[0]["dur_us"] / 1e6, abs=1e-6)
        assert s["backend_compiles"] == 1

    def test_spans_hang_under_the_open_span_of_their_thread(
            self, listening):
        import threading

        import jax
        stats = listening()
        t = stats.timeline

        def on_a_thread(x):
            return x + 7

        def work():
            with t.span("setup/program", {"program": "threaded"}):
                jax.jit(on_a_thread)(np.ones(3, np.float32))

        with t.span("setup/init"):
            th = threading.Thread(target=work)
            th.start()
            th.join()
        events = t.events()
        program, = _named(events, "setup/program")
        assert "parent_id" not in program      # another thread's stack
        xla = [e for e in events if e["name"].startswith("xla/")]
        assert len(xla) == 3
        assert {e["parent_id"] for e in xla} == {program["span_id"]}
        assert {e["tid"] for e in xla} == {program["tid"]}

    def test_zero_compile_scope_names_what_compiled(self, timeline):
        import jax
        stats = install_global_watch()

        def escaped_the_warmup(x):
            return x * 1.5

        with pytest.raises(SteadyStateCompileError) as e:
            with stats.zero_compile_scope("a promised steady state"):
                jax.jit(escaped_the_warmup)(np.ones(11, np.float32))
        assert "escaped_the_warmup" in str(e.value)
        assert e.value.functions == ("escaped_the_warmup",)
        assert e.value.stats["backend_compiles"] == 1
        with stats.zero_compile_scope("nothing compiles"):
            pass

    def test_the_module_alone_imports_no_jax(self):
        """``startup`` is born enabled with the module, and the module
        alone still imports no jax (``annotate=False``)."""
        import subprocess
        code = (
            "import importlib.util, sys\n"
            "spec = importlib.util.spec_from_file_location('t', "
            f"{tracing.__file__!r})\n"
            "m = importlib.util.module_from_spec(spec)\n"
            "sys.modules['t'] = m\n"
            "spec.loader.exec_module(m)\n"
            "assert m.startup.enabled and not m.trace.enabled\n"
            "with m.startup.span('setup/init', {'layers': 1}):\n"
            "    assert m.startup.open_span_id() is not None\n"
            "assert m.startup.open_span_id() is None\n"
            "assert [e['name'] for e in m.startup.events()] == "
            "['setup/init']\n"
            "assert 'jax' not in sys.modules\n"
            "print('ok')\n")
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == "ok"


# ---------------------------------------------------------------------------
# the benchmark's readers over a hand-made timeline
# ---------------------------------------------------------------------------

T_START = 1000.0            # run.py's first reading of the clock, s
SETUP_S = 10.0


def reader(name):
    return spec.load_module("layer_metrics", name)


@pytest.fixture
def hand_made(monkeypatch):
    """A timeline whose answers are computed by hand, in place of the
    process's, and ``__main__.T_START`` as ``run.py`` keeps it.
    Seconds after T_START:

    - ``setup/init`` 1.0-3.0 with ``setup/init/optimizer`` 2.0-3.0 and
      an eager op's trace 1.1-1.3, lowering 1.3-1.4 and load 1.4-1.7
      (0.25 s of it retrieval); a second ``setup/init`` 3.5-4.0;
    - ``setup/program`` 5.0-7.0 around a trace 5.0-5.5, a lowering
      5.5-5.8 and a cold compile 5.8-6.9;
    - on another thread a load 6.0-8.0 (1.5 s of it retrieval);
    - outside the set-up: an init before T_START, a program that
      starts at 9.5 and ends at 10.5 in the window, a trace at 11."""
    t = Tracer(enabled=True, annotate=False)
    at = lambda s: int((T_START + s) * S)

    def add(name, t0, t1, parent=None, tid=1, **attrs):
        return t.record_span(name, at(t0), at(t1) - at(t0),
                             parent_id=parent, attrs=attrs, tid=tid)

    init = add("setup/init", 1.0, 3.0, layers=2, param_bytes=10)
    add("setup/init/optimizer", 2.0, 3.0, init, state_bytes=20)
    add("xla/trace", 1.1, 1.3, init, fun_name="_normal")
    add("xla/lower", 1.3, 1.4, init, fun_name="_normal")
    add("xla/compile", 1.4, 1.7, init, fun_name="_normal",
        cache="hit", load_s=0.25)
    add("setup/init", 3.5, 4.0, layers=2, param_bytes=10)
    program = add("setup/program", 5.0, 7.0, program="train_step")
    add("xla/trace", 5.0, 5.5, program, fun_name="train_step")
    add("xla/lower", 5.5, 5.8, program, fun_name="train_step")
    add("xla/compile", 5.8, 6.9, program, fun_name="train_step",
        cache="miss", load_s=0.0)
    add("xla/compile", 6.0, 8.0, tid=2, fun_name="leaf_norms",
        cache="hit", load_s=1.5)
    add("setup/init", -2.0, -1.0, layers=9, param_bytes=1)
    add("setup/program", 9.5, 10.5, program="late")
    add("xla/trace", 11.0, 12.0, fun_name="in_the_window")
    monkeypatch.setattr(tracing, "startup", t)
    monkeypatch.setattr(sys.modules["__main__"], "T_START", T_START,
                        raising=False)
    return {"end_to_end": {"setup_s": SETUP_S}}


WANT = {"setup_init_s": 2.5,            # 1.0-3.0 and 3.5-4.0
        "setup_trace_lower_s": 1.1,     # 0.2 + 0.1 + 0.5 + 0.3
        "setup_cache_load_s": 1.75,     # 0.25 + 1.5, not the miss
        "setup_programs_s": 2.0,        # 5.0-7.0, not the late one
        "setup_named_pct": 55.0}        # 1-3, 3.5-4, 5-8 of 10 s


class TestSetupReaders:
    @pytest.mark.parametrize("name", sorted(WANT))
    def test_by_hand(self, hand_made, name):
        assert reader(name).read(hand_made) == pytest.approx(
            WANT[name], abs=1e-6)

    @pytest.mark.parametrize("name", sorted(WANT))
    def test_nothing_to_read(self, hand_made, name, monkeypatch):
        read = reader(name).read
        # ``obs`` before ``result()`` has put ``setup_s`` there
        assert read({}) is None
        assert read({"end_to_end": {}}) is None
        # a test that drives ``main(argv)``: no ``__main__.T_START``
        with monkeypatch.context() as m:
            m.delattr(sys.modules["__main__"], "T_START")
            assert read(hand_made) is None
        # a program older than the timeline
        with monkeypatch.context() as m:
            m.delattr(tracing, "startup")
            assert read(hand_made) is None
        assert read(hand_made) is not None

    def test_an_empty_set_up_reads_zero(self, hand_made, monkeypatch):
        """A timeline that is there and holds nothing inside the
        set-up is a reading (a cold run loads nothing), not a gap."""
        monkeypatch.setattr(sys.modules["__main__"], "T_START",
                            T_START + 100.0)
        for name in WANT:
            assert reader(name).read(hand_made) == 0.0

    def test_benchmark_json_lists_them_for_every_cell(self):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        entries = {m["name"]: m for m in bench["per_layer"]}
        for name in WANT:
            m = entries[name]
            assert m["layer"] == "Entry points"
            assert m["source"] == "program_span"
            assert m["moves"] == "setup_s" and "workloads" not in m
        for w in bench["workloads"]:
            cell = spec.load(w["name"])
            assert set(WANT) <= {m["name"] for m in cell.per_layer}
