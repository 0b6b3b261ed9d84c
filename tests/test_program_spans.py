"""Program spans on one clock (ISSUE 24): the tracer's raw clock,
parents and profiler annotations; the spans of one ``fit`` iteration on
both the k=1 and the fused path; the batcher loop's per-step parts and
slot-step counts; and the layer names on the train step's ops.
"""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACING = os.path.join(REPO, "deeplearning4j_tpu", "observability",
                       "tracing.py")


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
            .add_layer("hidden", DenseLayer(n_out=8, activation="relu"),
                       "in")
            .add_layer("out", OutputLayer(n_out=3, loss="mcxent"),
                       "hidden")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(4)).build())
    return ComputationGraph(conf).init()


def _data(n=32):
    from deeplearning4j_tpu.data.dataset import DataSet
    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, (n, 4)).astype("float32")
    y = np.eye(3, dtype="float32")[rng.integers(0, 3, n)]
    return DataSet(x, y)


def _leaves(net):
    import jax
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(net.params)]


def _batches(n, rows=8):
    """n distinct batches."""
    from deeplearning4j_tpu.data.dataset import DataSet
    rng = np.random.default_rng(1)
    return [DataSet(rng.normal(0, 1, (rows, 4)).astype("float32"),
                    np.eye(3, dtype="float32")[rng.integers(0, 3, rows)])
            for _ in range(n)]


def _features(m):
    """The (first) features array of a DataSet or MultiDataSet."""
    f = m.features
    return f[0] if isinstance(f, (list, tuple)) else f


def _logged_fit(net, batches, **fit_kwargs):
    """``fit`` over an iterator, a listener and the executor's
    placement (``_batch_tuple``) that write what they do, in order,
    into one list: ("iter",), ("pull", i or None at exhaustion),
    ("place", i), ("listener", iteration), ("epoch_start",),
    ("epoch_end",)."""
    from deeplearning4j_tpu.data.iterators import DataSetIterator
    from deeplearning4j_tpu.train.listeners import TrainingListener
    log = []
    index = {id(b.features): i for i, b in enumerate(batches)}

    class Feed(DataSetIterator):
        def reset(self):
            log.append(("iter",))

        def _iterate(self):
            for i, ds in enumerate(batches):
                log.append(("pull", i))
                yield ds
            log.append(("pull", None))

    class Listener(TrainingListener):
        def on_epoch_start(self, model):
            log.append(("epoch_start",))

        def on_epoch_end(self, model):
            log.append(("epoch_end",))

        def iteration_done(self, model, iteration, score, batch_size):
            log.append(("listener", iteration))

    place = net._batch_tuple

    def logged_place(ds):
        log.append(("place", index[id(_features(ds))]))
        return place(ds)

    net._batch_tuple = logged_place
    net.set_listeners(Listener())
    net.fit(Feed(), **fit_kwargs)
    return log


@pytest.fixture
def traced():
    """The process-wide tracer, on for one test and empty before and
    after."""
    from deeplearning4j_tpu.observability.tracing import trace
    trace.clear()
    trace.enable()
    try:
        yield trace
    finally:
        trace.disable()
        trace.clear()


def _inside(child, parent):
    c0, p0 = child["t_ns"], parent["t_ns"]
    return (p0 <= c0 and c0 + child["dur_us"] * 1e3
            <= p0 + parent["dur_us"] * 1e3 + 1)


# ---------------------------------------------------------------------------
# the tracer
# ---------------------------------------------------------------------------

class TestOneClock:
    def test_disabled_tracer_does_not_import_jax(self):
        """The module alone, in a fresh interpreter: a disabled tracer
        hands out the shared no-op and never touches jax; ``enable()``
        is what imports it."""
        code = (
            "import importlib.util, sys\n"
            f"spec = importlib.util.spec_from_file_location('t', "
            f"{TRACING!r})\n"
            "m = importlib.util.module_from_spec(spec)\n"
            "sys.modules['t'] = m\n"
            "spec.loader.exec_module(m)\n"
            "t = m.Tracer()\n"
            "a, b = t.span('x'), t.span('y', annotate=False)\n"
            "assert a is b\n"
            "with a as s:\n"
            "    s.set('k', 1).discard()\n"
            "t.instant('i')\n"
            "assert t.events() == [] and t.clock_anchor is None\n"
            "assert 'jax' not in sys.modules, 'disabled tracer "
            "imported jax'\n"
            "t.enable()\n"
            "assert 'jax' in sys.modules\n"
            "print('ok')\n")
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == "ok"

    def test_events_carry_the_raw_clock_and_their_parent(self):
        from deeplearning4j_tpu.observability.tracing import Tracer
        before = (time.perf_counter_ns(), time.time_ns())
        t = Tracer().enable()
        perf, wall = t.clock_anchor
        assert before[0] <= perf <= time.perf_counter_ns()
        assert before[1] <= wall <= time.time_ns()
        with t.span("outer", annotate=False):
            lo = time.perf_counter_ns()
            with t.span("inner", {"k": 1}):
                pass
            t.instant("mark")
            hi = time.perf_counter_ns()
        with t.span("next"):
            pass
        ev = {e["name"]: e for e in t.events()}
        assert lo <= ev["inner"]["t_ns"] <= hi
        assert ev["inner"]["parent_id"] == ev["outer"]["span_id"]
        assert ev["mark"]["parent_id"] == ev["outer"]["span_id"]
        assert "parent_id" not in ev["outer"]
        assert "parent_id" not in ev["next"]       # the stack unwound
        assert _inside(ev["inner"], ev["outer"])
        # ts_us is the same instant, relative to the tracer's origin
        assert ev["inner"]["ts_us"] == pytest.approx(
            (ev["inner"]["t_ns"] - t.origin_ns) / 1e3)

    def test_discarded_span_leaves_no_event(self):
        from deeplearning4j_tpu.observability.tracing import Tracer
        t = Tracer(enabled=True)
        with t.span("pass") as outer:
            with t.span("work"):
                pass
            outer.discard()
        with t.span("kept"):
            pass
        ev = {e["name"]: e for e in t.events()}
        assert set(ev) == {"work", "kept"}
        assert "parent_id" not in ev["kept"]

    def test_switched_on_and_off_inside_a_span(self):
        """The benchmark switches the tracer from inside the iterator:
        spans opened before the switch are the no-op, those closed
        after it still unwind."""
        from deeplearning4j_tpu.observability.tracing import Tracer
        t = Tracer()
        with t.span("step"):                # no-op: opened while off
            t.enable()
            with t.span("train_step"):
                pass
        with t.span("step"):
            with t.span("data_wait"):
                t.disable()
            with t.span("train_step"):      # no-op again
                pass
        names = [e["name"] for e in t.events()]
        assert names == ["train_step", "data_wait", "step"]
        assert "parent_id" not in t.events()[0]


# ---------------------------------------------------------------------------
# one fit iteration's spans
# ---------------------------------------------------------------------------

def _by_id(events):
    """Instants (a compile watch's ``xla_compile`` marks, when an
    earlier test installed one) carry no id."""
    return {e["span_id"]: e for e in events if "span_id" in e}


def _by_iteration(events):
    """{iteration: (step event, [its descendants])} for whole
    iterations."""
    by_id = _by_id(events)
    out = {}
    for e in events:
        if e["name"] == "step" and "iteration" in (e.get("args") or {}):
            out[e["args"]["iteration"]] = (e, [])
    for e in by_id.values():
        up = by_id.get(e.get("parent_id"))
        while up is not None and up["name"] != "step":
            up = by_id.get(up.get("parent_id"))
        if up is not None and "iteration" in (up.get("args") or {}):
            out[up["args"]["iteration"]][1].append(e)
    return out


EXECUTORS = pytest.mark.parametrize("make", [_mlp, _graph],
                                   ids=["multilayer", "graph"])


class TestFitSpans:
    @EXECUTORS
    def test_single_step_path(self, traced, make):
        """k=1 looks one batch ahead: the pull and the placement of
        batch n+1 hang under iteration n's ``step``, after its enqueue
        and ``h2d_wait`` and before its listeners."""
        net = make()
        net.fit(_data(24), batch_size=8) if make is _mlp else net.fit(
            [_data(8), _data(8), _data(8)])
        events = traced.events()
        by_id = _by_id(events)
        its = _by_iteration(events)
        assert sorted(its) == [0, 1, 2]
        batch_bytes = 8 * 4 * 4 + 8 * 3 * 4
        # the first iteration pulls and places its own batch; the
        # last one's lookahead finds the iterator empty
        want = {0: ["data_wait", "train_step", "batch_to_device",
                    "enqueue", "h2d_wait", "data_wait",
                    "batch_to_device", "listeners"],
                1: ["train_step", "enqueue", "h2d_wait", "data_wait",
                    "batch_to_device", "listeners"],
                2: ["train_step", "enqueue", "h2d_wait", "data_wait",
                    "listeners"]}
        ahead = {0: [False, True], 1: [True], 2: []}
        for i, (step, kids) in its.items():
            assert step["args"]["samples"] == 8
            assert step["args"]["prefetched"] is (i > 0)
            for e in kids:
                assert _inside(e, by_id[e["parent_id"]]), e["name"]
            kids = sorted(kids, key=lambda e: e["t_ns"])
            assert [e["name"] for e in kids] == want[i]
            train_step = next(e for e in kids if e["name"] == "train_step")
            for e in kids:
                under_train_step = e["name"] == "enqueue" or (
                    e["name"] == "batch_to_device"
                    and not e["args"]["ahead"])
                assert e["parent_id"] == (
                    train_step["span_id"] if under_train_step
                    else step["span_id"]), e["name"]
            assert [e["args"] for e in kids
                    if e["name"] == "batch_to_device"] == [
                {"bytes": batch_bytes, "ahead": a} for a in ahead[i]]
        # the pass that found the iterator empty is a step of its own
        tail = [e for e in events if e["name"] == "step"
                and (e.get("args") or {}).get("exhausted")]
        assert len(tail) == 1
        assert len([e for e in events if e["name"] == "step"]) == 4
        # every batch crossed once
        assert len([e for e in events
                    if e["name"] == "batch_to_device"]) == 3

    @EXECUTORS
    def test_next_batch_is_placed_before_the_listener_runs(self, make):
        log = _logged_fit(make(), _batches(3))
        assert log == [
            ("epoch_start",), ("iter",),
            ("pull", 0), ("place", 0),
            ("pull", 1), ("place", 1), ("listener", 0),
            ("pull", 2), ("place", 2), ("listener", 1),
            ("pull", None), ("listener", 2),
            ("epoch_end",)]

    @EXECUTORS
    def test_iterator_is_pulled_at_most_one_ahead(self, make):
        log = _logged_fit(make(), _batches(6))
        pulled = 0
        for what, *arg in log:
            if what == "pull" and arg[0] is not None:
                pulled += 1
            elif what == "listener":
                # iteration n has batches 0..n behind it
                assert pulled <= arg[0] + 2
        assert pulled == 6

    @EXECUTORS
    def test_no_pull_across_an_epoch_end(self, make):
        log = _logged_fit(make(), _batches(2), epochs=2)
        assert log == [
            ("epoch_start",), ("iter",),
            ("pull", 0), ("place", 0),
            ("pull", 1), ("place", 1), ("listener", 0),
            ("pull", None), ("listener", 1),
            ("epoch_end",), ("epoch_start",), ("iter",),
            ("pull", 0), ("place", 0),
            ("pull", 1), ("place", 1), ("listener", 2),
            ("pull", None), ("listener", 3),
            ("epoch_end",)]

    @EXECUTORS
    def test_tbptt_batch_is_never_placed_ahead(self, traced, make):
        """Batch 1 is tBPTT (stubbed: the loop only asks the executor's
        adapter): the lookahead of step 0 pulls it and hands it on
        unplaced, and the batch after it is pulled by its own pass."""
        net = make()
        batches = _batches(4)
        chunked = []
        net._batch_is_tbptt = lambda m, tbptt: (
            _features(m) is batches[1].features)

        def run_tbptt(m, tbptt, data_wait_s=0.0):
            chunked.append(_features(m))
            net.iteration_count += 1

        net._run_tbptt = run_tbptt
        log = _logged_fit(net, batches)
        assert log == [
            ("epoch_start",), ("iter",),
            ("pull", 0), ("place", 0), ("pull", 1), ("listener", 0),
            ("pull", 2), ("place", 2),
            ("pull", 3), ("place", 3), ("listener", 2),
            ("pull", None), ("listener", 3),
            ("epoch_end",)]
        assert len(chunked) == 1 and chunked[0] is batches[1].features
        steps = {e["args"]["iteration"]: e["args"]
                 for e in traced.events() if e["name"] == "step"
                 and "iteration" in (e.get("args") or {})}
        assert {i: a.get("prefetched") for i, a in steps.items()} == {
            0: False, 1: None, 2: False, 3: True}

    @EXECUTORS
    def test_fused_window_is_never_placed_ahead(self, traced, make):
        """k=2 over five batches: two windows and a tail through the
        k=1 program, none pulled before the listeners of the one
        before it, no ``prefetched`` and no ``ahead``."""
        log = _logged_fit(make(), _batches(5), steps_per_device_call=2)
        assert log == [
            ("epoch_start",), ("iter",),
            ("pull", 0), ("pull", 1), ("listener", 0), ("listener", 1),
            ("pull", 2), ("pull", 3), ("listener", 2), ("listener", 3),
            ("pull", 4), ("pull", None), ("place", 4), ("listener", 4),
            ("epoch_end",)]
        events = traced.events()
        assert not any("prefetched" in (e.get("args") or {})
                       for e in events if e["name"] == "step")
        assert [e["args"]["ahead"] for e in events
                if e["name"] == "batch_to_device"
                and "ahead" in e["args"]] == [False]

    def test_fused_path(self, traced):
        """k=2: every batch still gets its ``step`` and ``data_wait``;
        the window's spans hang under the step of its last batch."""
        net = _mlp()
        net.fit(_data(32), batch_size=8, steps_per_device_call=2)
        events = traced.events()
        by_id = _by_id(events)
        its = _by_iteration(events)
        assert sorted(its) == [0, 1, 2, 3]
        for i in (0, 2):
            assert [e["name"] for e in its[i][1]] == ["data_wait"]
        for i in (1, 3):
            step, kids = its[i]
            ev = {e["name"]: e for e in kids}
            assert sorted(ev) == sorted(
                ["data_wait", "train_step_fused", "batch_to_device",
                 "enqueue", "h2d_wait", "listeners"])
            assert ev["train_step_fused"]["args"]["steps"] == 2
            for name in ("batch_to_device", "enqueue"):
                assert (ev[name]["parent_id"]
                        == ev["train_step_fused"]["span_id"])
            for e in kids:
                assert _inside(e, by_id[e["parent_id"]]), e["name"]
            # the stacked window: two batches
            assert ev["batch_to_device"]["args"]["bytes"] == 2 * (
                8 * 4 * 4 + 8 * 3 * 4)

    @pytest.mark.parametrize("k", [1, 2])
    def test_params_identical_with_the_tracer_on_and_off(self, k):
        from deeplearning4j_tpu.observability.tracing import trace
        off = _mlp()
        off.fit(_data(32), batch_size=8, steps_per_device_call=k)
        on = _mlp()
        trace.clear()
        trace.enable()
        try:
            on.fit(_data(32), batch_size=8, steps_per_device_call=k)
        finally:
            trace.disable()
            trace.clear()
        assert on.iteration_count == off.iteration_count == 4
        for a, b in zip(_leaves(on), _leaves(off)):
            assert a.tobytes() == b.tobytes()

    def test_tracer_switched_inside_the_iterator(self):
        """As the benchmark does it: on inside the second ``next``, off
        inside the fourth, both of which the lookahead calls from the
        iteration before. The first iteration's ``step`` was opened
        with the tracer off (what runs after the switch hangs under
        nothing), the second is whole, the third is cut short after
        its lookahead's pull, nothing raises."""
        from deeplearning4j_tpu.observability.tracing import trace
        from deeplearning4j_tpu.data.iterators import DataSetIterator

        class Feed(DataSetIterator):
            def reset(self):
                pass

            def _iterate(self):
                for i in range(5):
                    if i == 1:
                        trace.enable()
                    if i == 3:
                        trace.disable()
                    yield _data(8)

        net = _mlp()
        trace.clear()
        try:
            net.fit(Feed())
            events = trace.events()
        finally:
            trace.disable()
            trace.clear()
        assert net.iteration_count == 5
        its = _by_iteration(events)
        assert sorted(its) == [1, 2]
        assert len(its[1][1]) == 6
        assert its[1][0]["args"]["prefetched"] is True
        assert [e["name"] for e in its[2][1]] == [
            "enqueue", "train_step", "h2d_wait", "data_wait"]
        waits = [e for e in events if e["name"] == "h2d_wait"]
        assert len(waits) == 2              # iterations 1 and 2
        assert all("parent_id" in e for e in waits)
        # the placement of the second batch, under no step
        placed = [e for e in events if e["name"] == "batch_to_device"]
        assert [("parent_id" in e, e["args"]["ahead"])
                for e in placed] == [(False, True), (True, True)]


# ---------------------------------------------------------------------------
# layer names on the train step's ops
# ---------------------------------------------------------------------------

class TestLayerScopes:
    def _lowered(self, net, batch):
        import jax
        if net._jit_train_step is None:
            net._jit_train_step = net._make_train_step()
        return net._jit_train_step.lower(
            net.params, net.state, net.opt_state, batch, net._rng_key,
            np.int32(0)).as_text(debug_info=True)

    def test_multilayer_ops_carry_layer_and_updater_names(self):
        net = _mlp()
        text = self._lowered(net, net._batch_tuple(_data(8)))
        for scope in ("0_DenseLayer", "1_OutputLayer", "updater"):
            assert scope in text, scope
        # the backward pass keeps the layer's name
        assert "transpose(jvp(0_DenseLayer))" in text

    def test_graph_ops_carry_vertex_names(self):
        net = _graph()
        text = self._lowered(
            net, net._batch_tuple(net._as_multi(_data(8))))
        for scope in ("hidden", "out", "updater"):
            assert f"/{scope}/" in text or f"({scope})" in text, scope


# ---------------------------------------------------------------------------
# the batcher loop
# ---------------------------------------------------------------------------

LM_V, LM_CAP = 13, 32


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


PROMPTS = [np.array([1, 2, 3]), np.array([4, 5]), np.array([6]),
           np.array([7, 8, 9, 10]), np.array([2, 9]), np.array([3])]
N_TOKENS = 5


def _metric(snap, name, **labels):
    for key, v in snap.items():
        if key.startswith(name + "{") and all(
                f'{k}="{val}"' in key for k, val in labels.items()):
            return v
    raise KeyError((name, labels))


CHUNK_T = 2


class TestBatcherSteps:
    @pytest.fixture(autouse=True)
    def _two_tokens_a_chunk(self, monkeypatch):
        from deeplearning4j_tpu.serving import continuous
        monkeypatch.setattr(continuous, "CHUNK_ROWS", 2 * CHUNK_T)
        monkeypatch.setattr(continuous, "WIDE_CHUNK_ROWS", 2 * CHUNK_T)

    def _run(self):
        from deeplearning4j_tpu.serving.continuous import (
            ContinuousBatcher)
        cb = ContinuousBatcher(_lm(), slots=2, capacity=LM_CAP,
                               queue_limit=16)
        assert cb._chunk_t == CHUNK_T
        handles = [cb.submit(p, N_TOKENS) for p in PROMPTS]
        got = [cb.wait(h) for h in handles]
        assert cb.drain()
        assert all(len(g) == N_TOKENS for g in got)
        return cb.metrics.registry.snapshot()

    def test_slot_steps_and_parts(self):
        """The loop's own rule: a request of P prompt tokens and N
        emitted tokens takes ceil(P / t) - 1 prompt slot-steps (each
        feeds t prompt tokens and discards the output; the step that
        feeds the last prompt token samples) and N decode slot-steps.
        No prompt here shares a whole page with another, so none is
        skipped by a prefix hit."""
        snap = self._run()
        prompt = _metric(snap, "serving_slot_steps_total", kind="prompt")
        decode = _metric(snap, "serving_slot_steps_total", kind="decode")
        assert prompt == sum(-(-len(p) // CHUNK_T) - 1 for p in PROMPTS)
        assert decode == N_TOKENS * len(PROMPTS)
        assert _metric(snap, "serving_prompt_tokens_total") == sum(
            len(p) for p in PROMPTS)
        batches = _metric(snap, "serving_batches_total")
        assert batches == sum(
            _metric(snap, "serving_steps_total", program=p)
            for p in ("single", "chunk"))
        items = _metric(snap, "serving_batch_items_total")
        assert prompt + decode == items     # every live slot did one
        parts = {p: _metric(snap, "serving_step_seconds", part=p)
                 for p in ("admit", "device", "sample")}
        for p, h in parts.items():
            assert h["count"] == batches, p
            assert h["sum"] > 0
        assert prompt + decode <= 2 * batches

    def test_serve_step_spans_when_the_tracer_is_on(self, traced):
        snap = self._run()
        events = [e for e in traced.events()
                  if e["name"].startswith("serve_step")]
        steps = [e for e in events if e["name"] == "serve_step"]
        assert len(steps) == _metric(snap, "serving_batches_total")
        by_parent = {}
        for e in events:
            if e["name"] != "serve_step":
                by_parent.setdefault(e["parent_id"], []).append(e)
        total = {"prompt_slots": 0, "decode_slots": 0, "active": 0,
                 "prompt_tokens": 0}
        assert {s["args"]["rows"] for s in steps} == {1, CHUNK_T}
        assert sum(s["args"]["rows"] > 1 for s in steps) == _metric(
            snap, "serving_steps_total", program="chunk")
        for s in steps:
            kids = by_parent[s["span_id"]]
            assert [k["name"] for k in kids] == [
                "serve_step/admit", "serve_step/device",
                "serve_step/sample"]
            assert all(_inside(k, s) for k in kids)
            for key in total:
                total[key] += s["args"][key]
        assert total["prompt_slots"] == sum(
            -(-len(p) // CHUNK_T) - 1 for p in PROMPTS)
        assert total["prompt_tokens"] == sum(len(p) for p in PROMPTS)
        assert total["decode_slots"] == N_TOKENS * len(PROMPTS)
        assert total["active"] == _metric(
            snap, "serving_batch_items_total")
        # ``ahead``: the step was enqueued while the one before it was
        # still owed its ids; the counter counts the same steps. Six
        # greedy requests through two slots keep the pool live, so
        # only the first step (and one after the pool ran empty, had
        # it) finds nothing in flight
        ahead = [bool(s["args"]["ahead"]) for s in steps]
        assert sum(ahead) == _metric(
            snap, "serving_lookahead_steps_total")
        assert not ahead[0] and sum(ahead) >= len(steps) - 2

    def test_the_parts_say_schedule_wait_and_deliver(self):
        """``admit`` is the scheduling on both sides of the enqueue
        (the plan before it, the count-only advance after it),
        ``device`` the enqueue and the wait for the ids that are due,
        ``sample`` their delivery: a sleep planted in each lands in
        its own part and in no other."""
        from deeplearning4j_tpu.serving.continuous import (
            ContinuousBatcher)
        cb = ContinuousBatcher(_lm(), slots=2, capacity=LM_CAP,
                               queue_limit=16)
        advance, deliver = cb._advance, cb._deliver
        step_ids = cb.session.step_ids

        def slept(fn, seconds):
            def slow(*a):
                time.sleep(seconds)
                return fn(*a)
            return slow

        cb._advance = slept(advance, 0.05)
        cb._deliver = slept(deliver, 0.02)
        cb.session.step_ids = slept(step_ids, 0.01)
        assert len(cb.generate(PROMPTS[0], N_TOKENS)) == N_TOKENS
        assert cb.drain()
        snap = cb.metrics.registry.snapshot()
        n = _metric(snap, "serving_batches_total")
        assert n == -(-len(PROMPTS[0]) // CHUNK_T) + N_TOKENS - 1
        parts = {p: _metric(snap, "serving_step_seconds", part=p)["sum"]
                 for p in ("admit", "device", "sample")}
        assert parts["admit"] >= 0.05 * n
        # every step but the warm-up's pair went through the slowed
        # entry point; the advance's sleep is not in this part
        assert 0.01 * n <= parts["device"] < 0.05 * n
        # the last step's ids are collected after the pool ran empty,
        # in a pass that enqueues nothing and records no parts
        assert 0.02 * (n - 1) <= parts["sample"] < 0.05 * n

    def test_evicting_the_endpoint_drops_the_step_series(self):
        from deeplearning4j_tpu.serving.metrics import ServingMetrics
        m = ServingMetrics()
        m.batcher_steps("generate/lm/v1").record(0.1, 0.2, 0.3, 1, 2)
        assert any(k.startswith("serving_step_seconds")
                   for k in m.registry.snapshot())
        m.evict_endpoint("generate/lm/v1")
        left = [k for k in m.registry.snapshot()
                if k.startswith(("serving_step_seconds",
                                 "serving_slot_steps_total",
                                 "serving_steps_total",
                                 "serving_prompt_tokens_total"))]
        assert left == []
