"""The readers of the program's own spans and of the batcher's step
counters (ISSUE 24), each on a made-up ``obs`` whose answer is computed
by hand; ``idle_named_pct.train`` on the recorded TPU trace beside
made-up spans, with the anchor event, fitted, and with residuals too
wide to fit; and a CPU profiler capture showing that an enabled
tracer's annotations and its anchor reach ``xplane.from_profile``."""

import json
import os

import pytest

from benchmark.harness import program_spans, spec, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000
# raw clock = trace clock - OFFSET
OFFSET = -5_000_000_000_000
# the recorded trace's three bursts of device ops (read by hand)
BURSTS = (48489957, 62404742, 76370169)
GAPS = ((56668962, 62404742), (70580244, 76370169))


def reader(name):
    return spec.load_module("layer_metrics", name)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return json.load(f)


def made_up_spans(jitter_ns=(0, 20_000, -10_000),
                  h2d_ms=(3.0, 4.0, 4.4)):
    """Three iterations laid around the recorded bursts, on the raw
    clock: ``h2d_wait`` ends where the device starts (plus jitter).
    The first iteration has no ``step`` (the tracer was switched on
    inside its ``data_wait``)."""
    events, n = [], [0]

    def add(name, t0, t1, parent=None, args=None):
        n[0] += 1
        ev = {"name": name, "t_ns": int(t0) - OFFSET,
              "dur_us": (t1 - t0) / 1e3, "span_id": f"s{n[0]}",
              "depth": 0, "tid": 1, "ts_us": 0.0}
        if parent is not None:
            ev["parent_id"] = parent
        if args:
            ev["args"] = args
        events.append(ev)
        return ev["span_id"]

    ends = list(BURSTS[1:]) + [84546727 + MS // 2 + 5.3 * MS]
    for i, b in enumerate(BURSTS):
        step = None
        if i > 0:
            step = add("step", b - 5.3 * MS, ends[i] - 5.3 * MS,
                       args={"iteration": i, "samples": 4})
            add("data_wait", b - 5.3 * MS, b - 5.1 * MS, step)
        ts = add("train_step", b - 5.0 * MS, b - 4.5 * MS, step)
        add("batch_to_device", b - 5.0 * MS, b - 4.7 * MS, ts,
            {"bytes": 100})
        add("enqueue", b - 4.7 * MS, b - 4.5 * MS, ts)
        h = b + jitter_ns[i]
        add("h2d_wait", h - h2d_ms[i] * MS, h, step)
        add("listeners", b + 0.1 * MS, ends[i] - 5.3 * MS, step)
    return events


def with_anchor(tr, at_raw=OFFSET * -1 + 1_000):
    """The trace plus the tracer's anchor host event."""
    out = dict(tr)
    out["host"] = tr["host"] + [[
        "python", f"{program_spans.ANCHOR}{at_raw}", at_raw + OFFSET, 800]]
    return out


# ---- program_span means

def test_means_take_whole_iterations_only():
    obs = {"spans": made_up_spans()}
    # iterations 1 and 2 have a step; the first does not
    assert reader("h2d_wait_ms.train").read(obs) == pytest.approx(4.2)
    assert reader("batch_to_device_ms.train").read(obs) == \
        pytest.approx(0.3)
    assert len(program_spans.in_whole_iterations(obs, "h2d_wait")) == 2
    # a step cut short (no iteration yet: data ran out) is not whole
    cut = made_up_spans()
    for e in cut:
        if e["name"] == "step":
            e["args"] = {"exhausted": True}
    assert reader("h2d_wait_ms.train").read({"spans": cut}) is None


def test_a_program_without_the_new_keys_gives_nothing():
    """The parent's tracer: no ``t_ns``, no ids, no ``h2d_wait``."""
    old = [{"name": n, "ts_us": 1.0, "dur_us": 5.0, "tid": 1, "depth": 0}
           for n in ("data_wait", "train_step", "listeners")]
    obs = {"spans": old, "trace": {"devices": [], "host": []},
           "counters": {"before": {}, "after": {}}}
    for name in ("h2d_wait_ms.train", "batch_to_device_ms.train",
                 "idle_named_pct.train", "step_device_ms.serve",
                 "step_host_ms.serve", "prompt_slot_steps_pct.serve"):
        assert reader(name).read(obs) is None
    assert reader("idle_named_pct.train").read({"spans": []}) is None


# ---- the offset

def test_anchor_offset_is_exact(recorded):
    assert program_spans.anchor_offset(recorded) is None
    assert program_spans.anchor_offset(with_anchor(recorded)) == OFFSET
    assert program_spans.anchor_offset({"host": []}) is None


def test_burst_starts_on_the_recorded_trace(recorded):
    ops = recorded["devices"][0]["ops"]
    assert program_spans.burst_starts(ops, 1_500_000) == list(BURSTS)
    assert program_spans.burst_starts(ops, 10 * MS) == [BURSTS[0]]


def test_fitted_offset_is_the_median_residual(recorded):
    fit = program_spans.fitted_offset(recorded, made_up_spans())
    # burst - wait end = -jitter: (0, -20, +10) us, median 0
    assert fit["offset_ns"] == OFFSET
    assert fit["steps"] == 3 and fit["dropped"] == (0, 0)
    assert fit["spread_ns"] == 30_000 and fit["worst_ns"] == 20_000
    # a sharded step starts when its last device does
    two = dict(recorded, devices=[recorded["devices"][0], {
        "name": "/device:TPU:1", "async": [],
        "ops": [[n, s + 7_000, d]
                for n, s, d in recorded["devices"][0]["ops"]]}])
    assert program_spans.fitted_offset(two, made_up_spans())[
        "offset_ns"] == OFFSET + 7_000
    # too few steps to pair
    assert program_spans.fitted_offset(
        recorded, made_up_spans()[:6]) is None


# ---- idle time by span

def _expected_split(h2d_ms, jitter_ns):
    """By hand, for the two long gaps (the ~1,900 gaps inside the
    bursts add 0.03 ms and are left to the tolerance)."""
    want = {}

    def add(name, s, e, gap):
        lo, hi = max(s, gap[0]), min(e, gap[1])
        if hi > lo:
            want[name] = want.get(name, 0.0) + (hi - lo) / 1e9

    for i, gap in zip((1, 2), GAPS):
        b = BURSTS[i]
        h = b + jitter_ns[i]
        add("listeners", BURSTS[i - 1] + 0.1 * MS, b - 5.3 * MS, gap)
        add("data_wait", b - 5.3 * MS, b - 5.1 * MS, gap)
        add("batch_to_device", b - 5.0 * MS, b - 4.7 * MS, gap)
        add("enqueue", b - 4.7 * MS, b - 4.5 * MS, gap)
        add("h2d_wait", h - h2d_ms[i] * MS, h, gap)
        # the step's own time: what no child covers
        add("step (self)", b - 5.1 * MS, b - 5.0 * MS, gap)
        add("step (self)", b - 4.5 * MS, h - h2d_ms[i] * MS, gap)
        add("step (self)", h, b, gap)
    return want


@pytest.mark.parametrize("how", ["anchor", "fitted"])
def test_idle_named_on_the_recorded_trace(recorded, how, capsys):
    jitter, h2d = (0, 20_000, -10_000), (3.0, 4.0, 4.4)
    tr = with_anchor(recorded) if how == "anchor" else recorded
    obs = {"trace": tr, "spans": made_up_spans(jitter, h2d)}
    got = reader("idle_named_pct.train").read(obs)
    out = capsys.readouterr().out
    assert "fitted offset" in out and "residuals spread 30.0 us" in out
    assert ("exact offset" in out) == (how == "anchor")
    if how == "anchor":
        assert "fitted - exact = 0.0 us" in out
    idle, split, named = program_spans.idle_by_span(
        tr, obs["spans"], OFFSET)
    busy, span = xplane.busy_and_window(recorded)
    assert idle == pytest.approx(span - busy)
    want = _expected_split(h2d, jitter)
    assert set(want) <= set(split)
    for name, sec in want.items():
        assert split[name] == pytest.approx(sec, abs=4e-5), name
    # h2d_wait names most of it: 3.98 + 4.4 ms of 11.56
    assert split["h2d_wait"] == pytest.approx(0.00838, abs=1e-6)
    # named: every leaf span; not the step's own 0.62 + 0.20 ms
    leaves = sum(v for k, v in split.items() if "(self)" not in k)
    assert named == pytest.approx(leaves)
    assert split["step (self)"] == pytest.approx(0.00082, abs=4e-5)
    assert got == pytest.approx(100.0 * named / idle)
    assert 91.0 < got < 94.0


def test_a_fit_too_wide_gives_nothing(recorded, capsys):
    """Residuals of +3 and -2 ms: no number without an anchor; with
    one the exact offset is used whatever the fit says."""
    spans = made_up_spans(jitter_ns=(0, 3 * MS, -2 * MS))
    r = reader("idle_named_pct.train")
    assert r.read({"trace": recorded, "spans": spans}) is None
    assert "nothing to read" in capsys.readouterr().out
    assert r.read({"trace": with_anchor(recorded),
                   "spans": spans}) is not None


# ---- the batcher's counters

def _serve_obs():
    def hist(s, c):
        return {"sum": s, "count": c, "p50": 0, "p95": 0, "p99": 0}
    ep = 'endpoint="generate/lm/v1"'
    key = "serving_step_seconds{%s,part=\"%%s\"}" % ep
    kind = "serving_slot_steps_total{%s,kind=\"%%s\"}" % ep
    before = {key % "admit": hist(1.0, 100), key % "device": hist(10.0, 100),
              key % "sample": hist(2.0, 100),
              kind % "prompt": 500.0, kind % "decode": 300.0}
    after = {key % "admit": hist(1.2, 300), key % "device": hist(15.0, 300),
             key % "sample": hist(2.6, 300),
             kind % "prompt": 1700.0, kind % "decode": 700.0}
    return {"counters": {"before": before, "after": after}}


def test_serve_step_readers():
    obs = _serve_obs()
    # 5.0 s over 200 steps
    assert reader("step_device_ms.serve").read(obs) == pytest.approx(25.0)
    # (0.2 + 0.6) s over the device part's 200 steps
    assert reader("step_host_ms.serve").read(obs) == pytest.approx(4.0)
    # 1,200 prompt slot-steps of 1,600
    assert reader("prompt_slot_steps_pct.serve").read(obs) == \
        pytest.approx(75.0)


def test_the_registry_writes_the_keys_the_readers_match():
    """A real ``BatcherStepMetrics`` through ``registry.snapshot()``,
    the way the serve driver takes it."""
    from deeplearning4j_tpu.serving.metrics import ServingMetrics
    m = ServingMetrics()
    steps = m.batcher_steps("generate/lm/v1")
    obs = {"counters": {"before": m.registry.snapshot()}}
    steps.record(0.001, 0.020, 0.003, 6, 2)
    steps.record(0.003, 0.030, 0.001, 2, 6)
    obs["counters"]["after"] = m.registry.snapshot()
    assert reader("step_device_ms.serve").read(obs) == pytest.approx(25.0)
    assert reader("step_host_ms.serve").read(obs) == pytest.approx(4.0)
    assert reader("prompt_slot_steps_pct.serve").read(obs) == \
        pytest.approx(50.0)


def test_every_new_metric_has_an_entry_and_a_reader():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, source, moves in (
            ("h2d_wait_ms.train", "program_span",
             "train_samples_per_s_per_chip"),
            ("batch_to_device_ms.train", "program_span",
             "train_samples_per_s_per_chip"),
            ("idle_named_pct.train", "device_trace",
             "train_samples_per_s_per_chip"),
            ("step_device_ms.serve", "program_counter",
             "serve_tokens_per_s"),
            ("step_host_ms.serve", "program_counter",
             "serve_tokens_per_s"),
            ("prompt_slot_steps_pct.serve", "program_counter",
             "serve_tokens_per_s")):
        assert by_name[name]["source"] == source
        assert by_name[name]["moves"] == moves
        assert reader(name) is not None


# ---- the by-hand tool's reading of an op's scope

@pytest.mark.parametrize("op_name, want", [
    ("jit(train_step)/jvp(1_TransformerEncoderLayer)/mlp/dot_general",
     ("TransformerEncoderLayer_mlp", "forward")),
    ("jit(train_step)/transpose(jvp(7_TransformerEncoderLayer))/ln2/neg",
     ("TransformerEncoderLayer_ln2", "backward")),
    ("jit(train_step)/transpose(jvp(25_RnnOutputLayer))/jit",
     ("RnnOutputLayer", "backward")),
    ("jit(train_step)/jvp(0_EmbeddingSequenceLayer)/jit",
     ("EmbeddingSequenceLayer", "forward")),
    ("jit(train_step)/updater/mul", ("updater", "update")),
    ("jit(train_step)/jvp(s2b3_b_conv)/conv_general_dilated",
     ("s2b3_b_conv", "forward")),
    ("jit(train_step)/transpose(jvp(stem_bn))/reduce_sum",
     ("stem_bn", "backward")),
    ("jit(train_step)/jvp(out)/dot_general", ("out", "forward")),
    ("jit(train_step)/broadcast_in_dim", ("(no scope)", "forward")),
    ("", ("(no scope)", "forward")),
])
def test_scope_of_an_op_name(op_name, want):
    from benchmark.tests import measure_layer_scopes as tool
    assert tool.scope_of(op_name) == want


def test_op_names_of_a_compiled_step():
    """The tool's hook on a real (CPU-compiled) train step: the
    compiled module's instructions carry the layers' scopes."""
    import numpy as np
    from benchmark.tests import measure_layer_scopes as tool
    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    conf = (NeuralNetConfiguration.builder().set_seed(0).list()
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=3, loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    net = MultiLayerNetwork(conf).init()
    found = {}
    tool.grab_hlo(found)(net)
    rng = np.random.default_rng(0)
    net.fit(DataSet(rng.normal(0, 1, (8, 4)).astype("float32"),
                    np.eye(3, dtype="float32")[rng.integers(0, 3, 8)]))
    scopes = {tool.scope_of(v)[0]
              for v in tool.op_names(found["hlo"]).values()}
    assert {"DenseLayer", "OutputLayer", "updater"} <= scopes


def test_kind_of_a_resnet_vertex():
    from benchmark.tests import measure_layer_scopes as tool
    assert tool.kind_of("s2b3_b_conv") == "s2 conv"
    assert tool.kind_of("s0b0_sc_bn") == "s0 bn"
    assert tool.kind_of("s3b1_add") == "s3 add"
    assert tool.kind_of("stem_pool") == "stem pool"
    assert tool.kind_of("stem_conv") == "stem conv"
    assert tool.kind_of("TransformerEncoderLayer_mlp") == \
        "TransformerEncoderLayer_mlp"
    assert tool.kind_of("out") == "out" and tool.kind_of(
        "updater") == "updater"


# ---- the tracer's annotations in a profiler capture (CPU)

def test_annotations_and_anchor_reach_the_host_list(tmp_path):
    import time

    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.observability.tracing import Tracer
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    tracer = Tracer()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        tracer.enable()
        with tracer.span("step", annotate=False):
            with tracer.span("h2d_wait"):
                time.sleep(0.005)
                jnp.ones((64, 64)).sum().block_until_ready()
        events = tracer.events()
        tracer.disable()
    finally:
        jax.profiler.stop_trace()
    tr = xplane.load_dir(str(tmp_path))
    names = [h[1] for h in tr["host"]]
    assert "dl4j/h2d_wait" in names
    assert "dl4j/step" not in names          # a group: not annotated
    offset = program_spans.anchor_offset(tr)
    assert offset is not None
    wait = next(h for h in tr["host"] if h[1] == "dl4j/h2d_wait")
    span = next(e for e in events if e["name"] == "h2d_wait")
    # the span's raw start lands on the annotation's start to 0.2 ms
    # (a few microseconds in practice)
    assert abs(span["t_ns"] + offset - wait[2]) < 200_000
    assert abs(span["dur_us"] * 1e3 - wait[3]) < 200_000
