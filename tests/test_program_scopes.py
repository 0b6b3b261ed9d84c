"""The registry of step programs (``observability/programs.py``) and
the benchmark's reduction of a trace by it (``benchmark/harness/
scopes.py``), on the CPU: who registers what and when, what a
registration keeps alive, that a step past the first calls nothing of
it, and that ``assign`` / ``busy_by`` give back exactly the shares of
a trace made from the tables. The batcher's enqueue histogram
(``serving_step_enqueue_seconds``) is held here too."""

import gc
import json
import os
import sys
import weakref

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402

from benchmark.harness import scopes, spec, weights  # noqa: E402
from deeplearning4j_tpu import (  # noqa: E402
    ComputationGraph, MultiLayerNetwork, NeuralNetConfiguration)
from deeplearning4j_tpu.data.dataset import DataSet  # noqa: E402
from deeplearning4j_tpu.models.paged_kv import PagedSlotSession  # noqa: E402
from deeplearning4j_tpu.nn.conf import updaters  # noqa: E402
from deeplearning4j_tpu.nn.conf.inputs import InputType  # noqa: E402
from deeplearning4j_tpu.nn.conf.layers import (  # noqa: E402
    DenseLayer, EmbeddingSequenceLayer, OutputLayer, RnnOutputLayer,
    TransformerEncoderLayer)
from deeplearning4j_tpu.observability import programs  # noqa: E402
from deeplearning4j_tpu.observability.compile_watch import (  # noqa: E402
    install_global_watch)

SERVE_PRESETS = ("mimo_serve_mixedlen", "gpt2m_serve_closed")


@pytest.fixture(autouse=True)
def empty_registry():
    programs.PROGRAMS.clear()
    yield
    programs.PROGRAMS.clear()


def _merge(dst, src):
    for k, v in src.items():
        if isinstance(v, dict) and isinstance(dst.get(k), dict):
            _merge(dst[k], v)
        else:
            dst[k] = v


def _tiny_session(workload):
    """The paged session of a serving cell at its tiny preset
    (``benchmark/tests/tiny``), built as the serving driver builds
    its network; and the chunk width its batcher would run."""
    cell = spec.load(workload)
    with open(os.path.join(ROOT, "benchmark", "tests", "tiny",
                           workload + ".json")) as f:
        over = json.load(f)
    _merge(cell.config, over["config"])
    _merge(cell.traffic, over["traffic"])
    builder = spec.load_module("builders", cell.config["builder"])
    sv = cell.traffic["server"]
    with builder.policy(cell.config):
        net = builder.build(cell.config).init()
        shapes = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), net.params)
        net.params = weights.maker(shapes, cell.config["init"])(7)
        sess = PagedSlotSession(net, sv["slots"], sv["capacity"],
                                sv["page_size"])
        for t in (2, 1):
            _idle_step(sess, t)
    return sess


def _idle_step(sess, t):
    idle = np.zeros((sess.slots,), np.int32)
    return sess.step_ids(np.zeros((sess.slots, t, 1), np.float32), idle,
                         idle > 0)


def _mlp_net():
    conf = (NeuralNetConfiguration.builder().set_seed(1)
            .updater(updaters.adam(1e-2)).list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
            .layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                               loss="mcxent")).build())
    return MultiLayerNetwork(conf).init()


def _graph_net():
    g = (NeuralNetConfiguration.builder().set_seed(1)
         .updater(updaters.adam(1e-2)).graph_builder()
         .add_inputs("in")
         .add_layer("d", DenseLayer(n_in=4, n_out=8, activation="relu"),
                    "in")
         .add_layer("out", OutputLayer(n_in=8, n_out=3,
                                       activation="softmax",
                                       loss="mcxent"), "d")
         .set_outputs("out").build())
    return ComputationGraph(g).init()


def _batch(n=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    return x, np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]


def _op_names(table):
    return [op_name for _, op_name in table]


# ---- (a) who registers, and what the tables hold

@pytest.mark.parametrize("workload", SERVE_PRESETS)
def test_a_paged_session_registers_both_widths_of_its_step(workload):
    _tiny_session(workload)
    stats = install_global_watch()
    tables = programs.scope_tables()
    assert sorted(tables) == ["paged_step_ids/t=1", "paged_step_ids/t=2"]
    want = {"mimo_serve_mixedlen": ("attn/window", "attn/global",
                                    "moe/experts", "mlp"),
            "gpt2m_serve_closed": ("ln1", "attn", "ln2", "mlp")}[workload]
    for name, table in tables.items():
        labels = {scopes.label(o) for o in _op_names(table)}
        for part in want:
            assert any(l.endswith("/" + part) for l in labels), (
                name, part, sorted(labels))
        # the row-returning step was not asked for: no table of it
    mark = stats.mark()
    again = programs.scope_tables()
    assert again == tables
    assert stats.summary(mark)["backend_compiles"] == 0
    assert stats.summary(mark)["cache_requests"] == 0


def test_the_row_returning_step_registers_when_first_asked_for():
    sess = _tiny_session("gpt2m_serve_closed")
    assert not any(k.startswith("paged_step") and "ids" not in k
                   for k in programs.scope_tables())
    sess.step_slots(np.zeros((sess.slots, 1, 1), np.float32),
                    np.zeros((sess.slots,), bool))
    sess.step_chunk(np.zeros((sess.slots, 2, 1), np.float32),
                    np.zeros((sess.slots,), np.int32))
    assert {"paged_step/t=1", "paged_step_chunk/t=2"} <= set(
        programs.scope_tables())


@pytest.mark.parametrize("make", [_mlp_net, _graph_net],
                         ids=["multi_layer_network", "computation_graph"])
def test_an_executor_registers_its_train_step_and_fused_window(make):
    net = make()
    x, y = _batch()
    net.fit(DataSet(x, y))
    tables = programs.scope_tables()
    assert list(tables) == ["train_step"]
    ops = _op_names(tables["train_step"])
    assert any(scopes.group(o) == {"updater"} for o in ops)
    assert any("backward" in scopes.group(o) for o in ops)
    assert any("transpose(" in o for o in ops)
    net.fit_batches([DataSet(x, y)] * 3, steps_per_device_call=3)
    assert sorted(programs.scope_tables()) == ["train_step",
                                               "train_step_fused/k=3"]


def test_a_sharded_train_step_is_lowered_with_its_shardings():
    net = _mlp_net()
    x, y = _batch()
    net.fit(x, y, mesh_spec="dp=4")
    table = programs.scope_tables()["train_step"]
    assert any(name.startswith("all-reduce") for name, _ in table)


def test_a_table_is_built_under_the_policy_the_program_ran_under():
    from deeplearning4j_tpu import dtypes
    with dtypes.policy_scope(dtypes.tpu_bf16()):
        net = _mlp_net()
        net.fit(*_batch())
    calls = []
    real = dtypes.policy_scope
    try:
        dtypes.policy_scope = lambda p: (calls.append(p), real(p))[1]
        programs.scope_tables()
    finally:
        dtypes.policy_scope = real
    assert [p.compute_dtype for p in calls] == [
        dtypes.tpu_bf16().compute_dtype]


# ---- (c) what a registration keeps alive

def _reachable_arrays(root):
    seen, stack, found = set(), [root], []
    while stack:
        obj = stack.pop()
        if id(obj) in seen or isinstance(obj, (type, type(sys))):
            continue
        seen.add(id(obj))
        if isinstance(obj, jax.Array):
            found.append(obj)
            continue
        stack.extend(gc.get_referents(obj))
    return found


@pytest.mark.parametrize("what", ["session", "network"])
def test_the_registry_keeps_no_array_and_no_owner_alive(what):
    if what == "session":
        owner = _tiny_session("gpt2m_serve_closed")
        net = weakref.ref(owner.net)
    else:
        owner = _mlp_net()
        owner.fit(*_batch())
        net = weakref.ref(owner)
    assert not _reachable_arrays(programs.PROGRAMS._programs)
    ref = weakref.ref(owner)
    del owner
    gc.collect()
    assert ref() is None and net() is None
    # and the table can still be built from what was kept
    assert all(programs.scope_tables().values())


# ---- (d) one registration a program, never one a step

@pytest.fixture
def registry_calls(monkeypatch):
    calls = []
    for name in ("register", "scope_tables", "clear"):
        real = getattr(programs.ProgramRegistry, name)

        def counted(self, *a, _real=real, _name=name, **kw):
            calls.append(_name)
            return _real(self, *a, **kw)
        monkeypatch.setattr(programs.ProgramRegistry, name, counted)
    monkeypatch.setattr(programs, "register", programs.PROGRAMS.register)
    return calls


def test_a_paged_step_past_the_first_calls_nothing_of_the_registry(
        registry_calls):
    sess = _tiny_session("gpt2m_serve_closed")
    assert registry_calls == ["register", "register"]
    for _ in range(20):
        _idle_step(sess, 2)
        _idle_step(sess, 1)
    assert registry_calls == ["register", "register"]


def test_a_fit_step_past_the_first_calls_nothing_of_the_registry(
        registry_calls):
    net = _mlp_net()
    x, y = _batch(64)
    net.fit(x, y, batch_size=16)
    assert registry_calls == ["register"]
    jitted = net._jit_train_step
    net.fit(x, y, batch_size=16, epochs=5)          # 20 steps
    assert registry_calls == ["register"]
    assert net._jit_train_step is jitted            # and no wrapper


# ---- (b) assign / busy_by on a trace made from the tables

def _trace(runs, stray_ns=0):
    """A trace whose first device runs ``runs`` (lists of
    ``(instruction, ns)``) one after another, 1 us apart, with one
    ``copy.99`` of ``stray_ns`` between the first two."""
    ops, at = [], 1_000
    for k, run in enumerate(runs):
        for name, ns in run:
            ops.append(["%" + name, at, ns])
            at += ns
        at += 1_000
        if k == 0 and stray_ns:
            ops.append(["%copy.99", at, stray_ns])
            at += stray_ns + 1_000
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "async": []}], "host": [], "text": {}}


# two programs with the same instruction names in another order and
# under other scopes; ``param.0`` and ``tuple.9`` never run
WIDE = [("param.0", "x"),
        ("fusion.1", "jit(step_ids)/0_Block/attn/global/dot_general"),
        ("fusion.2", "jit(step_ids)/0_Block/attn/window/dot_general"),
        ("fusion.3", "jit(step_ids)/0_Block/moe/experts/dot_general"),
        ("copy.4", ""),
        ("fusion.5", "jit(step_ids)/0_Block/mlp/dot_general"),
        ("fusion.6", "jit(step_ids)/argmax"),
        ("tuple.9", "")]
NARROW = [("param.0", "x"),
          ("fusion.2", "jit(step_ids)/0_Block/moe/experts/dot_general"),
          ("fusion.1", "jit(step_ids)/0_Block/mlp/dot_general"),
          ("fusion.3", "jit(step_ids)/0_Block/attn/window/dot_general"),
          ("fusion.5", "jit(step_ids)/1_RMSNormalization/mul"),
          ("tuple.9", "")]
TABLES = {"paged_step_ids/t=2": WIDE, "paged_step_ids/t=1": NARROW}
NS = {"fusion.1": 100, "fusion.2": 200, "fusion.3": 400, "copy.4": 50,
      "fusion.5": 150, "fusion.6": 100}


def _run(table):
    return [(name, NS[name]) for name, _ in table if name in NS]


def test_runs_of_two_programs_with_colliding_names_are_told_apart():
    wide, narrow = _run(WIDE), _run(NARROW)
    tr = _trace([wide, wide, narrow] * 4, stray_ns=30)
    ops, rows, runs = scopes.assign(tr, TABLES)
    assert runs == {"paged_step_ids/t=2": 8, "paged_step_ids/t=1": 4}
    assert [r for r in rows if r[0] is None] == [(None, "")]
    by = scopes.busy_by({"trace": tr}, TABLES)
    busy = 8 * 1000 + 4 * 850 + 30
    assert by["busy"] == pytest.approx(busy / 1e9)
    want = {"attention": 8 * 300 + 4 * 400,
            "window_attention": 8 * 200 + 4 * 400,
            "experts": 8 * 400 + 4 * 200,
            "mlp": 8 * 150 + 4 * 100,
            "unscoped": 8 * 150 + 30}
    for g, ns in want.items():
        assert by[g] == pytest.approx(ns / 1e9), g
    obs = {"trace": tr, "busy_by_scope": by}
    assert scopes.share_pct(obs, "experts") == pytest.approx(
        100.0 * want["experts"] / busy)
    assert scopes.share_pct(obs, "updater") == 0.0


def test_six_percent_outside_every_program_gives_no_number(capsys):
    wide = _run(WIDE)
    tr = _trace([wide] * 4, stray_ns=256)           # 256 of 4256
    assert scopes.busy_by({"trace": tr}, TABLES) is None
    assert "matched no registered program" in capsys.readouterr().out
    assert scopes.share_pct({"trace": tr, "busy_by_scope": None},
                            "attention") is None
    tr = _trace([wide] * 4, stray_ns=200)           # 4.8 %
    assert scopes.busy_by({"trace": tr}, TABLES) is not None


@pytest.mark.parametrize("tables, why", [
    ({}, "registered no step program"),
    (None, "registered no step program")])
def test_an_empty_registry_gives_no_number(tables, why, capsys):
    tr = _trace([_run(WIDE)] * 2)
    assert scopes.busy_by({"trace": tr}, tables) is None
    assert why in capsys.readouterr().out


def test_a_loop_body_inside_its_while_is_counted_once():
    ops = [["%while.1", 0, 1000], ["%fusion.1", 100, 300],
           ["%fusion.2", 500, 400], ["%fusion.3", 1000, 50]]
    assert scopes.own_ns(ops) == [300, 300, 400, 50]


def test_tables_of_real_programs_give_back_their_own_shares():
    """A trace made from the tiny mimo preset's two tables, each
    instruction an op 1 us long, two chunk steps to one single."""
    _tiny_session("mimo_serve_mixedlen")
    tables = programs.scope_tables()
    runs = {p: [(n, 1000) for n, _ in t] for p, t in tables.items()}
    wide, narrow = runs["paged_step_ids/t=2"], runs["paged_step_ids/t=1"]
    tr = _trace([wide, wide, narrow] * 3)
    by = scopes.busy_by({"trace": tr}, tables)
    want = {}
    for p, n in (("paged_step_ids/t=2", 6), ("paged_step_ids/t=1", 3)):
        for _, op_name in tables[p]:
            for g in scopes.group(op_name):
                want[g] = want.get(g, 0) + n * 1000
    assert want["window_attention"] and want["experts"]
    for g, ns in want.items():
        assert by[g] == pytest.approx(ns / 1e9), g


# ---- (e) the enqueue, apart from the wait

def test_the_enqueue_histogram_counts_every_step_and_stays_within_device():
    from deeplearning4j_tpu.serving import ContinuousBatcher
    from deeplearning4j_tpu.serving.metrics import ServingMetrics
    v = 13
    conf = (NeuralNetConfiguration.builder().set_seed(3)
            .updater(updaters.adam(1e-3)).list()
            .layer(EmbeddingSequenceLayer(n_in=v, n_out=16))
            .layer(TransformerEncoderLayer(n_heads=2, causal=True))
            .layer(RnnOutputLayer(n_out=v, loss="mcxent"))
            .set_input_type(InputType.recurrent(v, 64)).build())
    metrics = ServingMetrics()
    cb = ContinuousBatcher(MultiLayerNetwork(conf).init(), slots=2,
                           capacity=64, kv_mode="paged", page_size=4,
                           metrics=metrics, name="lm")
    try:
        for seed in range(3):
            prompt = np.random.default_rng(seed).integers(0, v, 9)
            assert len(cb.generate(prompt.tolist(), 6)) == 6
    finally:
        cb.shutdown()
    snap = metrics.registry.snapshot()
    enqueue = snap['serving_step_enqueue_seconds{endpoint="lm"}']
    device = snap['serving_step_seconds{endpoint="lm",part="device"}']
    assert enqueue["count"] == device["count"] > 0
    assert 0.0 < enqueue["sum"] <= device["sum"]
