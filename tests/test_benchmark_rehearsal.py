"""Every cell of ``BENCHMARK.json`` rehearsed in tier-1: the benchmark
the driver runs on the chip is guarded here on the CPU, so a program
change that breaks a cell, or a per-layer reader's span or counter, is
a named failure and not a reading that vanished from the ledger.

Three contracts a cell:

- ``benchmark/run.py`` prints no result without a chip;
- at its tiny preset, given the CPU's devices by the test (no option of
  the program does that), the cell runs to a ``correct`` result that
  reports exactly its end-to-end metrics and names the platform;
- a ``--trace 1`` rehearsal brings back every per-layer metric the
  program itself feeds (``source`` ``program_span`` /
  ``program_counter``), the five ``setup_*`` readers of the set-up
  timeline among them. The ``device_trace`` readers need the TPU's
  "XLA Ops" plane and are the chip's to show.

A fourth, for the serving cells: a served token altered where it is
produced comes out not ``correct``. Since PR 32 a greedy token is
produced on the device and reaches the host in ``_fetch``;
``benchmark/tests/test_cells_cpu.py`` still alters ``_sample``, which
only a request with a temperature passes now (a ``benchmark`` issue's
to move: PERF.md section 7), so the contract is held here.

``python -m pytest benchmark/tests -q`` remains the fuller hand run
(controls, broken steps, the recorded trace). Nothing of it is
imported: its conftest sets the environment of its own process.
"""

import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run          # noqa: E402
from benchmark.harness import session, spec     # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]

# One fixed seed a cell, the hand run's (more than 32 signed bits
# hold). Each passed 5 runs of 5 of this file at PR 28's parent on the
# builder's CPU. No seed makes the tiny axk1_serve_decode preset's
# widest-gap check steady: the same seed read 0.015, 0.17, 0.17 in
# three runs, and 2 runs of 18 over six seeds crossed its limit of 0.5
# (my CPU runs, PR 28). The number is a maximum over some 300 served
# tokens of what bfloat16 router flips cost, and which requests are in
# flight together, hence which flips, is the threads' timing. So that
# cell alone gets ATTEMPTS runs to come out ``correct``: a token
# altered where it is produced fails every one of them, a flip past
# the limit one in nine.
SEED = 2 ** 31 + 12345
ATTEMPTS = {"axk1_serve_decode": 3}


def _merge(dst, src):
    for k, v in src.items():
        if isinstance(v, dict) and isinstance(dst.get(k), dict):
            _merge(dst[k], v)
        else:
            dst[k] = v


@pytest.fixture
def tiny(monkeypatch):
    """``spec.load`` returns each cell at its tiny preset
    (``benchmark/tests/tiny/<cell>.json`` over the real files): the
    real sizes need the chip."""
    real = spec.load

    def load(workload):
        cell = real(workload)
        with open(os.path.join(ROOT, "benchmark", "tests", "tiny",
                               workload + ".json")) as f:
            over = json.load(f)
        _merge(cell.config, over.get("config", {}))
        _merge(cell.traffic, over.get("traffic", {}))
        return cell

    monkeypatch.setattr(spec, "load", load)
    return load


@pytest.fixture(autouse=True)
def compile_cache_of_this_run(tmp_path_factory, monkeypatch):
    """A ``Session`` switches jax's persistent compile cache on for
    the whole process (``util/platform.py``). Here it gets a directory
    of this run's own, so a cell's traced rehearsal finds what its
    end-to-end rehearsal compiled, and afterwards the process compiles
    as every other test expects."""
    import jax
    from jax.experimental.compilation_cache import (
        compilation_cache as cc)
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    where = str(tmp_path_factory.getbasetemp() / "rehearsal_jax_cache")
    # what jax does at import where the variable is set: with it set,
    # setup_compile_cache names no directory of its own
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", where)
    jax.config.update("jax_compilation_cache_dir", where)
    cc.reset_cache()
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    cc.reset_cache()


def _cpu_devices(chips):
    import jax
    return jax.devices()[:chips]


def _args(cell, trace=0):
    return ["--workload", cell, "--seed", str(SEED),
            "--seconds", "2", "--trace", str(trace)]


@pytest.mark.parametrize("cell", CELLS)
def test_refuses_without_a_chip(cell, tiny, capsys):
    with pytest.raises(SystemExit) as e:
        bench_run.main(_args(cell))
    assert e.value.code not in (0, None)
    assert '"correct"' not in capsys.readouterr().out


@pytest.mark.parametrize("cell", CELLS)
def test_cell_end_to_end_tiny(cell, tiny, capsys):
    for _ in range(ATTEMPTS.get(cell, 1)):
        r = bench_run.main(_args(cell), find_devices=_cpu_devices)
        out = capsys.readouterr().out
        if r["correct"]:
            break
    assert r["correct"], out
    assert r["failed"] == 0 and r["attempted"] > 0
    want = {m["name"] for m in spec.load(cell).end_to_end}
    assert set(r["metrics"]) == want and "setup_s" in want
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert json.loads(out.strip().splitlines()[-1]) == r
    assert r["device"]["platform"] == "cpu"      # and says so


@pytest.mark.parametrize("cell", CELLS)
def test_program_fed_layer_metrics_come_back(cell, tiny, capsys,
                                             monkeypatch):
    c = spec.load(cell)
    want = {m["name"] for m in c.per_layer
            if m["source"] in ("program_span", "program_counter")}
    assert want >= {"setup_init_s", "setup_trace_lower_s",
                    "setup_cache_load_s", "setup_programs_s",
                    "setup_named_pct"}
    # the ``setup_*`` readers bound the set-up by ``run.py``'s first
    # reading of the clock, ``__main__.T_START``: here the session's
    t_start = time.perf_counter()
    monkeypatch.setattr(sys.modules["__main__"], "T_START", t_start,
                        raising=False)
    s = session.Session(c, SEED, 2.0, 1, t_start, find=_cpu_devices)
    driver = spec.load_module("drivers", c.traffic["driver"])
    # the CPU's profiler capture has no "XLA Ops" plane, so result()
    # stops at the device's busy share: after the window, with every
    # span and counter of the traced part already in s.obs
    with pytest.raises(RuntimeError, match="holds no device operation"):
        driver.run(s)
    got = {name: spec.load_module("layer_metrics", name).read(s.obs)
           for name in want}
    # the tiny pools are 4 slots of a page of 4: none holds a wide
    # chunk program, and where there is none its share has no reading
    assert got.pop("wide_steps_pct.serve", None) is None
    # off a TPU the experts' dense pass runs at every width
    assert got.pop("moe_grouped_steps_pct.serve", None) is None
    missing = sorted(n for n, v in got.items() if v is None)
    assert not missing, (missing, capsys.readouterr().out[-4000:])
    # the set-up timeline: ``init()`` and each step program's first
    # call are inside it, every compile is traced and lowered first,
    # and what is named is part of ``setup_s``
    assert got["setup_init_s"] > 0 and got["setup_programs_s"] > 0
    assert got["setup_trace_lower_s"] > 0
    assert got["setup_cache_load_s"] >= 0
    assert 0 < got["setup_named_pct"] <= 100
    # the CPU's paged step gathers: it reads what its tables span
    assert got.get("kv_read_pct.serve", 100.0) == 100.0


GROUPED = next(m for m in BENCH["per_layer"]
               if m["name"] == "moe_grouped_steps_pct.serve")


@pytest.mark.parametrize("cell", GROUPED["workloads"])
def test_grouped_steps_are_read_where_a_program_groups(
        cell, tiny, capsys, monkeypatch):
    """The four expert cells with the layer's predicate moved down to
    the tiny pools (the test's steering: every chunk step groups, the
    single-row program keeps the dense pass) and the kernel
    interpreted: the batcher's counter reaches the reader through the
    driver, and the share it reads is the chunk steps'."""
    import functools

    from deeplearning4j_tpu.ops import grouped_experts
    c = spec.load(cell)
    slots = c.traffic["server"]["slots"]
    monkeypatch.setattr(grouped_experts, "grouped_pass",
                        lambda n, *_: n > slots)
    monkeypatch.setattr(
        grouped_experts, "pallas_grouped_experts", functools.partial(
            grouped_experts.pallas_grouped_experts, interpret=True))
    s = session.Session(c, SEED, 2.0, 1, time.perf_counter(),
                        find=_cpu_devices)
    driver = spec.load_module("drivers", c.traffic["driver"])
    with pytest.raises(RuntimeError, match="holds no device operation"):
        driver.run(s)
    read = lambda name: spec.load_module("layer_metrics", name).read(s.obs)
    got = read("moe_grouped_steps_pct.serve")
    assert got is not None and got > 0, capsys.readouterr().out[-4000:]
    assert got == pytest.approx(read("chunk_steps_pct.serve"))


SERVE = [w["name"] for w in BENCH["workloads"]
         if "serve_tokens_per_s" in {
             m["name"] for m in BENCH["end_to_end"]
             if w["name"] in m.get("workloads", [w["name"]])}]


@pytest.mark.parametrize("cell", SERVE)
def test_a_token_altered_where_it_is_produced_is_not_correct(
        cell, tiny, capsys, monkeypatch):
    def break_token(server):
        from deeplearning4j_tpu.serving.continuous import (
            ContinuousBatcher)
        vocab = spec.load(cell).config["vocab_size"]
        real = ContinuousBatcher._fetch

        def off_by_one(self, st):
            got = real(self, st)
            if st.rows:
                return got
            return ((got[0] + 1) % vocab,) + tuple(got[1:])
        monkeypatch.setattr(ContinuousBatcher, "_fetch", off_by_one)

    r = bench_run.main(_args(cell), find_devices=_cpu_devices,
                       break_token=break_token)
    assert r["attempted"] > 0
    assert r["correct"] is False, capsys.readouterr().out
