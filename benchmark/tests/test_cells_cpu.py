"""Every cell end to end at its tiny preset on the CPU, two seconds:
the run refuses without a chip; with the rehearsal hook (given inside
the test, not through an option of the program) it drives everything
but the look for a chip. Then the two proofs that ``correct`` can come
out false: the control (the reference in the precision below the
configuration's) and a timed path broken underneath."""

import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.harness import spec, train_check, weights
from benchmark.tests.conftest import ROOT, cpu_devices

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
TRAIN = [w["name"] for w in BENCH["workloads"]
         if spec.Cell(BENCH, w).traffic["driver"] == "train_iterator"]
SERVE = [c for c in CELLS if c not in TRAIN]


def _args(cell, seed=7, trace=0):
    return ["--workload", cell, "--seed", str(seed), "--seconds", "2",
            "--trace", str(trace)]


@pytest.mark.parametrize("cell", CELLS)
def test_refuses_without_a_chip(cell, tiny, capsys):
    with pytest.raises(SystemExit) as e:
        bench_run.main(_args(cell))
    assert e.value.code not in (0, None)
    assert '"correct"' not in capsys.readouterr().out


@pytest.mark.parametrize("cell", CELLS)
def test_cell_end_to_end_tiny(cell, tiny, capsys):
    big_seed = 2 ** 31 + 12345          # more than 32 signed bits hold
    r = bench_run.main(_args(cell, seed=big_seed),
                       find_devices=cpu_devices)
    assert r["correct"], capsys.readouterr().out
    assert r["failed"] == 0 and r["attempted"] > 0
    want = {m["name"] for m in spec.load(cell).end_to_end}
    assert set(r["metrics"]) == want and "setup_s" in want
    assert all(v["value"] > 0 for v in r["metrics"].values())
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == r
    assert r["device"]["platform"] == "cpu"      # and says so


@pytest.mark.parametrize("cell", TRAIN)
def test_same_seed_same_inputs(cell, tiny):
    from benchmark.harness import inputs
    c = spec.load(cell)
    a = inputs.train_pool(c.traffic, c.config, 2 ** 31 + 5)
    b = inputs.train_pool(c.traffic, c.config, 2 ** 31 + 5)
    d = inputs.train_pool(c.traffic, c.config, 6)
    assert all((x == y).all() and (p == q).all()
               for (x, p), (y, q) in zip(a, b))
    assert not (a[0][0] == d[0][0]).all()
    # rows all differ
    flat = a[0][0].reshape(a[0][0].shape[0], -1)
    assert len({r.tobytes() for r in flat}) == flat.shape[0]


@pytest.mark.parametrize("cell", TRAIN)
def test_control_in_lower_precision_is_not_correct(cell, tiny):
    """The reference computed in the precision below the
    configuration's, put in the program's place, must fail at least
    one limit (at the tiny size; PERF.md has the chip's readings)."""
    import jax
    from benchmark.harness import inputs
    c = spec.load(cell)
    builder = spec.load_module("builders", c.config["builder"])
    ref = spec.load_module("reference", c.config["reference"])
    pool = inputs.train_pool(c.traffic, c.config, 3)
    with builder.policy(c.config):
        seq = c.traffic["inputs"].get("seq_len")
        shapes = jax.eval_shape(
            lambda: builder.build(c.config, seq).init().params)
    maker = weights.maker(shapes, c.config["init"])
    make = lambda: maker(11)
    batches = [ref.batch_of(*pool[i % len(pool)])
               for i in range(c.traffic["check_steps"])]
    want = train_check.reference_steps(ref, c.config, make, batches)
    ctrl = train_check.reference_steps(ref, c.config, make, batches,
                                       control=True)

    class Checks:
        def __init__(self):
            self.ok = []

        def check(self, name, value, limit):
            self.ok.append(value == value and value <= limit)

    control, sound = Checks(), Checks()
    train_check.compare(control, ctrl, want, c.traffic["limits"])
    assert not all(control.ok)
    train_check.compare(sound, want, want, c.traffic["limits"])
    assert all(sound.ok)


@pytest.mark.parametrize("cell", TRAIN)
def test_step_that_returns_its_state_unchanged_is_not_correct(
        cell, tiny, capsys):
    def break_step(net):
        real = net._make_train_step

        def broken():
            step = real()

            def unchanged(params, state, opt_state, *rest):
                import jax
                import jax.numpy as jnp
                # the real step donates its inputs: keep copies
                keep = jax.tree_util.tree_map(
                    jnp.copy, (params, state, opt_state))
                out = step(params, state, opt_state, *rest)
                return keep + tuple(out[3:])
            return unchanged
        net._make_train_step = broken
        net._jit_train_step = None

    r = bench_run.main(_args(cell), find_devices=cpu_devices,
                       break_step=break_step)
    assert r["correct"] is False, capsys.readouterr().out


@pytest.mark.parametrize("cell", SERVE)
def test_token_altered_where_it_is_produced_is_not_correct(
        cell, tiny, capsys, monkeypatch):
    def break_token(server):
        from deeplearning4j_tpu.serving.continuous import (
            ContinuousBatcher)
        real = ContinuousBatcher._sample

        def off_by_one(probs, slot):
            return (real(probs, slot) + 1) % probs.size
        monkeypatch.setattr(ContinuousBatcher, "_sample",
                            staticmethod(off_by_one))

    r = bench_run.main(_args(cell), find_devices=cpu_devices,
                       break_token=break_token)
    assert r["correct"] is False, capsys.readouterr().out


@pytest.mark.parametrize("cell", SERVE)
def test_serve_control_in_lower_precision_is_not_correct(cell, tiny):
    """The reference in bfloat16, put in the program's place, fails
    the limit on the divergence of its next-token distribution (at the
    tiny size, where the CPU's float32 program matches the reference
    to rounding; PERF.md has the chip's readings)."""
    from benchmark.tests import measure_serve_limits
    row = measure_serve_limits.main(cell, 2.0, [5], find=cpu_devices)[0]
    limits = spec.load(cell).traffic["limits"]
    assert row["correct"]
    assert row["program_kl"] <= limits["served_logprob_kl"]
    assert row["control_kl"] > limits["served_logprob_kl"]
    assert row["control_kl"] > 3 * row["program_kl"]


@pytest.mark.parametrize("cell", TRAIN)
def test_mfu_reads_the_count_the_configuration_names(cell):
    """At the real sizes, from a made-up traced part: one step a
    second on a v5e is the count over the peak."""
    from benchmark.harness import peaks, readers

    class Device:
        device_kind = "TPU v5 lite"

    c = spec.load(cell)
    obs = {"cell": c, "device": Device(), "n_devices": c.chips,
           "samples_per_step": c.traffic["batch"],
           "traced": {"steps": 3, "seconds": 3.0}}
    count = spec.load_module("counts", c.config["train_flops"])
    want = (100.0 * count.train_flops(c.config, c.traffic)
            * c.traffic["batch"] / c.chips
            / peaks.peaks_for("TPU v5 lite")["flops_per_s"])
    assert readers.model_flops_util_pct(obs) == pytest.approx(want)
    assert 0.1 < want < 105.0
    c.config.pop("train_flops")      # no count: nothing to read
    assert readers.model_flops_util_pct(obs) is None


@pytest.mark.parametrize("cell", CELLS)
def test_the_mix_sets_the_profilers_host_level(cell, monkeypatch):
    """2 unless the mix's file says otherwise (the image mix and
    the four-chip mix trace the devices alone: their batches' relayout
    floods the host's trace)."""
    import jax
    from benchmark.harness import session
    seen = {}
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda d, profiler_options: seen.update(
            host=profiler_options.host_tracer_level,
            python=profiler_options.python_tracer_level))
    c = spec.load(cell)
    s = session.Session(c, 1, 1.0, 1, 0.0, find=cpu_devices)
    s.trace_start()
    assert seen == {"host": c.traffic.get("trace_host_level", 2),
                    "python": 0}
    assert seen["host"] == (0 if cell in ("resnet50_train",
                                          "gpt2m_train_dp4") else 2)
