"""The two readers of ISSUE 29 on made-up ``obs`` whose answers are
computed by hand: ``kv_read_pct.serve`` from a real
``BatcherStepMetrics`` through ``registry.snapshot()``, the way the
serve driver takes it, and ``paged_attn_time_pct.serve`` from a made-up
device trace and from the recorded one, which holds no such kernel.
Each gives None, and raises nothing, where the program has nothing for
it to read (as this PR's parent has not)."""

import json
import os

import pytest

from benchmark.harness import spec

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


def reader(name):
    return spec.load_module("layer_metrics", name)


@pytest.mark.parametrize("read, spanned, want", [
    (983_040, 8_192_000, 12.0),     # by table: what the slots hold
    (8_192_000, 8_192_000, 100.0),  # the gather: what the tables span
    (None, None, None),             # a program without the counters
    (0, 0, None)])                  # no step in the window
def test_kv_read_pct_reader(read, spanned, want):
    from deeplearning4j_tpu.serving.metrics import ServingMetrics
    m = ServingMetrics()
    steps = m.batcher_steps("generate/lm/v1")
    steps.record(0.001, 0.020, 0.003, 6, 2)
    if read is not None:
        steps.record_kv_positions(40, 50)      # before the window
    obs = {"counters": {"before": m.registry.snapshot()}}
    if read is not None:
        steps.record_kv_positions(read, spanned)
    obs["counters"]["after"] = m.registry.snapshot()
    assert reader("kv_read_pct.serve").read(obs) == want


def test_paged_attn_time_pct_reader():
    read = reader("paged_attn_time_pct.serve").read
    assert read({}) is None                    # an untraced run
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        assert read({"trace": json.load(f)}) is None   # no such kernel
    ops = [["%fusion.1", 0, 6 * MS],
           ["%pallas_paged_attention.3", 6 * MS, 1 * MS],
           ["%copy", 10 * MS, 2 * MS],
           ["%pallas_paged_attention.7", 12 * MS, 1 * MS]]
    trace = {"devices": [{"name": "/device:TPU:0", "async": [],
                          "ops": ops}], "host": [], "text": {}}
    assert read({"trace": trace}) == pytest.approx(20.0)


def test_both_have_an_entry_that_says_where_they_are_read():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        by_name = {m["name"]: m for m in json.load(f)["per_layer"]}
    kv, attn = (by_name[n] for n in ("kv_read_pct.serve",
                                     "paged_attn_time_pct.serve"))
    assert kv["source"] == "program_counter" and "workloads" not in kv
    assert attn["source"] == "device_trace"
    assert attn["workloads"] == ["gpt2m_serve_closed"]
    assert kv["moves"] == attn["moves"] == "serve_tokens_per_s"
