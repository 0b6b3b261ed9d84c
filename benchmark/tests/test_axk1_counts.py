"""The byte count of an A.X-K1 decode step at the published widths,
held against the configuration's own arithmetic, and the three
readers of its cell on made-up counters (and on none)."""

import pytest

from benchmark.harness import spec

CELL = "axk1_serve_decode"


def test_step_bytes_at_the_published_widths():
    c = spec.load(CELL)
    m = spec.load_module("counts", c.config["serve_step_bytes"])
    attn, dense, outside, expert = m.layer_params(c.config)
    # ISSUE 26's count: MLA 101.1M, an expert 44.0M, dense MLP 396.4M
    assert attn == pytest.approx(101.1e6, rel=1e-3)
    assert expert == 3 * 7168 * 2048
    assert dense == 3 * 7168 * 18432
    assert outside == 7168 * 192 + expert
    weights = (attn + dense + 7 * (attn + outside + 12 * expert)
               + 2 * 7168 * 20480 + 7168)
    assert 2 * weights == pytest.approx(11.03e9, rel=5e-3)
    rows = m.mean_cached_rows(c.traffic)
    assert 100 < rows < 400
    # every weight but the embedding table, the rows of 64 slots, and
    # their cache: all 12 held experts of a layer are read, hit or not
    cache = 2 * 64 * rows * 8 * 576
    assert m.serve_step_bytes(c.config, c.traffic, 64) == pytest.approx(
        2 * (weights - 7168 * 20480 + 64 * 7168) + cache)


def test_builder_refuses_another_topk_method():
    c = spec.load(CELL)
    b = spec.load_module("builders", c.config["builder"])
    with pytest.raises(ValueError, match="plain top-k"):
        b.block(dict(c.config, topk_method="group_limited_greedy"), 1)


def _obs(cell, counters):
    class Device:
        device_kind = "TPU v5 lite"

    zero = {k: ({"sum": 0.0, "count": 0} if isinstance(v, dict) else 0)
            for k, v in counters.items()}
    return {"cell": cell, "device": Device(),
            "counters": {"before": zero, "after": counters}}


def test_readers_on_counters_and_on_none():
    c = spec.load(CELL)
    e = '{endpoint="generate/lm/v1"}'
    obs = _obs(c, {
        "serving_moe_local_pairs_total" + e: 3200,
        "serving_moe_expert_hits_total" + e: 4500,
        "serving_moe_expert_slots_total" + e: 4800,
        "serving_batch_items_total" + e: 6000,
        "serving_batches_total" + e: 100,
        'serving_step_seconds{endpoint="generate/lm/v1",part="device"}':
            {"sum": 1.5, "count": 100}})
    read = lambda name: spec.load_module("layer_metrics", name).read
    assert read("moe_local_pairs_per_step.serve")(obs) == 32.0
    assert read("moe_experts_hit_pct.serve")(obs) == 93.75
    # the device's own time: 3 steps of two ops, 20 ms busy a step,
    # and a page copy that ran once
    assert read("model_bandwidth_util_pct.serve")(obs) is None
    ms = 1_000_000
    obs["trace"] = {"devices": [{"name": "/device:TPU:0", "async": [],
                                 "ops": [
        [op, (25 * k + at) * ms, 10 * ms]
        for k in range(3) for op, at in (("%a", 0), ("%b", 10))]
        + [["%copy", 70 * ms, 3 * ms]]}], "host": [], "text": {}}
    m = spec.load_module("counts", c.config["serve_step_bytes"])
    want = 100 * m.serve_step_bytes(c.config, c.traffic, 60) \
        / 819e9 / 0.021
    assert read("model_bandwidth_util_pct.serve")(obs) == \
        pytest.approx(want)
    assert 50 < want < 75
    # a program without the counters: nothing to read, nothing raised
    bare = _obs(c, {"serving_steps_total" + e: 100})
    for name in ("moe_local_pairs_per_step.serve",
                 "moe_experts_hit_pct.serve",
                 "model_bandwidth_util_pct.serve"):
        assert read(name)(bare) is None
