"""The parameter and byte counts of the MiMo-V2 shaped share at the
published widths, held against the configuration's own arithmetic
(ISSUE 34: global attention 89.13M, window attention 94.37M, the
dense MLP 201.33M, an expert 25.17M, 3,430M parameters = 6.86 GB on
this chip), the count against the built network leaf by leaf, and the
two ring readers on made-up counters (and on none)."""

import pytest

from benchmark.harness import spec

CELL = "mimo_serve_mixedlen"


def _counts():
    c = spec.load(CELL)
    return c, spec.load_module("counts", c.config["serve_step_bytes"])


def test_parameters_at_the_published_widths():
    c, m = _counts()
    d = 4096
    assert m.layer_kinds(c.config) == [(False, False)] + [
        (True, True)] * 4 + [(False, True), (True, True)]
    g, w = (m.attention_params(c.config, False),
            m.attention_params(c.config, True))
    # Wq 50.33M, Wk 3.15M / 6.29M, Wv 2.10M / 4.19M, Wo 33.55M, the
    # input norm's gain, and 64 sink logits on a window layer
    assert g == (d * 64 * 192 + d * 4 * 192 + d * 4 * 128
                 + 64 * 128 * d + d)
    assert w == (d * 64 * 192 + d * 8 * 192 + d * 8 * 128
                 + 64 * 128 * d + d + 64)
    assert g == pytest.approx(89.13e6, rel=1e-3)
    assert w == pytest.approx(94.37e6, rel=1e-3)
    dense, experts = (m.ffn_params(c.config, False),
                      m.ffn_params(c.config, True))
    assert dense == 3 * d * 16384 + d == pytest.approx(201.33e6, rel=1e-3)
    expert = 3 * d * 2048
    assert expert == pytest.approx(25.17e6, rel=1e-3)
    assert experts == 16 * expert + d * 256 + 256 + d
    assert g + dense == pytest.approx(290.5e6, rel=1e-3)      # layer 0
    assert w + experts == pytest.approx(498.1e6, rel=1e-3)
    assert g + experts == pytest.approx(492.8e6, rel=1e-3)
    total = m.parameters(c.config)
    assert total == ((g + dense) + 5 * (w + experts) + (g + experts)
                     + 2 * d * 19072 + d)
    assert total == pytest.approx(3430e6, rel=1e-3)
    assert 2 * total == pytest.approx(6.86e9, rel=1e-3)
    # ten following layers (8 window + 2 global) would leave neither
    # the pools nor the float32 reference room
    assert 2 * (total + 3 * (w + experts) + (g + experts)) > 10.8e9


def test_the_count_is_the_builders_parameters():
    """At the published widths (shapes only) and at the tiny preset
    the count is the number of parameters the program's own network
    has, and at the published widths leaf by leaf by kind of layer."""
    import json
    import os

    import jax
    import numpy as np
    size = lambda tree: sum(int(np.prod(a.shape))
                            for a in jax.tree_util.tree_leaves(tree))
    c, m = _counts()
    b = spec.load_module("builders", c.config["builder"])
    shapes = b.build(c.config).init().params
    assert m.parameters(c.config) == size(shapes)
    for p, (window, expert) in zip(shapes[1:-2],
                                   m.layer_kinds(c.config)):
        assert ("sink" in p["attn"]) is window
        assert ("moe" in p) is expert
        assert m.attention_params(c.config, window) == \
            size(p["attn"]) + size(p["norm1_gain"])
        rest = {k: v for k, v in p.items()
                if k not in ("attn", "norm1_gain")}
        assert m.ffn_params(c.config, expert) == size(rest)
    with open(os.path.join(os.path.dirname(__file__), "tiny",
                           CELL + ".json")) as f:
        c.config.update({k: v for k, v in json.load(f)["config"].items()
                         if k != "init"})
    assert m.parameters(c.config) == size(
        b.build(c.config).init().params)


def test_step_bytes_at_the_published_widths():
    c, m = _counts()
    rows = m.mean_cached_rows(c.traffic)
    in_window = m.mean_cached_rows(c.traffic, 128)
    assert 300 < rows < 900 and 100 < in_window <= 128
    # a cached position: 4 x (192 + 128) values in a global layer,
    # 8 x (192 + 128) in a window layer
    assert m.cache_values(c.config, False) == 1280
    assert m.cache_values(c.config, True) == 2560
    cache = 2 * 64 * (2 * rows * 1280 + 5 * in_window * 2560)
    want = 2 * (m.parameters(c.config) - 4096 * 19072 + 64 * 4096) \
        + cache
    assert m.serve_step_bytes(c.config, c.traffic, 64) == \
        pytest.approx(want)
    assert want == pytest.approx(7.1e9, rel=0.03)
    assert cache < 0.06 * want


def _obs(cell, counters):
    zero = {k: ({"count": 0} if isinstance(v, dict) else 0)
            for k, v in counters.items()}
    return {"cell": cell,
            "counters": {"before": zero, "after": counters}}


def test_ring_readers_on_counters_and_on_none():
    c, _ = _counts()
    e = '{endpoint="generate/lm/v1"}'
    held = spec.load_module("layer_metrics",
                            "kv_ring_held_pct.serve").read
    wraps = spec.load_module("layer_metrics",
                             "kv_ring_wraps_per_step.serve").read
    obs = _obs(c, {
        "serving_kv_ring_pages_held_total" + e: 900,
        "serving_kv_ring_pages_full_total" + e: 3600,
        "serving_kv_ring_wraps_total" + e: 50,
        'serving_step_seconds{endpoint="generate/lm/v1",part="device"}':
            {"count": 25}})
    assert held(obs) == pytest.approx(25.0)
    assert wraps(obs) == pytest.approx(2.0)
    # a program without the counters (the parent of the PR that
    # brought them, a network without a window layer): nothing to
    # read, nothing raised
    bare = _obs(c, {"serving_moe_local_pairs_total" + e: 9})
    assert held(bare) is None and wraps(bare) is None
    assert held({"cell": c, "counters": {}}) is None
    assert wraps({"cell": c, "counters": {}}) is None
