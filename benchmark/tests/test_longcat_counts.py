"""The parameter and byte counts of the LongCat-Flash shaped share at
the published widths, held against the configuration's own arithmetic
(ISSUE 31: 638.9M a layer outside its routed experts, 37.75M an
expert, 5.17B parameters = 10.35 GB on this chip), and the zero-pair
reader on made-up counters (and on none)."""

import pytest

from benchmark.harness import spec

CELL = "longcat_serve_tooluse"


def test_parameters_at_the_published_widths():
    c = spec.load(CELL)
    m = spec.load_module("counts", c.config["serve_step_bytes"])
    attn, dense, router, expert = m.layer_params(c.config)
    d = 6144
    # Wqa 9.44M, Wqb 18.87M, Wkva 3.54M, Wkvb 8.39M, Wo 50.33M, the
    # two latent norms' gains and the input norm's
    assert attn == (d * 1536 + 1536 * 64 * 192 + d * 576
                    + 512 * 64 * 256 + 64 * 128 * d + 1536 + 512 + d)
    assert attn == pytest.approx(90.57e6, rel=1e-3)
    assert dense == 3 * d * 12288 + d == pytest.approx(226.5e6, rel=1e-3)
    assert router == d * 768 + 768
    assert expert == 3 * d * 2048 == pytest.approx(37.75e6, rel=1e-3)
    outside = 2 * attn + 2 * dense + router
    assert outside == pytest.approx(638.9e6, rel=1e-3)
    layer = outside + 16 * expert
    assert 2 * layer == pytest.approx(2.486e9, rel=1e-3)
    total = m.parameters(c.config)
    assert total == 4 * layer + 2 * d * 16384 + d
    assert total == pytest.approx(5.17e9, rel=2e-3)
    assert 2 * total == pytest.approx(10.35e9, rel=1e-3)
    # a fifth layer would not leave the reference room
    assert 2 * (total + layer) > 12.8e9


def test_the_count_is_the_builders_parameters():
    """At the tiny preset the count is the number of parameters the
    program's own network has, leaf by leaf."""
    import json
    import os

    import jax
    import numpy as np
    c = spec.load(CELL)
    with open(os.path.join(os.path.dirname(__file__), "tiny",
                           CELL + ".json")) as f:
        c.config.update({k: v for k, v in json.load(f)["config"].items()
                         if k != "init"})
    m = spec.load_module("counts", c.config["serve_step_bytes"])
    b = spec.load_module("builders", c.config["builder"])
    shapes = b.build(c.config).init().params
    n = sum(int(np.prod(a.shape))
            for a in jax.tree_util.tree_leaves(shapes))
    assert m.parameters(c.config) == n


def test_step_bytes_at_the_published_widths():
    c = spec.load(CELL)
    m = spec.load_module("counts", c.config["serve_step_bytes"])
    rows = m.mean_cached_rows(c.traffic)
    assert 150 < rows < 500
    # every weight but the embedding table, the rows of 32 slots, and
    # their cache in both pools of 4 layers: 1,152 values a token a
    # layer
    cache = 2 * 32 * rows * 4 * 1152
    want = 2 * (m.parameters(c.config) - 6144 * 16384 + 32 * 6144) + cache
    assert m.serve_step_bytes(c.config, c.traffic, 32) == \
        pytest.approx(want)
    assert want == pytest.approx(10.15e9, rel=0.02)
    assert cache < 0.01 * want


def _obs(cell, counters):
    zero = {k: 0 for k in counters}
    return {"cell": cell,
            "counters": {"before": zero, "after": counters}}


def test_zero_pair_reader_on_counters_and_on_none():
    c = spec.load(CELL)
    e = '{endpoint="generate/lm/v1"}'
    read = spec.load_module("layer_metrics",
                            "moe_zero_pairs_pct.serve").read
    obs = _obs(c, {"serving_moe_zero_pairs_total" + e: 1600,
                   "serving_moe_selected_pairs_total" + e: 4800})
    assert read(obs) == pytest.approx(100 / 3)
    # a program without the counters (the parent of the PR that
    # brought them): nothing to read, nothing raised
    assert read(_obs(c, {"serving_moe_local_pairs_total" + e: 9})) \
        is None
    assert read({"cell": c, "counters": {}}) is None
