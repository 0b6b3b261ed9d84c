"""The parameter and byte counts of the Granite-4.0-H-Micro
configuration at the published sizes, held against ISSUE 39's own
arithmetic (a mixer 25,847,232, the MLP 50,331,648, an attention
10,485,760; 36 x 76,182,976 + 4 x 60,821,504 + the embedding + the
last gain = 3,191,396,096 as published, 3,396,916,992 = 6.79 GB with
the head of its own; 64 slots x 36 x 2,123,264 B = 4.89 GB of state),
the count against the built network leaf by leaf by shape alone, and
the three new readers on made-up observations (and on none)."""

import pytest

from benchmark.harness import spec

CELL = "granite_serve_chat"


def _counts():
    c = spec.load(CELL)
    return c, spec.load_module("counts", c.config["serve_step_bytes"])


def test_the_file_holds_the_catalogs_row_uncut():
    import json
    c, _ = _counts()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") \
            as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-micro")
    assert c.config["source"] == row["source_url"]
    assert {k: c.config[k] for k in row["config"]} == row["config"]
    assert c.config["reduced"] == []


def test_parameters_at_the_published_sizes():
    c, m = _counts()
    d = 2048
    assert m.layer_counts(c.config) == (36, 4)
    assert [i for i, k in enumerate(c.config["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    assert m.conv_dim(c.config) == 4096 + 2 * 128 == 4352
    # W_in 2048 x 8512, the convolution 4352 x 4 and its bias, three
    # vectors a head, the gain of 4096, W_out 4096 x 2048
    assert m.mixer_params(c.config) == (
        d * (4096 + 4352 + 64) + 4352 * 4 + 4352 + 3 * 64 + 4096
        + 4096 * d) == 25_847_232
    assert m.mlp_params(c.config) == 3 * d * 8192 == 50_331_648
    assert m.attention_params(c.config) == (
        2 * d * d + 2 * d * 8 * 64) == 10_485_760
    assert m.mixer_params(c.config) + m.mlp_params(c.config) + 2 * d \
        == 76_182_976
    assert m.attention_params(c.config) + m.mlp_params(c.config) \
        + 2 * d == 60_821_504
    assert m.parameters(c.config, tied=True) == (
        36 * 76_182_976 + 4 * 60_821_504 + 205_520_896 + d
    ) == 3_191_396_096 == c.config["published"]["parameters"]
    assert m.parameters(c.config) == 3_396_916_992
    assert 2 * m.parameters(c.config) == pytest.approx(6.79e9, rel=1e-3)


def test_the_count_is_the_builders_parameters():
    """At the published sizes (shapes only: nothing is allocated) and
    at the tiny preset the count is the number of parameters the
    program's own network has, and at the published sizes leaf by leaf
    by kind of layer."""
    import json
    import os
    import jax
    import numpy as np
    size = lambda tree: sum(int(np.prod(a.shape))
                            for a in jax.tree_util.tree_leaves(tree))
    c, m = _counts()
    b = spec.load_module("builders", c.config["builder"])
    shapes = b.build(c.config).init().params
    assert all(isinstance(a, jax.ShapeDtypeStruct)
               for a in jax.tree_util.tree_leaves(shapes))
    assert m.parameters(c.config) == size(shapes)
    assert shapes[0]["W"].shape == (100352, 2048) == \
        shapes[-1]["W"].shape[::-1]
    for p, kind in zip(shapes[1:-2], c.config["layer_types"]):
        assert ("ssm" in p) is (kind == "mamba")
        assert ("attn" in p) is (kind == "attention")
        mixer = p["ssm"] if kind == "mamba" else p["attn"]
        assert size(mixer) == (m.mixer_params(c.config)
                               if kind == "mamba"
                               else m.attention_params(c.config))
        assert size({k: p[k] for k in ("Wg", "Wu", "Wd")}) == \
            m.mlp_params(c.config)
    ssm = shapes[1]["ssm"]
    assert ssm["W_in"].shape == (2048, 8512)
    assert ssm["conv_w"].shape == (4, 1, 4352)
    assert ssm["W_out"].shape == (4096, 2048)
    # the one 3-D leaf is the convolution: the ``init`` rule by rank
    # (harness/weights.py) reaches it alone
    assert [a.shape for a in jax.tree_util.tree_leaves(shapes)
            if len(a.shape) == 3] == [(4, 1, 4352)] * 36
    with open(os.path.join(os.path.dirname(__file__), "tiny",
                           CELL + ".json")) as f:
        c.config.update({k: v for k, v in json.load(f)["config"].items()
                         if k != "init"})
    assert m.parameters(c.config) == size(
        b.build(c.config).init().params)


def test_state_and_step_bytes_at_the_published_sizes():
    c, m = _counts()
    # 64 x 64 x 128 float32 and 3 x 4352 bfloat16 a layer a stream
    assert m.state_bytes(c.config) == 2_097_152 + 26_112
    state = 64 * 36 * m.state_bytes(c.config)
    assert state == pytest.approx(4.89e9, rel=1e-3)
    # the attention layers' pages: 4 x (64 x 64 + 1) pages of 16 rows
    # of 8 x (64 + 64) bfloat16 values
    assert m.cache_values(c.config) == 1024
    assert 4 * 4097 * 16 * 1024 * 2 == pytest.approx(0.54e9, rel=1e-2)
    rows = m.mean_cached_rows(c.traffic)
    assert 100 < rows < 400
    weights = 2 * (m.parameters(c.config) - 2048 * 100352)
    assert weights == pytest.approx(6.38e9, rel=1e-3)
    want = (weights + 2 * 64 * 2048 + 2 * 4 * 64 * 1024 * rows
            + 2 * state)
    assert m.serve_step_bytes(c.config, c.traffic, 64) == \
        pytest.approx(want)
    # the state, read and written, is the larger part of a step
    assert 2 * state == pytest.approx(9.78e9, rel=1e-3)
    assert 2 * state > weights
    assert want == pytest.approx(16.3e9, rel=0.02)


def _obs(cell, counters):
    zero = {k: ({"count": 0} if isinstance(v, dict) else 0)
            for k, v in counters.items()}
    return {"cell": cell,
            "counters": {"before": zero, "after": counters}}


def test_restart_reader_on_counters_and_on_none():
    c, _ = _counts()
    e = '{endpoint="generate/lm/v1"}'
    read = spec.load_module("layer_metrics",
                            "state_restarts_per_step.serve").read
    obs = _obs(c, {
        "serving_state_rows_restarted_total" + e: 40,
        'serving_step_seconds{endpoint="generate/lm/v1",part="device"}':
            {"count": 100}})
    assert read(obs) == pytest.approx(0.4)
    # a program without the counter (the parent, a network without a
    # state layer): nothing to read, nothing raised
    assert read(_obs(c, {"serving_kv_ring_wraps_total" + e: 9})) is None
    assert read({"cell": c, "counters": {}}) is None


def test_scope_readers_walk_the_programs_own_tables(monkeypatch):
    """``ssm_time_pct.serve`` and ``ssm_state_time_pct.serve`` on a
    made-up trace of two steps of a made-up program: the first
    device's busy time under ``ssm`` and under ``ssm/state``; None
    without a trace and where the tables do not match."""
    import sys
    import types
    from benchmark.harness import scopes
    ssm = spec.load_module("layer_metrics", "ssm_time_pct.serve").read
    state = spec.load_module("layer_metrics",
                             "ssm_state_time_pct.serve").read
    step = "jit(step_ids)/jit(step)/"
    table = [("fusion.1", step + "0_EmbeddingSequenceLayer/take"),
             ("fusion.2", step + "1_StateSpaceDecoderBlock/ssm/dot_general"),
             ("fusion.3", step + "1_StateSpaceDecoderBlock/ssm/state/mul"),
             ("fusion.4", step + "1_StateSpaceDecoderBlock/mlp/dot_general"),
             ("fusion.5", step + "2_GroupedQueryDecoderBlock/attn/global"
                                 "/dot_general")]
    programs = types.ModuleType("programs")
    programs.scope_tables = lambda: {"paged_step_ids/t=2": table}
    monkeypatch.setitem(
        sys.modules, "deeplearning4j_tpu.observability.programs", programs)
    ops = []
    for s in range(2):
        for k, dur in enumerate((10, 20, 50, 15, 5)):
            ops.append((f"%fusion.{k + 1}", 1000 * s + 100 * k, dur))
    obs = {"trace": {"devices": [{"ops": ops}]}}
    assert ssm(obs) == pytest.approx(70.0)
    assert state(obs) == pytest.approx(50.0)
    assert scopes.share_pct(obs, "mlp") == pytest.approx(15.0)
    assert ssm({"trace": None}) is None and state({}) is None
    stray = {"trace": {"devices": [{"ops": [("%other.1", 0, 10),
                                            ("%other.2", 20, 10)]}]}}
    assert ssm(stray) is None and state(stray) is None
