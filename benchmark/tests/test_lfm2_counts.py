"""The parameter and byte counts of the LFM2-24B-A2B configuration at
the published sizes, held against ISSUE 41's own arithmetic (an
attention 10,485,888, a convolution mixer 16,783,360, the dense MLP
72,351,744, an expert half 604,110,912; whole and tied 23,843,661,440
as published; the cut, published layers 1-9 with every expert and the
whole vocabulary, 5,312,168,704 = 10.62 GB), the count against the
built network leaf by leaf by shape alone, and the new reader on a
made-up observation (and on none)."""

import json

import pytest

from benchmark.harness import spec

CELL = "lfm2_serve_agent"
D = 2048


def _counts():
    c = spec.load(CELL)
    return c, spec.load_module("counts", c.config["serve_step_bytes"])


def _row():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") \
            as f:
        return next(r for r in map(json.loads, f)
                    if r["name"] == "LFM2-24B-A2B")


def test_the_file_holds_the_catalogs_row_cut_in_depth_alone():
    c, _ = _counts()
    row = _row()
    assert c.config["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items()
                    if c.config[k] != v)
    assert differ == c.config["reduced"] == ["num_hidden_layers"]
    assert c.config["published"]["num_hidden_layers"] == \
        row["config"]["num_hidden_layers"] == 40
    # published layers 1-9: the second dense layer, then two whole
    # periods at 3 conv : 1 full_attention, eight expert layers
    first, n = c.config["first_layer"], c.config["num_hidden_layers"]
    assert (first, n) == (1, 9)
    assert c.config["layer_types"][first:first + n] == [
        "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv"]
    assert [i for i, k in enumerate(c.config["layer_types"])
            if k == "full_attention"] == list(range(2, 40, 4))


def test_parameters_at_the_published_sizes():
    c, m = _counts()
    assert m.attention_params(c.config) == (
        2 * D * D + 2 * D * 512 + 2 * 64) == 10_485_888
    assert m.conv_params(c.config) == (
        D * 6144 + D * D + 3 * D) == 16_783_360
    assert m.ffn_params(c.config, False) == 3 * D * 11776 == 72_351_744
    assert m.ffn_params(c.config, True) == (
        D * 64 + 64 + 64 * 3 * D * 1536) == 604_110_912
    assert m.layer_kinds(c.config) == [(True, False), (False, True)] + \
        [(True, True)] * 3 + [(False, True)] + [(True, True)] * 3
    nine = (m.layer_params(c.config, True, False)
            + 2 * m.layer_params(c.config, False, True)
            + 6 * m.layer_params(c.config, True, True))
    assert nine == 5_043_731_200
    assert m.parameters(c.config) == nine + 2 * 134_217_728 + D \
        == 5_312_168_704
    assert 2 * m.parameters(c.config) == pytest.approx(10.62e9, rel=1e-3)
    # the whole model as published, the head tied
    whole = dict(_row()["config"])
    assert m.parameters(whole, tied=True) == 23_843_661_440 == \
        c.config["published"]["parameters"]


def test_the_count_is_the_builders_parameters():
    """At the published sizes (shapes only: nothing is allocated) and
    at the tiny preset the count is the number of parameters the
    program's own network has, and at the published sizes leaf by leaf
    by kind of layer."""
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
    assert shapes[0]["W"].shape == (65536, D) == \
        shapes[-1]["W"].shape[::-1]
    for p, (conv, expert) in zip(shapes[1:-2], m.layer_kinds(c.config)):
        assert ("conv" in p) is conv and ("attn" in p) is not conv
        assert ("moe" in p) is expert and ("Wg" in p) is not expert
        assert size(p["conv"] if conv else p["attn"]) == (
            m.conv_params(c.config) if conv
            else m.attention_params(c.config))
        assert size(p["moe"] if expert else
                    {k: p[k] for k in ("Wg", "Wu", "Wd")}) == \
            m.ffn_params(c.config, expert)
    conv = shapes[1]["conv"]
    assert conv["W_in"].shape == (D, 3 * D)
    assert conv["conv_w"].shape == (3, D)       # 2-D: init.matrix2d's
    assert shapes[2]["attn"]["q_norm_gain"].shape == (64,)
    assert shapes[2]["moe"]["Wg"].shape == (64, D, 1536)
    assert shapes[2]["moe"]["br"].shape == (64,)
    with open(os.path.join(os.path.dirname(__file__), "tiny",
                           CELL + ".json")) as f:
        c.config.update({k: v for k, v in json.load(f)["config"].items()
                         if k != "init"})
    assert m.parameters(c.config) == size(
        b.build(c.config).init().params)


def test_step_bytes_at_the_published_sizes():
    c, m = _counts()
    assert m.window_bytes(c.config) == 2 * D * 2 == 8192
    assert 7 * 64 * m.window_bytes(c.config) == 3_670_016
    assert m.cache_values(c.config) == 1024
    # the pages as the pool keeps them: a value head of 64 in a lane
    # tile of 128
    assert 2 * 8193 * 16 * (512 + 1024) * 2 == pytest.approx(0.81e9,
                                                             rel=1e-2)
    rows = m.mean_cached_rows(c.traffic)
    assert 200 < rows < 700
    weights = 2 * (m.parameters(c.config) - D * 65536)
    assert weights == pytest.approx(10.36e9, rel=1e-3)
    experts = 2 * 8 * m.ffn_params(c.config, True)
    assert experts == pytest.approx(9.66e9, rel=1e-3)
    assert experts / weights == pytest.approx(0.93, abs=0.005)
    want = (weights + 2 * 64 * D + 2 * 2 * 64 * 1024 * rows
            + 7 * 64 * 2 * 8192)
    assert m.serve_step_bytes(c.config, c.traffic, 64) == \
        pytest.approx(want)
    assert want == pytest.approx(10.5e9, rel=0.02)


def test_conv_reader_walks_the_programs_own_tables(monkeypatch):
    """``conv_time_pct.serve`` on a made-up trace of two steps of a
    made-up program: the first device's busy time under ``conv``;
    None without a trace and where the tables do not match."""
    import sys
    import types
    read = spec.load_module("layer_metrics", "conv_time_pct.serve").read
    step = "jit(step_ids)/jit(step)/"
    table = [("fusion.1", step + "0_EmbeddingSequenceLayer/take"),
             ("fusion.2", step + "1_ShortConvDecoderBlock/conv/dot_general"),
             ("fusion.3", step + "1_ShortConvDecoderBlock/conv/window/mul"),
             ("fusion.4", step + "1_ShortConvDecoderBlock/mlp/dot_general"),
             ("fusion.5", step + "2_GroupedQueryDecoderBlock/attn/global"
                                 "/dot_general"),
             ("fusion.6", step + "3_ShortConvDecoderBlock/moe/experts/mul")]
    programs = types.ModuleType("programs")
    programs.scope_tables = lambda: {"paged_step_ids/t=2": table}
    monkeypatch.setitem(
        sys.modules, "deeplearning4j_tpu.observability.programs", programs)
    ops = []
    for s in range(2):
        for k, dur in enumerate((10, 20, 30, 15, 5, 20)):
            ops.append((f"%fusion.{k + 1}", 1000 * s + 100 * k, dur))
    assert read({"trace": {"devices": [{"ops": ops}]}}) == \
        pytest.approx(50.0)
    assert read({"trace": None}) is None and read({}) is None
    stray = {"trace": {"devices": [{"ops": [("%other.1", 0, 10),
                                            ("%other.2", 20, 10)]}]}}
    assert read(stray) is None
