"""The parameter and byte counts of the Olmo-Hybrid-7B configuration at
the published sizes, held against ISSUE 45's own arithmetic (a mixer
88,750,332, the MLP 126,812,160, an attention 58,990,080; 24 x
215,570,172 + 8 x 185,809,920 + the embedding, the head and the last
gain = 7,430,870,688 as published; kept, layers 0-15, 4,100,788,944 =
8.20 GB; 64 slots x 12 x 2,280,960 B = 1.75 GB of state), the count
against the built network leaf by leaf by shape alone, and the two new
readers on made-up observations (and on none)."""

import pytest

from benchmark.harness import spec

CELL = "olmo_hybrid_serve_reason"


def _counts():
    c = spec.load(CELL)
    return c, spec.load_module("counts", c.config["serve_step_bytes"])


def test_the_file_holds_the_catalogs_row_cut_in_depth_alone():
    import json
    c, _ = _counts()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") \
            as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Olmo-Hybrid-7B")
    assert c.config["source"] == row["source_url"]
    differs = {k for k in row["config"]
               if c.config[k] != row["config"][k]}
    assert differs == {"num_hidden_layers"} == set(c.config["reduced"])
    assert c.config["num_hidden_layers"] == 16
    assert c.config["published"]["num_hidden_layers"] == \
        row["config"]["num_hidden_layers"] == 32
    assert len(c.config["layer_types"]) == 32
    assert set(c.config["assumed"]) >= {
        "block", "qk_norm", "position", "state_precision", "gated_norm",
        "init"}


def test_parameters_at_the_published_sizes():
    c, m = _counts()
    d = 3840
    assert m.layer_counts(c.config) == (12, 4)
    assert [i for i, k in enumerate(c.config["layer_types"])
            if k == "full_attention"] == list(range(3, 32, 4))
    assert m.conv_dim(c.config) == 2 * 2880 + 5760 == 11520
    assert m.mixer_params(c.config) == (
        2 * d * 2880 + 3 * d * 5760 + 2 * d * 30 + 4 * 11520 + 2 * 30
        + 192) == 88_750_332
    assert m.mlp_params(c.config) == 3 * d * 11008 == 126_812_160
    assert m.attention_params(c.config) == 4 * d * d + 2 * d \
        == 58_990_080
    linear = m.mixer_params(c.config) + m.mlp_params(c.config) + 2 * d
    full = m.attention_params(c.config) + m.mlp_params(c.config) + 2 * d
    assert (linear, full) == (215_570_172, 185_809_920)
    ends = 2 * 100_352 * d + d
    assert m.parameters(c.config, layers=32) == \
        24 * linear + 8 * full + ends == 7_430_870_688 == \
        c.config["published"]["parameters"]
    assert m.parameters(c.config) == 12 * linear + 4 * full + ends == \
        4_100_788_944 == c.config["kept"]["parameters"]
    assert 2 * m.parameters(c.config) == pytest.approx(8.20e9, rel=1e-3)


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
    assert m.parameters(c.config) == size(shapes) == 4_100_788_944
    assert shapes[0]["W"].shape == (100352, 3840) == \
        shapes[-1]["W"].shape[::-1]
    assert len(shapes) == 16 + 3
    for p, kind in zip(shapes[1:-2], c.config["layer_types"]):
        assert ("delta" in p) is (kind == "linear_attention")
        assert ("attn" in p) is (kind == "full_attention")
        mixer = p["delta"] if "delta" in p else p["attn"]
        assert size(mixer) == (m.mixer_params(c.config) if "delta" in p
                               else m.attention_params(c.config))
        assert size({k: p[k] for k in ("Wg", "Wu", "Wd")}) == \
            m.mlp_params(c.config)
    delta, attn = shapes[1]["delta"], shapes[4]["attn"]
    assert delta["Wq"].shape == delta["Wk"].shape == (3840, 2880)
    assert delta["Wv"].shape == delta["Wg"].shape == (3840, 5760)
    assert delta["Wo"].shape == (5760, 3840)
    assert delta["g"].shape == (192,)
    assert attn["q_norm_gain"].shape == attn["k_norm_gain"].shape == \
        (3840,)
    # every matrix is 2-D, the convolutions' taps among them: the
    # ``init`` rule by rank (harness/weights.py) reaches them as
    # ``matrix``, and every other vector but the gains is zeros
    assert delta["conv_w"].shape == (4, 11520)
    assert {len(a.shape) for a in jax.tree_util.tree_leaves(shapes)} \
        == {1, 2}
    vectors = {str(path[-1].key) for path, a in
               jax.tree_util.tree_flatten_with_path(shapes)[0]
               if len(a.shape) == 1}
    assert vectors - set(c.config["init"]["gains"]) == {"A_log",
                                                        "dt_bias"}
    with open(os.path.join(os.path.dirname(__file__), "tiny",
                           CELL + ".json")) as f:
        c.config.update({k: v for k, v in json.load(f)["config"].items()
                         if k != "init"})
    assert m.parameters(c.config) == size(
        b.build(c.config).init().params)


def test_state_and_step_bytes_at_the_published_sizes():
    c, m = _counts()
    # 30 x 96 x 192 float32 and 3 x 11520 bfloat16 a layer a stream,
    # as counted, whatever layout the program holds them in
    assert m.state_bytes(c.config) == 2_211_840 + 69_120 == 2_280_960
    state = 64 * 12 * m.state_bytes(c.config)
    assert state == pytest.approx(1.75e9, rel=2e-3)
    # the attention layers' pages: 4 x (64 x 48 + 1) pages of 16 rows
    # of 30 x (128 + 128) bfloat16 values
    assert m.cache_values(c.config) == 7680
    assert c.traffic["server"]["capacity"] // 16 == 48
    assert 4 * 3073 * 16 * 7680 * 2 == pytest.approx(3.02e9, rel=1e-2)
    rows = m.mean_cached_rows(c.traffic)
    assert 150 < rows < 350
    weights = 2 * (m.parameters(c.config) - 3840 * 100352)
    assert weights == pytest.approx(7.43e9, rel=1e-3)
    want = (weights + 2 * 64 * 3840 + 2 * 4 * 64 * 7680 * rows
            + 2 * state)
    assert m.serve_step_bytes(c.config, c.traffic, 64) == \
        pytest.approx(want)
    assert 2 * state == pytest.approx(3.50e9, rel=2e-3)
    assert 11.5e9 < want < 13e9
    # held on the chip: weights, state rows and pages
    assert 2 * m.parameters(c.config) + state + 4 * 3073 * 16 * 7680 * 2 \
        == pytest.approx(12.97e9, rel=2e-3)


def test_the_mix_is_the_issues():
    c, _ = _counts()
    assert c.chips == 1 and c.traffic["driver"] == "serve_closed_loop"
    assert c.traffic["server"] == {
        "slots": 64, "capacity": 768, "page_size": 16,
        "kv_mode": "paged", "queue_limit": 256}
    assert c.traffic["clients"] == 128
    assert c.traffic["lengths"] == {
        "prompt_median": 96, "prompt_sigma": 0.6, "prompt_min": 32,
        "prompt_max": 256, "output_median": 288, "output_sigma": 0.5,
        "output_min": 128, "output_max": 512, "set_seed": 2026,
        "set_size": 256}
    assert (c.traffic["ramp_s"], c.traffic["check_requests"]) == (15.0, 8)
    assert {m["name"] for m in c.per_layer} >= {
        "delta_time_pct.serve", "delta_state_time_pct.serve",
        "model_bandwidth_util_pct.serve", "state_restarts_per_step.serve",
        "kv_read_pct.serve", "attention_time_pct.serve"}


def test_scope_readers_walk_the_programs_own_tables(monkeypatch):
    """``delta_time_pct.serve`` and ``delta_state_time_pct.serve`` on a
    made-up trace of two steps of a made-up program: the first
    device's busy time under ``delta`` and under ``delta/state``; None
    without a trace, where the tables do not match, and over a program
    that has no such layer (the parent)."""
    import sys
    import types
    from benchmark.harness import scopes
    delta = spec.load_module("layer_metrics", "delta_time_pct.serve").read
    state = spec.load_module("layer_metrics",
                             "delta_state_time_pct.serve").read
    step = "jit(step_ids)/jit(step)/"
    table = [("fusion.1", step + "0_EmbeddingSequenceLayer/take"),
             ("fusion.2", step + "1_DeltaRuleDecoderBlock/delta/dot_general"),
             ("fusion.3", step + "1_DeltaRuleDecoderBlock/delta/state/mul"),
             ("fusion.4", step + "1_DeltaRuleDecoderBlock/mlp/dot_general"),
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
    assert delta(obs) == pytest.approx(70.0)
    assert state(obs) == pytest.approx(50.0)
    assert scopes.share_pct(obs, "mlp") == pytest.approx(15.0)
    assert delta({"trace": None}) is None and state({}) is None
    stray = {"trace": {"devices": [{"ops": [("%other.1", 0, 10),
                                            ("%other.2", 20, 10)]}]}}
    assert delta(stray) is None and state(stray) is None
    # a program without the layer: nothing to read, nothing raised
    table[1:4] = [("fusion.2", step + "1_StateSpaceDecoderBlock/ssm/dot"),
                  ("fusion.3", step + "1_StateSpaceDecoderBlock/ssm/state"
                                      "/mul"),
                  ("fusion.4", step + "1_StateSpaceDecoderBlock/mlp/dot")]
    other = {"trace": {"devices": [{"ops": ops}]}}
    assert delta(other) is None and state(other) is None
