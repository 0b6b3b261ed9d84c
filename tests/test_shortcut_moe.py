"""LongCat-Flash's shortcut-connected expert layer: two latent
attentions over two paged latent pools, two dense MLPs, a softmax
router with a selection-only bias over routed and zero-compute experts
as one chip's share of an expert-parallel group, held at a small size
against the plain reference (benchmark/reference/longcat.py: float32
jax.numpy, no code of the program)."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chunk_parity
from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    LatentAttentionLayer, RMSNormalization, ShortcutExpertBlock,
    SparseExpertsLayer, layer_from_dict)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}",
        os.path.join(ROOT, "benchmark", kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("reference", "longcat")
BUILDER = _load("builders", "longcat_dsl")

# hidden 64, 4 heads, ranks 16/16 (MLA scales 2 and 2), nope 8 / rope
# 4 / v 8, 16 routed + 8 zero experts, top-4, 2 layers
TINY = {"attention_bias": False, "attention_method": "MLA",
        "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 16,
        "kv_lora_rank": 16, "qk_nope_head_dim": 8,
        "qk_rope_head_dim": 4, "v_head_dim": 8, "rope_theta": 10000000,
        "rms_norm_eps": 1e-5, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "ffn_hidden_size": 96,
        "expert_ffn_hidden_size": 32, "router_experts": 16,
        "n_routed_experts": 16, "held_first_expert": 0,
        "zero_expert_num": 8, "zero_expert_type": "identity",
        "moe_topk": 4, "routed_scaling_factor": 6, "num_layers": 2,
        "vocab_size": 96, "max_position_embeddings": 64}
MOE = dict(n_in=64, n_routed_experts=16, n_zero_experts=8, top_k=4,
           expert_width=32, n_shared_experts=0, norm_topk_prob=False,
           routed_scaling_factor=6.0, scoring_func="softmax",
           router_bias=True)


def _net(config, seed=3, std=0.1):
    """The DSL network of ``config`` with seeded normal weights (gains
    drawn around one, so that a dropped gain shows; the router's bias
    drawn too, so that a bias in the wrong place shows)."""
    net = BUILDER.build(config).net.init()
    leaves, treedef = jax.tree_util.tree_flatten_with_path(net.params)
    rng = np.random.default_rng(seed)
    new = []
    for path, leaf in leaves:
        w = rng.normal(0.0, std, leaf.shape)
        if "gain" in str(path[-1]):
            w = 1.0 + w
        new.append(jnp.asarray(w, leaf.dtype))
    net.params = jax.tree_util.tree_unflatten(treedef, new)
    return net


def _ref_logp(net, config, ids):
    z = np.asarray(REF.logits(net.params, np.asarray(ids), config),
                   np.float64)
    z = z - z.max(axis=-1, keepdims=True)
    return z - np.log(np.exp(z).sum(axis=-1, keepdims=True))


def _ids(n, t, seed=0, vocab=96):
    return np.random.default_rng(seed).integers(0, vocab, (n, t))


def _moe_params(layer, seed=0, scale=4.0):
    p, _ = layer.initialize(jax.random.PRNGKey(seed),
                            InputType.recurrent(64))
    p = jax.tree_util.tree_map(lambda w: w * scale, p)
    p["br"] = jax.random.normal(jax.random.PRNGKey(seed + 7),
                                p["br"].shape) * 0.05
    return p


@pytest.fixture(scope="module")
def tiny_net():
    return _net(TINY)


def test_full_sequence_logits_match_the_reference(tiny_net):
    ids = _ids(2, 12)
    got = np.log(np.asarray(tiny_net.output(ids[..., None].astype(
        np.float32)), np.float64))
    for b in range(2):
        np.testing.assert_allclose(
            got[b], _ref_logp(tiny_net, TINY, ids[b]), atol=2e-5)


def test_router_picks_the_references_experts_and_keeps_the_bias_out():
    layer = SparseExpertsLayer(held=(4, 4), **MOE)
    p = _moe_params(layer, scale=8.0)
    # the router keeps its whole width, zero experts behind the routed
    assert p["Wr"].shape == (64, 24) and p["br"].shape == (24,)
    assert p["Wg"].shape[0] == 4 and "Wsg" not in p
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 64))
    ids, w = layer.route(p, x)
    config = dict(TINY, held_first_expert=4)
    want = REF._experts(p, x, config)[1]
    np.testing.assert_array_equal(np.sort(np.asarray(ids), axis=-1),
                                  np.asarray(want))
    # the bias moves the selection: without it other experts win
    plain, _ = layer.route(dict(p, br=jnp.zeros_like(p["br"])), x)
    assert not np.array_equal(np.sort(np.asarray(plain), axis=-1),
                              np.asarray(want))
    # and it is not in the weights: 6 x the softmax probability
    # itself, no normaliser over the selected
    prob = jax.nn.softmax(x @ p["Wr"], axis=-1)
    np.testing.assert_allclose(
        w, 6.0 * np.take_along_axis(np.asarray(prob), np.asarray(ids),
                                    axis=-1), rtol=1e-5)
    with pytest.raises(ValueError, match="scoring_func"):
        SparseExpertsLayer(scoring_func="tanh")


def test_a_zero_experts_part_is_the_token_times_its_weight():
    layer = SparseExpertsLayer(**MOE)
    p = _moe_params(layer)
    # the held experts give nothing: what is left is the zero part
    p = dict(p, Wd=jnp.zeros_like(p["Wd"]))
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 5, 64))
    out, tally = layer.apply_tallied(p, x)
    ids, w = layer.route(p, x.reshape(-1, 64))
    wz = np.where(np.asarray(ids) >= 16, np.asarray(w), 0.0).sum(-1)
    assert 0 < int(tally["zero"]) == int((np.asarray(ids) >= 16).sum())
    assert int(tally["selected"]) == 15 * 4
    assert int(tally["held"].sum()) == 60 - int(tally["zero"])
    np.testing.assert_allclose(out.reshape(-1, 64),
                               wz[:, None] * np.asarray(x).reshape(-1, 64),
                               rtol=1e-5, atol=1e-6)


def test_scaled_absorbed_attention_equals_unabsorbed():
    kw = dict(n_in=64, n_heads=4, q_lora_rank=16, kv_lora_rank=32)
    layer = LatentAttentionLayer(scale_q_lora=True, scale_kv_lora=True,
                                 **kw)
    p, _ = layer.initialize(jax.random.PRNGKey(0),
                            InputType.recurrent(64))
    p = jax.tree_util.tree_map(
        lambda w: w * 3.0 if w.ndim == 2 else w, p)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 10, 64))
    full, _ = layer.apply(p, {}, x)
    np.testing.assert_allclose(layer.apply_absorbed(p, x), full,
                               rtol=1e-5, atol=1e-5)
    pos = jnp.broadcast_to(jnp.arange(10)[None], (2, 10))
    qn, qr, ckv, kr = layer._project(p, x, pos)
    un, ur, uckv, ukr = LatentAttentionLayer(**kw)._project(p, x, pos)
    # the query's both halves by sqrt(64/16), the latent by
    # sqrt(64/32), the rotary key not at all
    np.testing.assert_allclose(qn, 2.0 * un, rtol=1e-6)
    np.testing.assert_allclose(qr, 2.0 * ur, rtol=1e-6)
    np.testing.assert_allclose(ckv, np.sqrt(2.0) * uckv, rtol=1e-6)
    np.testing.assert_array_equal(kr, ukr)


def test_paged_prefill_then_decode_matches_the_reference(tiny_net):
    """Token by token through ``PagedSlotSession`` over both latent
    pools of every layer: at every position the session's
    distribution is the reference's full forward pass (logits, not
    tokens)."""
    net, T = tiny_net, 14
    ids = _ids(3, T, seed=1)
    sess = net.paged_slot_streaming_session(capacity=16, slots=3,
                                            page_size=4)
    pool = sess._pools[1]
    assert set(pool) == {"a0", "a1"}
    for half in pool.values():
        assert half["ckv"].shape == (13, 4, 16)  # 12 pages + scratch
        assert half["kr"].shape == (13, 4, 128)     # one lane tile
    for i in range(3):
        sess.bind(i, sess.reserve(ids[i, :1], T - 1))
    got = []
    for t in range(T):
        x = ids[:, t].reshape(3, 1, 1).astype(np.float32)
        got.append(np.asarray(sess.step_slots(x, np.ones(3, bool))))
    got = np.log(np.concatenate(got, axis=1).astype(np.float64))
    for b in range(3):
        np.testing.assert_allclose(got[b], _ref_logp(net, TINY, ids[b]),
                                   atol=2e-5)
    # the two attentions of a layer hold different caches
    assert not np.array_equal(np.asarray(sess._pools[1]["a0"]["ckv"]),
                              np.asarray(sess._pools[1]["a1"]["ckv"]))
    # the tally comes back beside the logits: two expert layers x 16
    # held experts, 3 tokens x top-4 pairs each, zero experts' picks
    # apart
    aux = jax.device_get(sess.step_aux)
    assert aux["held"].shape == (2, 16)
    assert aux["zero"].shape == aux["selected"].shape == (2,)
    assert (aux["selected"] == 12).all()
    assert (aux["held"].sum(axis=1) + aux["zero"] == 12).all()


@pytest.mark.parametrize("path", ["gather", "by_table"])
@pytest.mark.parametrize("case", chunk_parity.CASES)
def test_chunk_step_matches_token_by_token(tiny_net, monkeypatch, case,
                                           path):
    """The two-pool layer's cases of tests/chunk_parity.py (ragged
    ``n_valid`` among them): every leaf of both pools, and the three
    counts of a chunk are the one-by-one counts summed, so rows past
    ``n_valid`` reach no expert, routed or zero. Once by the gather
    (the CPU's path) and once with both pools read by table (the
    chip's: the predicate forced, the kernel interpreted)."""
    if path == "by_table":
        chunk_parity.latent_by_table(monkeypatch)
    chunk_parity.run_case(tiny_net, 96, case)


@pytest.mark.parametrize("by_table", [False, True],
                         ids=["gather", "by_table"])
def test_kv_positions_follow_the_dispatch(tiny_net, monkeypatch, by_table):
    chunk_parity.kv_positions_follow_the_dispatch(tiny_net, monkeypatch,
                                                  by_table)


def test_chunk_step_at_the_cells_width_matches_token_by_token(tiny_net):
    """t = 4, the width the benchmark's cell runs, with a ragged
    ``n_valid``."""
    sessions = [tiny_net.paged_slot_streaming_session(
        capacity=32, slots=4, page_size=4) for _ in range(2)]
    prompts = {0: _ids(1, 4, seed=8)[0], 1: _ids(1, 3, seed=9)[0],
               3: _ids(1, 1, seed=10)[0]}
    for s in sessions:
        for slot, ids in prompts.items():
            s.bind(slot, s.reserve(list(map(int, ids)), 4))
    x = np.zeros((4, 4, 1), np.float32)
    n_valid = np.zeros((4,), np.int32)
    for slot, ids in prompts.items():
        x[slot, :len(ids), 0], n_valid[slot] = ids, len(ids)
    h = np.asarray(sessions[0].step_chunk(x, n_valid))
    last, counts = chunk_parity.feed_single(
        sessions[1], {k: list(map(int, v)) for k, v in prompts.items()})
    for slot in prompts:
        np.testing.assert_allclose(h[slot, 0], last[slot], atol=1e-5)
    got = jax.device_get(sessions[0].step_aux)
    for k in ("held", "zero", "selected"):
        np.testing.assert_array_equal(got[k], counts[k])
    assert (got["selected"] == 8 * 4).all()


def test_free_slots_reach_no_expert(tiny_net):
    sess = tiny_net.paged_slot_streaming_session(capacity=8, slots=4,
                                                 page_size=4)
    sess.bind(2, sess.reserve([5], 3))
    active = np.array([False, False, True, False])
    sess.step_slots(np.full((4, 1, 1), 5, np.float32), active)
    aux = jax.device_get(sess.step_aux)
    assert (aux["selected"] == 4).all()
    assert (aux["held"].sum(axis=1) + aux["zero"] == 4).all()


def test_shares_of_an_expert_group_add_up_to_the_whole_layer():
    """4 shares of 4 of 16 routed experts: the parts that the shares
    give, the zero experts' part (which every chip computes alike)
    counted once and nothing else, are the uncut reference's layer
    output."""
    whole = SparseExpertsLayer(**MOE)
    p = _moe_params(whole)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 64))
    # the zero experts' part alone: a share whose experts give nothing
    zero_part = whole.apply_counted(
        dict(p, Wd=jnp.zeros_like(p["Wd"])), x)[0]
    assert float(jnp.abs(zero_part).max()) > 0.1
    total, counted, zeros = zero_part, 0, set()
    for first in (0, 4, 8, 12):
        part = SparseExpertsLayer(held=(first, 4), **MOE)
        pp = dict(p, **{k: p[k][first:first + 4]
                        for k in ("Wg", "Wu", "Wd")})
        out, tally = part.apply_tallied(pp, x)
        total = total + (out - zero_part)
        counted += int(tally["held"].sum())
        zeros.add(int(tally["zero"]))
        assert int(tally["selected"]) == 2 * 9 * 4
    # every pair served once: by one share's expert or by a zero one
    assert len(zeros) == 1 and counted + zeros.pop() == 2 * 9 * 4
    want = np.stack([np.asarray(REF._experts(p, x[b], TINY)[0])
                     for b in range(2)])
    assert float(np.abs(want).max()) > 0.1
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=5e-5)
    np.testing.assert_allclose(whole.apply_counted(p, x)[0], want,
                               rtol=1e-5, atol=5e-5)


def test_block_round_trips_through_json():
    config = dict(TINY, n_routed_experts=4, held_first_expert=8)
    net = BUILDER.build(config).net
    text = net.conf.to_json()
    back = type(net.conf).from_json(text)
    assert back.to_json() == text
    blk = back.layers[2]
    assert isinstance(blk, ShortcutExpertBlock)
    assert blk.held == (8, 4) and blk.n_zero_experts == 8
    assert blk.scale_q_lora and blk.scale_kv_lora
    assert isinstance(back.layers[-2], RMSNormalization)
    for layer in (LatentAttentionLayer(n_heads=2, scale_kv_lora=True),
                  SparseExpertsLayer(held=(2, 3), n_routed_experts=8,
                                     n_zero_experts=4, top_k=2,
                                     scoring_func="softmax",
                                     router_bias=True)):
        d = json.loads(json.dumps(layer.to_dict()))
        assert layer_from_dict(d) == layer
    with pytest.raises(ValueError, match="identities"):
        BUILDER.block(dict(TINY, zero_expert_type="constant"))


def test_bfloat16_policy_keeps_parameters_and_both_pools_in_bfloat16():
    with BUILDER.policy(TINY):
        shapes = BUILDER.build(TINY).init().params
        assert all(s.dtype == jnp.bfloat16
                   for s in jax.tree_util.tree_leaves(shapes))
        net = _net(TINY)
    assert all(w.dtype == jnp.bfloat16
               for w in jax.tree_util.tree_leaves(net.params))
    sess = net.paged_slot_streaming_session(capacity=8, slots=2,
                                            page_size=4)
    leaves = [v for p in sess._pools if p is not None
              for v in jax.tree_util.tree_leaves(p)]
    assert len(leaves) == 2 * 2 * 2
    assert {v.dtype for v in leaves} == {jnp.dtype(jnp.bfloat16)}
    ids = _ids(2, 6, seed=2)
    sess.bind(0, sess.reserve(ids[0, :1], 5))
    sess.bind(1, sess.reserve(ids[1, :1], 5))
    got = []
    for t in range(6):
        h = sess.step_slots(ids[:, t].reshape(2, 1, 1).astype(
            np.float32), np.ones(2, bool))
        assert h.dtype == jnp.float32      # logits and softmax
        got.append(np.asarray(h))
    got = np.log(np.concatenate(got, axis=1).astype(np.float64))
    want = np.stack([_ref_logp(net, TINY, ids[b]) for b in range(2)])
    # bfloat16 rounding, not a different function
    assert np.median(np.abs(got - want).max(axis=-1)) < 0.1
    assert dtypes.policy().param_dtype == jnp.float32


def test_lease_export_import_and_page_copy_on_the_two_pool_layer(
        tiny_net):
    net = tiny_net
    prompt = list(map(int, _ids(1, 9, seed=4)[0]))

    def feed(sess, slot, tokens):
        h = None
        for tok in tokens:
            x = np.zeros((sess.slots, 1, 1), np.float32)
            x[slot, 0, 0] = tok
            active = np.zeros(sess.slots, bool)
            active[slot] = True
            h = np.asarray(sess.step_slots(x, active))[slot, 0]
        return h

    new = lambda slots: net.paged_slot_streaming_session(
        capacity=16, slots=slots, page_size=4)
    a = new(2)
    # four leaves a layer travel: both pools' latent and rotary key
    assert [len(s) for s in a._pool_schema() if s] == [4, 4]
    a.bind(1, a.reserve(prompt, 4))
    feed(a, 1, prompt[:-1])
    b = new(2)
    lease, extra = b.import_lease(a.export_lease(1, extra={"k": 1}),
                                  len(prompt) + 4)
    b.bind(0, lease)
    assert extra == {"k": 1} and lease.resume_pos == len(prompt) - 1
    np.testing.assert_array_equal(feed(b, 0, prompt[-1:]),
                                  feed(a, 1, prompt[-1:]))
    # a whole-prompt prefix hit copies its boundary page on write, in
    # both pools
    a.release(1, register_prompt=prompt)
    again = a.reserve(prompt[:8], 4)
    assert again.prefix_hit_tokens == 7
    a.bind(0, again)
    z = feed(a, 0, prompt[7:8])
    fresh = new(1)
    fresh.bind(0, fresh.reserve(prompt[:8], 4))
    np.testing.assert_allclose(z, feed(fresh, 0, prompt[:8]),
                               atol=1e-6)


def test_batcher_serves_what_the_session_decodes(tiny_net):
    from deeplearning4j_tpu.serving.continuous import ContinuousBatcher
    from deeplearning4j_tpu.serving.metrics import ServingMetrics
    net = tiny_net
    prompts = [list(map(int, _ids(1, n, seed=n)[0])) for n in (3, 6, 9)]
    want = []
    for prompt in prompts:
        sess = net.paged_slot_streaming_session(capacity=32, slots=1,
                                                page_size=4)
        sess.bind(0, sess.reserve(prompt, 5))
        out, feed = [], list(prompt)
        while len(out) < 5:
            h = np.asarray(sess.step_slots(
                np.full((1, 1, 1), feed.pop(0), np.float32),
                np.ones(1, bool)))
            if not feed:
                out.append(int(h[0, 0].argmax()))
                feed.append(out[-1])
        want.append(out)
    metrics = ServingMetrics()
    cb = ContinuousBatcher(net, slots=2, capacity=32, kv_mode="paged",
                           page_size=4, metrics=metrics, name="lc")
    try:
        got = [list(map(int, cb.generate(p, 5))) for p in prompts]
    finally:
        cb.shutdown(drain=True)
    assert got == want
    snap = metrics.registry.snapshot()
    series = lambda what: snap[
        'serving_moe_%s_total{endpoint="lc"}' % what]
    steps = snap['serving_step_seconds{endpoint="lc",part="device"}'][
        "count"]
    assert series("expert_slots") == steps * 2 * 16
    assert 0 < series("expert_hits") <= series("local_pairs")
    # every token fed, the prompts' and each sampled token but a
    # request's last, picked top-4 of 16 + 8 in two expert layers; a
    # pick went to a held routed expert or to a zero one
    fed = snap['serving_prompt_tokens_total{endpoint="lc"}']
    assert fed == 3 + 6 + 9
    assert series("selected_pairs") == (fed + 3 * (5 - 1)) * 2 * 4
    assert 0 < series("zero_pairs") < series("selected_pairs")
    assert series("local_pairs") + series("zero_pairs") == \
        series("selected_pairs")


def test_the_defaults_are_the_layers_they_were():
    """No new field changes a layer that does not set it: at its
    defaults the expert layer gives, bit for bit, what its equations
    before the softmax router, the bias and the zero experts give
    (written out here), and a latent attention without the scales
    multiplies by nothing."""
    from deeplearning4j_tpu.dtypes import einsum_f32
    from deeplearning4j_tpu.nn.conf.layers.moe import swiglu
    layer = SparseExpertsLayer(n_in=64, held=(4, 8))
    assert (layer.scoring_func, layer.n_zero_experts,
            layer.router_bias) == ("sigmoid", 0, False)
    p, _ = layer.initialize(jax.random.PRNGKey(0),
                            InputType.recurrent(64))
    assert set(p) == {"Wr", "Wg", "Wu", "Wd", "Wsg", "Wsu", "Wsd"}
    p = jax.tree_util.tree_map(lambda w: w * 4.0, p)
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 64))
    scores = jax.nn.sigmoid(einsum_f32("nd,de->ne", x, p["Wr"]))
    w, ids = jax.lax.top_k(scores, 4)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    hit = (ids - 4)[:, :, None] == jnp.arange(8)
    comb = jnp.sum(jnp.where(hit, w[:, :, None], 0.0), axis=1)
    g = einsum_f32("nd,edw->enw", x, p["Wg"])
    u = einsum_f32("nd,edw->enw", x, p["Wu"])
    y = einsum_f32("enw,ewd->end", jax.nn.silu(g) * u, p["Wd"])
    want = (jnp.einsum("end,ne->nd", y, comb)
            + swiglu(x, p["Wsg"], p["Wsu"], p["Wsd"]))
    out, counts = layer.apply_counted(p, x)
    np.testing.assert_array_equal(out, want)
    np.testing.assert_array_equal(counts, jnp.sum(hit, axis=(0, 1)))
    assert float(jnp.abs(want).max()) > 0.1
    tally = layer.apply_tallied(p, x)[1]
    assert int(tally["zero"]) == 0 and int(tally["selected"]) == 24
    attn = LatentAttentionLayer(n_in=64)
    assert not attn.scale_q_lora and not attn.scale_kv_lora
