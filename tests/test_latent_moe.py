"""Latent attention over a paged latent cache, sigmoid-routed experts
as one chip's share of an expert-parallel group, and the decoder block
that carries both, held at a small size against the plain reference
(benchmark/reference/axk1.py: float32 jax.numpy, no code of the
program)."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chunk_parity
from deeplearning4j_tpu import (MultiLayerNetwork, NeuralNetConfiguration,
                                dtypes)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    EmbeddingSequenceLayer, LatentAttentionLayer, LatentDecoderBlock,
    RMSNormalization, RnnOutputLayer, SparseExpertsLayer,
    TransformerEncoderLayer, layer_from_dict)
from deeplearning4j_tpu.nn.conf.layers.latent_attention import (
    yarn_inv_freq)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}",
        os.path.join(ROOT, "benchmark", kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("reference", "axk1")
BUILDER = _load("builders", "axk1_dsl")

YARN = {"type": "yarn", "factor": 32, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 8}

# hidden 64, 4 heads, ranks 24/16, nope 8 / rope 4 / v 8, 16 experts,
# top-4, 1 dense + 2 expert layers
TINY = {"hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 24,
        "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
        "v_head_dim": 8, "rope_theta": 10000, "rope_scaling": YARN,
        "rms_norm_eps": 1e-6, "intermediate_size": 96,
        "moe_intermediate_size": 32, "n_shared_experts": 1,
        "router_experts": 16, "n_routed_experts": 16,
        "held_first_expert": 0, "num_experts_per_tok": 4,
        "routed_scaling_factor": 2.5, "norm_topk_prob": True,
        "topk_method": "none",
        "first_k_dense_replace": 1, "num_hidden_layers": 3,
        "vocab_size": 96, "max_position_embeddings": 64}


def _net(config, seed=3, std=0.1):
    """The DSL network of ``config`` with seeded normal weights (gains
    drawn around one, so that a dropped gain shows)."""
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


@pytest.fixture(scope="module")
def tiny_net():
    return _net(TINY)


def test_yarn_frequencies_match_the_reference():
    got = yarn_inv_freq(64, 10000.0, dict(YARN, **{
        "original_max_position_embeddings": 4096}))
    want = REF._inv_freq({"qk_rope_head_dim": 64, "rope_theta": 10000,
                          "rope_scaling": dict(YARN, **{
                              "original_max_position_embeddings":
                                  4096})})[0]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # fast dimensions are kept, slow ones are divided by the factor
    assert got[0] == pytest.approx(1.0)
    assert got[-1] == pytest.approx(10000.0 ** (-62 / 64) / 32)


@pytest.mark.parametrize("norm_topk_prob", [True, False])
def test_full_sequence_logits_match_the_reference(norm_topk_prob):
    config = dict(TINY, norm_topk_prob=norm_topk_prob)
    net = _net(config)
    ids = _ids(2, 12)
    got = np.log(np.asarray(net.output(ids[..., None].astype(
        np.float32)), np.float64))
    for b in range(2):
        np.testing.assert_allclose(got[b], _ref_logp(net, config, ids[b]),
                                   atol=2e-5)


def test_router_selects_the_top_k_sigmoid_scores():
    layer = SparseExpertsLayer(n_in=64, n_routed_experts=16, top_k=4,
                               held=(4, 4), routed_scaling_factor=2.5)
    p, _ = layer.initialize(jax.random.PRNGKey(0),
                            InputType.recurrent(64))
    # the router keeps its whole width whatever the layer holds
    assert p["Wr"].shape == (64, 16) and p["Wg"].shape[0] == 4
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 64))
    ids, w = layer.route(p, x)
    scores = 1.0 / (1.0 + np.exp(-np.asarray(x @ p["Wr"], np.float64)))
    want = np.argsort(-scores, axis=-1)[:, :4]
    assert np.array_equal(np.sort(np.asarray(ids), axis=-1),
                          np.sort(want, axis=-1))
    # the normaliser runs over all selected, held or not
    np.testing.assert_allclose(np.asarray(w).sum(axis=-1), 2.5,
                               rtol=1e-5)
    with pytest.raises(ValueError, match="outside the router"):
        SparseExpertsLayer(n_in=64, n_routed_experts=16, held=(12, 8))


def test_absorbed_attention_equals_unabsorbed():
    layer = LatentAttentionLayer(n_in=64, n_heads=4, rope_scaling=YARN)
    p, _ = layer.initialize(jax.random.PRNGKey(0),
                            InputType.recurrent(64))
    p = jax.tree_util.tree_map(
        lambda w: w * 3.0 if w.ndim == 2 else w, p)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 10, 64))
    full, _ = layer.apply(p, {}, x)
    np.testing.assert_allclose(layer.apply_absorbed(p, x), full,
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(full).max()) > 1e-2


def test_paged_prefill_then_decode_matches_the_reference(tiny_net):
    """Token by token through ``PagedSlotSession`` over the latent
    pool: at every position the session's distribution is the
    reference's full forward pass (logits, not tokens)."""
    net, T = tiny_net, 14
    ids = _ids(3, T, seed=1)
    sess = net.paged_slot_streaming_session(capacity=16, slots=3,
                                            page_size=4)
    pool = sess._pools[1]
    assert set(pool) == {"ckv", "kr"}
    assert pool["ckv"].shape == (13, 4, 16)      # 12 pages + scratch
    # the rotary key of 4 in a row of one lane tile, zeros past it
    assert pool["kr"].shape == (13, 4, 128)
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
    # the expert layers' counts come back beside the logits: two
    # expert layers x 16 held experts, 3 tokens x top-4 pairs each
    aux = np.asarray(sess.step_aux)
    assert aux.shape == (2, 16) and (aux.sum(axis=1) == 12).all()


@pytest.mark.parametrize("t", [8, 4])
@pytest.mark.parametrize("path", ["gather", "by_table"])
@pytest.mark.parametrize("case", chunk_parity.CASES)
def test_chunk_step_matches_token_by_token(tiny_net, monkeypatch, case,
                                           path, t):
    """The latent pool's cases of tests/chunk_parity.py: the two
    expert layers' counts of a chunk are the one-by-one counts
    summed, so rows past ``n_valid`` reach no routed expert. Once by
    the gather (the CPU's path) and once with the pages read by table
    (the chip's: the predicate forced, the kernel interpreted), at
    chunks of 8 rows and of 4 (a 64-slot pool's wide program)."""
    if path == "by_table":
        chunk_parity.latent_by_table(monkeypatch)
    chunk_parity.run_case(tiny_net, 96, case, t=t)


def test_free_slots_reach_no_expert(tiny_net):
    sess = tiny_net.paged_slot_streaming_session(capacity=8, slots=4,
                                                 page_size=4)
    sess.bind(2, sess.reserve([5], 3))
    active = np.array([False, False, True, False])
    sess.step_slots(np.full((4, 1, 1), 5, np.float32), active)
    assert (np.asarray(sess.step_aux).sum(axis=1) == 4).all()


def test_a_network_without_experts_keeps_its_step():
    conf = (NeuralNetConfiguration.builder().set_seed(0).list()
            .layer(EmbeddingSequenceLayer(n_in=32, n_out=16))
            .layer(TransformerEncoderLayer(n_heads=2, causal=True))
            .layer(RnnOutputLayer(n_out=32, loss="mcxent"))
            .set_input_type(InputType.recurrent(32, 8)).build())
    net = MultiLayerNetwork(conf).init()
    sess = net.paged_slot_streaming_session(capacity=8, slots=2,
                                            page_size=4)
    sess.bind(0, sess.reserve([1], 2))
    sess.step_slots(np.ones((2, 1, 1), np.float32),
                    np.array([True, False]))
    assert sess.step_aux is None and sess._aux_layers == []
    assert sess._pools[1]["k"].dtype == jnp.float32


@pytest.mark.parametrize("path", ["gather", "by_table"])
def test_batcher_serves_what_the_session_decodes(tiny_net, monkeypatch,
                                                 path):
    """Through ``ContinuousBatcher`` (chunked prefill, ids picked on
    the device, one step ahead) the ids of a session fed token by
    token, the expert counters, a prefix hit; with the latent pool
    read by table (the predicate forced, the kernel interpreted) the
    same, and the KV positions read fall under the span."""
    from deeplearning4j_tpu.serving.continuous import ContinuousBatcher
    from deeplearning4j_tpu.serving.metrics import ServingMetrics
    if path == "by_table":
        chunk_parity.latent_by_table(monkeypatch)
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
                           page_size=4, metrics=metrics, name="axk")
    try:
        assert cb._paged and cb.session._pools[1]["ckv"].dtype == \
            jnp.float32
        got = [list(map(int, cb.generate(p, 5))) for p in prompts]
        # the same prompt again resumes after its cached pages
        hits = cb.session.prefix_cache.hits_total
        again = list(map(int, cb.generate(prompts[2], 5)))
    finally:
        cb.shutdown(drain=True)
    assert got == want and again == want[2]
    assert cb.session.prefix_cache.hits_total == hits + 1
    snap = metrics.registry.snapshot()
    pairs = snap['serving_moe_local_pairs_total{endpoint="axk"}']
    slots = snap['serving_moe_expert_slots_total{endpoint="axk"}']
    hit = snap['serving_moe_expert_hits_total{endpoint="axk"}']
    steps = snap['serving_step_seconds{endpoint="axk",part="device"}'][
        "count"]
    assert slots == steps * 2 * 16 and 0 < hit <= min(pairs, slots)
    # every token fed, the prompts' (a chunk a prompt; the repeat
    # found all but its last token cached) and each sampled token but
    # a request's last, went to top-4 of 16 held in two expert layers
    fed = snap['serving_prompt_tokens_total{endpoint="axk"}']
    assert fed == 3 + 6 + 9 + (9 - 8)
    assert snap['serving_steps_total{endpoint="axk",program="chunk"}'] \
        == 3
    assert pairs == (fed + 4 * (5 - 1)) * 2 * 4
    read = snap['serving_kv_positions_read_total{endpoint="axk"}']
    spanned = snap['serving_kv_positions_spanned_total{endpoint="axk"}']
    assert (read == spanned) if path == "gather" else (
        0 < read < 0.5 * spanned)


def test_lease_export_import_on_the_latent_pool(tiny_net):
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

    a = net.paged_slot_streaming_session(capacity=16, slots=2,
                                         page_size=4)
    a.bind(1, a.reserve(prompt, 4))
    feed(a, 1, prompt[:-1])
    blob = a.export_lease(1, extra={"k": 1})
    b = net.paged_slot_streaming_session(capacity=16, slots=2,
                                         page_size=4)
    lease, extra = b.import_lease(blob, len(prompt) + 4)
    b.bind(0, lease)
    assert extra == {"k": 1} and lease.resume_pos == len(prompt) - 1
    np.testing.assert_array_equal(feed(b, 0, prompt[-1:]),
                                  feed(a, 1, prompt[-1:]))
    # a whole-prompt prefix hit copies its boundary page on write
    a.release(1, register_prompt=prompt)
    again = a.reserve(prompt[:8], 4)
    assert again.prefix_hit_tokens == 7
    a.bind(0, again)
    z = feed(a, 0, prompt[7:8])
    fresh = net.paged_slot_streaming_session(capacity=16, slots=1,
                                             page_size=4)
    fresh.bind(0, fresh.reserve(prompt[:8], 4))
    np.testing.assert_allclose(z, feed(fresh, 0, prompt[:8]),
                               atol=1e-6)


def _axk1_layer(p, x):
    return REF._experts(p, x, dict(TINY, held_first_expert=0))[0]


def _lfm2_layer(p, x):
    return _load("reference", "lfm2_moe").experts(
        p, x, {"num_experts_per_tok": 4, "routed_scaling_factor": 1})


@pytest.mark.parametrize("kw, shares, reference", [
    (dict(n_routed_experts=16, routed_scaling_factor=2.5), 4,
     _axk1_layer),
    # every expert on one chip (``held=None``): a sigmoid router with
    # a selection-only bias, no shared expert
    (dict(n_routed_experts=64, n_shared_experts=0, router_bias=True), 8,
     _lfm2_layer),
], ids=["axk1_4_shares_of_4", "lfm2_8_shares_of_8"])
def test_shares_of_an_expert_group_add_up_to_the_whole_layer(
        kw, shares, reference):
    """The parts that the shares of an expert-parallel group give,
    the shared expert (where there is one) counted once, are the
    uncut reference's layer output, and so is what the layer that
    holds every expert (``held=None``) gives."""
    kw = dict(kw, n_in=64, top_k=4, expert_width=32)
    whole = SparseExpertsLayer(**kw)
    assert whole.held is None
    p, _ = whole.initialize(jax.random.PRNGKey(0),
                            InputType.recurrent(64))
    p = jax.tree_util.tree_map(lambda w: w * 4.0, p)
    if "br" in p:       # zeros at first: a bias that moves the picks
        p["br"] = 0.2 * jax.random.normal(jax.random.PRNGKey(2),
                                          p["br"].shape)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 64))
    from deeplearning4j_tpu.nn.conf.layers.moe import swiglu
    shared = swiglu(x, p["Wsg"], p["Wsu"], p["Wsd"]) if "Wsg" in p \
        else jnp.zeros_like(x)
    total, counted = shared, 0
    each = kw["n_routed_experts"] // shares
    for first in range(0, kw["n_routed_experts"], each):
        part = SparseExpertsLayer(held=(first, each), **kw)
        pp = dict(p, **{k: p[k][first:first + each]
                        for k in ("Wg", "Wu", "Wd")})
        out, counts = part.apply_counted(pp, x)
        total = total + (out - shared)
        counted += int(counts.sum())
    assert counted == 2 * 9 * 4          # every pair served once
    p32 = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), p)
    want = np.stack([np.asarray(reference(p32, x[b])) for b in range(2)])
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
    assert isinstance(blk, LatentDecoderBlock)
    assert blk.held == (8, 4) and blk.rope_scaling == YARN
    assert isinstance(back.layers[-2], RMSNormalization)
    assert back.layers[-1].has_bias is False
    for layer in (LatentAttentionLayer(n_heads=2, rope_scaling=YARN),
                  SparseExpertsLayer(held=(2, 3), n_routed_experts=8,
                                     top_k=2)):
        d = json.loads(json.dumps(layer.to_dict()))
        assert layer_from_dict(d) == layer
    with pytest.raises(ValueError, match="held"):
        SparseExpertsLayer(held=(14, 4), n_routed_experts=16)


def test_bfloat16_policy_keeps_parameters_and_cache_in_bfloat16():
    with BUILDER.policy(TINY):
        shapes = BUILDER.build(TINY).init().params
        assert all(s.dtype == jnp.bfloat16
                   for s in jax.tree_util.tree_leaves(shapes))
        net = _net(TINY)
    assert all(w.dtype == jnp.bfloat16
               for w in jax.tree_util.tree_leaves(net.params))
    sess = net.paged_slot_streaming_session(capacity=8, slots=2,
                                            page_size=4)
    assert {v.dtype for p in sess._pools if p is not None
            for v in p.values()} == {jnp.dtype(jnp.bfloat16)}
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
    full = np.log(np.asarray(net.output(ids[..., None].astype(
        np.float32)), np.float64))
    want = np.stack([_ref_logp(net, TINY, ids[b]) for b in range(2)])
    # bfloat16 rounding, not a different function (one position may
    # sit far off: a near-tie of the router's scores flips an expert)
    assert np.median(np.abs(got - want).max(axis=-1)) < 0.1
    assert np.median(np.abs(full - want).max(axis=-1)) < 0.1
    # a lease of bfloat16 pages survives the wire
    blob = sess.export_lease(0)
    other = net.paged_slot_streaming_session(capacity=8, slots=2,
                                             page_size=4)
    lease, _ = other.import_lease(blob, 8)
    np.testing.assert_array_equal(
        np.asarray(other._pools[1]["ckv"][lease.pages[0]], np.float32),
        np.asarray(sess._pools[1]["ckv"][sess._leases[0].pages[0]],
                   np.float32))
    assert dtypes.policy().param_dtype == jnp.float32


def test_fit_runs_through_the_block(tiny_net):
    net = _net(TINY)
    ids = _ids(4, 8, seed=5)
    x = ids[..., None].astype(np.float32)
    y = np.eye(96, dtype=np.float32)[np.roll(ids, -1, axis=1)]
    net.fit(x, y, epochs=1)
    assert np.isfinite(net.score_value)


# ---- the latent pool read by table ---------------------------------
# (ops/paged_attention.py's latent kernel in Pallas' interpret mode
# against ``_attend`` over the gathered table, which stays the CPU
# path; Mosaic's verdict on the kernel: tests/test_chip_compile.py)

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("t", [1, 2, 4])
@pytest.mark.parametrize("fields", [
    dict(rope_scaling=YARN), dict(scale_q_lora=True, scale_kv_lora=True)],
    ids=["yarn_softmax_scale", "scaled_loras"])
def test_latent_by_table_kernel_matches_attend(monkeypatch, fields, t,
                                               dtype):
    """``apply_stream_paged``, the kernel's path against the gather's,
    on one pool, table and chunk: a free slot, a slot with one token,
    slots that end on a page's last row, on the last key of the first
    block of 128 and at capacity (two blocks and a half), one
    mid-page over a shared prefix with ``n_valid`` short of ``t``, one
    in the second block, and stale table tails that point at pages of
    large finite values. YaRN gives the softmax a scale that is not
    ``(dn + dr) ** -0.5``; the scaled layer caches a scaled latent."""
    ps, P, S, C = 16, 20, 8, 64
    cap = P * ps
    layer = LatentAttentionLayer(n_in=C, n_heads=8, kv_lora_rank=32,
                                 **fields)
    if "rope_scaling" in fields:
        assert layer._softmax_scale() != pytest.approx(12 ** -0.5)
    assert not layer.paged_reads_by_table(ps, t, dtype)      # the CPU
    rng = np.random.default_rng([t, len(dtype), len(fields)])
    params = jax.tree_util.tree_map(
        lambda w: jnp.asarray(
            (w.ndim == 1) + rng.normal(0.0, 0.25, w.shape), dtype),
        layer.initialize(jax.random.PRNGKey(0),
                         InputType.recurrent(C))[0])
    n_live = S * P
    pool = {name: np.zeros((n_live + 3,) + leaf.shape[1:])
            for name, leaf in layer.zero_pool(1, ps, dtype).items()}
    pool["ckv"][:] = rng.normal(size=pool["ckv"].shape)
    pool["kr"][..., :4] = rng.normal(size=pool["kr"][..., :4].shape)
    # pages no slot holds: large finite garbage, which stale table
    # entries past a slot's length point at
    garbage = [n_live + 1, n_live + 2]
    for leaf in pool.values():
        leaf[garbage] = 1e30
    table = rng.permutation(np.arange(1, n_live + 1)).reshape(S, P)
    #        free  one  a page  a block   full     shares 3's
    pos = [0,      0,   ps - t, 128 - t,  cap - t, 2 * ps + 3,
           0,      8 * ps + 5]         # parked; in the second block
    n_valid = [0,  1,   t,      t,        t,       max(t - 1, 1),
               0,  1]
    pos, n_valid = np.array(pos, np.int32), np.array(n_valid, np.int32)
    table[0] = 0
    table[5, :2] = table[3, :2]               # a shared prompt prefix
    for s in range(S):
        held = -(-(pos[s] + n_valid[s]) // ps)
        if s != 6:                            # 6 keeps a whole table
            table[s, held:] = garbage[s % 2]
    pool = {name: jnp.asarray(leaf, dtype) for name, leaf in pool.items()}
    x = jnp.asarray(rng.normal(size=(S, t, C)), dtype)
    args = (params, pool, jnp.asarray(table, jnp.int32), jnp.asarray(pos),
            x, jnp.asarray(n_valid))
    want, want_pool = layer.apply_stream_paged(*args)
    chunk_parity.latent_by_table(monkeypatch)
    assert layer.paged_reads_by_table(ps, t, dtype)
    got, got_pool = layer.apply_stream_paged(*args)
    for name in pool:
        np.testing.assert_array_equal(
            np.asarray(got_pool[name], np.float32),
            np.asarray(want_pool[name], np.float32))
    # a written row is the rotary key and zeros past it
    assert not np.asarray(got_pool["kr"], np.float32)[
        table[3, 128 // ps - 1], :, 4:].any()
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    assert not got[[0, 6]].any()              # length 0: zeros, not NaN
    rows = np.arange(t)[None, :] < n_valid[:, None]
    assert rows.sum() >= 6
    assert np.abs(want[rows]).max() > 0.5
    # float32 to rounding; in bfloat16 one rounding of the output
    # (``_attend`` rounds the normalised probabilities where the
    # kernel normalises in float32 after the value product)
    np.testing.assert_allclose(got[rows], want[rows], rtol=0,
                               atol=2e-5 if dtype == "float32" else 4e-2)


@pytest.mark.parametrize("changed, page, backend, want", [
    ({}, 16, "tpu", (True, True, True)),
    ({}, 16, "cpu", (False, False, False)),
    ({}, 8, "tpu", (False, False, False)),
    ({"kv_lora_rank": 192}, 16, "tpu", (False, False, False)),
    ({"n_heads": 4}, 16, "tpu", (False, False, True)),
    ({"qk_rope_head_dim": 32}, 16, "tpu", (True, True, True)),
    ({"n_heads": 512}, 16, "tpu", (True, True, False))],
    ids=["axk1", "off_a_tpu", "page_no_whole_tile",
         "latent_no_lane_tile", "rows_no_sublane_tile_under_t4",
         "rotary_key_of_any_width", "rows_past_the_fast_memory_at_t4"])
def test_the_predicate_is_of_the_shapes(monkeypatch, changed, page,
                                        backend, want):
    """``paged_reads_by_table`` at t = 1, 2, 4 of the layer and of
    the two blocks that carry it (``ShortcutExpertBlock``: one answer
    for both its attentions, which are one layer over two pools)."""
    from deeplearning4j_tpu.nn.conf.layers import ShortcutExpertBlock
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    shape = dict(dict(n_in=256, n_heads=64, kv_lora_rank=512,
                      qk_rope_head_dim=64), **changed)
    for layer in (LatentAttentionLayer(**shape),
                  LatentDecoderBlock(**shape),
                  ShortcutExpertBlock(**shape)):
        assert tuple(layer.paged_reads_by_table(page, t, jnp.bfloat16)
                     for t in (1, 2, 4)) == want


@pytest.mark.parametrize("by_table", [False, True],
                         ids=["gather", "by_table"])
def test_kv_positions_follow_the_dispatch(tiny_net, monkeypatch, by_table):
    chunk_parity.kv_positions_follow_the_dispatch(tiny_net, monkeypatch,
                                                  by_table)
