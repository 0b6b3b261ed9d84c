"""MiMo-V2's layer: grouped-query attention with key heads of 12 and
value heads of 8 (192 / 128 at the published size), partial rotary, a
value scale, global layers in the allocator's pages beside
sliding-window layers with a learned sink in a slot-owned ring of
pages, and a sigmoid router with a selection-only bias as one chip's
share of an expert-parallel group, held at a small size against the
plain reference (benchmark/reference/mimo.py: float32 jax.numpy, no
code of the program).

Tolerances. Program and reference are both float32 here and differ in
the order of their sums (the program scores all key heads in one
einsum and normalises with the running maximum folded in; the
reference goes one key head at a time): log-probabilities of a
7-layer network agree to a few 1e-6, and 2e-5 leaves room for the
CPU's own reassociation. The same weights rounded to bfloat16 move
them by about 1e-2 (``test_bfloat16_weights_fail_the_tolerance``), so
a lower precision fails by three orders."""

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
    GroupedQueryAttentionLayer, GroupedQueryDecoderBlock,
    SparseExpertsLayer, layer_from_dict)
from deeplearning4j_tpu.serving.errors import KVLeaseVersionError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ATOL = 2e-5


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}",
        os.path.join(ROOT, "benchmark", kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("reference", "mimo")
BUILDER = _load("builders", "mimo_dsl")

# hidden 64, 8 query heads of 12 over 2 (global) or 4 (window) key
# heads of 12 and value heads of 8, 4 of 12 values rotated, window 32,
# 16 experts of which 4..7 are held, top-4; published layers 0-6:
# G | W W W W G W
WINDOW = 32
TINY = {"attention_bias": False, "hidden_act": "silu",
        "hidden_size": 64, "num_attention_heads": 8,
        "swa_num_attention_heads": 8, "num_key_value_heads": 2,
        "swa_num_key_value_heads": 4, "head_dim": 12,
        "swa_head_dim": 12, "v_head_dim": 8, "swa_v_head_dim": 8,
        "partial_rotary_factor": 0.334, "rope_theta": 10000000,
        "swa_rope_theta": 10000, "sliding_window": WINDOW,
        "attention_value_scale": 0.707,
        "add_full_attention_sink_bias": False,
        "add_swa_attention_sink_bias": True,
        "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1],
        "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1],
        "layernorm_epsilon": 1e-5, "intermediate_size": 96,
        "moe_intermediate_size": 32, "router_experts": 16,
        "n_routed_experts": 4, "held_first_expert": 4,
        "num_experts_per_tok": 4, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "n_group": 1, "norm_topk_prob": True,
        "n_shared_experts": None, "routed_scaling_factor": None,
        "num_hidden_layers": 7, "vocab_size": 96,
        "max_position_embeddings": 256}
PAGE = 16
RING = WINDOW // PAGE + 1           # pages of a slot's ring


def _seeded(params, seed, std=0.1):
    """Seeded normal weights: gains drawn around one, so that a
    dropped gain shows; the sinks and the router's bias drawn too, so
    that either in the wrong place shows."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    rng = np.random.default_rng(seed)
    new = []
    for path, leaf in leaves:
        w = rng.normal(0.0, std, leaf.shape)
        if "gain" in str(path[-1]):
            w = 1.0 + w
        if "sink" in str(path[-1]):
            w = 10 * w
        new.append(jnp.asarray(w, leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, new)


def _net(config, seed=3):
    net = BUILDER.build(config).net.init()
    net.params = _seeded(net.params, seed)
    return net


def _ref_logp(net, ids, config=TINY):
    z = np.asarray(REF.logits(net.params, np.asarray(ids), config),
                   np.float64)
    z = z - z.max(axis=-1, keepdims=True)
    return z - np.log(np.exp(z).sum(axis=-1, keepdims=True))


def _ids(n, seed=0, vocab=96):
    return [int(v) for v in
            np.random.default_rng(seed).integers(0, vocab, n)]


@pytest.fixture(scope="module")
def tiny_net():
    return _net(TINY)


def _session(net, slots=3, capacity=256, page=PAGE):
    return net.paged_slot_streaming_session(capacity=capacity,
                                            slots=slots, page_size=page)


def _feed(sess, slot, ids, t):
    """``ids`` to ``slot`` in chunks of ``t`` (through ``step_slots``
    at 1); the session's log-probabilities at each chunk's last row,
    {position: (V,)}."""
    got = {}
    for lo in range(0, len(ids), t):
        part = ids[lo:lo + t]
        x = np.zeros((sess.slots, t, 1), np.float32)
        n_valid = np.zeros((sess.slots,), np.int32)
        x[slot, :len(part), 0], n_valid[slot] = part, len(part)
        h = (sess.step_slots(x, n_valid > 0) if t == 1
             else sess.step_chunk(x, n_valid))
        got[int(sess.slot_pos[slot]) - 1] = np.log(np.asarray(
            h[slot, 0], np.float64))
    return got


# ---- the attention layer alone -------------------------------------

def _attention(window, **changed):
    c = dict(TINY, **changed)
    layer = BUILDER.block(c, 1 if window else 0)._ensure_parts()[0]
    layer.n_in = 64
    params = _seeded(layer.initialize(
        jax.random.PRNGKey(0), InputType.recurrent(64))[0], seed=11)
    return layer, params


@pytest.mark.parametrize("window", [False, True],
                         ids=["global", "window"])
def test_attention_matches_the_references(window):
    """Both kinds (their key-head counts, rotary bases, the window and
    its sink, the value scale) over 80 positions, 2.5 windows."""
    layer, params = _attention(window)
    assert layer.n_kv_heads == (4 if window else 2)
    assert layer.rotary_dim == 4 and layer.sink is window
    x = np.random.default_rng(1).normal(0, 1, (80, 64)).astype(np.float32)
    want = np.asarray(REF.attention(params, jnp.asarray(x), TINY, window))
    got = np.asarray(layer.apply(params, {}, jnp.asarray(x)[None])[0][0])
    np.testing.assert_allclose(got, want, atol=ATOL)


@pytest.mark.parametrize("field, other", [
    ("window", WINDOW - 1), ("window", None), ("sink", False),
    ("value_scale", 1.0), ("rotary_dim", 12), ("rotary_dim", 0),
    ("rope_theta", 1e7), ("n_kv_heads", 8)])
def test_each_setting_of_the_window_layer_matters(field, other):
    """The comparison above is not blind to any of them: the layer
    with one setting changed is off the reference by far more than
    the tolerance."""
    layer, params = _attention(True)
    x = np.random.default_rng(1).normal(0, 1, (80, 64)).astype(np.float32)
    want = np.asarray(REF.attention(params, jnp.asarray(x), TINY, True))
    if field == "n_kv_heads":
        # the same keys and values read by the wrong query heads
        params = dict(params, Wk=jnp.tile(params["Wk"], (1, 2)),
                      Wv=jnp.tile(params["Wv"], (1, 2)))
    setattr(layer, field, other)
    got = np.asarray(layer.apply(params, {}, jnp.asarray(x)[None])[0][0])
    assert np.abs(got - want).max() > 100 * ATOL


def test_attention_round_trips_through_json():
    layer, _ = _attention(True)
    again = layer_from_dict(json.loads(json.dumps(layer.to_dict())))
    assert isinstance(again, GroupedQueryAttentionLayer)
    assert again == layer and again.window == WINDOW
    with pytest.raises(ValueError, match="not divisible"):
        GroupedQueryAttentionLayer(n_heads=8, n_kv_heads=3)
    with pytest.raises(ValueError, match="rotary_dim"):
        GroupedQueryAttentionLayer(qk_head_dim=8, rotary_dim=10)


# ---- the network ---------------------------------------------------

def test_full_sequence_logits_match_the_reference(tiny_net):
    ids = np.asarray([_ids(70, seed=s) for s in (0, 1)])
    got = np.log(np.asarray(tiny_net.output(
        ids[..., None].astype(np.float32)), np.float64))
    for b in range(2):
        np.testing.assert_allclose(got[b], _ref_logp(tiny_net, ids[b]),
                                   atol=ATOL)


def test_bfloat16_weights_fail_the_tolerance(tiny_net):
    ids = _ids(70)
    rounded = _net(TINY)
    rounded.params = jax.tree_util.tree_map(
        lambda w: w.astype(jnp.bfloat16).astype(w.dtype), rounded.params)
    got = np.log(np.asarray(rounded.output(np.asarray(
        ids, np.float32)[None, :, None]), np.float64))[0]
    assert np.abs(got - _ref_logp(tiny_net, ids)).max() > 50 * ATOL


@pytest.mark.parametrize("t", [1, 2, 16])
@pytest.mark.parametrize("length", [WINDOW - 12, WINDOW + 1,
                                    3 * RING * PAGE + 9],
                         ids=["under_window", "window_plus_1",
                              "three_wraps"])
def test_chunked_prefill_then_decode_matches_the_reference(tiny_net, t,
                                                           length):
    """A prompt in chunks of ``t`` and then 6 tokens one by one
    through the paged session, both kinds of cache under one table:
    at every row the session returns, its distribution is the
    reference's full forward pass (logits, not tokens). The longest
    goes three times round a slot's ring of 48 positions."""
    ids = _ids(length + 6, seed=length)
    sess = _session(tiny_net)
    sess.bind(1, sess.reserve(ids[:length], 6))
    got = _feed(sess, 1, ids[:length], t)
    got.update(_feed(sess, 1, ids[length:], 1))
    want = _ref_logp(tiny_net, ids)
    assert len(got) >= 7
    for pos, row in got.items():
        np.testing.assert_allclose(row, want[pos], atol=ATOL)


def test_a_slot_let_again_never_sees_the_last_tenants_rows(tiny_net):
    """A long request fills slot 0's ring several times over; the
    slot is released (nothing is zeroed) and let to a shorter request,
    whose positions the stale rows lie ahead of: every row is the
    reference's, and the ring still holds the old tenant's rows where
    the new one has not written."""
    sess = _session(tiny_net, slots=2)
    long, short = _ids(120, seed=5), _ids(40, seed=6)
    sess.bind(0, sess.reserve(long, 1))
    _feed(sess, 0, long, 16)
    sess.release(0)
    ring = np.asarray(sess._pools[2]["k"])[1:1 + RING]
    assert np.abs(ring).min(axis=-1).max() > 0      # every row written
    sess.bind(0, sess.reserve(short, 1))
    got = _feed(sess, 0, short[:32], 16)
    got.update(_feed(sess, 0, short[32:], 1))
    want = _ref_logp(tiny_net, short)
    for pos, row in got.items():
        np.testing.assert_allclose(row, want[pos], atol=ATOL)
    after = np.asarray(sess._pools[2]["k"])[1:1 + RING].reshape(
        RING * PAGE, -1)
    np.testing.assert_array_equal(after[40:], ring.reshape(
        RING * PAGE, -1)[40:])


def test_a_chunk_wider_than_the_ring_raises(tiny_net, own_programs):
    """The step that raises has registered its program by then
    (``own_programs`` takes it out of the registry again)."""
    sess = _session(tiny_net)
    assert sess.chunk_rows_max == PAGE
    ids = _ids(40)
    sess.bind(0, sess.reserve(ids, 1))
    x = np.zeros((3, 18, 1), np.float32)
    with pytest.raises(ValueError, match="needs a ring of 49"):
        sess.step_chunk(x, np.array([18, 0, 0], np.int32))
    # 17 rows are the most a ring of 48 has room for at a window of 32
    sess.reinit_states()
    sess.bind(0, sess.reserve(ids, 1))
    sess.step_chunk(x[:, :17], np.array([17, 0, 0], np.int32))


@pytest.mark.parametrize("page, t", [(8, 8), (4, 4)],
                         ids=["ring5_t8", "ring9_t4"])
@pytest.mark.parametrize("case", ["ragged", "near_capacity"])
def test_chunk_step_matches_token_by_token(tiny_net, case, page, t):
    """tests/chunk_parity.py's cases over both kinds of cache (a page
    of 8: a ring of 5 pages has room for its 8-row chunks; a page of
    4: a ring of 9 pages, the published count, under chunks of 4, a
    64-slot pool's wide program): the rows past ``n_valid`` alter no
    ring row either, and an expert layer's counts of a chunk are the
    one-by-one counts summed."""
    assert tiny_net.layers[2].paged_cache(page).ring_pages == {8: 5, 4: 9}[page]
    chunk_parity.run_case(tiny_net, 96, case, page=page, t=t)


def test_a_slot_that_sits_a_step_out_keeps_its_ring(tiny_net):
    """The single-row program has no ``n_valid``: a bound slot that is
    not stepped is marked by its zeroed table row, and its dummy row
    goes to the scratch page, not to ring row 0."""
    sess = _session(tiny_net, slots=2)
    ids = _ids(20)
    sess.bind(0, sess.reserve(ids, 4))
    _feed(sess, 0, ids, 1)
    was = [np.asarray(p["k"])[1:1 + RING] for p in sess._pools[2:6]]
    sess.bind(1, sess.reserve(_ids(3, seed=2), 4))
    x = np.full((2, 1, 1), 7, np.float32)
    sess.step_slots(x, np.array([False, True]))
    for pool, rows in zip(sess._pools[2:6], was):
        np.testing.assert_array_equal(np.asarray(pool["k"])[1:1 + RING],
                                      rows)
    assert int(sess.slot_pos[0]) == 20


def test_shares_of_an_expert_group_add_up_to_the_whole_layer():
    """4 shares of 4 of 16 experts: the parts the shares give add up
    to the uncut reference's expert layer (weights normalised over all
    4 selected, held or not; no shared expert to count once)."""
    moe = dict(n_in=64, n_routed_experts=16, top_k=4, expert_width=32,
               n_shared_experts=0, norm_topk_prob=True,
               scoring_func="sigmoid", router_bias=True)
    whole = SparseExpertsLayer(**moe)
    p = _seeded(whole.initialize(jax.random.PRNGKey(0),
                                 InputType.recurrent(64))[0], 4, std=0.3)
    h = jnp.asarray(np.random.default_rng(2).normal(0, 1, (24, 64)),
                    jnp.float32)
    ones = jnp.ones((64,), jnp.float32)
    uncut = dict(TINY, n_routed_experts=16, held_first_expert=0,
                 layernorm_epsilon=0.0)
    # the reference's expert half is h + F(rms(h)); eps 0 and a gain
    # of ones make rms a rescaling, which the shares are given too
    z = h / jnp.sqrt(jnp.mean(h * h, axis=-1, keepdims=True))
    want = np.asarray(REF._experts(p, ones, h, uncut)[0] - h)
    total = 0.0
    for first in (0, 4, 8, 12):
        share = SparseExpertsLayer(held=(first, 4), **moe)
        part = {k: (w[first:first + 4] if k in ("Wg", "Wu", "Wd") else w)
                for k, w in p.items()}
        out, counts = share.apply_counted(part, z[None])
        total = total + np.asarray(out[0], np.float64)
    np.testing.assert_allclose(total, want, atol=ATOL)


def test_block_round_trips_through_json(tiny_net):
    conf = tiny_net.conf
    again = type(conf).from_json(conf.to_json())
    assert again.to_json() == conf.to_json()
    blocks = [layer for layer in again.layers
              if isinstance(layer, GroupedQueryDecoderBlock)]
    assert [b.window for b in blocks] == [None, WINDOW, WINDOW, WINDOW,
                                          WINDOW, None, WINDOW]
    assert [b.n_kv_heads for b in blocks] == [2, 4, 4, 4, 4, 2, 4]
    assert [b.stream_aux for b in blocks] == [False] + [True] * 6


def test_two_kinds_of_pool_in_one_session(tiny_net):
    """Global layers in the allocator's pages (slots x pages + the
    scratch page), window layers in ``slots x ring + 1`` pages
    whatever the capacity; bfloat16 parameters give bfloat16 pools of
    both kinds."""
    sess = _session(tiny_net, slots=3, capacity=256)
    assert sess._ring == [0, 0, RING, RING, RING, RING, 0, RING, 0, 0]
    assert sess._pools[1]["k"].shape == (3 * 16 + 1, PAGE, 2 * 12)
    assert sess._pools[1]["v"].shape == (3 * 16 + 1, PAGE, 2 * 8)
    assert sess._pools[2]["k"].shape == (3 * RING + 1, PAGE, 4 * 12)
    assert sess._pools[2]["v"].shape == (3 * RING + 1, PAGE, 4 * 8)
    wide = _session(tiny_net, slots=3, capacity=1024)
    assert wide._pools[2]["k"].shape == sess._pools[2]["k"].shape
    with dtypes.policy_scope(dtypes.Policy(
            param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16,
            output_dtype=jnp.bfloat16)):
        half = BUILDER.build(TINY).net.init()
    assert {leaf.dtype for leaf in jax.tree_util.tree_leaves(
        half.params)} == {jnp.dtype(jnp.bfloat16)}
    hs = _session(half, slots=2, capacity=64)
    assert {leaf.dtype for pool in hs._pools if pool is not None
            for leaf in jax.tree_util.tree_leaves(pool)} \
        == {jnp.dtype(jnp.bfloat16)}
    hs.bind(0, hs.reserve(_ids(5), 3))
    out = hs.step_slots(np.full((2, 1, 1), 3, np.float32),
                        np.array([True, False]))
    assert np.isfinite(np.asarray(out, np.float32)[0]).all()


@pytest.fixture(scope="module")
def plain_lm():
    """A small causal transformer LM, no window anywhere."""
    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers import (
        EmbeddingSequenceLayer, RnnOutputLayer, TransformerEncoderLayer)
    conf = (NeuralNetConfiguration.builder().set_seed(0).list()
            .layer(EmbeddingSequenceLayer(n_in=96, n_out=32))
            .layer(TransformerEncoderLayer(n_heads=4, causal=True))
            .layer(RnnOutputLayer(n_out=96, loss="mcxent"))
            .set_input_type(InputType.recurrent(96, 64)).build())
    return MultiLayerNetwork(conf).init()


def test_no_prefix_is_taken_or_registered_over_a_ring(tiny_net, plain_lm):
    """A ring cannot be shared and a hit would resume behind an empty
    window: a repeated prompt is served cold, to the reference's
    logits, and neither ``release`` nor ``register_written_prefix``
    registers anything. A network without a window still hits."""
    prompt = _ids(40, seed=9)
    sess = _session(tiny_net, slots=2)
    sess.bind(0, sess.reserve(prompt, 2))
    _feed(sess, 0, prompt, 16)
    assert sess.register_written_prefix(0, prompt) == 0
    sess.release(0, register_prompt=prompt)
    assert len(sess.prefix_cache) == 0
    lease = sess.reserve(prompt, 2)
    assert lease.resume_pos == 0 and lease.prefix_hit_tokens == 0
    assert sess.prefix_cache.hits_total == 0
    sess.bind(1, lease)
    got = _feed(sess, 1, prompt, 16)
    want = _ref_logp(tiny_net, prompt)
    for pos, row in got.items():
        np.testing.assert_allclose(row, want[pos], atol=ATOL)

    plain = plain_lm.paged_slot_streaming_session(
        capacity=64, slots=2, page_size=PAGE)
    assert plain.chunk_rows_max == 64 and not any(plain._ring)
    plain.bind(0, plain.reserve(prompt, 2))
    _feed(plain, 0, prompt, 8)
    plain.release(0, register_prompt=prompt)
    assert len(plain.prefix_cache) == 2
    assert plain.reserve(prompt, 2).resume_pos == 2 * PAGE
    assert plain.prefix_cache.hits_total == 1
    assert plain.step_ring_pages == (0, 0, 0)


@pytest.mark.parametrize("pos", [20, 3 * RING * PAGE - 5],
                         ids=["before_a_wrap", "after_wraps"])
def test_lease_export_import_gives_the_same_next_logits(tiny_net, pos):
    """A stream exported mid-way (its global pages and, of each window
    layer, the rows its ring still holds, oldest position first) and
    imported into another session's other slot goes on to the same
    logits as the stream that stayed, and as the reference."""
    ids = _ids(pos + 8, seed=pos)
    a, b = _session(tiny_net, slots=2), _session(tiny_net, slots=3)
    a.bind(0, a.reserve(ids[:pos], 8))
    _feed(a, 0, ids[:pos], 16)
    blob = a.export_lease(0, extra={"n": 1})
    # another stream has been through the importing slot before
    b.bind(2, b.reserve(_ids(70, seed=1), 1))
    _feed(b, 2, _ids(70, seed=1), 16)
    b.release(2)
    lease, extra = b.import_lease(blob, pos + 8)
    assert extra == {"n": 1} and lease.resume_pos == pos
    b.bind(2, lease)
    assert lease.ring_rows is None      # on the device now
    stayed = _feed(a, 0, ids[pos:], 1)
    moved = _feed(b, 2, ids[pos:], 1)
    want = _ref_logp(tiny_net, ids)
    for p in stayed:
        np.testing.assert_allclose(moved[p], stayed[p], atol=1e-6)
        np.testing.assert_allclose(moved[p], want[p], atol=ATOL)
    # the header names the ring: a session of another page size or
    # another window refuses the blob
    other = _net(dict(TINY, sliding_window=48))
    with pytest.raises(KVLeaseVersionError, match="schema"):
        _session(other, slots=2).import_lease(blob, pos + 8)


def test_ring_pages_accounting(tiny_net):
    """``step_ring_pages`` is host arithmetic from the positions a
    step feeds: (held, full, overwritten) over the fed slots."""
    sess = _session(tiny_net, slots=4)
    span = RING * PAGE                       # 48 positions
    pos = np.array([0, 40, 47, 100], np.int32)
    sess._note_ring(pos, np.array([16, 2, 2, 0], np.int32))
    # slot 0 ends at 16: 1 page of 1; slot 1 at 42: 3 of 3; slot 2 at
    # 49: 3 of 4, and position 48 began to reuse ring page 0; slot 3
    # was not fed
    assert sess.step_ring_pages == (1 + 3 + 3, 1 + 3 + 4, 1)
    sess._note_ring(np.array([span * 3 - 1, 0, 0, 0], np.int32),
                    np.array([16, 0, 0, 0], np.int32))
    assert sess.step_ring_pages == (3, 10, 1)


@pytest.mark.parametrize("path", ["gather", "by_table"])
def test_batcher_serves_what_the_reference_decodes(tiny_net, monkeypatch,
                                                   path):
    """Through ``ContinuousBatcher`` (chunked prefill at the ring's
    width, ids picked on the device, one step ahead): the greedy ids
    of requests that outgrow their rings are the reference's at every
    position where its best leads by a margin, the expert counters
    fill as for every expert network, and the three ring counters
    exist and move. With the global layers' pages read by table (the
    predicate forced, the kernel in interpret mode) the same ids, and
    the KV positions read fall under the span."""
    from deeplearning4j_tpu.serving.continuous import ContinuousBatcher
    from deeplearning4j_tpu.serving.metrics import ServingMetrics
    if path == "by_table":
        _by_table(monkeypatch)
    metrics = ServingMetrics()
    cb = ContinuousBatcher(tiny_net, slots=2, capacity=128,
                           page_size=PAGE, kv_mode="paged",
                           metrics=metrics)
    try:
        assert cb._chunk_t == PAGE
        prompts = [_ids(70, seed=21), _ids(9, seed=22), _ids(50, seed=23)]
        outs = [cb.generate(p, 12) for p in prompts]
    finally:
        cb.shutdown(drain=True)
    for prompt, out in zip(prompts, outs):
        ids = [int(v) for v in out]
        assert len(ids) == 12
        z = np.asarray(REF.logits(tiny_net.params,
                                  np.asarray(prompt + ids), TINY))
        z = z[len(prompt) - 1:len(prompt) + 11]
        top2 = np.sort(z, axis=-1)[:, -2:]
        sure = top2[:, 1] - top2[:, 0] > 1e-3
        assert sure.sum() >= 10
        np.testing.assert_array_equal(
            np.asarray(ids)[sure], z.argmax(axis=-1)[sure])
    snap = metrics.registry.snapshot()
    read = lambda name: sum(v for k, v in snap.items()
                            if k.startswith(name + "{"))
    assert read("serving_moe_local_pairs_total") > 0
    held = read("serving_kv_ring_pages_held_total")
    full = read("serving_kv_ring_pages_full_total")
    assert 0 < held < full
    assert read("serving_kv_ring_wraps_total") > 0
    assert read("serving_steps_total") > 0
    spanned = read("serving_kv_positions_spanned_total")
    assert spanned == read("serving_steps_total") * 2 * 128
    if path == "gather":
        assert read("serving_kv_positions_read_total") == spanned
    else:
        assert 0 < read("serving_kv_positions_read_total") < 0.7 * spanned

# ---- the global layer's pages read by table ------------------------
# (ops/paged_attention.py's grouped kernel in Pallas' interpret mode
# against ``_attend`` over the gathered table, which stays the CPU
# path; Mosaic's verdict on the kernel: tests/test_chip_compile.py)

def _by_table(monkeypatch, holds=True):
    """The layers' shape predicate forced, and the kernel in interpret
    mode for the CPU."""
    import functools
    from deeplearning4j_tpu.ops import paged_attention as PA
    monkeypatch.setattr(PA, "grouped_reads_by_table", lambda *a: holds)
    monkeypatch.setattr(
        PA, "pallas_paged_attention_grouped",
        functools.partial(PA.pallas_paged_attention_grouped,
                          interpret=True))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("t", [1, 2, 16])
@pytest.mark.parametrize("heads", [(16, 2, 192, 128), (8, 4, 128, 128)],
                         ids=["16_over_2_qk192_v128",
                              "8_over_4_qk128_v128"])
def test_grouped_by_table_kernel_matches_attend(monkeypatch, heads, t,
                                                dtype):
    _kernel_against_attend(monkeypatch, heads, t, dtype)


@pytest.mark.parametrize("H, t, dtype", [
    (6, 1, "float32"), (6, 2, "bfloat16"), (30, 1, "bfloat16"),
    (30, 2, "bfloat16"), (30, 2, "float32")])
def test_rows_of_no_whole_sublane_tile_read_by_table(monkeypatch, H, t,
                                                     dtype):
    """6 and 30 heads, each its own key head (Olmo-Hybrid's 30): a
    slot's ``t * H`` rows are no whole sublane tiles (8 rows of
    float32, 16 of bfloat16), so the wrapper rounds them up with zero
    query rows and drops their output; the kernel's path is still
    ``_attend``'s."""
    _kernel_against_attend(monkeypatch, (H, H, 128, 128), t, dtype)


@pytest.mark.parametrize("H, padded", [(6, True), (30, True), (8, False),
                                       (16, False), (32, False)])
def test_only_rows_of_no_whole_tile_are_padded(H, padded):
    """Head counts that were whole tiles lower as they did: no pad,
    no slice; 6 and 30 heads gain one of each."""
    from deeplearning4j_tpu.ops import paged_attention as PA
    S, P, ps, d = 2, 2, 16, 128
    q = jnp.zeros((S, 2, H, d), jnp.bfloat16)
    pool = jnp.zeros((S * P + 1, ps, H * d), jnp.bfloat16)
    ints = jnp.zeros((S,), jnp.int32)
    text = str(jax.make_jaxpr(lambda *a: PA.pallas_paged_attention_grouped(
        *a, n_heads=H, n_kv_heads=H, interpret=True))(
            q, pool, pool, jnp.zeros((S, P), jnp.int32), ints, ints))
    before = text[:text.index("pallas_call")]
    assert ("= pad[" in before) == padded
    rows = PA._whole_tiles(2 * H, jnp.bfloat16)
    assert (rows != 2 * H) == padded and rows % 16 == 0
    assert f"bf16[{S},{rows},{d}]" in before


def _kernel_against_attend(monkeypatch, heads, t, dtype):
    """``apply_stream_paged`` of a global layer, the kernel's path
    against the gather's, on one pool, table and chunk: a free slot, a
    slot with one token, slots that end on a page's last row and
    mid-page, one past the first block of 128 keys, a full one, a
    shared prefix, stale table tails that point at garbage, and
    ``n_valid`` short of ``t``."""
    H, K, dq, dv = heads
    ps, P, S, C = 16, 10, 8, 64               # two blocks of 8 pages
    cap = P * ps
    layer = GroupedQueryAttentionLayer(
        n_in=C, n_heads=H, n_kv_heads=K, qk_head_dim=dq, v_head_dim=dv,
        rotary_dim=64, value_scale=0.707)
    assert not layer.paged_reads_by_table(ps, t, dtype)      # the CPU
    params = jax.tree_util.tree_map(
        lambda w: w.astype(dtype),
        _seeded(layer.initialize(jax.random.PRNGKey(0),
                                 InputType.recurrent(C))[0], seed=5))
    rng = np.random.default_rng([t, len(dtype), dq])
    n_live = S * P
    pool = {"k": rng.normal(size=(n_live + 3, ps, K * dq)),
            "v": rng.normal(size=(n_live + 3, ps, K * dv))}
    # pages no slot holds: large finite garbage, which stale table
    # entries past a slot's length point at
    garbage = [n_live + 1, n_live + 2]
    for leaf in pool.values():
        leaf[garbage] = 1e30
    table = rng.permutation(np.arange(1, n_live + 1)).reshape(S, P)
    #        free  one  ends on a page  mid-page  full     shares 3's
    pos = [0,      0,   2 * ps - t,     37,       cap - t, 2 * ps + 3,
           0,      8 * ps + 5]         # parked; in the second block
    n_valid = [0,  1,   t,              t,        t,       max(t - 1, 1),
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
    _by_table(monkeypatch)
    assert layer.paged_reads_by_table(ps, t, dtype)
    got, got_pool = layer.apply_stream_paged(*args)
    for name in pool:
        np.testing.assert_array_equal(
            np.asarray(got_pool[name], np.float32),
            np.asarray(want_pool[name], np.float32))
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
                               atol=5e-6 if dtype == "float32" else 4e-2)


@pytest.mark.parametrize("changed, page, backend, want", [
    ({}, 16, "tpu", True), ({}, 16, "cpu", False), ({}, 4, "tpu", False),
    ({"window": 128}, 16, "tpu", False), ({"sink": True}, 16, "tpu", False),
    ({"v_head_dim": 96}, 16, "tpu", True),
    ({"n_heads": 4}, 16, "tpu", True),
    ({"n_heads": 2048}, 16, "tpu", False)],
    ids=["mimo_global", "off_a_tpu", "page_no_whole_tile", "window",
         "sink", "value_head_padded_to_a_lane_tile",
         "rows_rounded_to_a_sublane_tile", "rows_past_the_fast_memory"])
def test_the_predicate_is_of_the_shapes_and_the_window(monkeypatch, changed,
                                                       page, backend,
                                                       want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    layer = GroupedQueryAttentionLayer(**dict(
        dict(n_heads=64, n_kv_heads=4, qk_head_dim=192, v_head_dim=128),
        **changed))
    for t in (1, 2):
        assert layer.paged_reads_by_table(page, t, jnp.bfloat16) == want
    # since PR 39 a value head narrower than a lane tile takes one in
    # the pool where that alone admits the kernel
    pool = jax.eval_shape(lambda: layer.zero_pool(3, page,
                                                       jnp.bfloat16))
    wide = 128 if want else layer.v_head_dim
    assert pool["v"].shape == (3, page, layer.n_kv_heads * wide)


@pytest.mark.parametrize("by_table", [False, True],
                         ids=["gather", "by_table"])
def test_kv_positions_count_the_allocators_layers(tiny_net, monkeypatch,
                                                  by_table):
    """``step_kv_positions`` asks the layers in the allocator's pages
    alone: ring layers never read by table and do not make the step a
    gather. By table a step reads each slot's pages up to its length;
    with the predicate False the tables' whole span, as before."""
    _by_table(monkeypatch, by_table)
    sess = _session(tiny_net, slots=4)
    answers = [layer.paged_reads_by_table(PAGE, 2, jnp.float32)
               for layer in tiny_net.layers
               if hasattr(layer, "apply_stream_paged")]
    assert answers == [by_table if w is None else False
                       for w in (None, WINDOW, WINDOW, WINDOW, WINDOW,
                                 None, WINDOW)]
    sess._note_kv_read(2, np.array([0, 1, 40, 256], np.int32))
    spanned = 4 * 256
    assert sess.step_kv_positions == (
        (0 + 16 + 48 + 256) if by_table else spanned, spanned)


def test_the_defaults_are_the_layers_they_were(plain_lm):
    """No field of the new layers reaches the old ones: a network
    without a window has no ring, no ring counter and the chunk width
    it had."""
    from deeplearning4j_tpu.serving.continuous import (ContinuousBatcher,
                                                       chunk_width)
    from deeplearning4j_tpu.serving.metrics import ServingMetrics
    metrics = ServingMetrics()
    cb = ContinuousBatcher(plain_lm, slots=2, capacity=64,
                           page_size=8, kv_mode="paged", metrics=metrics)
    try:
        assert cb._chunk_t == chunk_width(2, 64) == 64
        cb.generate(_ids(12), 4)
    finally:
        cb.shutdown(drain=True)
    assert not [k for k in metrics.registry.snapshot()
                if "kv_ring" in k]
    ring = lambda **kw: GroupedQueryAttentionLayer(**kw).paged_cache(
        16).ring_pages
    assert (ring(), ring(window=128), ring(window=100)) == (0, 9, 8)
