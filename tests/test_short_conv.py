"""LFM2's two kinds of layer on the serving path: gated short
convolutions whose two-row window is a row a SLOT in the paged
session (the state pool's second tenant, beside Mamba-2's), some of
them over an expert layer whose counts come out of the paged step,
and grouped-query attention with a norm over every query and key
head, held at a small size against the plain reference
(benchmark/reference/lfm2_moe.py: float32 jax.numpy, no code of the
program) and, for the mixer alone, against a float64 loop over
positions written here.

Tolerances. Program and reference are both float32 here and differ in
the order of their sums (the program sums the taps over shifted
slices and scores all key heads in one einsum; the reference goes
position by position and expert by expert). Log-probabilities of the
5-layer network agree to 3e-6; ``ATOL`` 2e-5 leaves the CPU's own
reassociation room. The same weights rounded to bfloat16 move them by
more than fifty times that, and to float8_e4m3 (the benchmark's
control) by more still (``test_lower_precisions_fail_the_tolerance``).
The router's near-ties do not show at this size: both sides select in
float32."""

import functools
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
    ShortConvDecoderBlock, ShortConvMixerLayer, StateSpaceDecoderBlock,
    layer_from_dict)
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


REF = _load("reference", "lfm2_moe")
BUILDER = _load("builders", "lfm2_moe_dsl")

# hidden 32; a window of 2; 4 query heads over 2 key/value heads of 8;
# a dense MLP of 48 in the first layer, then 8 experts of 16, top 2;
# published layers 1-5 of C C A C C C: C(dense) A C C C
TINY = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 32,
        "intermediate_size": 48,
        "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                        "conv"],
        "max_position_embeddings": 256, "moe_intermediate_size": 16,
        "norm_eps": 1e-5, "norm_topk_prob": True,
        "num_attention_heads": 4, "num_dense_layers": 2,
        "num_experts": 8, "num_experts_per_tok": 2,
        "num_hidden_layers": 5, "first_layer": 1,
        "num_key_value_heads": 2,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 96}
PAGE = 8
VOCAB = TINY["vocab_size"]


def _perturbed(params, seed, std=0.1):
    """The layers' own initial values with every vector that starts at
    a constant (the gains, the router's selection bias) drawn around
    it, so that one dropped or misplaced shows."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    rng = np.random.default_rng(seed)
    new = []
    for path, leaf in leaves:
        name = str(getattr(path[-1], "key", path[-1]))
        if "gain" in name or name == "br":
            leaf = leaf + jnp.asarray(rng.normal(0, std, leaf.shape),
                                      leaf.dtype)
        new.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, new)


def _net(config=TINY, seed=3):
    net = BUILDER.build(config).net.init()
    net.params = _perturbed(net.params, seed)
    return net


@pytest.fixture(scope="module")
def tiny_net():
    return _net()


def _log_softmax(z):
    z = np.asarray(z, np.float64)
    z = z - z.max(axis=-1, keepdims=True)
    return z - np.log(np.exp(z).sum(axis=-1, keepdims=True))


def _ref_logp(net, ids, config=TINY, **kw):
    return _log_softmax(REF.logits(net.params, np.asarray(ids), config,
                                   **kw))


def _ids(n, seed=0):
    return [int(v) for v in
            np.random.default_rng(seed).integers(0, VOCAB, n)]


def _session(net, slots=3, capacity=256, page=PAGE):
    return net.paged_slot_streaming_session(capacity=capacity,
                                            slots=slots, page_size=page)


def _feed(sess, slot, ids, t, ahead=False):
    """``ids`` to ``slot`` in chunks of ``t`` (through ``step_slots``
    at 1, or every width through ``step_ids`` where ``ahead``); the
    session's log-probabilities at each chunk's last row, {position:
    (V,)}, or with ``ahead`` the greedy ids there."""
    got = {}
    for lo in range(0, len(ids), t):
        part = ids[lo:lo + t]
        x = np.zeros((sess.slots, t, 1), np.float32)
        n_valid = np.zeros((sess.slots,), np.int32)
        x[slot, :len(part), 0], n_valid[slot] = part, len(part)
        if ahead:
            picked, _ = sess.step_ids(x, n_valid,
                                      np.zeros(sess.slots, bool))
            got[int(sess.slot_pos[slot]) - 1] = int(picked[slot])
            continue
        h = (sess.step_slots(x, n_valid > 0) if t == 1
             else sess.step_chunk(x, n_valid))
        got[int(sess.slot_pos[slot]) - 1] = np.log(np.asarray(
            h[slot, 0], np.float64))
    return got


# ---- the mixer alone -------------------------------------------------

K, D = 3, 24


def _mixer(seed=0, width=K):
    layer = ShortConvMixerLayer(n_in=D, conv_width=width,
                                weight_init="normal")
    params, _ = layer.initialize(jax.random.PRNGKey(seed),
                                 InputType.recurrent(D))
    return layer, params


def _mixer_by_position(p, x, window=None):
    """The module docstring's equations for one sequence x (T, D),
    float64, one position at a time, behind ``window`` (K - 1, D),
    zeros where None: (out, the window the next position finds)."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    x = np.asarray(x, np.float64)
    width, d = p["conv_w"].shape
    seen = [np.zeros(d)] * (width - 1) if window is None else \
        list(np.asarray(window, np.float64))
    out = []
    for row in x:
        proj = row @ p["W_in"]
        b, c, v = proj[:d], proj[d:2 * d], proj[2 * d:]
        seen.append(b * v)
        conv = sum(p["conv_w"][k] * seen[len(seen) - width + k]
                   for k in range(width))
        out.append((c * conv) @ p["W_out"])
    return np.stack(out), np.stack(seen[-(width - 1):])


@pytest.mark.parametrize("width", [2, 3, 4])
def test_mixer_matches_the_loop_over_positions(width):
    layer, params = _mixer(width=width)
    assert params["conv_w"].shape == (width, D)
    assert params["W_in"].shape == (D, 3 * D)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, D))
    y, _ = layer.apply(params, {}, x)
    for b in range(2):
        np.testing.assert_allclose(
            np.asarray(y[b]), _mixer_by_position(params, x[b])[0],
            atol=ATOL)
    # and it is differentiable
    g = jax.grad(lambda p: jnp.sum(layer.apply(p, {}, x)[0] ** 2))(params)
    assert all(bool(jnp.all(jnp.isfinite(v))) and float(jnp.abs(v).max())
               > 0 for v in jax.tree_util.tree_leaves(g))


def test_every_tap_moves_the_output():
    """Each of the three taps carries its share: with one zeroed the
    mixer's output moves by far more than the tolerance."""
    layer, params = _mixer()
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 30, D))
    want = np.asarray(layer.apply(params, {}, x)[0])
    for k in range(K):
        cut = dict(params, conv_w=params["conv_w"].at[k].set(0.0))
        moved = np.asarray(layer.apply(cut, {}, x)[0]) - want
        assert np.abs(moved).max() > 1000 * ATOL


@pytest.mark.parametrize("t", [1, 2, 4, 16])
def test_mixer_stream_matches_apply(t):
    """Three streams of 21 tokens through ``apply_stream_paged`` in
    ragged steps of up to ``t`` rows (0 among them: a slot that sits a
    step out), over a pool an earlier tenant left non-zero and with
    junk in the rows past ``n_valid``, against ``apply``."""
    layer, params = _mixer()
    slots, T = 3, 21
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (slots, T, D)))
    want = np.asarray(layer.apply(params, {}, jnp.asarray(x))[0])
    pool = jax.tree_util.tree_map(lambda a: a + 7.0,
                                  layer.zero_pool(slots, 4, jnp.float32))
    assert list(pool) == ["conv"] and pool["conv"].shape == (slots, K - 1,
                                                             D)
    step = jax.jit(layer.apply_stream_paged)
    rng = np.random.default_rng(t)
    pos, got = np.zeros(slots, np.int32), [[] for _ in range(slots)]
    while (pos < T).any():
        nv = np.minimum(rng.integers(0, t + 1, slots), T - pos).astype(
            np.int32)
        xb = np.full((slots, t, D), 99.0, np.float32)
        for s in range(slots):
            xb[s, :nv[s]] = x[s, pos[s]:pos[s] + nv[s]]
        # the session's conventions: a slot that feeds nothing is
        # given position 0 and, in the single-row program, an
        # all-zero table row
        table = np.where(nv[:, None] > 0, 1, 0).astype(np.int32)
        args = (params, pool, jnp.asarray(table),
                jnp.asarray(np.where(nv > 0, pos, 0)), jnp.asarray(xb))
        out, pool = step(*args) if t == 1 else step(*args,
                                                    jnp.asarray(nv))
        for s in range(slots):
            got[s].append(np.asarray(out[s, :nv[s]]))
        pos += nv
    np.testing.assert_allclose(
        np.stack([np.concatenate(g) for g in got]), want, atol=ATOL)


# what slots 0 and 1 do in the one step; slots 2 and 3 feed all their
# rows in mid-stream
ONE_STEP = {
    "all_rows_valid": lambda t: dict(n_valid=(t, t), pos=(7, 3)),
    "fewer_rows_than_t": lambda t: dict(n_valid=(max(t - 1, 1), 1),
                                        pos=(7, 3)),
    "a_slot_feeds_nothing": lambda t: dict(n_valid=(0, t), pos=(0, 3)),
    "a_fresh_slot_over_nan": lambda t: dict(n_valid=(t, t), pos=(0, 3),
                                            nan=0),
}


@pytest.mark.parametrize("case", list(ONE_STEP))
@pytest.mark.parametrize("t", [1, 2, 4])
def test_one_step_over_a_used_pool(t, case):
    """ONE step of ``apply_stream_paged`` over a pool that an earlier
    tenant left non-zero, junk in the rows past ``n_valid``, against
    the loop in float64 from the same rows. A slot at position 0
    starts from zeros whatever its row holds (NaN too); a slot that
    feeds nothing keeps its row bit for bit; the window left is the
    two inputs before row ``n_valid``."""
    layer, params = _mixer(seed=3)
    slots = 4
    what = ONE_STEP[case](t)
    rng = np.random.default_rng(t)
    pool = {"conv": rng.normal(0, 1, (slots, K - 1, D)).astype(
        np.float32)}
    if "nan" in what:
        pool["conv"][what["nan"], :, ::3] = np.nan
    n_valid = np.array(what["n_valid"] + (t, t), np.int32)
    pos = np.array(what["pos"] + (11, 40), np.int32)
    x = rng.normal(0, 1, (slots, t, D)).astype(np.float32)
    for s in range(slots):
        x[s, n_valid[s]:] = 99.0
    table = np.where(n_valid[:, None] > 0, 1, 0).astype(np.int32)
    args = (params, jax.tree_util.tree_map(jnp.asarray, pool),
            jnp.asarray(table), jnp.asarray(pos), jnp.asarray(x))
    got, got_pool = jax.jit(layer.apply_stream_paged)(
        *args, *((jnp.asarray(n_valid),) if t > 1 else ()))
    got, got_pool = np.asarray(got), np.asarray(got_pool["conv"])
    for s in range(slots):
        n = n_valid[s]
        if n == 0:
            np.testing.assert_array_equal(got_pool[s], pool["conv"][s])
            continue
        want, window = _mixer_by_position(
            params, x[s, :n], pool["conv"][s] if pos[s] else None)
        np.testing.assert_allclose(got[s, :n], want, atol=ATOL)
        np.testing.assert_allclose(got_pool[s], window, atol=ATOL)


# ---- the per-head norm on queries and keys ---------------------------

def _qk_layer(**kw):
    layer = GroupedQueryAttentionLayer(
        n_in=32, n_heads=4, n_kv_heads=2, qk_head_dim=8, v_head_dim=8,
        rotary_dim=8, rope_theta=1e6, weight_init="normal", **kw)
    params, _ = layer.initialize(jax.random.PRNGKey(0),
                                 InputType.recurrent(32))
    return layer, params


def test_qk_norm_is_a_norm_over_each_head_before_the_rotation():
    """Against the equations in float64: q and k normed over each
    head's values with ONE gain for all query heads and one for all
    key heads, then rotated in half-split pairs, scores / sqrt(d)."""
    layer, params = _qk_layer(qk_norm=True, qk_norm_eps=1e-5)
    assert params["q_norm_gain"].shape == params["k_norm_gain"].shape \
        == (8,)
    params = _perturbed(params, 1)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (17, 32)),
                   np.float64)
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    T, dq = 17, 8
    rms = lambda a, g: a / np.sqrt((a * a).mean(-1, keepdims=True)
                                   + 1e-5) * g
    ang = np.arange(T)[:, None] * 1e6 ** (-np.arange(0, dq, 2) / dq)
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    rot = lambda a: np.concatenate(
        [a[..., :4] * cos - a[..., 4:] * sin,
         a[..., 4:] * cos + a[..., :4] * sin], -1)
    q = rot(rms((x @ p["Wq"]).reshape(T, 4, dq), p["q_norm_gain"]))
    k = rot(rms((x @ p["Wk"]).reshape(T, 2, dq), p["k_norm_gain"]))
    v = (x @ p["Wv"]).reshape(T, 2, 8)
    out = np.zeros((T, 4, 8))
    for h in range(4):
        s = q[:, h] @ k[:, h // 2].T / np.sqrt(dq)
        s = np.where(np.tril(np.ones((T, T), bool)), s, -np.inf)
        e = np.exp(s - s.max(-1, keepdims=True))
        out[:, h] = e / e.sum(-1, keepdims=True) @ v[:, h // 2]
    want = out.reshape(T, -1) @ p["Wo"]
    got = layer.apply(params, {}, jnp.asarray(x, jnp.float32)[None])[0][0]
    np.testing.assert_allclose(np.asarray(got), want, atol=ATOL)
    # and the plain layer over the same weights is another function
    plain = _qk_layer()[0].apply(params, {},
                                 jnp.asarray(x, jnp.float32)[None])[0][0]
    assert np.abs(np.asarray(plain) - want).max() > 100 * ATOL


def test_qk_norm_defaults_to_the_layer_it_was():
    """Left off, the field adds no parameter and nothing to the trace:
    the lowered program is the parent's, letter for letter."""
    plain, params = _qk_layer()
    assert sorted(params) == ["Wk", "Wo", "Wq", "Wv"]
    x = jnp.ones((2, 5, 32))
    text = lambda layer: jax.jit(
        lambda p, v: layer.apply(p, {}, v)[0]).lower(params, x).as_text()
    off = GroupedQueryAttentionLayer(
        n_in=32, n_heads=4, n_kv_heads=2, qk_head_dim=8, v_head_dim=8,
        rotary_dim=8, rope_theta=1e6, qk_norm=False, qk_norm_eps=1e-5)
    assert text(off) == text(plain)
    assert "rsqrt" not in text(plain)


@pytest.mark.parametrize("t", [1, 2])
def test_qk_norm_through_the_by_table_kernel(monkeypatch, t):
    """Heads of 64 as published, bfloat16: keys are cached normed and
    rotated, so the grouped by-table kernel (Pallas' interpret mode;
    the pool keeps a value head 128 wide) reads them as ``_attend``
    over the gather does; the predicate does not ask about the
    norm."""
    from deeplearning4j_tpu.ops import paged_attention as PA
    bf16 = jnp.bfloat16
    make = lambda **kw: GroupedQueryAttentionLayer(
        n_in=64, n_heads=16, n_kv_heads=2, qk_head_dim=64, v_head_dim=64,
        rotary_dim=64, rope_theta=1e6, **kw)
    layer = make(qk_norm=True, qk_norm_eps=1e-5)
    with dtypes.policy_scope(dtypes.Policy(
            param_dtype=bf16, compute_dtype=bf16, output_dtype=bf16)):
        params, _ = layer.initialize(jax.random.PRNGKey(0),
                                     InputType.recurrent(64))
    params = _perturbed(params, 2, std=0.3)
    slots, page = 2, 16
    table = jnp.asarray(1 + np.arange(slots * 4).reshape(slots, 4),
                        jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(2), (slots, 40 + t, 64), bf16)

    def run():
        pool = layer.zero_pool(slots * 4 + 1, page, bf16)
        for p in range(40):
            _, pool = layer.apply_stream_paged(
                params, pool, table, jnp.full((slots,), p, jnp.int32),
                x[:, p:p + 1])
        out, pool = layer.apply_stream_paged(
            params, pool, table, jnp.full((slots,), 40, jnp.int32),
            x[:, 40:], *((jnp.asarray([t, 1], jnp.int32),) if t > 1
                         else ()))
        return np.asarray(out, np.float32), pool

    assert not layer.paged_reads_by_table(page, t, bf16)     # the CPU
    want, pool = run()
    # the cache holds the keys normed and rotated: slot 0's first two
    # pages are ``_project``'s keys of positions 0..31
    at = jnp.broadcast_to(jnp.arange(32)[None], (slots, 32))
    _, k, _ = layer._project(params, x[:, :32], at)
    np.testing.assert_allclose(
        np.asarray(pool["k"], np.float32)[1:3].reshape(32, 2, 64),
        np.asarray(k[0], np.float32), atol=2e-2, rtol=2e-2)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        PA, "pallas_paged_attention_grouped",
        functools.partial(PA.pallas_paged_attention_grouped,
                          interpret=True))
    assert layer.paged_reads_by_table(page, t, bf16)
    assert layer.paged_reads_by_table(page, t, bf16) == \
        make().paged_reads_by_table(page, t, bf16)
    got, _ = run()
    valid = np.array([[True] * t, [True] + [False] * (t - 1)])
    np.testing.assert_allclose(got[valid], want[valid], atol=2e-2,
                               rtol=2e-2)


# ---- the network through the paged session ---------------------------

def test_full_sequence_logits_match_the_reference(tiny_net):
    ids = _ids(60, seed=1)
    out = tiny_net.output(np.asarray(ids, np.float32)[None, :, None])
    np.testing.assert_allclose(np.log(np.asarray(out[0], np.float64)),
                               _ref_logp(tiny_net, ids), atol=ATOL)


def test_lower_precisions_fail_the_tolerance(tiny_net):
    """The tolerance tells precisions apart: the reference over the
    same weights rounded to bfloat16 is fifty times past it, and the
    benchmark's control (float8_e4m3 weights) further still."""
    ids = _ids(60, seed=1)
    want = _ref_logp(tiny_net, ids)
    rounded = jax.tree_util.tree_map(
        lambda w: w.astype(jnp.bfloat16).astype(jnp.float32),
        tiny_net.params)
    low = _log_softmax(REF.logits(rounded, np.asarray(ids), TINY))
    assert np.abs(low - want).max() > 50 * ATOL
    control = _ref_logp(tiny_net, ids, control=True)
    assert np.abs(control - want).max() > np.abs(low - want).max()


@pytest.mark.parametrize("what", ["tap", "q_norm_gain", "k_norm_gain",
                                  "br"])
def test_each_new_part_matters(tiny_net, what):
    """One tap of one layer zeroed, a head norm's gain or the router's
    selection bias set back to its constant: the reference's
    log-probabilities move by far more than the tolerance, so a
    program that dropped the part would fail."""
    ids = _ids(40, seed=2)
    params = jax.tree_util.tree_map(lambda a: a, tiny_net.params)
    if what == "tap":
        conv = params[3]["conv"]
        conv["conv_w"] = conv["conv_w"].at[0].set(0.0)
    elif what == "br":
        params[3]["moe"]["br"] = jnp.zeros_like(params[3]["moe"]["br"])
    else:
        params[2]["attn"][what] = jnp.ones_like(params[2]["attn"][what])
    moved = _log_softmax(REF.logits(params, np.asarray(ids), TINY))
    assert np.abs(moved - _ref_logp(tiny_net, ids)).max() > 100 * ATOL


@pytest.mark.parametrize("t", [1, 2, 4, 16])
def test_chunked_prefill_then_decode_matches_the_reference(tiny_net, t):
    """A prompt of 45 in chunks of ``t`` (the last one ragged), then
    25 tokens one by one: the session's log-probabilities at every
    chunk's last row and at every decoded position are the
    reference's for the whole row."""
    ids = _ids(70, seed=t)
    sess = _session(tiny_net)
    assert sess.chunkable and sess.chunk_rows_max == 256
    sess.bind(1, sess.reserve(ids[:45], 25))
    got = _feed(sess, 1, ids[:45], t)
    got.update(_feed(sess, 1, ids[45:], 1))
    want = _ref_logp(tiny_net, ids)
    assert len(got) >= 26
    for pos, row in got.items():
        np.testing.assert_allclose(row, want[pos], atol=ATOL)


@pytest.mark.parametrize("t", [4, 8])
@pytest.mark.parametrize("case", ["ragged", "near_capacity"])
def test_chunk_step_matches_token_by_token(tiny_net, case, t):
    """tests/chunk_parity.py's cases over the two-row window and the
    attention layer's pages under one table, at chunks of 4 rows (a
    64-slot pool's wide program) and of 8: a fed slot's window is what
    the one-by-one steps leave, an unfed slot's is untouched, and the
    experts' counts of a chunk are the one-by-one counts summed."""
    chunk_parity.run_case(tiny_net, VOCAB, case, page=4, t=t)


def test_step_ids_picks_the_reference_best(tiny_net):
    """The id-returning step (what the batcher runs): chunks of 4,
    then single rows; where the reference's best leads by a margin
    the picked id is it."""
    ids = _ids(50, seed=11)
    sess = _session(tiny_net)
    sess.bind(0, sess.reserve(ids, 1))
    got = _feed(sess, 0, ids[:40], 4, ahead=True)
    got.update(_feed(sess, 0, ids[40:], 1, ahead=True))
    z = np.asarray(REF.logits(tiny_net.params, np.asarray(ids), TINY))
    top2 = np.sort(z, axis=-1)[:, -2:]
    sure = [p for p in got if top2[p, 1] - top2[p, 0] > 1e-3]
    assert len(sure) >= 15
    assert [got[p] for p in sure] == [int(z[p].argmax()) for p in sure]


def test_a_slot_let_again_starts_as_a_fresh_stream_bit_for_bit(tiny_net):
    """Nothing zeroes a window at ``release`` or ``bind``: the second
    tenant's position 0 restarts it, even over a row left non-finite,
    and its log-probabilities are those of the same stream in a
    session nobody used, bit for bit."""
    first, second = _ids(50, seed=5), _ids(40, seed=6)
    used, fresh = _session(tiny_net), _session(tiny_net)
    used.bind(2, used.reserve(first, 1))
    _feed(used, 2, first, 4)
    used.release(2)
    kept = [i for i, k in enumerate(used._state) if k]
    assert all(np.abs(np.asarray(used._pools[i]["conv"][2])).max() > 0
               for i in kept)                       # still there
    i = kept[0]
    used._pools[i] = {"conv": used._pools[i]["conv"].at[2, 0, ::2].set(
        jnp.nan)}
    used.bind(2, used.reserve(second, 1))
    fresh.bind(2, fresh.reserve(second, 1))
    a, b = _feed(used, 2, second, 4), _feed(fresh, 2, second, 4)
    assert a.keys() == b.keys()
    for pos in a:
        np.testing.assert_array_equal(a[pos], b[pos])
    assert fresh._state_used.tolist() == [False, False, True]


@pytest.mark.parametrize("t", [1, 4])
def test_a_slot_that_sits_steps_out_keeps_its_window(tiny_net, t):
    """Slot 0 stops after 20 tokens while slot 1 steps on (in the
    single-row program slot 0 is marked by its all-zero table row, in
    the chunk program by ``n_valid`` 0), then goes on: the same
    log-probabilities as a stream never interrupted."""
    ids, other = _ids(40, seed=7), _ids(24, seed=8)
    sess = _session(tiny_net)
    sess.bind(0, sess.reserve(ids, 1))
    sess.bind(1, sess.reserve(other, 1))
    got = _feed(sess, 0, ids[:20], 4)
    _feed(sess, 1, other, t)
    got.update(_feed(sess, 0, ids[20:], t))
    want = _ref_logp(tiny_net, ids)
    for pos, row in got.items():
        np.testing.assert_allclose(row, want[pos], atol=ATOL)


def test_the_state_pools_second_tenant(tiny_net):
    """A ``conv`` layer's pool is ONE leaf of ``slots`` rows and no
    page; the attention layer's has the allocator's pages; the schema
    names the kind; the byte count is the windows'; a state layer may
    be an ``aux`` layer too."""
    sess = _session(tiny_net, slots=3, capacity=64)
    assert sess._state == [False, True, False, True, True, True,
                           False, False]
    assert not any(sess._ring) and sess._slot_owned
    assert sess._aux_layers == [2, 3, 4, 5]
    conv, attn = sess._pools[1], sess._pools[2]
    assert list(conv) == ["conv"] and conv["conv"].shape == (3, 2, 32)
    assert attn["k"].shape == (3 * 8 + 1, PAGE, 2 * 8)
    schema = sess._pool_schema()
    assert schema[0] is None
    assert schema[1] == [{"shape": [2, 32], "dtype": "float32",
                          "state": True}]
    assert "state" not in schema[2][0]
    assert sess.state_pool_bytes == 4 * 3 * 2 * 32 * 4
    # the accounting of positions read counts the attention layer only
    sess.bind(0, sess.reserve(_ids(9), 1))
    _feed(sess, 0, _ids(9), 1)
    assert sess.step_kv_positions == (3 * 64, 3 * 64)


def test_no_prefix_is_taken_or_registered(tiny_net):
    prompt = _ids(40, seed=9)
    sess = _session(tiny_net, slots=2)
    sess.bind(0, sess.reserve(prompt, 2))
    _feed(sess, 0, prompt, 16)
    assert sess.register_written_prefix(0, prompt) == 0
    sess.release(0, register_prompt=prompt)
    assert len(sess.prefix_cache) == 0
    lease = sess.reserve(prompt, 2)
    assert lease.resume_pos == 0 and lease.prefix_hit_tokens == 0
    sess.bind(1, lease)
    got = _feed(sess, 1, prompt, 16)
    want = _ref_logp(tiny_net, prompt)
    for pos, row in got.items():
        np.testing.assert_allclose(row, want[pos], atol=ATOL)


def test_lease_export_import_carries_the_window(tiny_net):
    """A stream exported mid-way (the attention layer's pages and each
    ``conv`` layer's window) and imported into another session's other
    slot, which an earlier stream had used, goes on to the same logits
    as the stream that stayed, and as the reference; a session whose
    window has another shape, and one whose state rows are Mamba-2's,
    refuse the blob by the typed error."""
    pos = 37
    ids = _ids(pos + 12, seed=pos)
    a, b = _session(tiny_net, slots=2), _session(tiny_net, slots=3)
    a.bind(0, a.reserve(ids[:pos], 12))
    _feed(a, 0, ids[:pos], 16)
    blob = a.export_lease(0, extra={"n": 1})
    b.bind(2, b.reserve(_ids(30, seed=1), 1))
    _feed(b, 2, _ids(30, seed=1), 16)
    b.release(2)
    lease, extra = b.import_lease(blob, pos + 12)
    assert extra == {"n": 1} and lease.resume_pos == pos
    assert sorted(lease.state_rows) == [1, 3, 4, 5]
    assert [r.shape for r in lease.state_rows[1]] == [(2, 32)]
    b.bind(2, lease)
    assert lease.state_rows is None             # on the device now
    stayed = _feed(a, 0, ids[pos:], 1)
    moved = _feed(b, 2, ids[pos:], 1)
    want = _ref_logp(tiny_net, ids)
    for p in stayed:
        np.testing.assert_allclose(moved[p], stayed[p], atol=1e-6)
        np.testing.assert_allclose(moved[p], want[p], atol=ATOL)
    other = _net(dict(TINY, conv_L_cache=4))
    with pytest.raises(KVLeaseVersionError, match="schema"):
        _session(other, slots=2).import_lease(blob, pos + 12)


def test_expert_counts_come_out_of_a_state_layers_step(tiny_net):
    """The paged step returns, for every expert layer, ``conv`` blocks
    among them, the tokens each expert served among the valid rows:
    what ``SparseExpertsLayer.apply_tallied`` counts for the same
    rows of the block's own normed input."""
    sess = _session(tiny_net, slots=3, capacity=64)
    ids = [_ids(6, seed=s) for s in range(3)]
    for s in range(3):
        sess.bind(s, sess.reserve(ids[s], 1))
    x = np.zeros((3, 4, 1), np.float32)
    n_valid = np.array([4, 0, 2], np.int32)
    for s in range(3):
        x[s, :n_valid[s], 0] = ids[s][:n_valid[s]]
    sess.step_chunk(x, n_valid)
    counts = np.asarray(sess.step_aux)
    assert counts.shape == (4, 8)                 # 4 expert layers x 8
    assert counts.sum(axis=1).tolist() == [6 * 2] * 4
    # layer 3, a conv block: its experts' input is the full forward's
    params, layers = tiny_net.params, tiny_net.layers
    h = params[0]["W"][jnp.asarray(x[..., 0], jnp.int32)]
    for i in (1, 2):
        h = layers[i].apply(params[i], {}, h)[0]
    blk = layers[3]
    assert isinstance(blk, ShortConvDecoderBlock) and blk.stream_aux
    from deeplearning4j_tpu.nn.conf.layers.normalization import rms_norm
    mixer, moe = blk._ensure_parts()
    mid = h + mixer.apply(params[3]["conv"], {}, rms_norm(
        h, params[3]["norm1_gain"], blk.eps))[0]
    active = np.arange(4)[None, :] < n_valid[:, None]
    _, tally = moe.apply_tallied(
        params[3]["moe"], rms_norm(mid, params[3]["norm2_gain"], blk.eps),
        jnp.asarray(active))
    np.testing.assert_array_equal(counts[1], np.asarray(tally["held"]))


def test_batcher_serves_the_network_paged_and_ahead(tiny_net):
    """``kv_mode="auto"`` gives the network the paged session with
    chunked prefill and the lookahead; nothing in ``serving/`` names
    the model: the greedy ids of more requests than slots are the
    reference's wherever its best leads by a margin, and the state
    AND the expert counters exist and move."""
    from deeplearning4j_tpu.models.paged_kv import PagedSlotSession
    from deeplearning4j_tpu.serving.continuous import ContinuousBatcher
    from deeplearning4j_tpu.serving.metrics import ServingMetrics
    metrics = ServingMetrics()
    cb = ContinuousBatcher(tiny_net, slots=2, capacity=128,
                           page_size=PAGE, kv_mode="auto",
                           metrics=metrics)
    try:
        assert isinstance(cb.session, PagedSlotSession)
        assert cb._chunk_t == 64
        prompts = [_ids(70, seed=21), _ids(9, seed=22), _ids(50, seed=23),
                   _ids(33, seed=24)]
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
    assert read("serving_lookahead_steps_total") > 0
    assert read("serving_state_rows_restarted_total") >= 2
    assert read("serving_state_pool_bytes") == \
        cb.session.state_pool_bytes == 4 * 2 * 2 * 32 * 4
    assert read("serving_moe_local_pairs_total") > 0
    assert read("serving_moe_expert_slots_total") >= \
        read("serving_moe_expert_hits_total") > 0


def test_the_step_names_the_mixer_and_its_window(tiny_net, own_programs):
    """The paged step's ops carry the block's scopes, which the
    benchmark's ``conv_time_pct.serve`` reads from the program's own
    table: ``conv`` around the mixer, ``conv/window`` around what lies
    between its two projections, ``mlp`` / ``moe/*`` and
    ``attn/global`` as in the other blocks."""
    programs = own_programs
    sess = _session(tiny_net, slots=2, capacity=32)
    sess.bind(0, sess.reserve(_ids(5), 1))
    x = np.zeros((2, 2, 1), np.float32)
    sess.step_ids(x, np.array([2, 0], np.int32), np.zeros(2, bool))
    names = [op for _, op in programs.scope_tables()["paged_step_ids/t=2"]]
    under = lambda scope: [n for n in names if f"/{scope}/" in n]
    assert under("1_ShortConvDecoderBlock/conv/window")
    assert under("1_ShortConvDecoderBlock/mlp")
    assert under("3_ShortConvDecoderBlock/moe/experts")
    assert under("3_ShortConvDecoderBlock/moe/router")
    assert under("2_GroupedQueryDecoderBlock/attn/global")
    projections = [n for n in under("1_ShortConvDecoderBlock/conv")
                   if "/conv/window/" not in n]
    assert any("dot_general" in n for n in projections)
    assert not [n for n in under("conv/window") if "dot_general" in n]


# ---- the new fields --------------------------------------------------

def test_every_new_field_round_trips_through_json(tiny_net):
    for layer in (
            ShortConvMixerLayer(n_in=24, conv_width=4),
            ShortConvDecoderBlock(n_in=24, eps=1e-6, conv_width=4,
                                  intermediate_size=40),
            ShortConvDecoderBlock(n_in=24, n_routed_experts=8, top_k=2,
                                  expert_width=12,
                                  routed_scaling_factor=2.0),
            GroupedQueryDecoderBlock(n_in=16, qk_norm=True),
            GroupedQueryAttentionLayer(n_in=16, qk_norm=True,
                                       qk_norm_eps=1e-5)):
        again = layer_from_dict(json.loads(json.dumps(layer.to_dict())))
        assert again == layer and type(again) is type(layer)
    from deeplearning4j_tpu import MultiLayerConfiguration
    conf = tiny_net.conf
    assert MultiLayerConfiguration.from_json(
        conf.to_json()).to_json() == conf.to_json()
    # the block hands its own eps to the head norms
    block = GroupedQueryDecoderBlock(n_in=16, eps=1e-5, qk_norm=True)
    block.set_n_in(InputType.recurrent(16))
    attn = block._ensure_parts()[0]
    assert attn.qk_norm and attn.qk_norm_eps == 1e-5
    assert not GroupedQueryDecoderBlock(n_in=16)._ensure_parts()[0].qk_norm


def test_both_slot_state_blocks_are_one_block():
    """``StateSpaceDecoderBlock`` and ``ShortConvDecoderBlock`` share
    their residual halves, their paged step and their pool's kind;
    the mixer's name is its scope and its key in the parameters; only
    a block with routed experts is an ``aux`` layer."""
    ssm = StateSpaceDecoderBlock(n_in=16, n_heads=2, head_dim=8)
    conv = ShortConvDecoderBlock(n_in=16)
    moe = ShortConvDecoderBlock(n_in=16, n_routed_experts=4, top_k=2,
                                expert_width=8)
    assert type(ssm).apply_stream_paged_aux is \
        type(conv).apply_stream_paged_aux
    assert (ssm.mixer, conv.mixer) == ("ssm", "conv")
    assert [b.stream_aux for b in (ssm, conv, moe)] == [False, False,
                                                        True]
    t = InputType.recurrent(16)
    keys = lambda b: sorted(b.initialize(jax.random.PRNGKey(0), t)[0])
    assert keys(ssm) == ["Wd", "Wg", "Wu", "norm1_gain", "norm2_gain",
                         "ssm"]
    assert keys(conv) == ["Wd", "Wg", "Wu", "conv", "norm1_gain",
                          "norm2_gain"]
    assert keys(moe) == ["conv", "moe", "norm1_gain", "norm2_gain"]
    assert sorted(moe.initialize(jax.random.PRNGKey(0), t)[0]["moe"]) \
        == ["Wd", "Wg", "Wr", "Wu", "br"]
    assert list(conv.zero_pool(3, 4, jnp.bfloat16)) == ["conv"]
    assert sorted(ssm.zero_pool(3, 4, jnp.bfloat16)) == ["conv", "ssm"]
