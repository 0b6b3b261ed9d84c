"""Olmo-Hybrid's two kinds of layer on the serving path: gated
delta-rule mixers whose matrix state is a row a SLOT in the paged
session (the state pool's third tenant), beside attention of an
odd-tile head count without position encoding in the allocator's
pages, both with the norm BEHIND each branch, held at a small size
against the plain reference (benchmark/reference/olmo_hybrid.py:
float32 jax.numpy, no code of the program) and, for the mixer alone,
against a float64 loop over positions written here.

Sizes: 6 heads of 12 x 24 (``dk != dv``, neither a power of two, an
odd count of sublane tiles), attention of 6 heads of 8.

Tolerances. Program and reference are both float32 here. They differ
in the order of their sums: the serving step solves the rows of a
chunk by forward substitution over products of decays where the
reference multiplies position by position, the program spreads a
head's values over lanes where the reference broadcasts, and every
branch ends in a norm that divides by its own RMS (a rounding of the
branch is a rounding of the stream). Over 320 positions of the layer's
own long-memory weights (A in (0, 16], dt in [0.001, 0.1]: a state
that remembers hundreds of positions, so a state lost, zeroed late or
fed out of order shows) the 320 x 96 log-probabilities of the 8-layer
network agree to 5e-6 in the mean and 1.4e-4 .. 3.7e-4 at the worst
(three seeds of ids; chunked prefill then decode 1.4e-4 .. 2.5e-4):
the network is sensitive, sixteen norms in a row each divide a branch
by its own RMS. ``ATOL`` 1e-3 leaves three times the worst read. The
same weights rounded to bfloat16 move them by 1.3 .. 2.1 at the worst
and 0.1 in the mean (``test_bfloat16_weights_fail_the_tolerance``), so
a lower precision fails by a factor of a thousand. The mixer alone
against float64 agrees to 2e-6 on outputs of size 3 (``MIXER_ATOL``
2e-5)."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    DeltaRuleDecoderBlock, GatedDeltaMixerLayer,
    GroupedQueryAttentionLayer, GroupedQueryDecoderBlock, layer_from_dict)
from deeplearning4j_tpu.nn.conf.layers import delta_rule
from deeplearning4j_tpu.serving.errors import KVLeaseVersionError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ATOL = 1e-3
MIXER_ATOL = 2e-5


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}",
        os.path.join(ROOT, "benchmark", kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("reference", "olmo_hybrid")
BUILDER = _load("builders", "olmo_hybrid_dsl")

# hidden 48; 6 delta-rule heads of 12 x 24, convolutions of 4; 6
# attention heads of 8, each its own key head; L L L F L L L F
TINY = {"attention_bias": False, "hidden_act": "silu", "hidden_size": 48,
        "intermediate_size": 64,
        "layer_types": ["linear_attention"] * 3 + ["full_attention"]
        + ["linear_attention"] * 3 + ["full_attention"],
        "linear_allow_neg_eigval": True, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 12, "linear_num_key_heads": 6,
        "linear_num_value_heads": 6, "linear_value_head_dim": 24,
        "max_position_embeddings": 512, "num_attention_heads": 6,
        "num_hidden_layers": 8, "num_key_value_heads": 6,
        "rms_norm_eps": 1e-6, "rope_parameters": {"rope_theta": None},
        "tie_word_embeddings": False, "vocab_size": 96}
PAGE = 8
VOCAB = TINY["vocab_size"]


def _perturbed(params, seed):
    """The layers' own initial values with every gain (all ones as
    they start) drawn around one, so that one dropped or misplaced
    shows."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    rng = np.random.default_rng(seed)
    new = []
    for path, leaf in leaves:
        name = str(getattr(path[-1], "key", path[-1]))
        if name in ("norm1_gain", "norm2_gain", "gain", "g",
                    "q_norm_gain", "k_norm_gain"):
            leaf = leaf + jnp.asarray(rng.normal(0, 0.1, leaf.shape),
                                      leaf.dtype)
        new.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, new)


def _net(config=TINY, seed=3, **block_fields):
    built = BUILDER.build(config).net
    for layer in built.conf.layers:
        for k, v in block_fields.items():
            if hasattr(layer, k):
                setattr(layer, k, v)
    net = built.init()
    net.params = _perturbed(net.params, seed)
    return net


@pytest.fixture(scope="module")
def tiny_net():
    return _net()


def _log_softmax(z):
    z = np.asarray(z, np.float64)
    z = z - z.max(axis=-1, keepdims=True)
    return z - np.log(np.exp(z).sum(axis=-1, keepdims=True))


def _ref_logp(net, ids, config=TINY):
    return _log_softmax(REF.logits(net.params, np.asarray(ids), config))


def _ids(n, seed=0):
    return [int(v) for v in
            np.random.default_rng(seed).integers(0, VOCAB, n)]


def _session(net, slots=3, capacity=384, page=PAGE):
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


# ---- the mixer alone -------------------------------------------------

H, DK, DV, K, D = 6, 12, 24, 4, 36


def _mixer(seed=0, dims=(H, DK, DV), d=D, **fields):
    layer = GatedDeltaMixerLayer(
        n_in=d, n_heads=dims[0], key_head_dim=dims[1],
        value_head_dim=dims[2], conv_width=K,
        **dict(dict(allow_neg_eigval=True, weight_init="normal"),
               **fields))
    params, _ = layer.initialize(jax.random.PRNGKey(seed),
                                 InputType.recurrent(d))
    return layer, _perturbed(params, seed)


def _mixer_by_position(p, x, dims=(H, DK, DV), carried=None, neg=True,
                       l2=True, gate_last=True):
    """The module docstring's equations for one sequence x (T, D),
    float64, one position and one head at a time, from zeros; or,
    given ``carried`` (a state (H, dk, dv) and the K - 1 inputs before
    x), from there, and then ``(out, state)``. ``neg``, ``l2`` and
    ``gate_last`` switch off what the tests show to matter."""
    H, dk, dv = dims
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    x = np.asarray(x, np.float64)
    silu = lambda a: a / (1 + np.exp(-a))
    u = np.concatenate([x @ p["Wq"], x @ p["Wk"], x @ p["Wv"]], axis=1)
    z, a, b = x @ p["Wg"], x @ p["Wa"], x @ p["Wb"]
    S, window = (np.zeros((H, dk, dv)), np.zeros((K - 1, u.shape[1]))) \
        if carried is None else (np.array(carried[0], np.float64),
                                 np.asarray(carried[1], np.float64))
    padded, out = np.concatenate([window, u]), []
    for t in range(x.shape[0]):
        c = silu(sum(p["conv_w"][j] * padded[t + j] for j in range(K)))
        q = c[:H * dk].reshape(H, dk)
        k = c[H * dk:2 * H * dk].reshape(H, dk)
        v = c[2 * H * dk:].reshape(H, dv)
        alpha = np.exp(-np.exp(p["A_log"])
                       * np.log1p(np.exp(a[t] + p["dt_bias"])))
        beta = (2.0 if neg else 1.0) / (1 + np.exp(-b[t]))
        o = np.zeros((H, dv))
        for h in range(H):
            qh, kh = q[h], k[h]
            if l2:
                qh = qh / np.sqrt((qh * qh).sum() + 1e-6)
                kh = kh / np.sqrt((kh * kh).sum() + 1e-6)
            qh = qh / np.sqrt(dk)
            S[h] = alpha[h] * S[h]
            S[h] = S[h] + beta[h] * np.outer(kh, v[h] - S[h].T @ kh)
            o[h] = S[h].T @ qh
        zt = silu(z[t]).reshape(H, dv)
        if not gate_last:
            o = o * zt
        o = o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-6) * p["g"]
        if gate_last:
            o = o * zt
        out.append(o.reshape(-1) @ p["Wo"])
    return np.stack(out) if carried is None else (np.stack(out), S)


def _unpacked(layer, state):
    """The pool's (.., H / p, dk, p dv) state as (.., H, dk, dv)."""
    p = layer._pack
    s = np.asarray(state)
    s = s.reshape(*s.shape[:-1], p, layer.value_head_dim)
    return np.moveaxis(s, -2, -3).reshape(
        *s.shape[:-4], layer.n_heads, layer.key_head_dim,
        layer.value_head_dim)


def _packed(layer, state):
    """The inverse of ``_unpacked``."""
    p = layer._pack
    s = np.asarray(state)
    s = s.reshape(*s.shape[:-3], layer.n_heads // p, p, *s.shape[-2:])
    return np.moveaxis(s, -3, -2).reshape(
        *s.shape[:-4], layer.n_heads // p, layer.key_head_dim,
        p * layer.value_head_dim)


def test_mixer_matches_the_recurrence_position_by_position():
    layer, params = _mixer()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, D))
    y, _ = layer.apply(params, {}, x)
    for b in range(2):
        np.testing.assert_allclose(np.asarray(y[b]),
                                   _mixer_by_position(params, x[b]),
                                   atol=MIXER_ATOL)
    # and it is differentiable
    g = jax.grad(lambda p: jnp.sum(layer.apply(p, {}, x)[0] ** 2))(params)
    assert all(bool(jnp.all(jnp.isfinite(v))) and float(jnp.abs(v).max())
               > 0 for v in jax.tree_util.tree_leaves(g))


@pytest.mark.parametrize("what", ["neg", "l2", "gate_last"])
def test_each_part_of_the_mixer_matters(what):
    """``allow_neg_eigval`` (the write strength doubled), the L2 norms
    of q and k and the gate's place behind the norm: the recurrence
    without any one of them is a thousand tolerances away."""
    layer, params = _mixer()
    x = jax.random.normal(jax.random.PRNGKey(1), (40, D))
    y = np.asarray(layer.apply(params, {}, x[None])[0][0])
    off = _mixer_by_position(params, x, **{what: False})
    assert np.abs(y - off).max() > 1000 * MIXER_ATOL
    if what == "neg":       # and the field is what switches it
        plain, _ = _mixer(allow_neg_eigval=False)
        np.testing.assert_allclose(
            np.asarray(plain.apply(params, {}, x[None])[0][0]), off,
            atol=MIXER_ATOL)


@pytest.mark.parametrize("t, lanes", [(1, 128), (2, 128), (4, 128),
                                      (2, 16), (4, 16)])
def test_mixer_stream_matches_apply(t, lanes, monkeypatch):
    """Three streams of 21 tokens through ``apply_stream_paged`` in
    ragged steps of up to ``t`` rows (0 among them: a slot that sits a
    step out), over a pool an earlier tenant left non-zero and with
    junk in the rows past ``n_valid``, against ``apply``. With a lane
    tile of 16 two heads of 24 lie side by side (three tiles of 16, as
    two of 192 are three of 128 on the device): the packed layout."""
    monkeypatch.setattr(delta_rule, "_LANES", lanes)
    layer, params = _mixer()
    assert layer._pack == (2 if lanes == 16 else 1)
    slots, T = 3, 21
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (slots, T, D)))
    want = np.asarray(layer.apply(params, {}, jnp.asarray(x))[0])
    pool = jax.tree_util.tree_map(lambda a: a + 7.0,
                                  layer.zero_pool(slots, 4, jnp.float32))
    assert pool["state"].shape == (slots, H // layer._pack, DK,
                                   layer._pack * DV)
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
        np.stack([np.concatenate(g) for g in got]), want,
        atol=MIXER_ATOL)


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
def test_one_step_at_the_published_head_shape(t, case):
    """Heads of 96 x 192 as published (4 of them: two pairs side by
    side on 384 lanes): ONE step of ``apply_stream_paged`` over a pool
    that an earlier tenant left non-zero, junk in the rows past
    ``n_valid``, against the recurrence in float64 from the same rows.
    A slot at position 0 starts from zeros whatever its row holds (NaN
    too); a slot that feeds nothing keeps its row bit for bit."""
    dims, slots, d = (4, 96, 192), 4, 32
    layer, params = _mixer(3, dims, d)
    assert layer._pack == 2
    what = ONE_STEP[case](t)
    rng = np.random.default_rng(t)
    state = rng.normal(0, 1, (slots,) + dims).astype(np.float32)
    if "nan" in what:
        state[what["nan"], ::3, ::5] = np.nan
    pool = {"state": _packed(layer, state),
            "conv": rng.normal(0, 1, (slots, K - 1, layer.conv_dim)
                               ).astype(np.float32)}
    assert pool["state"].shape == (slots, 2, 96, 384)
    np.testing.assert_array_equal(_unpacked(layer, pool["state"]), state)
    n_valid = np.array(what["n_valid"] + (t, t), np.int32)
    pos = np.array(what["pos"] + (11, 40), np.int32)
    x = rng.normal(0, 1, (slots, t, d)).astype(np.float32)
    for s in range(slots):
        x[s, n_valid[s]:] = 99.0
    # the single-row program has no ``n_valid``: the all-zero table
    # row marks the slot that sits the step out
    table = np.where(n_valid[:, None] > 0, 1, 0).astype(np.int32)
    args = (params, jax.tree_util.tree_map(jnp.asarray, pool),
            jnp.asarray(table), jnp.asarray(pos), jnp.asarray(x))
    got, got_pool = jax.jit(layer.apply_stream_paged)(
        *args, *((jnp.asarray(n_valid),) if t > 1 else ()))
    got, got_pool = np.asarray(got), jax.tree_util.tree_map(
        np.asarray, got_pool)
    for s in range(slots):
        n = n_valid[s]
        if n == 0:
            for leaf in ("state", "conv"):
                np.testing.assert_array_equal(got_pool[leaf][s],
                                              pool[leaf][s])
            continue
        carried = (state[s], pool["conv"][s]) if pos[s] else \
            (np.zeros(dims), np.zeros((K - 1, layer.conv_dim)))
        want, left = _mixer_by_position(params, x[s, :n], dims, carried)
        np.testing.assert_allclose(got[s, :n], want, atol=MIXER_ATOL)
        np.testing.assert_allclose(
            _unpacked(layer, got_pool["state"][s]), left, atol=MIXER_ATOL)


# ---- the network through the paged session ---------------------------

def test_full_sequence_logits_match_the_reference(tiny_net):
    ids = _ids(320, seed=1)
    out = tiny_net.output(np.asarray(ids, np.float32)[None, :, None])
    np.testing.assert_allclose(np.log(np.asarray(out[0], np.float64)),
                               _ref_logp(tiny_net, ids), atol=ATOL)


def test_bfloat16_weights_fail_the_tolerance(tiny_net):
    """The tolerance tells precisions apart: the reference over the
    same weights rounded to bfloat16 is a thousand times past it."""
    ids = _ids(320, seed=1)
    rounded = jax.tree_util.tree_map(
        lambda w: w.astype(jnp.bfloat16).astype(jnp.float32),
        tiny_net.params)
    low = _log_softmax(REF.logits(rounded, np.asarray(ids), TINY))
    assert np.abs(low - _ref_logp(tiny_net, ids)).max() > 1000 * ATOL


@pytest.mark.parametrize("fields", [
    dict(norm_placement="pre"), dict(qk_norm=True), dict(qk_norm=False),
    dict(allow_neg_eigval=False), dict(rotary_dim=8)],
    ids=lambda f: "-".join(f"{k}={v}" for k, v in f.items()))
def test_each_assumed_choice_matters(tiny_net, fields):
    """The norm behind each branch, the q/k norm over the whole width
    (not a head's, not absent), the doubled write strength and the
    absence of rotary: a network with any one of them otherwise is
    more than a hundred tolerances from the reference on the same
    weights."""
    ids = _ids(60, seed=2)
    other = _net(**fields)
    if fields.get("qk_norm") is True:       # a gain a head: 8 values
        for p in other.params:
            if "attn" in p:
                for g in ("q_norm_gain", "k_norm_gain"):
                    p["attn"][g] = p["attn"][g][:8]
    elif fields.get("qk_norm", True) is not False:
        other.params = tiny_net.params
    else:
        other.params = [
            {**p, "attn": {k: v for k, v in p["attn"].items()
                           if "norm" not in k}} if "attn" in p else p
            for p in tiny_net.params]
    out = other.output(np.asarray(ids, np.float32)[None, :, None])
    assert np.abs(np.log(np.asarray(out[0], np.float64))
                  - _ref_logp(tiny_net, ids)).max() > 100 * ATOL


@pytest.mark.parametrize("t", [1, 2, 4])
def test_chunked_prefill_then_decode_matches_the_reference(tiny_net, t):
    """A prompt of 200 in chunks of ``t``, then 120 tokens one by one:
    the session's log-probabilities at every chunk's last row and at
    every decoded position are the reference's for the whole row of
    320."""
    ids = _ids(320, seed=t)
    sess = _session(tiny_net)
    assert sess.chunkable and sess.chunk_rows_max == 384
    sess.bind(1, sess.reserve(ids[:200], 120))
    got = _feed(sess, 1, ids[:200], t)
    got.update(_feed(sess, 1, ids[200:], 1))
    want = _ref_logp(tiny_net, ids)
    assert len(got) >= 126
    for pos, row in got.items():
        np.testing.assert_allclose(row, want[pos], atol=ATOL)


def test_a_slot_let_again_starts_as_a_fresh_stream_bit_for_bit(tiny_net):
    """Nothing zeroes a state row at ``release`` or ``bind``: the
    second tenant's position 0 restarts it, and its log-probabilities
    are those of the same stream in a session nobody used, bit for
    bit."""
    first, second = _ids(50, seed=5), _ids(40, seed=6)
    used, fresh = _session(tiny_net), _session(tiny_net)
    used.bind(2, used.reserve(first, 1))
    _feed(used, 2, first, 4)
    used.release(2)
    state = [np.asarray(leaf[2]) for pool, kept in zip(used._pools,
                                                       used._state)
             if kept for leaf in jax.tree_util.tree_leaves(pool)]
    assert all(np.abs(row).max() > 0 for row in state)   # still there
    used.bind(2, used.reserve(second, 1))
    fresh.bind(2, fresh.reserve(second, 1))
    a, b = _feed(used, 2, second, 4), _feed(fresh, 2, second, 4)
    assert a.keys() == b.keys()
    for pos in a:
        np.testing.assert_array_equal(a[pos], b[pos])
    assert fresh._state_used.tolist() == [False, False, True]


@pytest.mark.parametrize("t", [1, 4])
def test_a_slot_that_sits_steps_out_keeps_its_state(tiny_net, t):
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


def test_the_third_tenant_is_a_state_row_like_the_others(tiny_net):
    """A delta-rule layer's pool has ``slots`` rows and no page; the
    attention layer's has the allocator's pages and the scratch page;
    the schema names the kind; the byte count is the state pools';
    the accounting of positions read counts the attention layers."""
    sess = _session(tiny_net, slots=3, capacity=64)
    assert sess._state == [False] + [True, True, True, False] * 2 + [
        False, False]
    assert not any(sess._ring) and sess._slot_owned
    assert sess.unrolls_chunk_rows
    delta, attn = sess._pools[1], sess._pools[4]
    assert delta["state"].shape == (3, 6, 12, 24)
    assert delta["state"].dtype == jnp.float32
    assert delta["conv"].shape == (3, 3, 2 * 72 + 144)
    assert attn["k"].shape == (3 * 8 + 1, PAGE, 48)
    schema = sess._pool_schema()
    assert schema[0] is None and all(d["state"] for d in schema[1])
    assert "state" not in schema[4][0]
    assert sess.state_pool_bytes == 6 * 3 * (6 * 12 * 24 * 4
                                             + 3 * 288 * 4)
    sess.bind(0, sess.reserve(_ids(9), 1))
    _feed(sess, 0, _ids(9), 1)
    assert sess.step_kv_positions == (3 * 64, 3 * 64)


def test_no_prefix_is_taken_or_registered(tiny_net):
    """A state row cannot be shared and a hit would resume behind a
    state nobody kept: a repeated prompt is served cold, to the
    reference's logits, and nothing is registered."""
    prompt = _ids(40, seed=9)
    sess = _session(tiny_net, slots=2)
    sess.bind(0, sess.reserve(prompt, 2))
    _feed(sess, 0, prompt, 4)
    assert sess.register_written_prefix(0, prompt) == 0
    sess.release(0, register_prompt=prompt)
    assert len(sess.prefix_cache) == 0
    lease = sess.reserve(prompt, 2)
    assert lease.resume_pos == 0 and lease.prefix_hit_tokens == 0
    sess.bind(1, lease)
    got = _feed(sess, 1, prompt, 4)
    want = _ref_logp(tiny_net, prompt)
    for pos, row in got.items():
        np.testing.assert_allclose(row, want[pos], atol=ATOL)


def test_lease_export_import_continues_the_stream(tiny_net):
    """A stream exported mid-way (the attention layers' pages and each
    delta-rule layer's row) and imported into another session's other
    slot, which an earlier stream had used, goes on to the same
    logits as the stream that stayed, and as the reference."""
    pos = 37
    ids = _ids(pos + 12, seed=pos)
    a, b = _session(tiny_net, slots=2), _session(tiny_net, slots=3)
    a.bind(0, a.reserve(ids[:pos], 12))
    _feed(a, 0, ids[:pos], 4)
    blob = a.export_lease(0, extra={"n": 1})
    b.bind(2, b.reserve(_ids(30, seed=1), 1))
    _feed(b, 2, _ids(30, seed=1), 4)
    b.release(2)
    lease, extra = b.import_lease(blob, pos + 12)
    assert extra == {"n": 1} and lease.resume_pos == pos
    assert sorted(lease.state_rows) == [1, 2, 3, 5, 6, 7]
    b.bind(2, lease)
    stayed = _feed(a, 0, ids[pos:], 1)
    moved = _feed(b, 2, ids[pos:], 1)
    want = _ref_logp(tiny_net, ids)
    for p in stayed:
        np.testing.assert_allclose(moved[p], stayed[p], atol=1e-6)
        np.testing.assert_allclose(moved[p], want[p], atol=ATOL)
    # the header names the kind and the row's shape: a session over
    # another head width refuses the blob by the typed error
    other = _net(dict(TINY, linear_value_head_dim=16))
    with pytest.raises(KVLeaseVersionError, match="schema"):
        _session(other, slots=2).import_lease(blob, pos + 12)


def test_batcher_serves_the_hybrid_network_paged_and_ahead(tiny_net):
    """``kv_mode="auto"`` gives the network the paged session, not
    the dense fallback, with chunked prefill and the lookahead: the
    greedy ids of more requests than slots are the reference's at
    every position where its best leads by a margin, and the state
    and the key/value counters are fed as by the older tenants."""
    from deeplearning4j_tpu.models.paged_kv import PagedSlotSession
    from deeplearning4j_tpu.serving.continuous import ContinuousBatcher
    from deeplearning4j_tpu.serving.metrics import ServingMetrics
    metrics = ServingMetrics()
    cb = ContinuousBatcher(tiny_net, slots=8, capacity=128,
                           page_size=PAGE, kv_mode="auto",
                           metrics=metrics)
    try:
        assert isinstance(cb.session, PagedSlotSession)
        assert cb._chunk_t == 16 and not cb._wide_t
        prompts = [_ids(n, seed=20 + n) for n in (70, 9, 50, 33, 21, 40,
                                                  5, 64, 17, 30)]
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(len(prompts)) as pool:
            outs = list(pool.map(lambda p: cb.generate(p, 12), prompts))
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
        cb.session.state_pool_bytes > 0
    spanned = read("serving_kv_positions_spanned_total")
    assert spanned == read("serving_steps_total") * 8 * 128
    assert read("serving_kv_positions_read_total") == spanned  # the CPU
    assert not [k for k in snap if "kv_ring" in k]


def test_the_step_names_the_mixer_and_its_state(tiny_net, own_programs):
    """The paged step's ops carry the block's scopes, which the
    benchmark's ``delta_time_pct.serve`` / ``delta_state_time_pct.serve``
    read from the program's own table: ``delta`` around the mixer and
    the norm behind it, ``delta/state`` around what lies between the
    projections, ``mlp`` and ``attn/global`` as in the other blocks."""
    programs = own_programs
    sess = _session(tiny_net, slots=2, capacity=32)
    sess.bind(0, sess.reserve(_ids(5), 1))
    x = np.zeros((2, 2, 1), np.float32)
    sess.step_ids(x, np.array([2, 0], np.int32), np.zeros(2, bool))
    names = [op for _, op in programs.scope_tables()["paged_step_ids/t=2"]]
    under = lambda scope: [n for n in names if f"/{scope}/" in n]
    assert under("1_DeltaRuleDecoderBlock/delta/state")
    assert under("1_DeltaRuleDecoderBlock/mlp")
    assert under("4_GroupedQueryDecoderBlock/attn/global")
    projections = [n for n in under("1_DeltaRuleDecoderBlock/delta")
                   if "/delta/state/" not in n]
    assert sum("dot_general" in n for n in projections) >= 7
    assert not [n for n in under("delta/state") if "dot_general" in n]


# ---- the new fields --------------------------------------------------

def test_every_new_field_round_trips_through_json(tiny_net):
    for layer in (
            GatedDeltaMixerLayer(n_in=24, n_heads=6, key_head_dim=4,
                                 value_head_dim=12, conv_width=3,
                                 allow_neg_eigval=True, eps=1e-5),
            DeltaRuleDecoderBlock(n_in=24, eps=1e-5, n_heads=6,
                                  key_head_dim=4, value_head_dim=12,
                                  conv_width=3, allow_neg_eigval=True,
                                  intermediate_size=40,
                                  norm_placement="post"),
            GroupedQueryDecoderBlock(n_in=16, qk_norm="width",
                                     norm_placement="post"),
            GroupedQueryDecoderBlock(n_in=16, qk_norm=True),
            GroupedQueryAttentionLayer(n_in=16, qk_norm="width")):
        again = layer_from_dict(json.loads(json.dumps(layer.to_dict())))
        assert again == layer and type(again) is type(layer)
    from deeplearning4j_tpu import MultiLayerConfiguration
    conf = tiny_net.conf
    assert MultiLayerConfiguration.from_json(
        conf.to_json()).to_json() == conf.to_json()
    for bad in (dict(norm_placement="around"), dict(qk_norm="head")):
        with pytest.raises(ValueError):
            GroupedQueryDecoderBlock(n_in=16, **bad)._ensure_parts()


def test_the_defaults_are_the_layers_they_were():
    """A field left at its default is today's program: ``"pre"`` said
    aloud lowers to the text of a block that says nothing, ``"post"``
    to another; ``qk_norm`` True keeps a gain a head."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 16))
    block = GroupedQueryDecoderBlock(n_in=16, qk_norm=True)
    bp, _ = block.initialize(jax.random.PRNGKey(2),
                             InputType.recurrent(16))
    assert bp["attn"]["q_norm_gain"].shape == (8,)
    text = lambda b: jax.jit(lambda p, v: b.apply(p, {}, v)[0]).lower(
        bp, x).as_text()
    assert text(GroupedQueryDecoderBlock(
        n_in=16, qk_norm=True, norm_placement="pre")) == text(block)
    assert text(GroupedQueryDecoderBlock(
        n_in=16, qk_norm=True, norm_placement="post")) != text(block)
    wide = GroupedQueryDecoderBlock(n_in=16, qk_norm="width")
    wp, _ = wide.initialize(jax.random.PRNGKey(2), InputType.recurrent(16))
    assert wp["attn"]["q_norm_gain"].shape == (4 * 8,)
    assert wp["attn"]["k_norm_gain"].shape == (2 * 8,)
