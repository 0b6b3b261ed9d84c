"""Granite-4.0-H's two kinds of layer on the serving path: Mamba-2
mixers whose fixed-size state is a row a SLOT in the paged session,
beside grouped-query attention without position encoding in the
allocator's pages, held at a small size against the plain reference
(benchmark/reference/granite_hybrid.py: float32 jax.numpy, no code of
the program) and, for the mixer alone, against a float64 loop over
positions written here.

Tolerances. Program and reference are both float32 here. They differ
in the order of their sums: the serving step sums the rows of a chunk
in closed form (decays as exponentials of differences of a running
sum) where the reference multiplies position by position, and the
attention scores all key heads in one einsum. Log-probabilities of the
5-layer network agree to under 1e-6; ``ATOL`` 2e-5 leaves the CPU's own
reassociation room. The same weights rounded to bfloat16 move them by
1.2e-3 (``test_bfloat16_weights_fail_the_tolerance``; the logits are
divided by 8, so the rows are flat), so a lower precision fails by a
factor of sixty. The layer's own initialiser is used
(A in [1, 16], dt in [0.001, 0.1]): a state that remembers hundreds of
positions, so a state lost, zeroed late or fed out of order shows."""

import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    EmbeddingSequenceLayer, GroupedQueryAttentionLayer,
    GroupedQueryDecoderBlock, Mamba2MixerLayer, RnnOutputLayer,
    StateSpaceDecoderBlock, layer_from_dict)
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


REF = _load("reference", "granite_hybrid")
BUILDER = _load("builders", "granite_hybrid_dsl")

# hidden 32; 8 state heads of 8 over a state of 16, one group,
# convolution of 4; 4 query heads over 2 key/value heads of 8, scores
# times 1/4 (not 1/sqrt(8)); M M A M M
TINY = {"attention_bias": False, "attention_multiplier": 0.25,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 32,
        "layer_types": ["mamba", "mamba", "attention", "mamba", "mamba"],
        "logits_scaling": 8, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_head": 8, "mamba_d_state": 16, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 8,
        "mamba_proj_bias": False, "max_position_embeddings": 256,
        "normalization_function": "rmsnorm", "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 5,
        "num_local_experts": 0, "position_embedding_type": "nope",
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-5,
        "shared_intermediate_size": 64, "vocab_size": 96}
PAGE = 8
VOCAB = TINY["vocab_size"]


def _perturbed(params, seed):
    """The layers' own initial values with every vector that starts at
    a constant (gains, ``D``, the convolution's bias) drawn around it,
    so that one dropped or misplaced shows."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    rng = np.random.default_rng(seed)
    new = []
    for path, leaf in leaves:
        name = str(getattr(path[-1], "key", path[-1]))
        if name in ("norm1_gain", "norm2_gain", "gain", "D", "g",
                    "conv_b"):
            leaf = leaf + jnp.asarray(rng.normal(0, 0.1, leaf.shape),
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


def _ref_logp(net, ids, config=TINY):
    return _log_softmax(REF.logits(net.params, np.asarray(ids), config))


def _ids(n, seed=0):
    return [int(v) for v in
            np.random.default_rng(seed).integers(0, VOCAB, n)]


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


# ---- the mixer alone -------------------------------------------------

H, P, N, G, K, D = 4, 6, 8, 2, 4, 24


def _mixer(seed=0):
    layer = Mamba2MixerLayer(n_in=D, n_heads=H, head_dim=P, state_size=N,
                             n_groups=G, conv_width=K,
                             weight_init="normal")
    params, _ = layer.initialize(jax.random.PRNGKey(seed),
                                 InputType.recurrent(D))
    return layer, _perturbed(params, seed)


def _mixer_by_position(p, x, dims=(H, P, N, G), carried=None):
    """The module docstring's equations for one sequence x (T, D),
    float64, one position at a time, from zeros; or, given
    ``carried`` (a state (H, P, N) and the K - 1 inputs before x),
    from there, and then ``(out, state)``."""
    H, P, N, G = dims
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    x = np.asarray(x, np.float64)
    di, cd = H * P, H * P + 2 * G * N
    silu = lambda a: a / (1 + np.exp(-a))
    proj = x @ p["W_in"]
    z, u, dt_raw = proj[:, :di], proj[:, di:di + cd], proj[:, di + cd:]
    S, window = (np.zeros((H, P, N)), np.zeros((K - 1, cd))) \
        if carried is None else (np.array(carried[0], np.float64),
                                 np.asarray(carried[1], np.float64))
    padded, out = np.concatenate([window, u]), []
    for t in range(x.shape[0]):
        c = silu(sum(p["conv_w"][k, 0] * padded[t + k] for k in range(K))
                 + p["conv_b"])
        xs = c[:di].reshape(H, P)
        B = c[di:di + G * N].reshape(G, N)
        C = c[di + G * N:].reshape(G, N)
        dt = np.log1p(np.exp(dt_raw[t] + p["dt_bias"]))
        y = np.zeros((H, P))
        for h in range(H):
            g = h // (H // G)
            S[h] = (np.exp(-dt[h] * np.exp(p["A_log"][h])) * S[h]
                    + dt[h] * np.outer(xs[h], B[g]))
            y[h] = S[h] @ C[g] + p["D"][h] * xs[h]
        v = (y.reshape(-1) * silu(z[t])).reshape(G, -1)
        v = v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-5)
        out.append((v.reshape(-1) * p["g"]) @ p["W_out"])
    return np.stack(out) if carried is None else (np.stack(out), S)


def test_mixer_matches_the_recurrence_position_by_position():
    layer, params = _mixer()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, D))
    y, _ = layer.apply(params, {}, x)
    for b in range(2):
        np.testing.assert_allclose(np.asarray(y[b]),
                                   _mixer_by_position(params, x[b]),
                                   atol=ATOL)
    # and it is differentiable
    g = jax.grad(lambda p: jnp.sum(layer.apply(p, {}, x)[0] ** 2))(params)
    assert all(bool(jnp.all(jnp.isfinite(v))) and float(jnp.abs(v).max())
               > 0 for v in jax.tree_util.tree_leaves(g))


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
def test_one_step_at_the_published_head_shape(t, case):
    """Heads of 64 over a state of 128 as published (8 of them, one
    group): ONE step of ``apply_stream_paged`` over a pool that an
    earlier tenant left non-zero, junk in the rows past ``n_valid``,
    against the recurrence in float64 from the same rows. A slot at
    position 0 starts from zeros whatever its row holds (NaN too); a
    slot that feeds nothing keeps its row bit for bit. Outputs of
    size 3 agree to 2e-6 and states of size 9 to 8e-7."""
    dims, slots, d = (8, 64, 128, 1), 4, 32
    layer = Mamba2MixerLayer(n_in=d, n_heads=8, head_dim=64,
                             state_size=128, n_groups=1, conv_width=K,
                             weight_init="normal")
    params = _perturbed(layer.initialize(
        jax.random.PRNGKey(3), InputType.recurrent(d))[0], 3)
    what = ONE_STEP[case](t)
    rng = np.random.default_rng(t)
    pool = {"ssm": rng.normal(0, 2, (slots, 8, 64, 128)).astype(
                np.float32),
            "conv": rng.normal(0, 1, (slots, K - 1, layer.conv_dim)
                               ).astype(np.float32)}
    if "nan" in what:
        pool["ssm"][what["nan"], ::3, ::5] = np.nan
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
            for leaf in ("ssm", "conv"):
                np.testing.assert_array_equal(got_pool[leaf][s],
                                              pool[leaf][s])
            continue
        carried = (pool["ssm"][s], pool["conv"][s]) if pos[s] else \
            (np.zeros((8, 64, 128)), np.zeros((K - 1, layer.conv_dim)))
        want, state = _mixer_by_position(params, x[s, :n], dims, carried)
        np.testing.assert_allclose(got[s, :n], want, atol=ATOL)
        np.testing.assert_allclose(got_pool["ssm"][s], state, atol=ATOL)


# ---- the network through the paged session ---------------------------

def test_full_sequence_logits_match_the_reference(tiny_net):
    ids = _ids(60, seed=1)
    out = tiny_net.output(np.asarray(ids, np.float32)[None, :, None])
    np.testing.assert_allclose(np.log(np.asarray(out[0], np.float64)),
                               _ref_logp(tiny_net, ids), atol=ATOL)


def test_bfloat16_weights_fail_the_tolerance(tiny_net):
    """The tolerance tells precisions apart: the reference over the
    same weights rounded to bfloat16 is fifty times past it."""
    ids = _ids(60, seed=1)
    rounded = jax.tree_util.tree_map(
        lambda w: w.astype(jnp.bfloat16).astype(jnp.float32),
        tiny_net.params)
    low = _log_softmax(REF.logits(rounded, np.asarray(ids), TINY))
    assert np.abs(low - _ref_logp(tiny_net, ids)).max() > 50 * ATOL


@pytest.mark.parametrize("field, other", [
    ("attention_multiplier", 8 ** -0.5), ("embedding_multiplier", 1),
    ("residual_multiplier", 1.0), ("logits_scaling", 1)])
def test_each_multiplier_matters(tiny_net, field, other):
    ids = _ids(30, seed=2)
    moved = _log_softmax(REF.logits(tiny_net.params, np.asarray(ids),
                                    dict(TINY, **{field: other})))
    assert np.abs(moved - _ref_logp(tiny_net, ids)).max() > 100 * ATOL


@pytest.mark.parametrize("t", [1, 2, 4, 16])
def test_chunked_prefill_then_decode_matches_the_reference(tiny_net, t):
    """A prompt of 45 in chunks of ``t``, then 25 tokens one by one:
    the session's log-probabilities at every chunk's last row and at
    every decoded position are the reference's for the whole row."""
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
    assert used.step_state_restarts == 0          # the last step: none
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


def test_three_kinds_of_pool_are_told_apart(tiny_net):
    """A state layer's pool has ``slots`` rows and no page; the
    attention layer's has the allocator's pages and the scratch page;
    the schema names the kind; the byte count is the state pools'."""
    sess = _session(tiny_net, slots=3, capacity=64)
    assert sess._state == [False, True, True, False, True, True,
                           False, False]
    assert not any(sess._ring) and sess._slot_owned
    ssm, attn = sess._pools[1], sess._pools[3]
    assert ssm["ssm"].shape == (3, 8, 8, 16)
    assert ssm["ssm"].dtype == jnp.float32
    assert ssm["conv"].shape == (3, 3, 64 + 2 * 16)
    assert attn["k"].shape == (3 * 8 + 1, PAGE, 2 * 8)
    schema = sess._pool_schema()
    assert schema[0] is None and all(d["state"] for d in schema[1])
    assert "state" not in schema[3][0]
    assert sess.state_pool_bytes == 4 * 3 * (8 * 8 * 16 * 4 + 3 * 96 * 4)
    # the accounting of positions read counts the attention layer only
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


def test_lease_export_import_continues_the_stream(tiny_net):
    """A stream exported mid-way (the attention layer's pages and
    each state layer's row) and imported into another session's other
    slot, which an earlier stream had used, goes on to the same
    logits as the stream that stayed, and as the reference."""
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
    assert sorted(lease.state_rows) == [1, 2, 4, 5]
    b.bind(2, lease)
    assert lease.state_rows is None             # on the device now
    stayed = _feed(a, 0, ids[pos:], 1)
    moved = _feed(b, 2, ids[pos:], 1)
    want = _ref_logp(tiny_net, ids)
    for p in stayed:
        np.testing.assert_allclose(moved[p], stayed[p], atol=1e-6)
        np.testing.assert_allclose(moved[p], want[p], atol=ATOL)
    # the header names the kind and the row's shape: a session over
    # another state size refuses the blob by the typed error
    other = _net(dict(TINY, mamba_d_state=8))
    with pytest.raises(KVLeaseVersionError, match="schema"):
        _session(other, slots=2).import_lease(blob, pos + 12)


def test_state_restarts_are_counted_from_the_positions_fed(tiny_net):
    sess = _session(tiny_net, slots=4)
    feed = lambda *n: sess._note_state(np.array(n, np.int32))
    feed(2, 0, 1, 0)
    assert sess.step_state_restarts == 0     # nobody had been there
    sess.slot_pos[:] = [2, 0, 0, 0]          # slot 2 was let again
    feed(1, 1, 1, 0)
    assert sess.step_state_restarts == 1     # slot 2; slot 1 is new
    sess.slot_pos[:] = 0
    feed(1, 1, 1, 1)
    assert sess.step_state_restarts == 3
    sess.reinit_states()
    feed(1, 1, 1, 1)
    assert sess.step_state_restarts == 0


def test_batcher_serves_the_hybrid_network_paged_and_ahead(tiny_net):
    """``kv_mode="auto"`` gives the network the paged session, not
    the dense fallback, with chunked prefill and the lookahead: the
    greedy ids of more requests than slots are the reference's at
    every position where its best leads by a margin, and the state
    counters exist and move."""
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
        cb.session.state_pool_bytes > 0
    assert not [k for k in snap if "kv_ring" in k]


def test_the_step_names_the_mixer_and_its_state(tiny_net, own_programs):
    """The paged step's ops carry the block's scopes, which the
    benchmark's ``ssm_time_pct.serve`` / ``ssm_state_time_pct.serve``
    read from the program's own table: ``ssm`` around the mixer,
    ``ssm/state`` around what lies between its two projections,
    ``mlp`` and ``attn/global`` as in the other blocks."""
    programs = own_programs
    sess = _session(tiny_net, slots=2, capacity=32)
    sess.bind(0, sess.reserve(_ids(5), 1))
    x = np.zeros((2, 2, 1), np.float32)
    sess.step_ids(x, np.array([2, 0], np.int32), np.zeros(2, bool))
    names = [op for _, op in programs.scope_tables()["paged_step_ids/t=2"]]
    under = lambda scope: [n for n in names if f"/{scope}/" in n]
    assert under("1_StateSpaceDecoderBlock/ssm/state")
    assert under("1_StateSpaceDecoderBlock/mlp")
    assert under("3_GroupedQueryDecoderBlock/attn/global")
    projections = [n for n in under("1_StateSpaceDecoderBlock/ssm")
                   if "/ssm/state/" not in n]
    assert any("dot_general" in n for n in projections)
    assert not [n for n in under("ssm/state") if "dot_general" in n
                and "StateSpaceDecoderBlock" not in n]


def test_lstm_carries_still_fall_back_to_the_dense_session():
    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.models.paged_kv import PagedSlotSession
    from deeplearning4j_tpu.nn.conf.layers import LSTM
    conf = (NeuralNetConfiguration.builder().set_seed(0).list()
            .layer(LSTM(n_in=4, n_out=6))
            .layer(RnnOutputLayer(n_out=3, loss="mcxent"))
            .set_input_type(InputType.recurrent(4, 8)).build())
    assert not PagedSlotSession.supports(MultiLayerNetwork(conf).init())


# ---- the new fields --------------------------------------------------

def test_every_new_field_round_trips_through_json(tiny_net):
    for layer in (
            Mamba2MixerLayer(n_in=24, n_heads=6, head_dim=4,
                             state_size=12, n_groups=3, conv_width=3,
                             eps=1e-6),
            StateSpaceDecoderBlock(n_in=24, eps=1e-6, n_heads=6,
                                   head_dim=4, state_size=12, n_groups=3,
                                   conv_width=3, intermediate_size=40,
                                   residual_multiplier=0.22),
            GroupedQueryDecoderBlock(n_in=16, softmax_scale=0.015625,
                                     residual_multiplier=0.22),
            GroupedQueryAttentionLayer(n_in=16, softmax_scale=0.015625),
            EmbeddingSequenceLayer(n_in=9, n_out=4, multiplier=12),
            RnnOutputLayer(n_in=4, n_out=9, logits_divisor=8)):
        again = layer_from_dict(json.loads(json.dumps(layer.to_dict())))
        assert again == layer and type(again) is type(layer)
    from deeplearning4j_tpu import MultiLayerConfiguration
    conf = tiny_net.conf
    assert MultiLayerConfiguration.from_json(
        conf.to_json()).to_json() == conf.to_json()


def test_the_defaults_are_the_layers_they_were():
    """A field left at its default is today's program: the scale is
    ``qk_head_dim ** -0.5``, a multiplier of 1 adds nothing to the
    trace, the embedding and the head neither multiply nor divide."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 16))
    plain = GroupedQueryAttentionLayer(n_in=16)
    scaled = GroupedQueryAttentionLayer(n_in=16, softmax_scale=8 ** -0.5)
    params, _ = plain.initialize(jax.random.PRNGKey(1),
                                 InputType.recurrent(16))
    np.testing.assert_array_equal(plain.apply(params, {}, x)[0],
                                  scaled.apply(params, {}, x)[0])
    block = GroupedQueryDecoderBlock(n_in=16)
    bp, _ = block.initialize(jax.random.PRNGKey(2),
                             InputType.recurrent(16))
    text = lambda b: jax.jit(lambda p, v: b.apply(p, {}, v)[0]).lower(
        bp, x).as_text()
    assert "0.22" not in text(block)
    assert text(GroupedQueryDecoderBlock(
        n_in=16, residual_multiplier=0.22)) != text(block)
    emb = EmbeddingSequenceLayer(n_in=9, n_out=4)
    ep, _ = emb.initialize(jax.random.PRNGKey(3), InputType.recurrent(9))
    ids = jnp.asarray([[1, 5, 8]])
    np.testing.assert_array_equal(emb.apply(ep, {}, ids)[0],
                                  ep["W"][ids])
    np.testing.assert_allclose(
        EmbeddingSequenceLayer(n_in=9, n_out=4, multiplier=12).apply(
            ep, {}, ids)[0], 12 * ep["W"][ids], rtol=1e-6)


def test_narrow_heads_read_by_table_with_their_own_scale(monkeypatch):
    """Heads of 64 with scores times 1/64. The grouped kernel scales
    by ``qk_head_dim ** -0.5`` itself, so the layer folds the ratio
    into the queries (1/64 over 1/8 is a power of two: exact in
    bfloat16); a row of its output is one value head, a whole lane
    tile, so where the kernel is to be had the pool keeps a value head
    128 wide, zeros behind the 64, and the layer cuts them off again.
    In Pallas' interpret mode against ``_attend`` over the gathered
    table of an unpadded pool."""
    from deeplearning4j_tpu.ops import paged_attention as PA
    layer = GroupedQueryAttentionLayer(
        n_in=64, n_heads=16, n_kv_heads=2, qk_head_dim=64, v_head_dim=64,
        softmax_scale=0.015625)
    bf16 = jnp.bfloat16
    with dtypes.policy_scope(dtypes.Policy(
            param_dtype=bf16, compute_dtype=bf16, output_dtype=bf16)):
        params, _ = layer.initialize(jax.random.PRNGKey(0),
                                     InputType.recurrent(64))
    slots, t, page = 2, 2, 16
    table = jnp.asarray(1 + np.arange(slots * 4).reshape(slots, 4),
                        jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(2), (slots, 40 + t, 64), bf16)

    def run(layer):
        """40 positions token by token, then a chunk of two."""
        pool = layer.zero_pool(slots * 4 + 1, page, bf16)
        for p in range(40):
            _, pool = layer.apply_stream_paged(
                params, pool, table, jnp.full((slots,), p, jnp.int32),
                x[:, p:p + 1])
        out, pool = layer.apply_stream_paged(
            params, pool, table, jnp.full((slots,), 40, jnp.int32),
            x[:, 40:], jnp.asarray([2, 1], jnp.int32))
        return np.asarray(out, np.float32), pool

    assert layer._value_lanes(page, bf16) == 64      # the CPU: no kernel
    assert not layer.paged_reads_by_table(page, t, bf16)
    want, plain = run(layer)
    assert plain["v"].shape[-1] == 2 * 64
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        PA, "pallas_paged_attention_grouped",
        functools.partial(PA.pallas_paged_attention_grouped,
                          interpret=True))
    assert layer._value_lanes(page, bf16) == 128
    assert layer.paged_reads_by_table(page, t, bf16)
    got, padded = run(layer)
    assert padded["v"].shape[-1] == 2 * 128
    tail = np.asarray(padded["v"], np.float32).reshape(-1, 2, 128)
    assert np.abs(tail[..., :64]).max() > 0 and not tail[..., 64:].any()
    valid = np.array([[True, True], [True, False]])
    np.testing.assert_allclose(got[valid], want[valid], atol=2e-2,
                               rtol=2e-2)
    # the scale is in it: the kernel's own alone is far off
    off, _ = run(GroupedQueryAttentionLayer(
        n_in=64, n_heads=16, n_kv_heads=2, qk_head_dim=64, v_head_dim=64))
    assert np.abs(off[valid] - want[valid]).max() > 5e-2
    # a window or a sink keeps the plain width: the kernel has neither
    assert GroupedQueryAttentionLayer(
        n_in=64, n_heads=16, n_kv_heads=2, qk_head_dim=64, v_head_dim=64,
        window=32)._value_lanes(page, bf16) == 64
    # a head that is whole lane tiles already is left alone
    assert GroupedQueryAttentionLayer(
        n_in=64, n_heads=16, n_kv_heads=2, qk_head_dim=64,
        v_head_dim=128)._value_lanes(page, bf16) == 128
