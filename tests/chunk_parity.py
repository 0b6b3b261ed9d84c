"""The chunk step of a paged session against the same tokens fed one
by one: what ``tests/test_decode_paged.py`` (key/value pool) and
``tests/test_latent_moe.py`` (latent pool, expert counts) run as cases
of one parametrised test.

Two sessions over one network get the same leases; ``feed_both`` gives
``chunked`` one ``step_chunk`` call and ``single`` the same tokens
through ``step_slots``, one call a token, and holds the first to the
second: the written positions and each slot's last valid row to float
tolerance, every other position of every leased page bit for bit to
what it held before the call (the rows past ``n_valid`` may alter
nothing that lives), ``slot_pos``, and an expert layer's counts to the
one-by-one counts summed.

A layer that keeps a RING of pages a slot (a sliding window: the
session's ``_ring``) is read where its rows live: the slot's own ring
pages, position ``p`` at ring row ``p mod span``. Such a network's
cases run at a page of 8 (``run_case(..., page=8)``), where a ring has
room for the T rows of a chunk, and without ``prefix_resume``: a ring
is not shared. A layer that keeps a fixed-size STATE in a row a slot
(the session's ``_state``: a short convolution's window) has no
positions: its row after the chunk is the row the one-by-one steps
leave, and the row of a slot the call did not feed is bit for bit what
it was. ``run_case(..., t=4)`` runs the same cases at a chunk of 4 rows
(the batcher's wide program of a pool of 64 slots).

``latent_by_table`` steers a latent layer's step onto the by-table
kernel, as the chip takes it: the shapes' predicate forced and the
kernel in Pallas' interpret mode, for the same cases again; and
``kv_positions_follow_the_dispatch`` is the one test both files make
of the session's accounting under either answer."""

import functools

import jax
import numpy as np

CASES = ("ragged", "prefix_resume", "near_capacity")

SLOTS, CAPACITY, PAGE, T = 4, 32, 4, 8


def latent_by_table(monkeypatch, holds=True):
    """``LatentAttentionLayer``'s shape predicate forced, and the
    latent kernel in interpret mode for the CPU."""
    from deeplearning4j_tpu.ops import paged_attention as PA
    monkeypatch.setattr(PA, "latent_reads_by_table", lambda *a: holds)
    monkeypatch.setattr(
        PA, "pallas_paged_attention_latent",
        functools.partial(PA.pallas_paged_attention_latent,
                          interpret=True))


def kv_positions_follow_the_dispatch(net, monkeypatch, by_table):
    """The session's accounting asks the blocks of ``net``, which ask
    their latent attention: by table a step reads each slot's pages up
    to its length, by the gather its table's whole span; and the
    batcher's counters carry what the session says."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.serving.metrics import (BatcherStepMetrics,
                                                    ServingMetrics)
    latent_by_table(monkeypatch, by_table)
    blocks = [layer for layer in net.layers
              if hasattr(layer, "apply_stream_paged")]
    assert len(blocks) >= 2
    assert all(b.paged_reads_by_table(PAGE, 2, jnp.float32) == by_table
               for b in blocks)
    sess = net.paged_slot_streaming_session(capacity=CAPACITY, slots=SLOTS,
                                            page_size=PAGE)
    prompt = [int(v) for v in np.random.default_rng(6).integers(1, 96, 9)]
    sess.bind(1, sess.reserve(prompt, 4))
    x = np.zeros((SLOTS, T, 1), np.float32)
    x[1, :, 0] = prompt[:T]
    sess.step_chunk(x, np.array([0, T, 0, 0], np.int32))
    spanned = SLOTS * CAPACITY
    # slot 1 holds two pages, the free slots none
    assert sess.step_kv_positions == (8 if by_table else spanned, spanned)
    x = np.zeros((SLOTS, 1, 1), np.float32)
    x[1, 0, 0] = prompt[T]
    sess.step_slots(x, np.array([False, True, False, False]))
    # three pages; the free slots' dummy row fetches the scratch page
    # their tables name
    read = (12 + 3 * PAGE) if by_table else spanned
    assert sess.step_kv_positions == (read, spanned)
    metrics = ServingMetrics()
    BatcherStepMetrics(metrics.registry, "e").record_kv_positions(
        *sess.step_kv_positions)
    snap = metrics.registry.snapshot()
    assert snap['serving_kv_positions_read_total{endpoint="e"}'] == read
    assert snap['serving_kv_positions_spanned_total{endpoint="e"}'] == \
        spanned


def sessions(net, page=PAGE):
    return tuple(net.paged_slot_streaming_session(
        capacity=CAPACITY, slots=SLOTS, page_size=page)
        for _ in range(2))


def pages_of(sess, pages):
    """Per leaf of the pools in the allocator's pages, the contents
    of ``pages``: (len(pages), page size, ...) arrays."""
    return [np.asarray(leaf)[np.asarray(pages)]
            for pool, ring in zip(sess._pools, sess._ring)
            if pool is not None and not ring
            for leaf in jax.tree_util.tree_leaves(pool)]


def leaf_rings(sess):
    """Per pool leaf, in ``live_rows``' order, the pages of the ring
    a slot owns there (0: the leaf lives in the allocator's pages)."""
    return [ring for pool, ring, state in zip(sess._pools, sess._ring,
                                              sess._state)
            if pool is not None and not state
            for _ in jax.tree_util.tree_leaves(pool)]


def state_rows(sess, slot):
    """The slot's row of every leaf of the slot-owned state pools."""
    return [np.asarray(leaf)[slot]
            for pool, state in zip(sess._pools, sess._state) if state
            for leaf in jax.tree_util.tree_leaves(pool)]


def live_rows(sess, slot):
    """The slot's cache rows: per pool leaf a (positions held, ...)
    array: its leased pages in table order or, for a layer that keeps
    a ring, the slot's ring, position p at row p mod its length."""
    out = []
    for pool, ring, state in zip(sess._pools, sess._ring, sess._state):
        if pool is None or state:
            continue
        pages = (1 + slot * ring + np.arange(ring) if ring
                 else np.asarray(sess._leases[slot].pages))
        for leaf in jax.tree_util.tree_leaves(pool):
            rows = np.asarray(leaf)[pages]
            out.append(rows.reshape((-1,) + rows.shape[2:]))
    return out


def feed_single(sess, tokens):
    """``tokens``: {slot: [ids]} through ``step_slots``, a call a
    token. Returns ({slot: output at its last token}, counts summed
    or None)."""
    last, counts = {}, None
    for j in range(max(len(v) for v in tokens.values())):
        x = np.zeros((sess.slots, 1, 1), np.float32)
        active = np.zeros((sess.slots,), bool)
        for slot, ids in tokens.items():
            if j < len(ids):
                x[slot, 0, 0], active[slot] = ids[j], True
        h = np.asarray(sess.step_slots(x, active))
        for slot in np.flatnonzero(active):
            last[int(slot)] = h[slot, 0]
        if sess.step_aux is not None:
            # an array, or a dict of arrays (a layer with zero
            # experts): summed leaf by leaf
            aux = jax.device_get(sess.step_aux)
            counts = aux if counts is None else jax.tree_util.tree_map(
                np.add, counts, aux)
    return last, counts


def feed_both(chunked, single, tokens, atol=1e-5, t=T):
    """One ``step_chunk`` of width ``t`` on ``chunked``, the same
    tokens one by one on ``single``, and every comparison the module's
    text names. Returns the chunk's (slots, 1, V) output."""
    x = np.zeros((SLOTS, t, 1), np.float32)
    n_valid = np.zeros((SLOTS,), np.int32)
    for slot, ids in tokens.items():
        x[slot, :len(ids), 0], n_valid[slot] = ids, len(ids)
    before = {slot: live_rows(chunked, slot)
              for slot in chunked._leases}
    rows_before = {slot: state_rows(chunked, slot)
                   for slot in chunked._leases}
    pos0 = chunked.slot_pos.copy()
    h = np.asarray(chunked.step_chunk(x, n_valid))
    assert h.shape[:2] == (SLOTS, 1)
    last, counts = feed_single(single, tokens)
    np.testing.assert_array_equal(chunked.slot_pos, single.slot_pos)
    np.testing.assert_array_equal(chunked.slot_pos, pos0 + n_valid)
    for slot, ids in tokens.items():
        np.testing.assert_allclose(h[slot, 0], last[slot], atol=atol)
    for slot, was in before.items():
        lo, hi = int(pos0[slot]), int(pos0[slot] + n_valid[slot])
        now, want = live_rows(chunked, slot), live_rows(single, slot)
        for a, b, w, ring in zip(now, was, want, leaf_rings(chunked)):
            span = a.shape[0]
            written = np.zeros((span,), bool)
            written[np.arange(lo, hi) % span if ring
                    else slice(lo, hi)] = True
            np.testing.assert_array_equal(a[~written], b[~written])
            # every position a later step may read
            read = np.arange(max(0, hi - span) if ring else 0, hi) % span
            np.testing.assert_allclose(a[read], w[read], atol=atol)
    for slot, was in rows_before.items():
        for a, b, w in zip(state_rows(chunked, slot), was,
                           state_rows(single, slot)):
            if n_valid[slot]:
                np.testing.assert_allclose(a, w, atol=atol)
            else:
                np.testing.assert_array_equal(a, b)
    if counts is not None:
        jax.tree_util.tree_map(np.testing.assert_array_equal,
                               jax.device_get(chunked.step_aux), counts)
    return h


def run_case(net, vocab, case, page=PAGE, t=T):
    rng = np.random.default_rng(sum(map(ord, case)))
    ids = lambda n: [int(v) for v in rng.integers(1, vocab, n)]
    chunked, single = sessions(net, page)
    both = functools.partial(feed_both, chunked, single, t=t)

    def bind(slot, prompt, n_tokens):
        for s in (chunked, single):
            s.bind(slot, s.reserve(prompt, n_tokens))

    if case == "ragged":
        # a slot with t rows, one with fewer, a decode slot with 1, a
        # free slot with 0, in one call
        long, short, old = ids(t + 3), ids(3), ids(5)
        bind(0, long, 4)
        bind(1, short, 4)
        bind(3, old, 4)
        for s in (chunked, single):
            feed_single(s, {3: old})
        both({0: long[:t], 1: short, 3: ids(1)})
        # and again: the long prompt's ragged tail beside two decodes
        both({0: long[t:], 1: ids(1), 3: ids(1)})
    elif case == "prefix_resume":
        # a slot that starts at a prefix hit: its first two pages are
        # the cache's too, and stay as they were
        first = ids(11)
        bind(0, first, 2)
        for s in (chunked, single):
            feed_single(s, {0: first})
            s.release(0, register_prompt=first)
        again = first[:2 * page] + ids(min(5, t))
        bind(2, again, 4)
        lease = chunked._leases[2]
        assert lease.resume_pos == 2 * page
        shared = lease.pages[:2]
        assert all(chunked.allocator.refcount(p) > 1 for p in shared)
        was = pages_of(chunked, shared)
        both({2: again[2 * page:]})
        for a, b in zip(pages_of(chunked, shared), was):
            np.testing.assert_array_equal(a, b)
        assert all(chunked.allocator.refcount(p) > 1 for p in shared)
    elif case == "near_capacity":
        # a slot whose table is full to its width, within t tokens of
        # capacity: the rows past n_valid would run off the table,
        # where a clamped lookup lands in its last live page
        full, other = ids(CAPACITY - 2), ids(6)
        bind(0, full, 2)
        bind(1, other, 4)
        assert len(chunked._leases[0].pages) == chunked.pages_per_slot
        for lo in range(0, 24, t):
            both({0: full[lo:lo + t]})
        k = min(5, t)
        both({0: full[24:24 + k], 1: other[:t]})
        both({0: full[24 + k:], 1: other[t:] or ids(1)})
        both({0: ids(1), 1: ids(1)})
        both({0: ids(1)})
        assert int(chunked.slot_pos[0]) == CAPACITY
