"""The continuous batcher one step ahead of the device.

Over a paged session a greedy step picks its ids on the device
(``PagedSlotSession.step_ids``), the next step is enqueued feeding
them where they lie, and the host fetches a step's ids while the next
one runs. What is held here:

- the session's entry point against the row-returning ones (ids,
  finite flags, ``use_prev``);
- the pipelined batcher's greedy ids, id for id, against the same
  batcher made to take every step synchronously, over mixed traffic
  on one schedule (chunk and single programs, a prefix resume, slots
  handed on while their last token is still on the device), and
  against a token-by-token session;
- a temperature request: the parent's tokens for its seed, and its
  emitting steps never counted as ahead;
- a poisoned step and a raised one, at the enqueue and at the fetch;
- prefill export, a migration offered mid-decode and a drain, each
  with a step in flight;
- ``serving_lookahead_steps_total`` against a scripted schedule;
- a pool that holds a second, wider chunk program: single, narrow and
  wide steps in one run, a decoding slot's id fed on the device across
  a change of width, and the counters against the schedule written
  out from counts.
"""

import threading

import numpy as np
import pytest

from deeplearning4j_tpu import (MultiLayerNetwork, NeuralNetConfiguration,
                                chaos)
from deeplearning4j_tpu.nn.conf import updaters
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (EmbeddingSequenceLayer,
                                               RnnOutputLayer,
                                               TransformerEncoderLayer)
from deeplearning4j_tpu.serving import ContinuousBatcher, continuous
from deeplearning4j_tpu.serving.continuous import MigrationOffer
from deeplearning4j_tpu.serving.metrics import ServingMetrics

pytestmark = pytest.mark.decode

V, CAP, PS, SLOTS, T = 13, 64, 4, 2, 4


@pytest.fixture(scope="module")
def net():
    conf = (NeuralNetConfiguration.builder().set_seed(3)
            .updater(updaters.adam(1e-3)).list()
            .layer(EmbeddingSequenceLayer(n_in=V, n_out=16))
            .layer(TransformerEncoderLayer(n_heads=2, causal=True))
            .layer(RnnOutputLayer(n_out=V, loss="mcxent"))
            .set_input_type(InputType.recurrent(V, CAP)).build())
    return MultiLayerNetwork(conf).init()


@pytest.fixture(autouse=True)
def four_tokens_a_chunk(monkeypatch):
    monkeypatch.setattr(continuous, "CHUNK_ROWS", SLOTS * T)
    monkeypatch.setattr(continuous, "WIDE_CHUNK_ROWS", SLOTS * T)


@pytest.fixture(autouse=True)
def clean_injector():
    yield
    chaos.uninstall()


def _prompt(n, seed):
    return [int(v) for v in
            np.random.default_rng([seed, n]).integers(1, V, n)]


def _session(net, slots=SLOTS):
    return net.paged_slot_streaming_session(capacity=CAP, slots=slots,
                                            page_size=PS)


def _token_by_token(net, prompt, n_tokens, sample=None):
    """One slot fed a token a step through ``step_slots``: the ids
    (greedy, or drawn by ``sample(row)``) and, per token, how far the
    runner-up's probability lay below the best."""
    sess = _session(net, slots=1)
    sess.bind(0, sess.reserve(prompt, n_tokens))
    out, gaps, feed = [], [], list(prompt)
    while len(out) < n_tokens:
        h = np.asarray(sess.step_slots(
            np.full((1, 1, 1), feed.pop(0), np.float32),
            np.ones(1, bool)))[0, 0]
        if not feed:
            top = np.sort(h)[-2:]
            out.append(int(h.argmax()) if sample is None else sample(h))
            gaps.append(float(top[1] - top[0]))
            feed.append(out[-1])
    return out, gaps


def _same_ids(got, want, gaps):
    """Greedy ids agree; where they part, the reference's own top two
    were a near-tie there (the chunk program sums in another order),
    and nothing after a parting is compared."""
    for g, w, gap in zip(got, want, gaps):
        if g != w:
            assert gap < 1e-5, (got, want, gap)
            return
    assert len(got) == len(want)


class Batcher:
    """A paged batcher whose worker waits at its first pass until
    ``go()``: everything submitted before is then admitted in one
    order, so two batchers given the same requests run the same
    schedule step for step. ``synchronous`` makes every step take the
    synchronous pass (the test's steering: the program has no such
    option)."""

    def __init__(self, net, name, synchronous=False, slots=SLOTS,
                 **kw):
        self.metrics = ServingMetrics()
        self.name = name
        self.cb = cb = ContinuousBatcher(
            net, slots=slots, capacity=CAP, page_size=PS,
            kv_mode=kw.pop("kv_mode", "paged"), metrics=self.metrics,
            name=name, **kw)
        self._gate = threading.Event()
        gather, plan = cb._gather_step, cb._plan_step

        def gated():
            assert self._gate.wait(60)
            return gather()

        def all_sync():
            st = plan()
            if st is not None:
                st.sync = True
            return st

        cb._gather_step = gated
        if synchronous:
            cb._plan_step = all_sync

    def go(self):
        self._gate.set()

    def run(self, requests):
        """Submit all, open the gate, wait for all: a list of id
        lists (or the exception a request ended with)."""
        handles = [self.cb.submit(p, n, **kw) for p, n, kw in requests]
        self.go()
        out = []
        for h in handles:
            try:
                out.append([int(t) for t in self.cb.wait(h)])
            except Exception as e:      # the tests compare these
                out.append(e)
        return out

    def count(self, metric, **labels):
        snap = self.metrics.registry.snapshot()
        key = metric + "{" + ",".join(
            f'{k}="{v}"' for k, v in dict(endpoint=self.name,
                                          **labels).items()) + "}"
        return snap[key]

    def steps(self):
        return (self.count("serving_steps_total", program="chunk")
                + self.count("serving_steps_total", program="single"))

    def ahead(self):
        return self.count("serving_lookahead_steps_total")

    def close(self, drain=True):
        self.go()
        return self.cb.shutdown(drain=drain)


# ---------------------------------------------------------------------------
# the session's entry point
# ---------------------------------------------------------------------------

def test_step_ids_are_the_rows_argmax_and_prev_ids_feed_the_next(net):
    rows, ids = _session(net), _session(net)
    prompts = [_prompt(6, 1), _prompt(3, 2)]
    for sess in (rows, ids):
        for i, p in enumerate(prompts):
            sess.bind(i, sess.reserve(p, 8))
    # a chunk step: slot 0 feeds 4 prompt tokens, slot 1 all its 3
    x = np.zeros((SLOTS, T, 1), np.float32)
    x[0, :4, 0], x[1, :3, 0] = prompts[0][:4], prompts[1]
    n_valid = np.array([4, 3], np.int32)
    h = np.asarray(rows.step_chunk(x, n_valid))
    got, finite = ids.step_ids(x, n_valid, np.zeros(SLOTS, bool))
    assert got.dtype == np.int32 and got.shape == (SLOTS,)
    np.testing.assert_array_equal(np.asarray(got), h[:, 0].argmax(-1))
    assert np.asarray(finite).all()
    # next: slot 0 feeds its last 2 prompt tokens from the host, slot
    # 1 the id the step before picked for it, which nobody fetched
    x = np.zeros((SLOTS, T, 1), np.float32)
    x[0, :2, 0] = prompts[0][4:]
    n_valid = np.array([2, 1], np.int32)
    x_rows = x.copy()
    x_rows[1, 0, 0] = h[1, 0].argmax()
    h = np.asarray(rows.step_chunk(x_rows, n_valid))
    x[1, 0, 0] = 7                      # ignored under use_prev
    got, _ = ids.step_ids(x, n_valid, np.array([False, True]))
    np.testing.assert_array_equal(np.asarray(got), h[:, 0].argmax(-1))
    # and the single-token program, both slots on the device's ids
    x1 = h[:, 0].argmax(-1).reshape(SLOTS, 1, 1).astype(np.float32)
    h = np.asarray(rows.step_slots(x1, np.ones(SLOTS, bool)))
    got, finite = ids.step_ids(np.zeros((SLOTS, 1, 1), np.float32),
                               np.ones(SLOTS, np.int32),
                               np.ones(SLOTS, bool))
    np.testing.assert_array_equal(np.asarray(got), h[:, 0].argmax(-1))
    assert np.asarray(finite).all()
    np.testing.assert_array_equal(ids.slot_pos, rows.slot_pos)
    assert ids.step_kv_positions == rows.step_kv_positions


def test_step_ids_flags_a_row_that_is_not_finite(net):
    import jax
    sess = _session(net)
    sess.bind(0, sess.reserve([1, 2], 4))
    sess.bind(1, sess.reserve([3], 4))
    params = net.params
    try:
        # NaN in the embedding row of token 3 alone: slot 1's row
        leaves, tree = jax.tree_util.tree_flatten(params[0])
        emb = max(leaves, key=lambda a: a.size)
        bad = [a.at[3].set(np.nan) if a is emb else a for a in leaves]
        net.params = [jax.tree_util.tree_unflatten(tree, bad)] \
            + list(params[1:])
        x = np.array([1, 3], np.float32).reshape(SLOTS, 1, 1)
        _, finite = sess.step_ids(x, np.ones(SLOTS, np.int32),
                                  np.zeros(SLOTS, bool))
    finally:
        net.params = params
    assert np.asarray(finite).tolist() == [True, False]


def test_step_ids_refuses_what_step_chunk_refuses(net):
    sess = _session(net)
    none = np.zeros(SLOTS, bool)
    with pytest.raises(ValueError, match="n_valid must lie"):
        sess.step_ids(np.zeros((SLOTS, 1, 1), np.float32),
                      np.array([2, 0]), none)
    with pytest.raises(ValueError, match="want"):
        sess.step_ids(np.zeros((SLOTS, 1, 2), np.float32),
                      np.array([1, 0]), none)
    sess.bind(0, sess.reserve([1] * (CAP - 2), 2))
    sess.slot_pos[0] = CAP - 1
    with pytest.raises(ValueError, match="slot overflow"):
        sess.step_ids(np.zeros((SLOTS, T, 1), np.float32),
                      np.array([2, 0]), none)


# ---------------------------------------------------------------------------
# (a) greedy ids, pipelined against synchronous
# ---------------------------------------------------------------------------

MIXED = [(1, 5), (9, 3), (17, 4), (2, 1), (12, 6), (5, 2), (30, 7),
         (17, 3), (3, 5)]


def _mixed_requests():
    reqs = [(_prompt(n, k), n_tokens, {})
            for k, (n, n_tokens) in enumerate(MIXED)]
    # the eighth repeats the third's prompt: by the time a slot is
    # free for it the third has finished and left its 4 full pages
    reqs[7] = (reqs[2][0], MIXED[7][1], {})
    return reqs


def test_greedy_ids_equal_the_synchronous_pass(net):
    runs = {}
    for mode in ("ahead", "sync"):
        b = Batcher(net, mode, synchronous=mode == "sync")
        try:
            got = b.run(_mixed_requests())
            hits = b.cb._prefix_hits.value
        finally:
            assert b.close()
        runs[mode] = (got, hits, b.steps(),
                      b.count("serving_steps_total", program="chunk"),
                      b.ahead())
    got, hits, steps, chunks, ahead = runs["ahead"]
    # one schedule: the same steps of the same programs, the same
    # prefix resume, and the same ids
    assert runs["sync"][:4] == (got, hits, steps, chunks)
    assert hits >= 1 and 0 < chunks < steps
    # nine requests through two slots: seven slots were handed on at
    # the scheduling of a step whose ids were still on the device, and
    # the pool never ran empty
    assert ahead == steps - 1 and runs["sync"][4] == 0
    for (p, n, _), g in zip(_mixed_requests(), got):
        _same_ids(g, *_token_by_token(net, p, n))


# ---------------------------------------------------------------------------
# (b) a request with a temperature
# ---------------------------------------------------------------------------

def _draw(temperature, seed):
    """``ContinuousBatcher._sample``'s draw, stream and all."""
    class _S:
        pass
    s = _S()
    s.req = _S()
    s.req.temperature = temperature
    s.rng = np.random.default_rng(seed)
    return lambda row: ContinuousBatcher._sample(row, s)


def test_a_temperature_stream_is_the_parents_and_never_ahead(net):
    """Alone in the pool and from a one-token prompt every step is the
    single-token program, the hand-fed session's own: the tokens are
    the parent's for the seed, bit for bit. None of its steps emits
    ahead; from a prompt of 9 the one step that is ahead is the second
    chunk, which emits nothing."""
    want, _ = _token_by_token(net, [5], 8, sample=_draw(0.9, 11))
    b = Batcher(net, "temp")
    try:
        got = b.run([([5], 8, {"temperature": 0.9, "seed": 11})])[0]
        assert got == want
        assert (b.steps(), b.ahead()) == (8, 0)
        b.cb.generate(_prompt(9, 4), 5, temperature=0.9, seed=2)
        # chunks of 4 and 4, the tail of 1 with the first token, four
        # more: 7 steps, of which only the second chunk was ahead
        assert (b.steps(), b.ahead()) == (8 + 7, 1)
    finally:
        assert b.close()


def test_a_temperature_request_among_greedy_ones(net):
    reqs = _mixed_requests()
    reqs[1] = (reqs[1][0], 6, {"temperature": 0.7, "seed": 5})
    reqs[6] = (reqs[6][0], 4, {"temperature": 1.3, "seed": 9})
    runs = {}
    for mode in ("ahead", "sync"):
        b = Batcher(net, mode, synchronous=mode == "sync")
        try:
            runs[mode] = (b.run(reqs), b.steps(), b.ahead())
        finally:
            assert b.close()
    assert runs["ahead"][:2] == runs["sync"][:2]
    _, steps, ahead = runs["ahead"]
    # the 10 steps the two emit in are synchronous, and so nothing is
    # in flight at the step after each; the rest ran ahead
    assert 0 < ahead <= steps - 10


# ---------------------------------------------------------------------------
# (c) faults
# ---------------------------------------------------------------------------

def test_a_poisoned_step_fails_each_emitter_alone(net):
    """Step 3 is poisoned: both requests emit in it (a 9-token prompt
    ends there, a 1-token prompt's third token) and both fail with the
    non-finite error, though step 4 was already enqueued behind it.
    The worker survives and serves the next request."""
    chaos.install({"faults": [{"site": "serving.worker.step",
                               "kind": "poison", "at": [3]}]}, seed=1)
    b = Batcher(net, "poison")
    try:
        got = b.run([(_prompt(9, 1), 4, {}), ([7], 6, {})])
        for g in got:
            assert isinstance(g, ValueError) and "non-finite" in str(g)
        assert b.cb.active_slots() == 0
        assert b.cb.session.pages_in_use() == 0
        p = _prompt(6, 2)
        after = [int(t) for t in b.cb.generate(p, 5)]
        _same_ids(after, *_token_by_token(net, p, 5))
    finally:
        assert b.close()


def test_a_poisoned_last_step_flushes_the_prefix_it_donated(net):
    """The stream's last token is scheduled, its slot recycled and its
    prompt's pages donated before that step's flags are on the host:
    when they say the row was not finite the request fails and nothing
    it wrote stays in the prefix cache."""
    chaos.install({"faults": [{"site": "serving.worker.step",
                               "kind": "poison", "at": [4]}]}, seed=1)
    b = Batcher(net, "poison_last")
    try:
        # chunks of 4 and 4, then three single steps: the fourth step
        # emits the second of two tokens
        got = b.run([(_prompt(9, 1), 2, {}), ([7], 12, {})])
        assert isinstance(got[0], ValueError)
        assert len(b.cb.session.prefix_cache) == 0
        assert isinstance(got[1], ValueError)      # it emitted there too
    finally:
        assert b.close()


@pytest.mark.parametrize("at", ["enqueue", "fetch"])
def test_a_raised_device_step_fails_both_steps_in_flight(net, at):
    """[7] wants 2 tokens: step 2 schedules its last, its slot is
    recycled (and handed to the third request) while that token is
    still on the device. Then the device fails: at the enqueue of
    step 3, or at the fetch of step 2 behind it. All three requests
    get the error, none hangs, the pools are rebuilt and the next
    request is served from them."""
    b = Batcher(net, "raise")
    cb, sess = b.cb, b.cb.session
    calls = {"step": 0, "fetch": 0, "reinit": 0}
    step_ids, fetch, reinit = sess.step_ids, cb._fetch, sess.reinit_states

    def failing_step(*a):
        calls["step"] += 1
        if at == "enqueue" and calls["step"] == 3 + 2:   # 2: the warm-up
            raise RuntimeError("device step failed")
        return step_ids(*a)

    def failing_fetch(st):
        calls["fetch"] += 1
        if at == "fetch" and calls["fetch"] == 2:
            raise RuntimeError("device step failed")
        return fetch(st)

    def counted_reinit():
        calls["reinit"] += 1
        return reinit()

    sess.step_ids, cb._fetch = failing_step, failing_fetch
    sess.reinit_states = counted_reinit
    try:
        got = b.run([([7], 2, {}), (_prompt(6, 1), 9, {}),
                     (_prompt(3, 2), 4, {})])
        for g in got:
            assert isinstance(g, RuntimeError), got
        assert calls["reinit"] == 1
        assert cb.active_slots() == 0 and sess.pages_in_use() == 0
        assert cb._inflight is None
        p = _prompt(10, 3)
        after = [int(t) for t in cb.generate(p, 6)]
        _same_ids(after, *_token_by_token(net, p, 6))
    finally:
        assert b.close()


def test_a_worker_crash_reaches_a_stream_that_left_its_slot(net):
    """A crash at the top of step 3: [7]'s last token was scheduled by
    step 2 and is still on the device, its slot already recycled. It
    dies with the crash like the slotted streams; the loop restarts."""
    chaos.install({"faults": [{"site": "serving.worker.step",
                               "kind": "crash", "at": [3]}]}, seed=1)
    b = Batcher(net, "crash")
    try:
        got = b.run([([7], 2, {}), (_prompt(6, 1), 9, {})])
        for g in got:
            assert isinstance(g, chaos.SimulatedCrashError), got
        assert b.cb.session.pages_in_use() == 0
        assert len(b.cb.generate([3, 4], 3)) == 3
    finally:
        assert b.close()


# ---------------------------------------------------------------------------
# (d) export, migration and drain with a step in flight
# ---------------------------------------------------------------------------

def test_a_prefill_export_beside_streams_in_flight(net):
    long, prompt = _prompt(5, 8), _prompt(14, 9)
    a, b = Batcher(net, "exp"), Batcher(net, "imp")
    b.go()
    try:
        decoding = a.cb.submit(long, 30)
        export = a.cb.submit(prompt, 6, prefill_export=True)
        a.go()
        blob = a.cb.wait(export)
        assert isinstance(blob, bytes)
        got = [int(t) for t in b.cb.wait(b.cb.import_stream(blob))]
        beside = [int(t) for t in a.cb.wait(decoding)]
        # the export point is a synchronous step; around it the pool
        # ran ahead
        assert 0 < a.ahead() < a.steps()
    finally:
        assert a.close() and b.close()
    _same_ids(got, *_token_by_token(net, prompt, 6))
    _same_ids(beside, *_token_by_token(net, long, 30))


@pytest.mark.parametrize("finish_on", ["survivor", "incumbent"])
def test_a_migration_offered_mid_decode_resumes(net, finish_on):
    prompt = _prompt(6, 5)
    want, gaps = _token_by_token(net, prompt, 12)
    a, b = Batcher(net, "old"), Batcher(net, "new")
    b.go()
    try:
        # arm the drain from inside the worker, at the enqueue of the
        # step that schedules the fifth token: the offer has to wait
        # for that step's ids
        step_ids, calls = a.cb.session.step_ids, []

        def counted(x, n_valid, use_prev):
            out = step_ids(x, n_valid, use_prev)
            if int(np.sum(n_valid)):
                calls.append(bool(use_prev.any()))
                if len(calls) == 2 + 4:     # two chunks, then singles
                    a.cb.request_migration()
            return out

        a.cb.session.step_ids = counted
        h = a.cb.submit(prompt, 12)
        a.go()
        offer = a.cb.wait(h)
        assert isinstance(offer, MigrationOffer)
        assert offer.tokens_out == 5 and offer.pos == len(prompt) + 4
        # the armed step itself fed the device's own id
        assert calls[:6] == [False, False, True, True, True, True]
        if finish_on == "survivor":
            got = [int(t) for t in
                   b.cb.wait(b.cb.import_stream(offer.blob))]
            assert a.cb.ack_migration(offer.handle)
        else:
            got = [int(t) for t in a.cb.resume_stream(offer.handle)]
    finally:
        assert a.close() and b.close()
    _same_ids(got, want, gaps)
    assert len(got) == 12


def test_a_drain_collects_the_step_in_flight(net):
    reqs = _mixed_requests()[:5]
    b = Batcher(net, "drain")
    cb = b.cb
    armed, step_ids, n = threading.Event(), cb.session.step_ids, [0]

    def counted(x, n_valid, use_prev):
        n[0] += 1
        if n[0] == 2 + 6:
            cb._draining.set()          # what drain() does first
            armed.set()
        return step_ids(x, n_valid, use_prev)

    cb.session.step_ids = counted
    try:
        handles = [cb.submit(p, k) for p, k, _ in reqs]
        b.go()
        assert armed.wait(60)
        ahead_at_drain = b.ahead()
        got = [[int(t) for t in cb.wait(h)] for h in handles]
        # nothing runs ahead under a drain
        assert b.ahead() <= ahead_at_drain + 1 < b.steps() - 1
    finally:
        assert b.close()
    for (p, k, _), g in zip(reqs, got):
        _same_ids(g, *_token_by_token(net, p, k))


# ---------------------------------------------------------------------------
# (e) the counter against a scripted schedule
# ---------------------------------------------------------------------------

def test_lookahead_steps_follow_the_schedule(net):
    b = Batcher(net, "count")
    b.go()
    try:
        # alone: ceil(9 / 4) steps to the first token, 4 more; the
        # first finds nothing in flight
        b.cb.generate(_prompt(9, 1), 5)
        assert (b.steps(), b.ahead()) == (7, 6)
        # the pool ran empty in between: the chain starts again
        b.cb.generate(_prompt(2, 2), 3)
        assert (b.steps(), b.ahead()) == (7 + 3, 6 + 2)
    finally:
        assert b.close()


def test_the_dense_session_never_runs_ahead(net):
    b = Batcher(net, "dense", kv_mode="dense")
    try:
        got = b.run([(_prompt(5, 1), 4, {}), (_prompt(2, 2), 6, {})])
        assert not b.cb._paged and b.steps() > 0 and b.ahead() == 0
    finally:
        assert b.close()
    for (p, k), g in zip(((_prompt(5, 1), 4), (_prompt(2, 2), 6)), got):
        _same_ids(g, *_token_by_token(net, p, k))


# ---------------------------------------------------------------------------
# (f) a pool with a second, wider chunk program
# ---------------------------------------------------------------------------

WIDE_SLOTS, T_LO, T_HI = 16, 2, 4

# (prompt tokens, output tokens): bursts of long prompts fill the wide
# step, their tails and the short ones run the narrow one, and in
# the end the pool only decodes
ALTERNATING = ([(30, 3)] * 7 + [(1, 12), (2, 9), (5, 14), (3, 20)]
               + [(9, 2), (14, 5)] * 3 + [(27, 4)] * 8 + [(1, 3), (6, 6)]
               + [(22, 1)] * 6 + [(4, 18), (11, 13)])


def _schedule(sizes, slots, t_lo, t_hi):
    """The batcher's counters from counts alone: requests admitted in
    order into the lowest free slots, one step a pass; a slot holds
    [prompt tokens not yet fed, tokens still to emit]."""
    pending, pool = [list(s) for s in sizes], [None] * slots
    c = dict(single=0, chunk=0, wide=0, prompt=0, decode=0,
             prompt_tokens=0)
    while pending or any(pool):
        for i in range(slots):
            if pool[i] is None and pending:
                pool[i] = pending.pop(0)
        live = [s for s in pool if s]
        need = [max(1, s[0]) for s in live]
        rows = 1
        if any(s[0] > 1 for s in live):
            offered = sum(min(t_hi, n) for n in need)
            rows = t_hi if offered >= slots * t_lo else t_lo
        c["single" if rows == 1 else "chunk"] += 1
        c["wide"] += rows == t_hi
        for i, s in enumerate(pool):
            if not s:
                continue
            n = min(rows, max(1, s[0]))
            c["prompt_tokens"] += n if s[0] else 0
            if s[0] > n:
                s[0] -= n
                c["prompt"] += 1
                continue
            s[0] = 0
            s[1] -= 1
            c["decode"] += 1
            if not s[1]:
                pool[i] = None
    return c


def test_the_schedule_written_out_is_the_narrow_batchers():
    """``_schedule`` against counts derived by hand: alone, a prompt of
    9 at t 4 takes chunks of 4 and 4 and a single step, then 4 more
    steps (``test_lookahead_steps_follow_the_schedule``'s request)."""
    assert _schedule([(9, 5)], 4, 4, 8) == dict(
        single=5, chunk=2, wide=0, prompt=2, decode=5, prompt_tokens=9)
    # eight of sixteen slots in prefill fill the wide step
    c = _schedule([(9, 1)] * 8, 16, 2, 4)
    assert (c["chunk"], c["wide"], c["single"]) == (2, 2, 1)


def test_widths_alternate_and_the_ids_are_the_token_by_token_ids(
        net, monkeypatch):
    monkeypatch.setattr(continuous, "CHUNK_ROWS", WIDE_SLOTS * T_LO)
    monkeypatch.setattr(continuous, "WIDE_CHUNK_ROWS", WIDE_SLOTS * T_HI)
    reqs = [(_prompt(n, 100 + k), n_tokens, {})
            for k, (n, n_tokens) in enumerate(ALTERNATING)]
    runs = {}
    for mode in ("ahead", "sync"):
        b = Batcher(net, mode, synchronous=mode == "sync",
                    slots=WIDE_SLOTS, queue_limit=256)
        try:
            assert (b.cb._chunk_t, b.cb._wide_t) == (T_LO, T_HI)
            got = b.run(reqs)
            assert b.cb._prefix_hits.value == 0
        finally:
            assert b.close()
        runs[mode] = (got, dict(
            single=b.count("serving_steps_total", program="single"),
            chunk=b.count("serving_steps_total", program="chunk"),
            wide=b.count("serving_wide_steps_total"),
            prompt=b.count("serving_slot_steps_total", kind="prompt"),
            decode=b.count("serving_slot_steps_total", kind="decode"),
            prompt_tokens=b.count("serving_prompt_tokens_total")),
            b.ahead(), b.steps())
    got, counts, ahead, steps = runs["ahead"]
    want = _schedule(ALTERNATING, WIDE_SLOTS, T_LO, T_HI)
    # all three programs ran, a wide step counts as a chunk step, and
    # the counters are the schedule's
    assert 0 < want["wide"] < want["chunk"] and want["single"] > 0
    assert counts == want and runs["sync"][:2] == (got, counts)
    # the pool never ran empty: every step but the first was enqueued
    # behind one still on the device, whatever the two steps' widths
    assert ahead == steps - 1 and runs["sync"][2] == 0
    for (p, n, _), g in zip(reqs, got):
        _same_ids(g, *_token_by_token(net, p, n))
