"""Chunked prefill in the continuous batcher: a slot in prefill feeds
up to t prompt tokens a device step through the paged session's chunk
program; a pool that only decodes runs the single-token program.

What is held here: the greedy ids of a token-by-token session under
mixed traffic; ``ceil(n / t)`` steps from a prompt of n to its first
token; the step counters against the schedule; a prefill export that
stops one token short and imports to the same ids; a migration offered
mid-prefill that resumes, on a survivor or on the incumbent, to the
same ids. The session's own parity is in tests/chunk_parity.py."""

import functools

import numpy as np
import pytest

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.models.paged_kv import parse_lease
from deeplearning4j_tpu.nn.conf import updaters
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (EmbeddingSequenceLayer,
                                               LSTM, RnnOutputLayer,
                                               TransformerEncoderLayer)
from deeplearning4j_tpu.serving import ContinuousBatcher, continuous
from deeplearning4j_tpu.serving.continuous import (MigrationOffer,
                                                   chunk_width)
from deeplearning4j_tpu.serving.metrics import ServingMetrics

pytestmark = pytest.mark.decode

V, CAP, PS, SLOTS, T = 13, 64, 4, 4, 4


@pytest.fixture(scope="module")
def net():
    conf = (NeuralNetConfiguration.builder().set_seed(0)
            .updater(updaters.adam(1e-3)).list()
            .layer(EmbeddingSequenceLayer(n_in=V, n_out=16))
            .layer(TransformerEncoderLayer(n_heads=2, causal=True))
            .layer(TransformerEncoderLayer(n_heads=2, causal=True))
            .layer(RnnOutputLayer(n_out=V, loss="mcxent"))
            .set_input_type(InputType.recurrent(V, CAP)).build())
    return MultiLayerNetwork(conf).init()


@pytest.fixture
def four_rows(monkeypatch):
    """A row budget that gives 4 slots a chunk of 4 tokens."""
    monkeypatch.setattr(continuous, "CHUNK_ROWS", SLOTS * T)


def _prompt(n, seed):
    return [int(v) for v in
            np.random.default_rng([seed, n]).integers(1, V, n)]


def _token_by_token(net, prompt, n_tokens):
    """Greedy ids and, per emitted token, how far the runner-up's
    probability lay below it: a session of one slot fed a token a
    step."""
    sess = net.paged_slot_streaming_session(capacity=CAP, slots=1,
                                            page_size=PS)
    sess.bind(0, sess.reserve(prompt, n_tokens))
    out, gaps, feed = [], [], list(prompt)
    while len(out) < n_tokens:
        h = np.asarray(sess.step_slots(
            np.full((1, 1, 1), feed.pop(0), np.float32),
            np.ones(1, bool)))[0, 0]
        if not feed:
            top = np.sort(h)[-2:]
            out.append(int(h.argmax()))
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


def _batcher(net, name, **kw):
    metrics = ServingMetrics()
    cb = ContinuousBatcher(net, slots=SLOTS, capacity=CAP,
                           kv_mode=kw.pop("kv_mode", "paged"),
                           page_size=PS, metrics=metrics, name=name,
                           **kw)
    return cb, metrics


def _counts(metrics, name):
    snap = metrics.registry.snapshot()
    key = lambda metric, **lb: metric + "{" + ",".join(
        f'{k}="{v}"' for k, v in dict(endpoint=name, **lb).items()) + "}"
    return {"chunk": snap[key("serving_steps_total", program="chunk")],
            "single": snap[key("serving_steps_total",
                               program="single")],
            "prompt": snap[key("serving_slot_steps_total",
                               kind="prompt")],
            "decode": snap[key("serving_slot_steps_total",
                               kind="decode")],
            "prompt_tokens": snap[key("serving_prompt_tokens_total")]}


def test_the_width_follows_the_pool():
    assert continuous.CHUNK_ROWS == 128
    assert chunk_width(8, 1024) == 16 and chunk_width(64, 1024) == 2
    assert chunk_width(4, 1024) == 32 and chunk_width(128, 1024) == 1
    assert chunk_width(1, 1024) == 128 and chunk_width(2, 16) == 16


def test_mixed_requests_give_the_token_by_token_ids(net, four_rows):
    sizes = [(1, 5), (2, 3), (4, 6), (5, 2), (9, 7), (17, 4), (30, 9),
             (3, 1), (12, 5)]
    prompts = [_prompt(n, k) for k, (n, _) in enumerate(sizes)]
    cb, metrics = _batcher(net, "mixed")
    try:
        assert cb._chunk_t == T
        reqs = [cb.submit(p, n) for p, (_, n) in zip(prompts, sizes)]
        got = [[int(t) for t in cb.wait(r)] for r in reqs]
    finally:
        cb.shutdown(drain=True)
    for p, (_, n), g in zip(prompts, sizes, got):
        want, gaps = _token_by_token(net, p, n)
        _same_ids(g, want, gaps)
    c = _counts(metrics, "mixed")
    # every prompt token went to the device once, every output token
    # is a decode slot-step, and a prompt of n discards the output of
    # all its chunks but the last
    assert c["prompt_tokens"] == sum(n for n, _ in sizes)
    assert c["decode"] == sum(n for _, n in sizes)
    assert c["prompt"] == sum(-(-n // T) - 1 for n, _ in sizes)
    assert c["chunk"] >= -(-30 // T) and c["single"] > 0


@pytest.mark.parametrize("n", [1, 4, 5, 19])
def test_a_prompt_of_n_takes_ceil_n_over_t_steps(net, four_rows, n):
    cb, metrics = _batcher(net, "ttft")
    try:
        prompt = _prompt(n, 7)
        got = [int(t) for t in cb.generate(prompt, 3)]
    finally:
        cb.shutdown(drain=True)
    _same_ids(got, *_token_by_token(net, prompt, 3))
    c = _counts(metrics, "ttft")
    steps = -(-n // T)
    # alone in the pool: ceil(n / t) steps feed the prompt, the first
    # token comes with the last of them, two more steps follow. A
    # step that has one token left to feed (a one-token prompt, a
    # tail of one) is the single-token program's
    tail_of_one = int(n % T == 1)
    assert c == {"chunk": steps - tail_of_one,
                 "single": 2 + tail_of_one, "prompt": steps - 1,
                 "decode": 3, "prompt_tokens": n}


@pytest.mark.parametrize("path", ["gather", "by_table"])
def test_kv_positions_read_follow_the_path(net, four_rows, monkeypatch,
                                           path):
    """``serving_kv_positions_{read,spanned}_total`` over a scripted
    schedule. The gather reads what the tables span. By table (the
    layers' own predicate forced, and the kernel in interpret mode
    for the CPU) a step reads its live slots' pages up to their
    lengths, and in the single program one page of each slot that sits
    the step out: its dummy row's, which the kernel fetches."""
    from deeplearning4j_tpu.ops import paged_attention as PA
    if path == "by_table":
        monkeypatch.setattr(PA, "reads_by_table", lambda *a: True)
        monkeypatch.setattr(
            PA, "pallas_paged_attention",
            functools.partial(PA.pallas_paged_attention, interpret=True))
    sizes = [(19, 3), (9, 4), (2, 4)]
    cb, metrics = _batcher(net, "kv")
    try:
        # one at a time: each is alone in the pool, so its steps end
        # at t, 2t, ..., n, then n + 1, ... (its last token is never
        # fed)
        got = [[int(t) for t in cb.generate(_prompt(n, n), n_tokens)]
               for n, n_tokens in sizes]
        by_table = cb.session._by_table
    finally:
        cb.shutdown(drain=True)
    for (n, n_tokens), g in zip(sizes, got):
        _same_ids(g, *_token_by_token(net, _prompt(n, n), n_tokens))
    snap = metrics.registry.snapshot()
    read = snap['serving_kv_positions_read_total{endpoint="kv"}']
    spanned = snap['serving_kv_positions_spanned_total{endpoint="kv"}']
    c = _counts(metrics, "kv")
    assert spanned == (c["chunk"] + c["single"]) * SLOTS * CAP
    assert by_table == dict.fromkeys(by_table, path == "by_table")
    if path == "gather":
        assert read == spanned
        return
    ends = [e for n, n_tokens in sizes
            for e in list(range(T, n, T)) + list(range(n, n + n_tokens))]
    assert len(ends) == c["chunk"] + c["single"]
    assert read == (sum(-(-e // PS) * PS for e in ends)
                    + c["single"] * (SLOTS - 1) * PS)


def test_a_pool_that_only_decodes_never_runs_the_chunk_program(net):
    cb, metrics = _batcher(net, "decode")
    try:
        assert cb._chunk_t == 32
        reqs = [cb.submit([k + 1], 6) for k in range(6)]
        got = [[int(t) for t in cb.wait(r)] for r in reqs]
    finally:
        cb.shutdown(drain=True)
    for k, g in enumerate(got):
        _same_ids(g, *_token_by_token(net, [k + 1], 6))
    c = _counts(metrics, "decode")
    assert c["chunk"] == 0 and c["prompt"] == 0
    assert c["decode"] == 36 and c["prompt_tokens"] == 6


def test_the_dense_session_stays_token_by_token(net):
    conf = (NeuralNetConfiguration.builder().set_seed(0)
            .updater(updaters.adam(1e-3)).list()
            .layer(EmbeddingSequenceLayer(n_in=V, n_out=8))
            .layer(LSTM(n_out=8))
            .layer(RnnOutputLayer(n_out=V, loss="mcxent"))
            .set_input_type(InputType.recurrent(V, CAP)).build())
    for model, mode in ((MultiLayerNetwork(conf).init(), "auto"),
                        (net, "dense")):
        cb, metrics = _batcher(model, "dense", kv_mode=mode)
        try:
            assert cb._chunk_t == 1 and not cb._paged
            cb.generate(_prompt(9, 1), 2)
        finally:
            cb.shutdown(drain=True)
        c = _counts(metrics, "dense")
        assert c == {"chunk": 0, "single": 10, "prompt": 8,
                     "decode": 2, "prompt_tokens": 9}


def test_prefill_export_stops_one_token_short(net, four_rows):
    prompt = _prompt(19, 3)
    want, gaps = _token_by_token(net, prompt, 6)
    a, metrics = _batcher(net, "prefill")
    b, _ = _batcher(net, "decode")
    try:
        blob = a.prefill_export(prompt, 6)
        header, _ = parse_lease(blob)
        # every prompt position but the last is in the cache
        assert header["pos"] == len(prompt) - 1
        got = [int(t) for t in b.wait(b.import_stream(blob))]
    finally:
        a.shutdown(drain=True)
        b.shutdown(drain=True)
    _same_ids(got, want, gaps)
    c = _counts(metrics, "prefill")
    # 18 tokens in chunks of 4, 4, 4, 4 and 2: none of them sampled
    assert c == {"chunk": 5, "single": 0, "prompt": 5, "decode": 0,
                 "prompt_tokens": 18}
    # the written full pages went to the exporter's prefix cache
    assert len(a.session.prefix_cache) == (len(prompt) - 1) // PS


@pytest.mark.parametrize("finish_on", ["survivor", "incumbent"])
def test_a_migration_offered_mid_prefill_resumes(net, four_rows,
                                                 finish_on):
    prompt = _prompt(30, 5)
    want, gaps = _token_by_token(net, prompt, 5)
    a, _ = _batcher(net, "old")
    b, _ = _batcher(net, "new")
    try:
        # arm the drain from inside the worker, after the prompt's
        # third chunk: the offer is then cut at position 12 of 30
        step_ids, calls = a.session.step_ids, []

        def counted(x, n_valid, use_prev):
            out = step_ids(x, n_valid, use_prev)
            if x.shape[1] > 1 and int(np.sum(n_valid)):
                calls.append(int(np.sum(n_valid)))
                if len(calls) == 3:
                    a.request_migration()
            return out

        a.session.step_ids = counted
        offer = a.wait(a.submit(prompt, 5))
        assert isinstance(offer, MigrationOffer)
        assert (offer.pos, offer.tokens_out) == (3 * T, 0)
        if finish_on == "survivor":
            got = [int(t) for t in b.wait(b.import_stream(offer.blob))]
            assert a.ack_migration(offer.handle)
        else:
            got = [int(t) for t in a.resume_stream(offer.handle)]
            assert calls[:3] == [T, T, T] and sum(calls) == len(prompt)
    finally:
        a.shutdown(drain=True)
        b.shutdown(drain=True)
    _same_ids(got, want, gaps)
