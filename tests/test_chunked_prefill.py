"""Chunked prefill in the continuous batcher: a slot in prefill feeds
up to t prompt tokens a device step through the paged session's chunk
program; a pool that only decodes runs the single-token program.

What is held here: the greedy ids of a token-by-token session under
mixed traffic; ``ceil(n / t)`` steps from a prompt of n to its first
token; the step counters against the schedule; a prefill export that
stops one token short and imports to the same ids; a migration offered
mid-prefill that resumes, on a survivor or on the incumbent, to the
same ids. A pool of many slots holds a second chunk program twice as
wide: which pools do, the rule that picks a step's width on hand-made
pools either side of its turning point, an export at either width, and
the three programs warm before the first token (the ids where widths
alternate are held in tests/test_serve_lookahead.py). The wide
program's row budget follows the session: what it says of hand-made
expert layers, the widths that follow, the rule at t 2 / 8, and the
gauge of the two widths. The session's own parity is in
tests/chunk_parity.py."""

import functools
import json
import os

import numpy as np
import pytest

from test_serve_lookahead import Batcher

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.models.paged_kv import parse_lease
from deeplearning4j_tpu.nn.conf import updaters
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    EmbeddingSequenceLayer, GroupedQueryDecoderBlock, LSTM,
    RnnOutputLayer, ShortConvDecoderBlock, StateSpaceDecoderBlock,
    TransformerEncoderLayer)
from deeplearning4j_tpu.serving import ContinuousBatcher, continuous
from deeplearning4j_tpu.serving.continuous import (MigrationOffer,
                                                   chunk_width,
                                                   wide_chunk_width)
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
    """A row budget that gives 4 slots a chunk of 4 tokens, and no
    second width."""
    monkeypatch.setattr(continuous, "CHUNK_ROWS", SLOTS * T)
    monkeypatch.setattr(continuous, "WIDE_CHUNK_ROWS", SLOTS * T)


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
    assert continuous.WIDE_CHUNK_ROWS == 256
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


# ---------------------------------------------------------------------------
# the second, wider chunk program
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("slots, capacity, page, rows, want", [
    (64, 2048, 16, 256, (2, 4)),    # axk1_serve_decode (capacity 1024
                                    # gives the same)
    (64, 1024, 16, 256, (2, 4)),
    (32, 1024, 16, 256, (4, 8)),    # longcat_serve_tooluse
    (64, 16, 16, 256, (2, 4)),      # a ring caps a slot's rows at a
                                    # page
    (8, 1024, 16, 256, (16, 0)),    # gpt2m_serve_closed: a page a step
    (16, 1024, 16, 256, (8, 16)),
    (16, 1024, 8, 256, (8, 0)),     # the narrow width is a page already
    (128, 1024, 16, 256, (1, 2)),
    (256, 1024, 16, 256, (1, 0)),
    (4, 4, 4, 256, (4, 0)),         # the benchmark's tiny presets
    (64, 2, 16, 256, (2, 0)),       # no wider than a slot
    (64, 2048, 16, 512, (2, 8)),    # lfm2_serve_agent
    (64, 16, 16, 512, (2, 8)),      # mimo_serve_mixedlen: under its
                                    # ring's page
    (64, 4, 16, 512, (2, 4)),       # a page of 4 caps the ring at 4
    (32, 1024, 16, 512, (4, 16)),
    (8, 1024, 16, 512, (16, 0)),    # a page a step already
    (128, 1024, 16, 512, (1, 4)),
    (4, 4, 4, 512, (4, 0)),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_the_widths_follow_the_pool(slots, capacity, page, rows, want):
    """(t_lo, t_hi) at the budgets of 128 and of ``rows``, the
    session's 256 or 512; 0: the pool holds no wide program."""
    assert (chunk_width(slots, capacity),
            wide_chunk_width(slots, capacity, page, rows)) == want
    # the wide width is the same function under the wide budget, and
    # 256 is what is taken where no session is asked
    assert want[1] in (0, chunk_width(slots, capacity, rows))
    if rows == continuous.WIDE_CHUNK_ROWS:
        assert wide_chunk_width(slots, capacity, page) == want[1]


def _block_net(*blocks, width=16):
    b = (NeuralNetConfiguration.builder().set_seed(0)
         .updater(updaters.sgd(0.0)).list()
         .layer(EmbeddingSequenceLayer(n_in=V, n_out=width)))
    for block in blocks:
        b = b.layer(block)
    return MultiLayerNetwork(
        b.layer(RnnOutputLayer(n_out=V, loss="mcxent"))
        .set_input_type(InputType.recurrent(V, 256)).build()).init()


@pytest.mark.parametrize("kind, page, want", [
    ("ring", 16, (2, 4)), ("ring", 2, (2, 0)),
    ("state_space", 16, (2, 0)), ("short_conv", 16, (2, 4))])
def test_the_session_says_what_caps_the_widths(kind, page, want):
    """A ring caps a slot's rows at a page (``chunk_rows_max``), and
    a layer whose step unrolls over the chunk's rows keeps the pool to
    one chunk width (``unrolls_chunk_rows``: the Mamba-2 mixer says so
    of itself, the short convolution does not)."""
    block = {"ring": GroupedQueryDecoderBlock(window=8),
             "state_space": StateSpaceDecoderBlock(),
             "short_conv": ShortConvDecoderBlock()}[kind]
    cb = ContinuousBatcher(_block_net(block), slots=64, capacity=256,
                           page_size=page, kv_mode="paged",
                           metrics=ServingMetrics(), name=kind)
    try:
        sess = cb.session
        assert sess.chunk_rows_max == (page if kind == "ring" else 256)
        assert sess.unrolls_chunk_rows == (kind == "state_space")
        assert (cb._chunk_t, cb._wide_t) == want
        snap = cb.metrics.registry.snapshot()
        # the counter exists where the program does, at 0
        assert (f'serving_wide_steps_total{{endpoint="{kind}"}}'
                in snap) == bool(want[1])
    finally:
        cb.shutdown(drain=True)


def _expert_blocks(*widths):
    """A network of width 128 (a lane tile) with one short-convolution
    block a width: experts of that width, or none at 0."""
    return _block_net(*(
        ShortConvDecoderBlock(n_routed_experts=8, top_k=2,
                              expert_width=w) if w
        else ShortConvDecoderBlock() for w in widths), width=128)


@pytest.fixture
def a_small_turn(monkeypatch):
    """A TPU is asked for, and the kernel's turn is moved down between
    the sizes of its unrolled body at 512 rows of width 128 through
    experts 128 wide (28 passes) and 512 wide (64)."""
    import jax
    from deeplearning4j_tpu.ops import grouped_experts
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    size = lambda w: grouped_experts._mxu_passes(512, 128, w)
    assert size(128) < 40 < size(512)
    monkeypatch.setattr(grouped_experts, "_WEIGHT_BOUND_PASSES", 40)


@pytest.mark.parametrize("case, widths, dtype, slots, want", [
    ("no_experts", (0, 0), "bfloat16", 64, 256),
    ("dense_in_float32", (128,), "float32", 64, 256),
    ("dense_where_rows_make_no_tiles", (128,), "bfloat16", 40, 256),
    ("grouped_under_the_turn", (128, 0, 128), "bfloat16", 64, 512),
    ("grouped_at_32_slots", (128,), "bfloat16", 32, 512),
    ("grouped_over_the_turn", (512,), "bfloat16", 64, 256),
    ("one_layer_of_three_over_it", (128, 512, 128), "bfloat16", 64, 256),
])
def test_the_session_says_what_a_wide_step_may_carry(
        a_small_turn, case, widths, dtype, slots, want):
    """``wide_chunk_rows`` off ``PagedSlotSession.experts_carry_rows``:
    512 only where the network has expert layers and EVERY one runs
    the grouped pass at the rows the 512 budget gives the pool, under
    the kernel's turn."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.models.paged_kv import PagedSlotSession
    sess = PagedSlotSession(_expert_blocks(*widths), slots, 256, 16,
                            dtype=jnp.dtype(dtype))
    t = chunk_width(slots, 256, continuous.GROUPED_CHUNK_ROWS)
    assert sess.experts_carry_rows(t) == (want == 512)
    assert continuous.wide_chunk_rows(sess, slots, 256) == want


def test_off_a_tpu_every_session_keeps_256():
    import jax.numpy as jnp
    from deeplearning4j_tpu.models.paged_kv import PagedSlotSession
    sess = PagedSlotSession(_expert_blocks(128), 64, 256, 16,
                            dtype=jnp.bfloat16)
    assert not sess.experts_carry_rows(8)
    assert continuous.wide_chunk_rows(sess, 64, 256) == 256


@pytest.mark.parametrize("widths, window, want", [
    ((128,), None, (2, 8)), ((512,), None, (2, 4)),
    ((128,), 8, (2, 8))])
def test_the_batcher_takes_the_sessions_rows(a_small_turn, widths,
                                             window, want):
    """The batcher's two widths at 64 slots, and the gauge that says
    what they are in rows; a ring's page of 16 still holds t = 8."""
    import jax.numpy as jnp
    net = _expert_blocks(*widths)
    if window:
        net = _block_net(GroupedQueryDecoderBlock(
            window=window, n_routed_experts=8, top_k=2,
            expert_width=widths[0]), width=128)
    cb = ContinuousBatcher(net, slots=64, capacity=256, page_size=16,
                           kv_mode="paged", metrics=ServingMetrics(),
                           name="rows", dtype=jnp.bfloat16)
    try:
        assert (cb._chunk_t, cb._wide_t) == want
        snap = cb.metrics.registry.snapshot()
        rows = lambda program: snap[
            f'serving_chunk_rows{{endpoint="rows",program="{program}"}}']
        assert (rows("narrow"), rows("wide")) == (128, 64 * want[1])
    finally:
        cb.shutdown(drain=True)


def test_the_gauge_has_no_wide_series_where_no_wide_program():
    m = ServingMetrics()
    m.batcher_steps("one").holds_chunk_rows(128, 0)
    m.batcher_steps("two").holds_chunk_rows(128, 512)
    snap = m.registry.snapshot()
    series = {k: v for k, v in snap.items() if "serving_chunk_rows" in k}
    assert series == {
        'serving_chunk_rows{endpoint="one",program="narrow"}': 128,
        'serving_chunk_rows{endpoint="two",program="narrow"}': 128,
        'serving_chunk_rows{endpoint="two",program="wide"}': 512}


WIDE_SLOTS, T_LO, T_HI = 16, 2, 4


def _pin_widths(monkeypatch, t_hi):
    """Row budgets that give 16 slots chunks of 2 and of ``t_hi``
    tokens, whatever the session says of its experts."""
    monkeypatch.setattr(continuous, "CHUNK_ROWS", WIDE_SLOTS * T_LO)
    for budget in ("WIDE_CHUNK_ROWS", "GROUPED_CHUNK_ROWS"):
        monkeypatch.setattr(continuous, budget, WIDE_SLOTS * t_hi)


@pytest.fixture
def two_widths(monkeypatch):
    """Row budgets that give 16 slots chunks of 2 and of 4 tokens."""
    _pin_widths(monkeypatch, T_HI)


def _gated(net, name):
    """A 16-slot batcher that holds both chunk programs, its worker
    held before its first pass until ``go()``: what was submitted
    before is admitted at once (tests/test_serve_lookahead.py's)."""
    b = Batcher(net, name, slots=WIDE_SLOTS, queue_limit=256)
    assert (b.cb._chunk_t, b.cb._wide_t) == (T_LO, T_HI)
    return b


def _hand_made(left=0, export=False, decoding=False):
    """A slot with ``left`` prompt tokens beyond its ``feed``, or one
    in decode."""
    req = continuous._GenRequest(np.arange(1, left + 2), 4, 0.0, 0, None)
    req.prefill_export = export
    s = continuous._Slot(req)
    if decoding:
        s.prompt_left, s.feed, s.out, s.emitted = [], None, [3], 1
    return s


_LONG, _DECODE = dict(left=9), dict(decoding=True)

# (slots by hand, the step's rows): 16 slots, t 2 and 4, so the wide
# step runs from 32 rows on offer
POOLS = {
    "all_decode": ([_DECODE] * 16, 1),
    "one_token_prompts": ([dict(left=0)] * 3 + [_DECODE] * 5, 1),
    "5_of_16_in_prefill": ([_LONG] * 5 + [_DECODE] * 11, T_LO),   # 31
    "5_and_a_tail_of_2": ([_LONG] * 5 + [dict(left=1)]
                          + [_DECODE] * 10, T_HI),                # 32
    "6_of_16_in_prefill": ([_LONG] * 6 + [_DECODE] * 10, T_HI),   # 34
    "7_alone": ([_LONG] * 7, T_LO),                               # 28
    "7_and_a_tail_of_3": ([_LONG] * 7 + [dict(left=2)], T_LO),    # 31
    "8_alone": ([_LONG] * 8, T_HI),                               # 32
    "short_tails": ([dict(left=1)] * 15 + [_DECODE], T_LO),       # 31
    "exports_of_4": ([dict(left=4, export=True)] * 8, T_HI),      # 32
    "exports_of_3": ([dict(left=3, export=True)] * 10, T_LO),     # 30
    "exports_of_3_fill_it": ([dict(left=3, export=True)] * 10
                             + [_DECODE] * 2, T_HI),              # 32
}


def _check_plan(b, made, want_rows, t_lo, t_hi):
    """``_plan_step`` over slots made by hand is the rule, written
    out: the wide step where what it would feed fills the narrow
    one's rows."""
    cb = b.cb
    try:
        cb._slots = [_hand_made(**kw) for kw in made] + [None] * (
            WIDE_SLOTS - len(made))
        st = cb._plan_step()
        need = [1 if kw.get("decoding") else
                1 + kw["left"] - int(kw.get("export", False))
                for kw in made]
        offered = sum(min(t_hi, n) for n in need)
        assert want_rows == (
            1 if not any(kw.get("left") for kw in made) else
            t_hi if offered >= WIDE_SLOTS * t_lo else t_lo)
        assert st.x.shape == (WIDE_SLOTS, want_rows, 1)
        # each slot feeds what it has, up to the step's width: an
        # export stops one token short at either width
        assert st.n_valid.tolist() == [
            min(want_rows, n) for n in need] + [0] * (
                WIDE_SLOTS - len(made))
        assert [i for i, _ in st.emitters] == [
            i for i, (kw, n) in enumerate(zip(made, need))
            if not kw.get("export") and n <= want_rows]
    finally:
        cb._slots = [None] * WIDE_SLOTS
        assert b.close()


@pytest.mark.parametrize("pool", list(POOLS))
def test_the_plan_goes_wide_when_the_rows_on_offer_fill_it(
        net, two_widths, pool):
    made, want_rows = POOLS[pool]
    _check_plan(_gated(net, "plan"), made, want_rows, T_LO, T_HI)


T_8 = 8

# the same 16 slots at t 2 and 8, the widths of a 512-row pool of 64:
# the wide step still runs from 32 rows on offer, and then carries up
# to 128
POOLS_OF_8 = {
    "4_of_16_in_prefill": ([_LONG] * 4 + [_DECODE] * 7, T_8),     # 39
    "4_alone": ([_LONG] * 4, T_8),                                # 32
    "3_and_7_decoding": ([_LONG] * 3 + [_DECODE] * 7, T_LO),      # 31
    "3_and_8_decoding": ([_LONG] * 3 + [_DECODE] * 8, T_8),       # 32
    "tails_of_3": ([dict(left=2)] * 10, T_LO),                    # 30
    "tails_of_3_fill_it": ([dict(left=2)] * 10 + [_DECODE] * 2,
                           T_8),                                  # 32
    "exports_of_8": ([dict(left=8, export=True)] * 4, T_8),       # 32
    "exports_of_7": ([dict(left=7, export=True)] * 4
                     + [_DECODE] * 3, T_LO),                      # 31
    "all_decode": ([_DECODE] * 16, 1),
}


@pytest.mark.parametrize("pool", list(POOLS_OF_8) + ["a_ring_of_4"])
def test_the_plan_at_a_quarter_of_the_wide_width(net, monkeypatch, pool):
    """The rule is the same at t 2 / 8: it asks whether the narrow
    step's rows are filled, so a wide step a quarter full runs. A
    ring's page of 4 caps the wide width at 4 under the same
    budgets."""
    _pin_widths(monkeypatch, T_8)
    if pool == "a_ring_of_4":
        b = Batcher(_block_net(GroupedQueryDecoderBlock(window=8)),
                    "ring", slots=WIDE_SLOTS, queue_limit=256)
        assert b.cb.session.chunk_rows_max == PS
        assert (b.cb._chunk_t, b.cb._wide_t) == (T_LO, PS)
        return _check_plan(b, [_LONG] * 8, PS, T_LO, PS)          # 32
    b = Batcher(net, "plan8", slots=WIDE_SLOTS, queue_limit=256)
    assert (b.cb._chunk_t, b.cb._wide_t) == (T_LO, T_8)
    made, want_rows = POOLS_OF_8[pool]
    _check_plan(b, made, want_rows, T_LO, T_8)


@pytest.mark.parametrize("n_requests, wide", [(3, False), (10, True)])
def test_prefill_exports_stop_one_token_short_at_either_width(
        net, two_widths, n_requests, wide):
    prompts = [_prompt(19, 40 + k) for k in range(n_requests)]
    a, b = _gated(net, "prefill"), _gated(net, "decode")
    b.go()
    try:
        reqs = [a.cb.submit(p, 5, prefill_export=True) for p in prompts]
        a.go()
        blobs = [a.cb.wait(r) for r in reqs]
        for blob in blobs:
            # every prompt position but the last is in the cache
            assert parse_lease(blob)[0]["pos"] == 18
        got = [int(t) for t in
               b.cb.wait(b.cb.import_stream(blobs[-1]))]
        # 18 tokens a prompt: chunks of 4, 4, 4, 4 and 2 (the tails of
        # 2 do not fill a wide step), or nine of 2
        assert a.count("serving_wide_steps_total") == (4 if wide else 0)
        assert _counts(a.metrics, "prefill") == {
            "chunk": 5 if wide else 9, "single": 0,
            "prompt": n_requests * (5 if wide else 9), "decode": 0,
            "prompt_tokens": 18 * n_requests}
    finally:
        assert a.close() and b.close()
    _same_ids(got, *_token_by_token(net, prompts[-1], 5))


def test_three_programs_are_warm_before_the_first_token(net, two_widths):
    """The first step that feeds a token finds the single, the narrow
    and the wide id-returning program compiled, and traffic that runs
    all three compiles nothing."""
    from deeplearning4j_tpu.observability.compile_watch import (
        install_global_watch)
    b = _gated(net, "warm")
    cb = b.cb
    try:
        b.go()
        cb.generate([5], 1)
        assert cb.session._registered == {
            ("paged_step_ids", t) for t in (1, T_LO, T_HI)}
        sizes = [(30, 3)] * 9 + [(1, 6), (3, 2), (2, 9)] + [(25, 4)] * 3
        with install_global_watch().zero_compile_scope(
                "steps of three widths"):
            reqs = [cb.submit(_prompt(n, 60 + k), n_tokens)
                    for k, (n, n_tokens) in enumerate(sizes)]
            for r in reqs:
                cb.wait(r)
        c = _counts(b.metrics, "warm")
        assert 0 < b.count("serving_wide_steps_total") < c["chunk"] and c["single"] > 1
        assert cb.session._registered == {
            ("paged_step_ids", t) for t in (1, T_LO, T_HI)}
    finally:
        assert b.close()


def test_the_wide_steps_reader_over_two_snapshots():
    """benchmark/layer_metrics/wide_steps_pct.serve.py: the wide steps'
    share of the window's steps where the batcher has the counter, and
    nothing where it has not (a pool without a wide program, the
    parent of PR 42) or the window held no step; the key a real
    ``BatcherStepMetrics`` writes is the key the reader matches."""
    from benchmark.harness import spec
    reader = spec.load_module("layer_metrics", "wide_steps_pct.serve")
    read = lambda before, after: reader.read(
        {"counters": {"before": before, "after": after}})
    ep = 'endpoint="generate/lm/v1"'
    wide = "serving_wide_steps_total{%s}" % ep
    steps = 'serving_steps_total{%s,program="%%s"}' % ep
    before = {wide: 10.0, steps % "chunk": 60.0, steps % "single": 40.0}
    after = {wide: 100.0, steps % "chunk": 180.0, steps % "single": 120.0}
    assert read(before, after) == pytest.approx(45.0)
    held_not_run = dict(after, **{wide: 10.0})
    assert read(before, held_not_run) == 0.0
    del before[wide], after[wide]
    assert read(before, after) is None
    assert read(held_not_run, held_not_run) is None
    m = ServingMetrics()
    recorded = m.batcher_steps("generate/lm/v1")
    recorded.holds_wide_program()
    first = m.registry.snapshot()
    recorded.record(0.001, 0.002, 0.001, 2, 0, "chunk", wide=True)
    recorded.record(0.001, 0.002, 0.001, 1, 1, "chunk")
    recorded.record(0.001, 0.002, 0.001, 0, 2, "single")
    recorded.record(0.001, 0.002, 0.001, 2, 0, "chunk", wide=True)
    assert read(first, m.registry.snapshot()) == pytest.approx(50.0)
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert next(m for m in bench["per_layer"]
                if m["name"] == "wide_steps_pct.serve") == {
        "name": "wide_steps_pct.serve", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Serving",
        "moves": "serve_tokens_per_s",
        "workloads": ["axk1_serve_decode", "longcat_serve_tooluse",
                      "mimo_serve_mixedlen", "lfm2_serve_agent"]}


@pytest.fixture
def grouped_when_wide(monkeypatch):
    """The expert layers take the grouped pass, interpreted, in the
    steps that carry more than the narrow program's rows: the
    predicate's turn, moved down to the tests' 16 slots."""
    from deeplearning4j_tpu.ops import grouped_experts
    monkeypatch.setattr(grouped_experts, "grouped_pass",
                        lambda n, *_: n > WIDE_SLOTS * T_LO)
    monkeypatch.setattr(
        grouped_experts, "pallas_grouped_experts", functools.partial(
            grouped_experts.pallas_grouped_experts, interpret=True))


def _expert_net():
    return _block_net(ShortConvDecoderBlock(
        n_routed_experts=8, top_k=2, expert_width=32))


def test_grouped_steps_are_counted_where_a_program_runs_them(
        two_widths, grouped_when_wide):
    """``serving_moe_grouped_steps_total`` exists where some program
    of the session runs its expert layers grouped (here the wide one
    alone) and counts the steps that ran it; what is served is what
    the dense pass serves, token by token."""
    net = _expert_net()
    prompts = [_prompt(19, 70 + k) for k in range(10)]
    b = _gated(net, "experts")
    try:
        sess = b.cb.session
        assert [sess.runs_grouped_experts(t) for t in (1, T_LO, T_HI)] \
            == [False, False, True]
        assert b.cb._grouped_t == {T_HI}
        got = b.run([(p, 4, {}) for p in prompts])
        wide = b.count("serving_wide_steps_total")
        assert 0 < wide < b.steps()
        assert b.count("serving_moe_grouped_steps_total") == wide
    finally:
        assert b.close()
    for p, ids in zip(prompts[:3], got):
        _same_ids(ids, *_token_by_token(net, p, 4))


@pytest.mark.parametrize("net_of", ["lm", "experts"])
def test_no_grouped_counter_where_no_program_is_grouped(net, two_widths,
                                                        net_of):
    """Off a TPU the predicate is False at every width, and a network
    without expert layers has nothing to group: no series."""
    b = _gated(net if net_of == "lm" else _expert_net(), net_of)
    try:
        assert b.cb._grouped_t == set()
        assert not any("serving_moe_grouped_steps_total" in k
                       for k in b.metrics.registry.snapshot())
    finally:
        assert b.close()


def test_the_grouped_steps_reader_over_two_snapshots():
    """benchmark/layer_metrics/moe_grouped_steps_pct.serve.py: the
    grouped steps' share of the window's steps where the batcher has
    the counter (0 where such a program is held and never runs), and
    nothing where it has not (the dense pass at every width, the
    parent of PR 43) or the window held no step; the key a real
    ``BatcherStepMetrics`` writes is the key the reader matches."""
    from benchmark.harness import spec
    reader = spec.load_module("layer_metrics",
                              "moe_grouped_steps_pct.serve")
    read = lambda before, after: reader.read(
        {"counters": {"before": before, "after": after}})
    m = ServingMetrics()
    recorded = m.batcher_steps("generate/lm/v1")
    bare = m.registry.snapshot()
    recorded.record(0.001, 0.002, 0.001, 2, 0, "chunk")
    assert read(bare, m.registry.snapshot()) is None
    recorded.holds_grouped_program()
    first = m.registry.snapshot()
    assert read(first, first) is None
    recorded.record(0.001, 0.002, 0.001, 1, 1, "chunk")
    held_not_run = m.registry.snapshot()
    assert read(first, held_not_run) == 0.0
    recorded.record(0.001, 0.002, 0.001, 2, 0, "chunk", wide=False,
                    grouped=True)
    recorded.record(0.001, 0.002, 0.001, 0, 2, "single")
    recorded.record(0.001, 0.002, 0.001, 2, 0, "chunk", grouped=True)
    assert read(held_not_run, m.registry.snapshot()) == pytest.approx(
        200.0 / 3)
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert next(m for m in bench["per_layer"]
                if m["name"] == "moe_grouped_steps_pct.serve") == {
        "name": "moe_grouped_steps_pct.serve", "unit": "%",
        "better": "higher", "source": "program_counter",
        "layer": "Layers", "moves": "serve_tokens_per_s",
        "workloads": ["axk1_serve_decode", "longcat_serve_tooluse",
                      "mimo_serve_mixedlen", "lfm2_serve_agent"]}


@pytest.mark.parametrize("cell, reading, widths", [
    ("mimo_serve_mixedlen", 2, (2, 8)), ("lfm2_serve_agent", 2, (2, 8)),
    ("axk1_serve_decode", 8, (2, 4)),
    ("longcat_serve_tooluse", 4, (4, 8))])
def test_the_paged_kernels_admit_the_wide_width(monkeypatch, cell,
                                                reading, widths):
    """The widths the cell's pool takes on a TPU (512 rows where every
    expert layer of the published shapes carries them: ``lfm2_24b_a2b``
    and ``mimo_v25_ep16``; 256 for ``axk1_ep16``, whose kernel would
    pass its fast memory, and ``longcat_ep32``, whose kernel's time
    turns), and at the wide one every attention layer that reads its
    pages by table at the narrow one still does (the predicates of
    ``ops.paged_attention``: the tile conditions and ``_vmem_bytes``
    within ``_VMEM_BUDGET``; the backend asked for a TPU here): a
    silent fall back to the gather would cost the wide steps what the
    by-table kernels won. ``reading``: the layers that read by table
    (MiMo's window layers keep their rings)."""
    import jax
    import jax.numpy as jnp
    from benchmark.harness import spec
    from deeplearning4j_tpu.models.paged_kv import PagedSlotSession
    from deeplearning4j_tpu.nn.conf.layers.paged import PagedLayer
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    c = spec.load(cell)
    config, sv = c.config, c.traffic["server"]
    builder = spec.load_module("builders", config["builder"])
    with builder.policy(config):
        net = builder.build(config).init()       # parameters as shapes
    cap = min([sv["capacity"]] + [
        sv["page_size"] for layer in net.layers
        if isinstance(layer, PagedLayer)
        and layer.paged_cache(sv["page_size"]).ring_pages])
    # the session's statement without its pools (gigabytes of zeros)
    sess = object.__new__(PagedSlotSession)
    sess.net, sess.slots, sess._dtype = net, sv["slots"], jnp.bfloat16
    sess._aux_layers = [i for i, layer in enumerate(net.layers)
                        if getattr(layer, "stream_aux", False)]
    assert sess._aux_layers
    t_lo = chunk_width(sv["slots"], cap)
    t_hi = wide_chunk_width(
        sv["slots"], cap, sv["page_size"],
        continuous.wide_chunk_rows(sess, sv["slots"], cap))
    assert (t_lo, t_hi) == widths
    by_table = [layer for layer in net.layers
                if hasattr(layer, "paged_reads_by_table")
                and layer.paged_reads_by_table(sv["page_size"], t_lo,
                                               jnp.bfloat16)]
    assert len(by_table) == reading
    for layer in by_table:
        assert layer.paged_reads_by_table(sv["page_size"], t_hi,
                                          jnp.bfloat16)
