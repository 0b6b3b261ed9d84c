"""Decode fast path: paged KV cache, prefix cache, speculative decode.

The tier-1 contracts of the decode-fast-path PR:

- allocator discipline: alloc/free/refcount/double-free guards,
  copy-on-write on shared-page divergence, and OOM as a TYPED
  admission error carrying a Retry-After hint;
- paged-vs-dense parity: greedy tokens through the paged
  ContinuousBatcher are bit-identical to the dense path, slot reuse
  included;
- prefix cache end to end over live HTTP: the second identical
  prompt skips the cached prefill (asserted via the request's phase
  ledger attrs and the hit counter, not timing);
- speculative decode: greedy ids identical to vanilla decode for
  both a perfect and a near-useless draft;
- program-cache hygiene: per-request float temperature jitter cannot
  compile new fused-generate executables (GL002-style regression);
- chaos: a serving.worker.step crash must not leak page refcounts
  across the worker restart.
"""

import json
import time
import urllib.request

import numpy as np
import pytest

import chunk_parity
from deeplearning4j_tpu import (MultiLayerNetwork, chaos,
                                NeuralNetConfiguration)
from deeplearning4j_tpu.models.paged_kv import (PagedKVAllocator,
                                                PrefixCache)
from deeplearning4j_tpu.models.speculative import SpeculativeDecoder
from deeplearning4j_tpu.nn.conf import updaters
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (EmbeddingSequenceLayer,
                                               LSTM,
                                               RnnOutputLayer,
                                               TransformerEncoderLayer)
from deeplearning4j_tpu.serving import (ContinuousBatcher,
                                        KVPagePoolExhaustedError,
                                        ModelRegistry, ModelServer,
                                        QueueFullError)

pytestmark = pytest.mark.decode

V, CAP = 13, 64


def _lm(seed=0, width=16, layers=1, heads=2, cap=CAP):
    b = (NeuralNetConfiguration.builder().set_seed(seed)
         .updater(updaters.adam(1e-3)).list()
         .layer(EmbeddingSequenceLayer(n_in=V, n_out=width)))
    for _ in range(layers):
        b = b.layer(TransformerEncoderLayer(n_heads=heads,
                                            causal=True))
    conf = (b.layer(RnnOutputLayer(n_out=V, loss="mcxent"))
            .set_input_type(InputType.recurrent(V, cap)).build())
    return MultiLayerNetwork(conf).init()


def _rnn_lm(seed=0):
    conf = (NeuralNetConfiguration.builder().set_seed(seed)
            .updater(updaters.adam(1e-3)).list()
            .layer(EmbeddingSequenceLayer(n_in=V, n_out=8))
            .layer(LSTM(n_out=8))
            .layer(RnnOutputLayer(n_out=V, loss="mcxent"))
            .set_input_type(InputType.recurrent(V, CAP)).build())
    return MultiLayerNetwork(conf).init()


# ---------------------------------------------------------------------------
# the by-table kernel (ops/paged_attention.py) in Pallas' interpret
# mode against the gather path, which stays the CPU path; the Mosaic
# kernel itself: tests/test_chip_compile.py compiles it,
# benchmark/tests/measure_paged_attention.py runs it on the chip
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("t", [1, 2, 16])
def test_by_table_kernel_matches_the_gather(t, dtype):
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops import paged_attention as PA
    H, Dh, ps, P, S = 2, 64, 16, 10, 8       # two blocks of 8 pages
    cap, HD = P * ps, H * Dh
    rng = np.random.default_rng([t, len(dtype)])
    n_live = S * P
    k_pool = rng.normal(size=(n_live + 3, ps, HD))
    v_pool = rng.normal(size=(n_live + 3, ps, HD))
    # pages no slot holds: large finite garbage, which stale table
    # entries past a slot's length point at
    garbage = [n_live + 1, n_live + 2]
    k_pool[garbage] = v_pool[garbage] = 1e30
    table = rng.permutation(np.arange(1, n_live + 1)).reshape(S, P)
    #        free  one  ends on a page  mid-page  full     shares 3's
    pos = [0,      0,   2 * ps - t,     37,       cap - t, 2 * ps + 3,
           0,      ps + 5]             # parked; decodes in a chunk step
    n_valid = [0,  1,   t,              t,        t,       max(t - 1, 1),
               0,  1]
    pos, n_valid = np.array(pos, np.int32), np.array(n_valid, np.int32)
    table[0] = 0
    table[5, :2] = table[3, :2]               # a shared prompt prefix
    for s in range(S):
        held = -(-(pos[s] + n_valid[s]) // ps)
        if s != 6:                            # 6 keeps a whole table
            table[s, held:] = garbage[s % 2]
    q = rng.normal(size=(S, t, HD))
    q, k_pool, v_pool = (jnp.asarray(a, dtype) for a in (q, k_pool, v_pool))
    table = jnp.asarray(table, jnp.int32)
    got = np.asarray(PA.pallas_paged_attention(
        q, k_pool, v_pool, table, jnp.asarray(pos + n_valid),
        jnp.asarray(pos), n_heads=H, interpret=True), np.float32)
    want = np.asarray(PA.paged_attention_gather(
        q, k_pool, v_pool, table, jnp.asarray(pos), H), np.float32)
    assert np.isfinite(got).all()
    assert not got[[0, 6]].any()              # length 0: zeros, not NaN
    rows = np.arange(t)[None, :] < n_valid[:, None]
    assert rows.sum() >= 6
    # float32 to rounding; in bfloat16 the gather rounds its softmax to
    # bfloat16 where the kernel keeps float32
    np.testing.assert_allclose(got[rows], want[rows], rtol=0,
                               atol=2e-6 if dtype == "float32" else 4e-2)


@pytest.mark.parametrize("t, dtype, want", [
    (1, "float32", "HIGHEST"), (2, "float32", "DEFAULT"),
    (16, "float32", "DEFAULT"), (1, "bfloat16", "DEFAULT")])
def test_by_table_kernel_keeps_the_gathers_precision(t, dtype, want):
    """On the chip the gather's single-row einsums are float32
    multiply-reduce fusions and its chunk einsums matmuls at the
    default precision: the kernel's dots follow (the CPU computes
    both alike, so only the choice can be held here;
    benchmark/tests/measure_paged_attention.py reads the gap to
    float64 on the chip)."""
    import jax
    from deeplearning4j_tpu.ops import paged_attention as PA
    assert PA._dot_precision(t, dtype) == getattr(jax.lax.Precision, want)


# ---------------------------------------------------------------------------
# the chunk step against token-by-token steps (tests/chunk_parity.py;
# the latent pool's cases are in tests/test_latent_moe.py)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case", chunk_parity.CASES)
def test_chunk_step_matches_token_by_token(case):
    chunk_parity.run_case(_lm(layers=2), V, case)


def test_a_slot_that_sits_a_step_out_keeps_its_pages():
    """A bound slot that is not active in a step (a parked one) has
    nothing written: neither program may touch its position 0."""
    net = _lm(layers=1)
    sess = net.paged_slot_streaming_session(capacity=16, slots=2,
                                            page_size=4)
    sess.bind(0, sess.reserve([1, 2, 3, 4, 5], 2))
    sess.bind(1, sess.reserve([5, 6, 7], 2))
    chunk_parity.feed_single(sess, {0: [1, 2, 3], 1: [5, 6, 7]})
    was = chunk_parity.live_rows(sess, 1)
    sess.step_slots(np.full((2, 1, 1), 4, np.float32),
                    np.array([True, False]))
    x = np.full((2, 4, 1), 5, np.float32)
    sess.step_chunk(x, np.array([1, 0], np.int32))
    for a, b in zip(chunk_parity.live_rows(sess, 1), was):
        np.testing.assert_array_equal(a, b)
    assert sess.slot_pos.tolist() == [5, 3]


def test_chunk_step_refuses_what_it_cannot_hold():
    sess = _lm().paged_slot_streaming_session(capacity=8, slots=2,
                                              page_size=4)
    sess.bind(0, sess.reserve([1, 2, 3, 4, 5, 6], 2))
    x = np.ones((2, 4, 1), np.float32)
    with pytest.raises(ValueError, match="n_valid must lie"):
        sess.step_chunk(x, np.array([5, 0]))
    sess.step_chunk(x, np.array([4, 0]))
    sess.step_chunk(x, np.array([4, 0]))
    with pytest.raises(ValueError, match="slot overflow"):
        sess.step_chunk(x, np.array([1, 0]))


@pytest.mark.parametrize("n_valid", [None, [3, 0, 1]],
                         ids=["single_row_program", "chunk_program"])
def test_ring_write_targets(n_valid):
    """Where a layer that keeps a ring of 3 pages of 4 a slot writes
    a step's rows: slot s in pages 1 + 3 s .., position p at ring row
    p mod 12 whatever the table says; rows that carry no token in the
    scratch page (past ``n_valid``, or in the single-row program the
    slot whose table row is zeroed)."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf.layers.attention import (
        ring_write_targets)
    table = jnp.asarray([[7, 9, 0], [0, 0, 0], [5, 0, 0]], jnp.int32)
    pos = jnp.asarray([10, 0, 25], jnp.int32)
    t = 1 if n_valid is None else 3
    wpos, pages, offs, last = ring_write_targets(
        table, pos, t, 4, 3,
        None if n_valid is None else jnp.asarray(n_valid, jnp.int32))
    assert wpos.tolist() == [[10 + j for j in range(t)],
                             list(range(t)),
                             [25 + j for j in range(t)]]
    if n_valid is None:
        # 10 -> page 2 of slot 0's ring; 25 = 2 x 12 + 1 -> page 0 of
        # slot 2's; slot 1 sits the step out
        assert pages.tolist() == [[1 + 2], [0], [7 + 0]]
        assert offs.tolist() == [[2], [0], [1]]
        assert last.tolist() == [10, 0, 25]
    else:
        # 10, 11 in page 2 and 12 -> ring row 0 in page 0 of slot 0
        assert pages.tolist() == [[3, 3, 1], [0, 0, 0], [7, 0, 0]]
        assert offs.tolist() == [[2, 3, 0], [0, 1, 2], [1, 2, 3]]
        assert last.tolist() == [12, -1, 25]


# ---------------------------------------------------------------------------
# allocator + prefix-cache unit tests
# ---------------------------------------------------------------------------
class TestPagedAllocator:
    def test_alloc_free_refcount(self):
        a = PagedKVAllocator(n_pages=4, page_size=8)
        pages = a.alloc(3)
        assert len(set(pages)) == 3 and 0 not in pages
        assert a.in_use() == 3 and a.free_count() == 1
        a.incref(pages[:1])
        a.decref(pages)            # pages[0] survives on the incref
        assert a.in_use() == 1
        a.decref(pages[:1])
        assert a.in_use() == 0 and a.free_count() == 4

    def test_double_free_and_use_after_free_guarded(self):
        a = PagedKVAllocator(n_pages=2, page_size=8)
        (p,) = a.alloc(1)
        a.decref([p])
        with pytest.raises(ValueError, match="double free"):
            a.decref([p])
        with pytest.raises(ValueError, match="use-after-free"):
            a.incref([p])

    def test_oom_is_typed_admission_error_with_retry_after(self):
        a = PagedKVAllocator(n_pages=2, page_size=8)
        a.alloc(2)
        with pytest.raises(KVPagePoolExhaustedError) as ei:
            a.alloc(1)
        # admission semantics: a QueueFullError subclass (HTTP 429)
        # carrying a numeric backoff hint for the Retry-After header
        assert isinstance(ei.value, QueueFullError)
        assert ei.value.retry_after_s and ei.value.retry_after_s > 0
        # all-or-nothing: the failed alloc must not leak pages
        assert a.free_count() == 0 and a.in_use() == 2

    def test_prefix_register_lookup_and_lru_eviction(self):
        a = PagedKVAllocator(n_pages=6, page_size=4)
        pc = PrefixCache(a)
        toks = np.arange(8)               # 2 full pages
        pages = a.alloc(2)
        pc.register(toks, pages)
        a.decref(pages)                   # only the cache holds them
        assert a.in_use() == 2
        hit = pc.lookup(toks)
        assert hit == pages and pc.hits_total == 1
        a.decref(hit)
        # a prompt sharing only the first page still hits
        part = np.concatenate([toks[:4], [9, 9, 9, 9]])
        hit1 = pc.lookup(part)
        assert hit1 == pages[:1]
        a.decref(hit1)
        assert pc.lookup(np.arange(4) + 1) == []      # miss
        # pressure: a 5-page alloc forces LRU eviction. The 2-page
        # chain is the LRU entry (the 1-page chain was touched last);
        # dropping it frees page 1 outright while page 0 survives on
        # the 1-page entry's reference — 5 fresh + 1 cached in use
        got = a.alloc(5, evictor=pc)
        assert len(got) == 5
        assert pc.evictions_total == 1
        assert a.in_use() == 6 and len(pc) == 1
        assert a.refcount(pages[0]) == 1

    def test_eviction_frees_the_pages_an_allocation_needs(self):
        """A prompt of m pages holds m entries over the same pages:
        one ``alloc`` under pressure drops as many as it takes for
        the pages to come free (counting dropped references would
        free nothing and leave the request to be retried a device
        step later, several times over)."""
        a = PagedKVAllocator(n_pages=24, page_size=4)
        pc = PrefixCache(a)
        for k in range(2):                # two finished 12-page prompts
            pages = a.alloc(12)
            assert pc.register(np.arange(48) + 100 * k, pages) == 12
            a.decref(pages)
        assert a.free_count() == 0 and len(pc) == 24
        got = a.alloc(10, evictor=pc)     # one call, no retry
        assert len(got) == 10
        # the older prompt went whole, the newer one stays whole
        assert pc.evictions_total == 12 and len(pc) == 12
        assert pc.lookup(np.arange(48)) == []
        assert len(pc.lookup(np.arange(48) + 100)) == 12
        # what live leases hold cannot be freed: the cache empties
        # and the allocation still fails typed
        with pytest.raises(KVPagePoolExhaustedError):
            a.alloc(15, evictor=pc)
        assert len(pc) == 0 and a.free_count() == 2

    def test_session_reserve_cow_on_full_prompt_hit(self):
        net = _lm()
        sess = net.paged_slot_streaming_session(capacity=CAP,
                                                slots=2, page_size=4)
        prompt = (np.arange(8) % (V - 1)) + 1     # 2 full pages
        lease = sess.reserve(prompt, 4)
        sess.bind(0, lease)
        x = np.zeros((2, 1, 1), np.float32)
        act = np.array([True, False])
        for t in list(prompt) + [1, 1]:
            x[0, 0, 0] = t
            sess.step_slots(x, act)
        sess.release(0, register_prompt=prompt)
        shared_pages = sess.prefix_cache.lookup(prompt)
        sess.allocator.decref(shared_pages)
        # whole prompt covered: resume re-feeds the LAST prompt token,
        # whose page must be COW'd — the shared original keeps its
        # refcount and identity
        lease2 = sess.reserve(prompt, 4)
        assert lease2.resume_pos == len(prompt) - 1
        assert lease2.pages[0] == shared_pages[0]        # shared
        assert lease2.pages[1] != shared_pages[1]        # COW copy
        assert sess.allocator.refcount(shared_pages[1]) >= 1
        sess.allocator.decref(lease2.pages)

    def test_can_ever_fit_and_submit_rejection(self):
        net = _lm()
        cb = ContinuousBatcher(net, slots=2, capacity=CAP,
                               kv_mode="paged", page_size=8,
                               kv_pages=4, name="fit")
        try:
            assert cb._paged
            # 4 pages * 8 tokens = 32-token pool < the 40-token ask
            with pytest.raises(ValueError, match="whole pool"):
                cb.submit(np.arange(8) % V, 32)
        finally:
            cb.shutdown(drain=False)


# ---------------------------------------------------------------------------
# paged-vs-dense parity
# ---------------------------------------------------------------------------
class TestPagedDenseParity:
    def test_greedy_tokens_bit_identical_with_slot_reuse(self):
        """6 requests through 2 slots on BOTH paths (forced slot
        reuse + concurrent neighbours): every greedy token stream
        must match the dense path bit for bit."""
        net = _lm()
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, V, (n,))
                   for n in (5, 3, 9, 4, 7, 6)]
        results = {}
        for mode in ("dense", "paged"):
            cb = ContinuousBatcher(net, slots=2, capacity=CAP,
                                   kv_mode=mode, page_size=8,
                                   name=f"parity_{mode}")
            try:
                assert cb._paged == (mode == "paged")
                handles = [cb.submit(p, 12) for p in prompts]
                results[mode] = [np.asarray(cb.wait(h))
                                 for h in handles]
            finally:
                cb.shutdown(drain=True)
        for a, b in zip(results["dense"], results["paged"]):
            np.testing.assert_array_equal(a, b)

    def test_auto_falls_back_to_dense_for_recurrent_models(self):
        cb = ContinuousBatcher(_rnn_lm(), slots=1, capacity=CAP,
                               kv_mode="auto", name="auto_rnn")
        try:
            assert not cb._paged
            assert cb.kv_debug() is None
            out = cb.generate(np.array([1, 2, 3]), 4)
            assert len(out) == 4
        finally:
            cb.shutdown(drain=True)

    def test_paged_mode_rejects_recurrent_models(self):
        with pytest.raises(ValueError, match="paged"):
            ContinuousBatcher(_rnn_lm(), slots=1, capacity=CAP,
                              kv_mode="paged", name="forced_rnn")

    def test_auto_mode_surfaces_bad_kv_config(self):
        """auto's dense fallback is for UNSUPPORTED MODELS only: an
        invalid pool configuration must raise, never silently select
        the dense session behind the operator's back."""
        for bad in ({"kv_pages": 0}, {"page_size": -3}):
            with pytest.raises(ValueError):
                ContinuousBatcher(_lm(), slots=1, capacity=CAP,
                                  kv_mode="auto", name="badcfg",
                                  **bad)

    def test_more_concurrent_slots_than_dense_limit_at_fixed_mem(self):
        """At a fixed KV budget of 8 pages x 8 tokens = 64 tokens the
        dense session could host floor(64/32) = 2 capacity-32 slots;
        the paged batcher runs 4 streams CONCURRENTLY because each
        reserves only its actual 2-page need."""
        net = _lm(cap=32)
        cb = ContinuousBatcher(net, slots=4, capacity=32,
                               kv_mode="paged", page_size=8,
                               kv_pages=8, name="fixedmem")
        try:
            dense_limit = (8 * 8) // 32
            assert dense_limit == 2
            handles = [cb.submit(np.array([1 + i, 2, 3, 4]), 12)
                       for i in range(4)]
            peak = 0
            for _ in range(400):
                peak = max(peak, cb.active_slots())
                if peak == 4:
                    break
                time.sleep(0.002)
            for h in handles:
                assert len(cb.wait(h)) == 12
            assert peak > dense_limit
            assert peak == 4
        finally:
            cb.shutdown(drain=True)


# ---------------------------------------------------------------------------
# program-cache hygiene (GL002-style regression)
# ---------------------------------------------------------------------------
class TestTemperatureProgramCache:
    def test_temperature_jitter_reuses_one_fused_program(self):
        """Per-request float temperature is a traced operand of the
        fused generate program: 0.7 vs 0.7000001 vs 1.3 must share
        ONE executable (a float cache key would compile per distinct
        temperature — the recompile hazard graftlint GL002 exists
        for), with greedy keeping its own (structurally different)
        program."""
        import jax

        net = _lm()
        sess = net.streaming_session(capacity=CAP, batch=1)
        prompt = np.array([[1, 2, 3]], np.float32)
        for temp in (0.7, 0.7000001, 1.3):
            sess.reset()
            ids = sess.generate(prompt, 4, temperature=temp,
                                fused=True,
                                rng_key=jax.random.PRNGKey(5))
            assert np.asarray(ids).shape == (1, 4)
        assert len(sess._gen_cache) == 1
        sess.reset()
        sess.generate(prompt, 4, temperature=0.0, fused=True)
        assert set(sess._gen_cache) == {(4, False), (4, True)}

    def test_fused_traced_temperature_keeps_id_parity(self):
        """The traced-operand refactor must not change sampling
        math: fused and unfused ids stay identical for the same
        rng_key and temperature."""
        import jax

        net = _lm()
        prompt = np.array([[1, 2, 3]], np.float32)
        key = jax.random.PRNGKey(11)
        s1 = net.streaming_session(capacity=CAP, batch=1)
        ids_u = np.asarray(s1.generate(prompt, 8, temperature=0.8,
                                       rng_key=key))
        s2 = net.streaming_session(capacity=CAP, batch=1)
        ids_f = np.asarray(s2.generate(prompt, 8, temperature=0.8,
                                       rng_key=key, fused=True))
        np.testing.assert_array_equal(ids_u, ids_f)


# ---------------------------------------------------------------------------
# prefix cache end to end over live HTTP
# ---------------------------------------------------------------------------
def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return json.loads(r.read().decode())


def _post(url, body):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read().decode())


class TestPrefixCacheHTTP:
    def test_second_identical_prompt_skips_prefill(self):
        reg = ModelRegistry()
        reg.register("lm", _lm())
        srv = ModelServer(reg, port=0, slots=2, capacity=CAP,
                          page_size=8).start()
        base = f"http://127.0.0.1:{srv.port}"
        try:
            prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]
            body = {"model": "lm", "prompt": prompt, "n_tokens": 6}
            r1 = _post(base + "/v1/generate", body)
            r2 = _post(base + "/v1/generate", body)
            # identical ids — the shared pages hold the same KV
            assert r1["ids"] == r2["ids"]
            # the phase ledger proves the skip: the second request
            # resumed after the 8-token cached page (deterministic
            # attr, not a timing heuristic). The completion ring is
            # appended AFTER the response bytes go out (the finally
            # block must time the respond phase), so poll briefly —
            # on a loaded 2-core host the client can read back
            # before the handler's finally has run
            deadline = time.monotonic() + 5.0
            while True:
                recent = _get(base + "/debug/requests")["recent"]
                gen = [e for e in recent
                       if e["route"] == "/v1/generate"]
                if len(gen) >= 2 or time.monotonic() > deadline:
                    break
                time.sleep(0.02)
            assert gen[-2]["attrs"]["prefix_hit_tokens"] == 0
            assert gen[-1]["attrs"]["prefix_hit_tokens"] == 8
            # /debug/slots carries the pool + prefix-cache state
            kv = next(iter(
                _get(base + "/debug/slots")["backends"].values()))["kv"]
            assert kv["prefix_cache_hits_total"] == 1
            assert kv["kv_pages_total"] > 0
            assert kv["page_size"] == 8
            # ...and the counters are on the Prometheus exposition
            with urllib.request.urlopen(
                    base + "/metrics?format=prometheus",
                    timeout=10) as r:
                text = r.read().decode()
            assert "prefix_cache_hits_total" in text
            assert "kv_pages_in_use" in text
            assert "kv_pages_total" in text
        finally:
            srv.stop(drain=True)

    def test_loadgen_streaming_mode_reports_ttft_itl(self):
        """tools/loadgen generate mode: duplicate-prompt traffic
        through a live server, TTFT/ITL percentiles scraped from the
        server's own histograms."""
        from tools.loadgen import (LoadGen, generate_body_fn,
                                   scrape_streaming_latency)
        reg = ModelRegistry()
        reg.register("lm", _lm())
        srv = ModelServer(reg, port=0, slots=2, capacity=CAP,
                          page_size=8).start()
        base = f"http://127.0.0.1:{srv.port}"
        try:
            body_fn = generate_body_fn(model="lm", prompt_len=10,
                                       n_tokens=4, vocab=V,
                                       dup_ratio=0.5)
            dups = sum(body_fn(i)["prompt"] == body_fn(0)["prompt"]
                       for i in range(100))
            assert 40 <= dups <= 60        # deterministic mix
            rep = LoadGen(base, route="/v1/generate",
                          body_fn=body_fn, concurrency=2,
                          total=8, timeout_s=60).run()
            assert rep["ok"] == 8 and rep["failed"] == 0
            stream = scrape_streaming_latency(base)
            assert stream["serving_ttft_seconds"]["count"] >= 8
            assert stream["serving_itl_seconds"]["count"] > 0
            assert stream["serving_ttft_seconds"]["p50"] >= 0.0
        finally:
            srv.stop(drain=True)


# ---------------------------------------------------------------------------
# speculative decoding
# ---------------------------------------------------------------------------
class TestSpeculativeDecode:
    def test_greedy_parity_perfect_and_poor_draft(self):
        """Accept-prefix speculative decode must emit the target's
        exact greedy ids whatever the draft proposes: a perfect
        draft (the target itself, acceptance 1.0) and an unrelated
        random draft (acceptance ~1/vocab) both match vanilla."""
        target = _lm(0)
        prompt = np.array([[1, 2, 3, 4, 5]])
        ref = np.asarray(
            target.streaming_session(capacity=CAP, batch=1)
            .generate(prompt.astype(np.float32), 20))[0]
        for draft, lo, hi in ((_lm(0), 0.99, 1.01),
                              (_lm(9, width=8), 0.0, 0.9)):
            sd = SpeculativeDecoder(target, draft, k=4, capacity=CAP)
            out = sd.generate(prompt, 20)
            np.testing.assert_array_equal(out, ref)
            assert lo <= sd.acceptance_rate <= hi
            assert sd.tokens_proposed >= 20

    def test_counters_on_shared_registry(self):
        from deeplearning4j_tpu.observability.registry import (
            MetricsRegistry)
        reg = MetricsRegistry()
        sd = SpeculativeDecoder(_lm(0), _lm(0), k=4, capacity=CAP,
                                registry=reg, endpoint="spec")
        sd.generate(np.array([[1, 2, 3]]), 9)
        lbl = {"endpoint": "spec"}
        proposed = reg.get("spec_tokens_proposed_total", labels=lbl)
        accepted = reg.get("spec_tokens_accepted_total", labels=lbl)
        assert proposed.value == sd.tokens_proposed > 0
        assert accepted.value == sd.tokens_accepted
        assert accepted.value <= proposed.value

    def test_rejects_unrewindable_models(self):
        with pytest.raises(ValueError, match="rewind"):
            SpeculativeDecoder(_rnn_lm(), _lm(), k=2, capacity=CAP)
        with pytest.raises(ValueError, match="rewind"):
            SpeculativeDecoder(_lm(), _rnn_lm(), k=2, capacity=CAP)


# ---------------------------------------------------------------------------
# chaos: page refcounts across a worker crash
# ---------------------------------------------------------------------------
@pytest.mark.chaos
class TestPagedCrashRecovery:
    @pytest.fixture(autouse=True)
    def _clean_chaos(self):
        yield
        chaos.uninstall()

    def test_worker_crash_leaks_no_page_refcounts(self):
        """A serving.worker.step crash kills the mid-decode stream;
        its page lease must be released in the crash handler, the
        restarted worker must serve the pending request from a clean
        pool, and after everything drains the allocator must be back
        to every-page-free (refcount-leak regression)."""
        chaos.install({"faults": [{"site": "serving.worker.step",
                                   "kind": "crash", "at": [3]}]},
                      seed=1)
        net = _lm()
        cb = ContinuousBatcher(net, slots=1, capacity=CAP,
                               kv_mode="paged", page_size=8,
                               name="chaos_paged")
        try:
            assert cb._paged
            first = cb.submit(np.array([1, 2, 3]), 4)
            second = cb.submit(np.array([4, 5]), 3)     # pending
            with pytest.raises(chaos.SimulatedCrashError):
                cb.wait(first)
            assert len(cb.wait(second)) == 3            # restarted
            # the pool still decodes correctly after the restart
            out = cb.generate(np.array([1, 2, 3]), 4)
            assert len(out) == 4
            # slot release runs just after the waiter wakes; spin
            # briefly, then the allocator must be every-page-free
            # (neither the crashed stream, the survivor, nor the
            # post-restart request may leak a reference — their
            # prompts have no full page, so nothing is cached)
            for _ in range(200):
                if cb.session.pages_in_use() == 0:
                    break
                time.sleep(0.005)
            assert cb.session.pages_in_use() == 0
            assert cb.session.allocator.free_count() == \
                cb.session.pages_total()
        finally:
            cb.shutdown(drain=True)
