"""Disaggregated prefill/decode serving: KV lease export/import,
prefix-aware routing, mid-stream drain migration.

The acceptance pair from ISSUE 15:

- cross-replica resume e2e: a prompt prefilled on replica A streams
  its completion from replica B with the token sequence BIT-IDENTICAL
  to a single-replica run, one trace id spanning
  client → router → prefill → decode;
- drain-migration soak: ``fleet.replace()`` with a pinned mid-stream
  generate session migrates the session to a survivor and the client
  stream completes with zero dropped requests; chaos
  ``serving.kv.migrate`` corrupt/error during the drain falls back to
  finish-on-incumbent, still zero drops.

Plus the satellite contracts: the lease wire format's golden round
trip and typed corrupt/version errors, PrefixCache under concurrent
reserve/release (eviction must never free a page a live lease still
references), and the router's KV-aware prefix routing counters.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import (MultiLayerNetwork,
                                NeuralNetConfiguration, chaos)
from deeplearning4j_tpu.models import paged_kv
from deeplearning4j_tpu.models.paged_kv import (PagedKVAllocator,
                                                PrefixCache,
                                                parse_lease,
                                                prefix_fingerprint,
                                                prefix_fingerprints)
from deeplearning4j_tpu.nn.conf import updaters
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (EmbeddingSequenceLayer,
                                               RnnOutputLayer,
                                               TransformerEncoderLayer)
from deeplearning4j_tpu.serving.continuous import (ContinuousBatcher,
                                                   MigrationOffer)
from deeplearning4j_tpu.serving.errors import (KVLeaseCorruptError,
                                               KVLeaseVersionError,
                                               ServingError)
from deeplearning4j_tpu.serving.fleet import (ReplicaFleet,
                                              parse_roles)
from deeplearning4j_tpu.serving.router import Router

pytestmark = pytest.mark.disagg

V, CAP, PS = 13, 64, 8


def _lm(seed=0, width=16, heads=2, cap=CAP):
    b = (NeuralNetConfiguration.builder().set_seed(seed)
         .updater(updaters.adam(1e-3)).list()
         .layer(EmbeddingSequenceLayer(n_in=V, n_out=width))
         .layer(TransformerEncoderLayer(n_heads=heads, causal=True)))
    conf = (b.layer(RnnOutputLayer(n_out=V, loss="mcxent"))
            .set_input_type(InputType.recurrent(V, cap)).build())
    return MultiLayerNetwork(conf).init()


class SlowLM:
    """The shared tiny transformer with a throttled paged decode
    step, so a stream has real wall-clock life for the drain
    drills."""

    def __init__(self, delay=0.0):
        self.net = _lm()
        self.delay = delay

    @property
    def layers(self):
        return self.net.layers

    def paged_slot_streaming_session(self, **kw):
        s = self.net.paged_slot_streaming_session(**kw)
        if self.delay:
            d = self.delay

            def slowed(orig):
                def slow(*a):
                    time.sleep(d)
                    return orig(*a)
                return slow

            # greedy steps take ``step_ids``, sampled ones the rows
            s.step_slots = slowed(s.step_slots)
            s.step_ids = slowed(s.step_ids)
        return s

    def slot_streaming_session(self, **kw):
        return self.net.slot_streaming_session(**kw)


PROMPT = (np.arange(1, 12) % V).tolist()


@pytest.fixture(scope="module")
def net():
    return _lm()


@pytest.fixture(scope="module")
def reference_ids(net):
    """Single-backend greedy completions — every cross-replica path
    must reproduce these bit-for-bit."""
    cb = ContinuousBatcher(net, slots=2, capacity=CAP,
                           kv_mode="paged", page_size=PS,
                           name="ref")
    try:
        return {n: np.asarray(cb.generate(PROMPT, n)).tolist()
                for n in (12, 40)}
    finally:
        cb.shutdown(drain=False)


def _post(base, path, body, timeout=60.0, headers=None):
    req = urllib.request.Request(
        base + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json",
                 **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read().decode()), \
                dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode()), dict(e.headers)


# ---------------------------------------------------------------------------
# lease wire format
# ---------------------------------------------------------------------------
class TestLeaseWire:
    def _prefill(self, sess, prompt, n_tokens):
        lease = sess.reserve(prompt, n_tokens)
        sess.bind(0, lease)
        x = np.zeros((2, 1, 1), np.float32)
        active = np.array([True, False])
        for t in range(len(prompt) - 1):
            x[0, 0, 0] = prompt[t]
            sess.step_slots(x, active)
        return lease

    def _decode(self, sess, feed, n):
        x = np.zeros((2, 1, 1), np.float32)
        active = np.array([True, False])
        out, f = [], int(feed)
        for _ in range(n):
            x[0, 0, 0] = f
            h = np.asarray(sess.step_slots(x, active))
            f = int(np.argmax(h[0, 0]))
            out.append(f)
        return out

    def test_golden_round_trip_bit_identical(self, net):
        sA = net.paged_slot_streaming_session(capacity=CAP, slots=2,
                                              page_size=PS)
        sB = net.paged_slot_streaming_session(capacity=CAP, slots=2,
                                              page_size=PS)
        prompt = np.asarray(PROMPT)
        self._prefill(sA, prompt, 8)
        blob = sA.export_lease(0, extra={"k": "v"})
        lease, extra = sB.import_lease(blob,
                                       total_tokens=prompt.size + 8)
        assert extra == {"k": "v"}
        sB.bind(0, lease)
        assert int(sB.slot_pos[0]) == int(sA.slot_pos[0])
        a = self._decode(sA, prompt[-1], 8)
        b = self._decode(sB, prompt[-1], 8)
        assert a == b

    def test_corrupt_and_version_skew_fail_typed(self, net):
        sA = net.paged_slot_streaming_session(capacity=CAP, slots=2,
                                              page_size=PS)
        self._prefill(sA, np.asarray(PROMPT), 8)
        blob = sA.export_lease(0)
        # payload bit flip → CRC catches it
        bad = blob[:-3] + bytes([blob[-3] ^ 0xFF]) + blob[-2:]
        with pytest.raises(KVLeaseCorruptError):
            parse_lease(bad)
        # truncation
        with pytest.raises(KVLeaseCorruptError):
            parse_lease(blob[:10])
        # not a lease at all
        with pytest.raises(KVLeaseCorruptError):
            parse_lease(b"ZZZZ" + blob[4:])
        # wire-version skew (frame re-sealed with a valid trailing
        # CRC so only the version differs)
        import struct as _struct
        import zlib as _zlib
        hdr, payload = parse_lease(blob)
        h2 = json.dumps(dict(hdr, version=99)).encode()
        frame = (paged_kv._LEASE_MAGIC
                 + _struct.pack("<I", len(h2)) + h2 + payload)
        skew = frame + _struct.pack(
            "<I", _zlib.crc32(frame) & 0xFFFFFFFF)
        with pytest.raises(KVLeaseVersionError):
            parse_lease(skew)
        # a header bit flip (not just payload) must fail typed too:
        # flip one byte INSIDE the JSON header region
        at = len(paged_kv._LEASE_MAGIC) + 4 + 10
        hdr_flip = (blob[:at] + bytes([blob[at] ^ 0xFF])
                    + blob[at + 1:])
        with pytest.raises(KVLeaseCorruptError):
            parse_lease(hdr_flip)
        # page-size mismatch is version skew at import time
        sC = net.paged_slot_streaming_session(capacity=CAP, slots=2,
                                              page_size=16)
        with pytest.raises(KVLeaseVersionError):
            sC.import_lease(blob, total_tokens=32)

    def test_fingerprints_match_cache_advertisement(self, net):
        sess = net.paged_slot_streaming_session(
            capacity=CAP, slots=2, page_size=PS)
        prompt = np.asarray(PROMPT)
        lease = sess.reserve(prompt, 4)
        sess.bind(0, lease)
        x = np.zeros((2, 1, 1), np.float32)
        active = np.array([True, False])
        for t in range(len(prompt)):
            x[0, 0, 0] = prompt[t]
            sess.step_slots(x, active)
        sess.release(0, register_prompt=prompt)
        fps = sess.prefix_cache.fingerprints()
        # the router computes the SAME digests from the raw prompt
        assert prefix_fingerprint(prompt, PS) in fps
        longest = prefix_fingerprints(prompt, PS)[0]
        assert longest == (PS, prefix_fingerprint(prompt, PS))


# ---------------------------------------------------------------------------
# PrefixCache under concurrent reserve/release
# ---------------------------------------------------------------------------
class TestPrefixCacheConcurrency:
    def test_eviction_never_frees_live_lease_pages(self):
        """LRU eviction racing in-flight leases: refcount guards
        must hold (a double free / use-after-free raises), and the
        pool must account exactly once everything is released."""
        alloc = PagedKVAllocator(n_pages=12, page_size=4)
        cache = PrefixCache(alloc)
        errors = []
        stop = threading.Event()

        def churn(seed):
            rng = np.random.default_rng(seed)
            try:
                while not stop.is_set():
                    n = int(rng.integers(1, 4))
                    try:
                        pages = alloc.alloc(n, evictor=cache)
                    except Exception as e:
                        # typed exhaustion is fine; guard trips are
                        # not
                        if "exhausted" not in str(e):
                            raise
                        continue
                    if rng.random() < 0.5:
                        tokens = rng.integers(
                            0, 50, (len(pages) * 4,))
                        cache.register(tokens, pages)
                        chain = cache.lookup(tokens)
                        if chain:
                            alloc.decref(chain)
                    alloc.decref(pages)
            except Exception as e:      # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=churn, args=(i,),
                                    daemon=True) for i in range(4)]
        for t in threads:
            t.start()
        time.sleep(1.0)
        stop.set()
        for t in threads:
            t.join(5.0)
        assert not errors, errors
        cache.clear()
        assert alloc.free_count() == 12    # every page accounted

    def test_cow_boundary_page_keeps_shared_prefix_clean(self, net):
        """A full-prompt hit copies the boundary page before the
        re-fed token's write; the cached chain's page must stay
        bit-identical for the next hit — asserted via decode ids."""
        cb = ContinuousBatcher(net, slots=2, capacity=CAP,
                               kv_mode="paged", page_size=PS,
                               name="cow")
        try:
            prompt = (np.arange(0, 16) % V).tolist()   # 2 full pages
            cold = np.asarray(cb.generate(prompt, 6)).tolist()
            # repeated hits COW the boundary page each time; ids must
            # never drift (a corrupted shared page would change them)
            for _ in range(3):
                again = np.asarray(cb.generate(prompt, 6)).tolist()
                assert again == cold
            assert cb.session.prefix_cache.hits_total >= 3
        finally:
            cb.shutdown(drain=False)


# ---------------------------------------------------------------------------
# batcher-level handoff
# ---------------------------------------------------------------------------
class TestBatcherHandoff:
    def test_prefill_export_import_bit_identical(self, net,
                                                 reference_ids):
        A = ContinuousBatcher(net, slots=2, capacity=CAP,
                              kv_mode="paged", page_size=PS,
                              name="hA")
        B = ContinuousBatcher(net, slots=2, capacity=CAP,
                              kv_mode="paged", page_size=PS,
                              name="hB")
        try:
            blob = A.prefill_export(PROMPT, 12)
            ids = np.asarray(B.wait(B.import_stream(blob))).tolist()
            assert ids == reference_ids[12]
            assert A._kv_exports.value == 1
            assert B._kv_imports.value == 1
        finally:
            A.shutdown(drain=False)
            B.shutdown(drain=False)

    def test_temperature_stream_resumes_bit_identical(self, net):
        """The rng state rides the lease: a sampled stream crossing
        the hop draws the same tokens it would have locally."""
        A = ContinuousBatcher(net, slots=2, capacity=CAP,
                              kv_mode="paged", page_size=PS,
                              name="tA")
        B = ContinuousBatcher(net, slots=2, capacity=CAP,
                              kv_mode="paged", page_size=PS,
                              name="tB")
        C = ContinuousBatcher(net, slots=2, capacity=CAP,
                              kv_mode="paged", page_size=PS,
                              name="tC")
        try:
            ref = np.asarray(C.generate(
                PROMPT, 10, temperature=0.8, seed=42)).tolist()
            blob = A.prefill_export(PROMPT, 10, temperature=0.8,
                                    seed=42)
            ids = np.asarray(B.wait(B.import_stream(blob))).tolist()
            assert ids == ref
        finally:
            for b in (A, B, C):
                b.shutdown(drain=False)

    def test_prefill_export_needs_paged(self, net):
        dense = ContinuousBatcher(net, slots=2, capacity=CAP,
                                  kv_mode="dense", name="dense")
        try:
            with pytest.raises(ServingError):
                dense.prefill_export(PROMPT, 4)
        finally:
            dense.shutdown(drain=False)


# ---------------------------------------------------------------------------
# fleet / router e2e
# ---------------------------------------------------------------------------
@pytest.fixture()
def stack():
    built = []

    def build(n=2, roles=None, delay=0.0):
        def factory():
            return {"lm": SlowLM(delay=delay)}

        fleet = ReplicaFleet(
            factory, n=n, roles=roles,
            server_kwargs=dict(slots=2, capacity=CAP,
                               page_size=PS)).start()
        router = Router(fleet, probe_interval_s=0.1,
                        probe_timeout_s=1.0, hedge_after_s=None,
                        request_timeout_s=60.0,
                        sample_rate=1.0).start()
        built.append((fleet, router))
        return fleet, router

    yield build
    chaos.uninstall()
    for fleet, router in built:
        router.stop()
        fleet.stop(drain=False, timeout=3.0)


class TestDisaggE2E:
    def test_cross_replica_resume_bit_identical(self, stack,
                                                reference_ids):
        """ACCEPTANCE: prefill on replica A, decode on replica B,
        token sequence identical to a single-replica run, one trace
        id across the whole hop."""
        fleet, router = stack(n=2, roles=["prefill", "decode"])
        base = f"http://127.0.0.1:{router.port}"
        tp = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
        st, out, hdrs = _post(base, "/v1/generate",
                              {"model": "lm", "prompt": PROMPT,
                               "n_tokens": 12},
                              headers={"traceparent": tp})
        assert st == 200
        assert out["ids"] == reference_ids[12]
        # one trace id client → router → prefill → decode
        assert hdrs.get("traceparent", "")[3:35] == "ab" * 16
        assert router._kv_handoffs.value == 1
        assert router._kv_fallbacks.value == 0
        # the work really split: prefill replica exported, decode
        # replica imported
        lbl = {"endpoint": "generate/lm/v1"}
        per = {r.role: r.server.metrics.registry
               for r in fleet.snapshot()}
        assert per["prefill"].get("kv_stream_exports_total",
                                  labels=lbl).value == 1
        assert per["decode"].get("kv_stream_imports_total",
                                 labels=lbl).value == 1

    def test_prefix_aware_routing_counts(self, stack,
                                         reference_ids):
        """The second identical prompt routes to the replica whose
        prefix cache holds it (router_kv_routed_total /
        router_prefix_hit_tokens_total)."""
        fleet, router = stack(n=2)
        base = f"http://127.0.0.1:{router.port}"
        st, out, _ = _post(base, "/v1/generate",
                           {"model": "lm", "prompt": PROMPT,
                            "n_tokens": 12})
        assert st == 200 and out["ids"] == reference_ids[12]
        deadline = time.monotonic() + 10.0
        while (not any(v.prefix_fps for v in
                       router._views.values())
               and time.monotonic() < deadline):
            time.sleep(0.05)        # a probe must scrape the ad
        st, out, _ = _post(base, "/v1/generate",
                           {"model": "lm", "prompt": PROMPT,
                            "n_tokens": 12})
        assert st == 200 and out["ids"] == reference_ids[12]
        assert router._kv_routed.value >= 1
        assert router._prefix_hit_tokens.value >= PS
        # the serving replicas' hit counters reach the autoscaler
        # surface too
        sig = router.load_signals()
        assert all("prefix_cache_hits_total" in s for s in sig)
        assert all("role" in s for s in sig)


class TestDrainMigration:
    def _stream_async(self, base, session, n_tokens=40):
        res = {}

        def run():
            res["r"] = _post(base, "/v1/generate",
                             {"model": "lm", "prompt": PROMPT,
                              "n_tokens": n_tokens,
                              "session": session})

        t = threading.Thread(target=run, daemon=True)
        t.start()
        return t, res

    def _pinned_pos(self, fleet, router):
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            pins = router.pinned_sessions()
            if pins:
                rid = next(iter(pins))
                for i, r in enumerate(fleet.snapshot()):
                    if r.id == rid:
                        return i, rid
            time.sleep(0.02)
        raise AssertionError("stream never pinned")

    def test_replace_migrates_pinned_stream_zero_drops(
            self, stack, reference_ids):
        """ACCEPTANCE: a pinned mid-stream generate session rides a
        fleet.replace() onto a survivor; the client stream completes
        bit-identically, nothing drops, and the drain finishes in
        migration time, not stream time."""
        fleet, router = stack(n=2, delay=0.02)
        base = f"http://127.0.0.1:{router.port}"
        t, res = self._stream_async(base, "soak-1")
        time.sleep(0.6)              # provably mid-decode
        pos, rid = self._pinned_pos(fleet, router)
        fleet.replace(pos, drain_timeout=30.0)
        t.join(60.0)
        st, out, _ = res["r"]
        assert st == 200
        assert out["ids"] == reference_ids[40]
        assert router._kv_migrations.value >= 1
        # the session's pin moved off the retired replica
        assert rid not in router.pinned_sessions()

    def test_corrupt_chaos_falls_back_to_incumbent(
            self, stack, reference_ids):
        """ACCEPTANCE: serving.kv.migrate corrupt during the drain —
        the import fails typed on every survivor, the router resumes
        the stream on the incumbent, still zero drops."""
        fleet, router = stack(n=2, delay=0.02)
        base = f"http://127.0.0.1:{router.port}"
        chaos.install({"faults": [{"site": "serving.kv.migrate",
                                   "kind": "corrupt", "p": 1.0}]},
                      seed=3)
        t, res = self._stream_async(base, "soak-2")
        time.sleep(0.6)
        pos, rid = self._pinned_pos(fleet, router)
        fleet.replace(pos, drain_timeout=30.0)
        t.join(60.0)
        st, out, _ = res["r"]
        assert st == 200
        assert out["ids"] == reference_ids[40]
        assert router._kv_resumes.value >= 1
        assert router._kv_migrations.value == 0

    def test_error_chaos_finishes_on_incumbent(self, stack,
                                               reference_ids):
        """serving.kv.migrate error: the export itself fails, no
        offer is ever made — the stream finishes in place exactly
        like the PR-8 drain, zero drops."""
        fleet, router = stack(n=2, delay=0.02)
        base = f"http://127.0.0.1:{router.port}"
        chaos.install({"faults": [{"site": "serving.kv.migrate",
                                   "kind": "error", "p": 1.0}]},
                      seed=5)
        t, res = self._stream_async(base, "soak-3")
        time.sleep(0.6)
        pos, _ = self._pinned_pos(fleet, router)
        fleet.replace(pos, drain_timeout=30.0)
        t.join(60.0)
        st, out, _ = res["r"]
        assert st == 200
        assert out["ids"] == reference_ids[40]
        assert router._kv_migrations.value == 0
        assert router._kv_resumes.value == 0


# ---------------------------------------------------------------------------
# roles / CLI plumbing
# ---------------------------------------------------------------------------
class TestRoles:
    def test_parse_roles(self):
        assert parse_roles("prefill=1,decode=3") == \
            ["prefill", "decode", "decode", "decode"]
        assert parse_roles(None, 2) == ["mixed", "mixed"]
        with pytest.raises(ValueError):
            parse_roles("turbo=2")
        with pytest.raises(ValueError):
            parse_roles("prefill=1", 3)

    def test_replace_successor_inherits_role(self, stack):
        fleet, router = stack(n=2, roles=["prefill", "decode"])
        fleet.replace(0, drain_timeout=10.0)
        roles = sorted(r.role for r in fleet.snapshot())
        assert roles == ["decode", "prefill"]

    def test_serve_fleet_cli_rejects_bad_roles(self):
        from deeplearning4j_tpu.cli import main
        with pytest.raises(SystemExit):
            main(["serve-fleet", "--model", "m.zip",
                  "--replicas", "2", "--roles", "prefill=1"])
