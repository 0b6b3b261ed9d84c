"""Continuous batching over bounded-KV-cache decode sessions.

One-shot dynamic batching (scheduler.py) is wrong for autoregressive
generation: requests finish at different lengths, and draining the
whole batch before admitting new work leaves device slots idle exactly
when traffic is heaviest. This module does iteration-level scheduling
(the Orca/vLLM idea, here over ``models/streaming.py``'s
SlotStreamingSession): a fixed pool of KV-cache slots steps together
and between steps finished slots are recycled to queued requests.
Prompt prefill rides the same steps teacher-forced, so admission
never compiles anything: a step is one of two or three programs,
whoever is in the pool. Over a paged session a step that finds a slot
with prompt tokens to spare is (slots, t, 1), CHUNKED prefill: that
slot feeds its next ``min(t, tokens left)`` prompt tokens, a slot in
decode its one token, and the chunk that carries a prompt's last token
emits the request's first output token; a step whose slots all decode
is (slots, 1, 1). ``t`` follows from the pool (``chunk_width``), and a
pool of many slots holds a second, wider chunk program
(``wide_chunk_width``, at the row budget ``wide_chunk_rows`` reads off
the session), which a step runs only when the prompt rows its slots
have on offer would not fit the narrow one (``_plan_step``).
Over the dense session every step is (slots, 1, 1) and a prompt takes
a step a token.

Admission control mirrors the scheduler (the shared
``serving/lifecycle.py`` plumbing): bounded queue with
``QueueFullError`` shed, per-request deadline checked while queued,
graceful drain. Each slot's logits are bitwise independent of its
neighbours (vmapped B=1 math — slot-reuse parity against a sequential
decode is tested).

Over a paged session the loop runs ONE STEP AHEAD of the device.
Nothing it schedules depends on a token's value (a request ends
after ``n_tokens`` tokens, prompts are known, pages are reserved at
admission), so the step picks each slot's greedy id on the device
(``PagedSlotSession.step_ids``), step n+1 is enqueued feeding those
ids where they lie, and the host fetches step n's ids and their
finite flags, a few hundred bytes, while step n+1 runs: it schedules
ahead from counts and delivers tokens one pass behind. Temperature
sampling stays host-side with a per-request seeded RNG over the full
probability rows (``step_slots`` / ``step_chunk``), which keeps
per-request sampling parameters out of the compiled program: a step
in which such a request emits is taken synchronously, as is every
step under a drain, an armed migration or a prefill export reaching
its export point, and every step of the dense session.

Since the decode-fast-path PR the KV state behind the slots is PAGED
by default (``kv_mode="auto"``): transformer-style models get a
:class:`~deeplearning4j_tpu.models.paged_kv.PagedSlotSession` — a
refcounted page pool with per-slot page tables, so admission asks the
ALLOCATOR (pages for this request's ``prompt + n_tokens`` worst
case) instead of a per-slot capacity bucket, and slot count is
bounded by total KV memory. Repeated prompts hit the prefix cache
and skip the cached part of prefill entirely (the phase ledger
records ``prefix_hit_tokens``). A layer whose state has a fixed size
(a state-space recurrence) keeps it in that session too, a row a
slot, and a network with one shares no prefix. Models with LSTM-style
carries or running statistics fall back to the dense session
(``kv_mode="dense"`` forces it; greedy tokens are bit-identical
either way — tested).
"""

from __future__ import annotations

import queue
import threading
import time
import uuid
from typing import Dict, List, Optional

import numpy as np

from deeplearning4j_tpu import chaos
from deeplearning4j_tpu.observability.tracing import (RequestContext,
                                                      startup, trace)
from deeplearning4j_tpu.serving import tiers
from deeplearning4j_tpu.serving.errors import (KVLeaseError,
                                               KVPagePoolExhaustedError,
                                               ServingError)
from deeplearning4j_tpu.serving.lifecycle import (BaseRequest,
                                                  CircuitBreaker,
                                                  ServingBackend)
from deeplearning4j_tpu.serving.metrics import ServingMetrics

__all__ = ["ContinuousBatcher", "MigrationOffer"]

# Rows of one chunked-prefill step, slots x t. The program's shape is
# (slots, t) whoever is in prefill, so every such step pays for
# slots * t rows through every matmul and every slot's attention: the
# budget is in rows, and the pool's width sets t. Read on the chip at
# 128 / 256 / 512 in both serving cells of the benchmark (PERF.md
# section 6, PR 27): 8 slots serve the same at 128 and 256 and less at
# 512; 64 slots, where nearly every step has some slot in prefill,
# gain at 128 and lose at 256 and 512 where most slots decode
# (``axk1_serve_decode``, read again at PR 42 with every attention kind
# reading by table: 3,130 / 2,745 / 1,615 tokens/s). This is the width
# of the chunk program every chunkable pool holds.
CHUNK_ROWS = 128

# Rows of the second, wider chunk program (``wide_chunk_width``): where
# a bfloat16 matmul that streams its weights turns from memory-bound to
# compute-bound on a v5e (197 TFLOP/s over 819 GB/s is 240 FLOP a byte,
# and a row costs one FLOP a weight byte), so up to here rows ride on
# weights the step reads anyway. A step at this width costs more than a
# narrow one, so it runs only when the rows on offer fill it
# (``_plan_step``). Read on the chip at 128 / 256 / 512 rows in every
# chunk step of four serving cells (PERF.md section 6, PR 42): where
# most slot-steps feed prompts 256 gives 1.28 / 1.11 / 1.05 times the
# tokens/s of 128 for a step 1.21-1.30 times as long. 512 read less
# than 128 in all four THEN, under the expert layers' dense pass (every
# row through every held expert: a step 2.1-2.3 times as long); it is
# the budget of every pool whose experts still pay for a row.
WIDE_CHUNK_ROWS = 256

# The same where every expert layer of the session carries the rows on
# weights it reads anyway (``PagedSlotSession.experts_carry_rows``: the
# grouped pass over the selected pairs, whose time is its hit experts'
# weights for as long as the kernel stays weight-bound,
# ``ops.grouped_experts.weight_bound``). Read parent against change, two
# pairs a cell (my chip runs, PR 46; PERF.md section 6):
# ``lfm2_serve_agent`` 1,844 / 1,833 -> 2,367 / 2,364 tokens/s for a
# step of 14.5 -> 15.4 ms (the kernel 1.604 ms a call where 1.599),
# ``mimo_serve_mixedlen`` 2,634 / 2,683 -> 2,755 / 2,782 (attention and
# the dense MLP grow with the rows there). ``longcat_serve_tooluse``,
# whose kernel turns at 384 rows, read 1,069 -> 797 at this budget
# (builders, PR 43) and keeps 256. Not read past 512: the next width,
# 1,024 rows, is past the turn in every configuration.
GROUPED_CHUNK_ROWS = 512

_NON_FINITE = ("non-finite probabilities in decode step (device fault "
               "or poisoned model output)")


def chunk_width(slots: int, capacity: int,
                rows: Optional[int] = None) -> int:
    """Prompt tokens a slot in prefill may feed a device step: the
    largest power of two with ``slots * t <= rows`` (``CHUNK_ROWS``
    unless given: 16 at 8 slots, 2 at 64), no wider than a slot
    itself. At 1 prefill is token by token."""
    rows = CHUNK_ROWS if rows is None else rows
    t = 1
    while slots * t * 2 <= rows and t * 2 <= capacity:
        t *= 2
    return t


def wide_chunk_rows(session, slots: int, capacity: int) -> int:
    """The row budget of a pool's second chunk program, read off its
    session: ``GROUPED_CHUNK_ROWS`` where the session says that every
    expert layer carries the rows of the step that budget gives on
    weights it reads anyway (``experts_carry_rows``), else
    ``WIDE_CHUNK_ROWS``: a network without expert layers, one whose
    experts take the dense pass at that width (off a TPU, float32, the
    kernel over its fast memory) and one whose kernel's time turns
    there."""
    t = chunk_width(slots, capacity, GROUPED_CHUNK_ROWS)
    return (GROUPED_CHUNK_ROWS if session.experts_carry_rows(t)
            else WIDE_CHUNK_ROWS)


def wide_chunk_width(slots: int, capacity: int, page_size: int,
                     rows: Optional[int] = None) -> int:
    """The width of a pool's second chunk program, or 0 where it holds
    none: ``chunk_width`` under ``rows`` (``WIDE_CHUNK_ROWS`` unless
    given: the batcher gives ``wide_chunk_rows`` of its session), where
    that is wider than the narrow program and the narrow one feeds a
    slot less than a page a step (at 8 slots t is 16 already, and a
    prompt is a small part of a request's steps)."""
    t_lo = chunk_width(slots, capacity)
    t_hi = chunk_width(slots, capacity,
                       WIDE_CHUNK_ROWS if rows is None else rows)
    return t_hi if t_lo < min(t_hi, page_size) else 0


def _migrate_chaos(blob: bytes) -> bytes:
    """The ``serving.kv.migrate`` chaos site, hit once per lease hop
    (export and import): ``error`` raises a transient ChaosIOError
    (an export that fails leaves the stream on the incumbent; a
    failed import makes the router fall back), ``slow`` stalls the
    hop, ``corrupt`` flips one payload byte AFTER the CRC was
    stamped — the importer's integrity check must catch it."""
    fault = chaos.hit("serving.kv.migrate")
    if fault is None:
        return blob
    if fault.kind == "error":
        raise chaos.ChaosIOError(
            f"[chaos] KV lease hop failed at ordinal "
            f"#{fault.ordinal}")
    if fault.kind == "slow":
        time.sleep(float(fault.args.get("delay_s", 0.1)))
        return blob
    if fault.kind == "corrupt" and len(blob) > 8:
        # ordinal-spread flip index: an export-side and an
        # import-side corruption in one run must not XOR the same
        # byte back to clean
        b = bytearray(blob)
        b[-1 - (fault.ordinal % 4)] ^= 0xFF
        return bytes(b)
    return blob


class MigrationOffer:
    """A request completed with an OFFER instead of tokens: the
    draining backend exported the stream's KV lease and parked its
    slot. Whoever holds the response (the fleet router) either
    imports the ``blob`` on a survivor and ``/v1/kv/ack``s the
    ``handle`` (the parked pages free), or ``/v1/kv/resume``s it —
    the stream un-parks and finishes on the incumbent. A parked slot
    nobody claims within the failsafe window auto-resumes."""

    __slots__ = ("handle", "blob", "pos", "tokens_out")

    def __init__(self, handle: str, blob: bytes, pos: int,
                 tokens_out: int):
        self.handle = handle
        self.blob = blob
        self.pos = int(pos)
        self.tokens_out = int(tokens_out)


class _GenRequest(BaseRequest):
    __slots__ = ("prompt", "n_tokens", "temperature", "seed",
                 "prefill_export", "export_extra", "import_blob",
                 "import_state")

    def __init__(self, prompt, n_tokens, temperature, seed, deadline):
        super().__init__(deadline)
        self.prompt = prompt
        self.n_tokens = n_tokens
        self.temperature = temperature
        self.seed = seed
        # disaggregated-serving shapes of the same request: a
        # prefill-only submission completes with an exported lease
        # blob instead of tokens; an imported one starts from a
        # rebuilt lease instead of a cold prefill
        self.prefill_export = False
        self.export_extra: Optional[dict] = None
        self.import_blob: Optional[bytes] = None
        self.import_state: Optional[dict] = None


class _Slot:
    __slots__ = ("req", "feed", "prompt_left", "out", "emitted",
                 "rng", "t_slotted", "t_last_token", "prefix_hit",
                 "parked", "no_migrate")

    def __init__(self, req: _GenRequest, resume: int = 0):
        # ``resume``: prompt positions [0, resume) are already in the
        # KV cache (a prefix-cache hit) — prefill starts at the
        # resume token instead of token 0
        self.req = req
        # the token the next step's row 0 feeds; None once the stream
        # decodes: its last emitted token, ``out[-1]`` when that step
        # was delivered, else the id the step in flight left on the
        # device
        self.feed: Optional[int] = int(req.prompt[resume])
        self.prompt_left = list(int(t)
                                for t in req.prompt[resume + 1:])
        self.prefix_hit = int(resume)
        # ``emitted`` counts the tokens SCHEDULED (a step that emits
        # one was enqueued), ``out`` holds those delivered: the
        # scheduler runs on the count, one step ahead of the values
        self.out: List[int] = []
        self.emitted = 0
        self.rng = (np.random.default_rng(req.seed)
                    if req.temperature > 0 else None)
        self.t_slotted = time.monotonic()
        self.t_last_token: Optional[float] = None
        # parked = mid-migration: the slot holds its pages and is
        # skipped by the device step until acked (released) or
        # resumed (decoding continues here). A resumed stream sets
        # no_migrate — the handoff already failed once; offering it
        # again would ping-pong it forever.
        self.parked = False
        self.no_migrate = False

    @classmethod
    def restored(cls, req: _GenRequest, pos: int, out,
                 rng_state) -> "_Slot":
        """Rebuild a slot from an imported lease: ``pos`` KV
        positions already written elsewhere, ``out`` tokens already
        emitted. An out-empty restore is exactly the prefix-hit
        shape (resume at ``pos``); a mid-decode one re-feeds the
        last emitted token. The sampling rng resumes from the
        exporter's serialized state so temperature streams stay
        bit-identical across the hop."""
        out = [int(t) for t in (out or [])]
        if out:
            s = cls(req, resume=len(req.prompt) - 1)
            s.prompt_left = []
            s.feed = None
            s.out = out
            s.emitted = len(out)
        else:
            s = cls(req, resume=pos)
        s.prefix_hit = int(pos)
        if rng_state is not None and s.rng is not None:
            s.rng.bit_generator.state = rng_state
        return s


class _Step:
    """One device step from its planning to its delivery. ``live``
    and ``emitters`` hold (row, ``_Slot``) and not slot indices: by
    the time an enqueued step's ids reach the host, a slot whose
    stream it finished may already belong to the next request."""

    __slots__ = ("x", "n_valid", "use_prev", "live", "emitters",
                 "sync", "rows", "poison", "result", "n_prompt",
                 "prompt_tokens")

    def __init__(self, x, n_valid, use_prev, live):
        self.x, self.n_valid, self.use_prev = x, n_valid, use_prev
        self.live = live
        # the live slots whose fed rows end with their prompt's last
        # token or the token they decoded last: each emits one token
        self.emitters: list = []
        # collect the step in flight first, and deliver this one in
        # the pass that enqueues it
        self.sync = False
        # some emitter samples from its whole probability row
        self.rows = False
        self.poison = False
        # what the enqueue returned, unfetched: (ids, finite, aux), or
        # (rows, aux) through the row-returning entry points
        self.result = None
        self.n_prompt = self.prompt_tokens = 0


class ContinuousBatcher(ServingBackend):
    """Slot-recycling decode scheduler for one id-input
    (embedding-first) language model.

    ``slots`` is the device batch (the max continuous-batch
    occupancy); ``capacity`` bounds prompt+generation length per
    request.
    """

    def __init__(self, net, slots: int = 4, capacity: int = 256,
                 queue_limit: int = 64,
                 metrics: Optional[ServingMetrics] = None,
                 name: str = "generate", dtype=None,
                 breaker: Optional[CircuitBreaker] = None,
                 version: str = "0", kv_mode: str = "auto",
                 page_size: int = 16,
                 kv_pages: Optional[int] = None,
                 model_name: Optional[str] = None):
        if kv_mode not in ("auto", "paged", "dense"):
            raise ValueError(
                f"kv_mode must be auto|paged|dense, got {kv_mode!r}")
        super().__init__("contbatch", name, queue_limit, slots,
                         metrics, breaker=breaker)
        self._paged = False
        try:
            session = None
            if kv_mode in ("auto", "paged") and hasattr(
                    net, "paged_slot_streaming_session"):
                from deeplearning4j_tpu.models.paged_kv import (
                    PagedSlotSession)
                # auto's dense fallback keys on the SUPPORT predicate
                # only — a real construction error (bad page_size /
                # kv_pages) must surface, not silently select dense
                if PagedSlotSession.supports(net):
                    session = net.paged_slot_streaming_session(
                        capacity=capacity, slots=slots,
                        page_size=page_size, n_pages=kv_pages,
                        dtype=dtype)
                    self._paged = True
                elif kv_mode == "paged":
                    # build anyway for the layer-naming ValueError
                    net.paged_slot_streaming_session(
                        capacity=capacity, slots=slots,
                        page_size=page_size, n_pages=kv_pages,
                        dtype=dtype)
            if session is None:
                session = net.slot_streaming_session(
                    capacity=capacity, slots=slots, dtype=dtype)
            self.session = session
            if self._paged:
                self._register_kv_metrics()
        except BaseException:
            # super().__init__ already registered the queue-depth and
            # circuit-state gauges; a failed construction must not
            # leak them (a leaked gauge pins the half-built backend
            # AND the model via the bound method — the
            # unregister_gauge docstring's warning)
            self._unregister_gauges()
            raise
        # streaming latency (TTFT / inter-token), labeled by model
        # version — a whole-request histogram can't show a
        # first-token stall inside an otherwise-fast stream
        self._stream = self.metrics.streaming(name, version)
        # the worker loop's own view of a step (parts, slot-steps)
        self._steps = self.metrics.batcher_steps(name)
        # chunked prefill: the width of the chunk program, 1 where the
        # session has no chunk entry point (the dense one, a network
        # whose layers mix rows), and of the wide one, 0 where the
        # pool holds none
        self._chunk_t, self._wide_t = 1, 0
        if getattr(self.session, "chunkable", False):
            widest = min(capacity, self.session.chunk_rows_max)
            self._chunk_t = chunk_width(slots, widest)
            # the second, wider chunk program (``_plan_step`` says
            # when it runs); a layer whose step unrolls over the
            # chunk's rows keeps the one width
            if not self.session.unrolls_chunk_rows:
                self._wide_t = wide_chunk_width(
                    slots, widest, self.session.page_size,
                    wide_chunk_rows(self.session, slots, widest))
            self._steps.holds_chunk_rows(slots * self._chunk_t,
                                         slots * self._wide_t)
        if self._wide_t:
            self._steps.holds_wide_program()
        # the widths whose program runs its expert layers as the
        # grouped pass: the session says, of the shapes each has
        grouped = getattr(self.session, "runs_grouped_experts", None)
        self._grouped_t = {
            t for t in (1, self._chunk_t, self._wide_t)
            if t and grouped is not None and grouped(t)}
        if self._grouped_t:
            self._steps.holds_grouped_program()
        self._warmed = False
        # the step whose ids are still on the device: enqueued and
        # scheduled past, not yet delivered (``_loop``). At most one.
        self._inflight: Optional[_Step] = None
        self._collected = [0.0, 0.0]
        self.version = version
        # registry identity (the MODEL name, not the backend name):
        # exported leases carry it so an importing replica can
        # resolve the same model — without it a drain offer can only
        # ever resume on the incumbent
        self.model_name = model_name
        self.slots = slots
        self.capacity = capacity
        self._slots: List[Optional[_Slot]] = [None] * slots
        # admitted-but-unslotted requests live HERE, not in the queue:
        # deadlines must be enforceable while every slot is busy, and
        # a queue.Queue cannot be inspected without draining it
        self._pending: List[_GenRequest] = []
        # weighted-fair slot granting across the tiers pending
        # (worker-thread only — see _next_pending)
        self._picker = tiers.WeightedFairPicker()
        # the request whose KV reservation last failed: admissions
        # HOLD until it fits (or it leaves the pending list), so a
        # big request cannot be starved by a stream of small
        # higher-tier ones each grabbing the pages it was waiting
        # for — the pre-tier FIFO no-starvation contract, kept
        self._kv_blocked: Optional[_GenRequest] = None
        # drain-migration state: request_migration() arms the flag;
        # the worker loop then exports every active paged slot as a
        # MigrationOffer and parks it until acked / resumed /
        # failsafe-expired (migrate_resume_timeout_s)
        self._migrate = threading.Event()
        self._migrate_lock = threading.Lock()
        self._parked: Dict[str, dict] = {}
        self.migrate_resume_timeout_s = 10.0
        self._start_worker()

    # ---- paged-KV observability ----
    def _register_kv_metrics(self) -> None:
        """Pool gauges + prefix-cache counters, Prometheus-named on
        the shared registry and mirrored into the JSON gauges
        snapshot (what the fleet router's prober reads)."""
        reg = self.metrics.registry
        lbl = {"endpoint": self.name}
        sess = self.session
        reg.gauge("kv_pages_in_use",
                  help="KV cache pages currently referenced",
                  labels=lbl, fn=sess.pages_in_use)
        reg.gauge("kv_pages_total",
                  help="KV cache pages in the pool",
                  labels=lbl, fn=sess.pages_total)
        self._prefix_hits = reg.counter(
            "prefix_cache_hits_total",
            help="admissions that reused cached prompt-prefix pages",
            labels=lbl)
        self._prefix_evictions = reg.counter(
            "prefix_cache_evictions_total",
            help="prefix-cache entries LRU-evicted under page "
                 "pressure", labels=lbl)
        self._evictions_seen = 0
        self.metrics.register_gauge(f"{self.name}_kv_pages_in_use",
                                    sess.pages_in_use)
        self.metrics.register_gauge(f"{self.name}_kv_pages_total",
                                    sess.pages_total)
        # JSON-snapshot mirrors of the prefix-cache counters: the
        # fleet router's prober reads the gauges dict, so fleet-wide
        # prefix-cache effectiveness must be summable from there the
        # same way kv_pages_* already are
        cache = sess.prefix_cache
        self.metrics.register_gauge(
            f"{self.name}_prefix_cache_hits_total",
            lambda c=cache: c.hits_total)
        self.metrics.register_gauge(
            f"{self.name}_prefix_cache_evictions_total",
            lambda c=cache: c.evictions_total)
        # disaggregation traffic: prefill handoffs + drain offers
        # leaving this backend, exported streams rebuilt into it
        self._kv_exports = reg.counter(
            "kv_stream_exports_total",
            help="KV leases exported (prefill handoffs + drain "
                 "migration offers)", labels=lbl)
        self._kv_imports = reg.counter(
            "kv_stream_imports_total",
            help="exported streams rebuilt into this backend's "
                 "page pool", labels=lbl)

    def _unregister_gauges(self) -> None:
        super()._unregister_gauges()
        if self._paged:
            self.metrics.unregister_gauge(
                f"{self.name}_kv_pages_in_use")
            self.metrics.unregister_gauge(
                f"{self.name}_kv_pages_total")
            self.metrics.unregister_gauge(
                f"{self.name}_prefix_cache_hits_total")
            self.metrics.unregister_gauge(
                f"{self.name}_prefix_cache_evictions_total")
            lbl = {"endpoint": self.name}
            self.metrics.registry.unregister("kv_pages_in_use",
                                             labels=lbl)
            self.metrics.registry.unregister("kv_pages_total",
                                             labels=lbl)

    def _sync_evictions(self) -> None:
        # evictions happen inside the allocator mid-reserve; bridge
        # the cache's plain count onto the registry counter
        ev = self.session.prefix_cache.evictions_total
        if ev > self._evictions_seen:
            self._prefix_evictions.inc(ev - self._evictions_seen)
            self._evictions_seen = ev

    def _release_slot(self, i: int, register: bool = False) -> None:
        """Recycle slot ``i``: for paged sessions drop its page
        references — registering its prompt's full pages in the
        prefix cache first when the stream completed cleanly."""
        s = self._slots[i]
        if self._paged and s is not None:
            self.session.release(
                i, register_prompt=s.req.prompt if register else None)
        self._slots[i] = None

    # ---- admission ----
    def submit(self, prompt, n_tokens: int, temperature: float = 0.0,
               seed: int = 0,
               timeout: Optional[float] = None,
               ctx=None, tier: Optional[str] = None,
               prefill_export: bool = False,
               export_extra: Optional[dict] = None) -> _GenRequest:
        """Enqueue one generate request. ``prompt`` is a 1-d (or
        (1, T0)) sequence of token ids; returns a waitable handle.
        ``ctx`` is the request's trace context (minted at HTTP
        admission); a fresh unsampled one is created for in-process
        callers so phase attribution covers them too. ``tier`` is
        the priority-admission tier (gold/standard/best_effort):
        under queue pressure the cheapest backlogged tier is evicted
        first and slots are granted weighted-fair."""
        probe = self._admit_guard()
        tier = tiers.parse_tier(tier)
        if prefill_export and not self._paged:
            # the exported artifact IS the page set; a dense session
            # has no portable representation of its cache rows
            raise ServingError(
                f"{self.name!r} decodes over a dense KV session; "
                "prefill export needs kv_mode=paged (or auto with a "
                "transformer model)")
        prompt = np.asarray(prompt)
        if prompt.ndim > 1 and prompt.shape[0] != 1:
            # a (B, T) batch of prompts is NOT one request: silently
            # flattening would concatenate unrelated prompts and
            # generate over the junction
            raise ValueError(
                f"prompt must be one sequence (1-d or (1, T)); got "
                f"shape {prompt.shape} — submit one request per "
                "prompt")
        prompt = prompt.reshape(-1)
        if prompt.size == 0:
            raise ValueError("prompt must contain at least one token")
        if int(n_tokens) < 1:
            raise ValueError(
                f"n_tokens must be >= 1, got {n_tokens}")
        if prompt.size + n_tokens > self.capacity:
            raise ValueError(
                f"prompt ({prompt.size}) + n_tokens ({n_tokens}) "
                f"exceeds slot capacity {self.capacity}")
        if self._paged and not self.session.can_ever_fit(
                prompt.size, n_tokens):
            # admission asks the allocator: a request whose worst
            # case exceeds the WHOLE pool can never be admitted —
            # that is a client error, not transient pressure (which
            # keeps the request pending at slotting time, deadline
            # enforced — see KVPagePoolExhaustedError)
            raise ValueError(
                f"prompt ({prompt.size}) + n_tokens ({n_tokens}) "
                f"needs more KV pages than the whole pool "
                f"({self.session.pages_total()} pages of "
                f"{self.session.page_size} tokens)")
        deadline = (time.monotonic() + timeout
                    if timeout is not None else None)
        if ctx is None:
            ctx = RequestContext(route=self.name, deadline=deadline)
        ctx.attrs["tier"] = tier
        ctx.phase_done("admission", now_in="queue_wait")
        r = _GenRequest(prompt, int(n_tokens), float(temperature),
                        int(seed), deadline)
        r.ctx = ctx
        r.probe = probe
        r.tier = tier
        r.prefill_export = bool(prefill_export)
        r.export_extra = dict(export_extra or {}) if prefill_export \
            else None
        return self._enqueue(r)

    def generate(self, prompt, n_tokens: int, temperature: float = 0.0,
                 seed: int = 0,
                 timeout: Optional[float] = None,
                 ctx=None, tier: Optional[str] = None) -> np.ndarray:
        return self.wait(self.submit(prompt, n_tokens, temperature,
                                     seed, timeout=timeout, ctx=ctx,
                                     tier=tier))

    # ---- disaggregated prefill/decode (models/paged_kv.py leases) --
    def prefill_export(self, prompt, n_tokens: int,
                       temperature: float = 0.0, seed: int = 0,
                       timeout: Optional[float] = None, ctx=None,
                       tier: Optional[str] = None,
                       export_extra: Optional[dict] = None) -> bytes:
        """Run the prompt's prefill (all but the last token) and
        return the stream's serialized KV lease instead of decoding:
        the prefill half of disaggregated serving. The blob imports
        on any replica holding the same model
        (:meth:`import_stream`), which resumes at the last prompt
        token and streams the completion — token-for-token identical
        to running the whole request here."""
        return self.wait(self.submit(
            prompt, n_tokens, temperature, seed, timeout=timeout,
            ctx=ctx, tier=tier, prefill_export=True,
            export_extra=export_extra))

    def import_stream(self, blob: bytes,
                      timeout: Optional[float] = None, ctx=None,
                      tier: Optional[str] = None,
                      header: Optional[dict] = None) -> _GenRequest:
        """Admit an exported stream (a prefill handoff or a
        drain-migration offer): validate the blob, reconstruct the
        request, and queue it for slotting — where the lease is
        rebuilt into this session's page pool and decode resumes
        mid-stream. Corrupt blobs raise
        :class:`~.errors.KVLeaseCorruptError`, version/model skew
        :class:`~.errors.KVLeaseVersionError` (both at submit, both
        mapped to 422 — re-sending a bad blob elsewhere cannot
        help). Pool pressure parks the request pending exactly like
        a cold reservation."""
        from deeplearning4j_tpu.models.paged_kv import parse_lease
        probe = self._admit_guard()
        tier = tiers.parse_tier(tier)
        if not self._paged:
            raise ServingError(
                f"{self.name!r} decodes over a dense KV session; "
                "lease import needs kv_mode=paged")
        blob = _migrate_chaos(bytes(blob))
        if header is None:
            # synchronous integrity gate (callers that already
            # parsed the blob — the HTTP handler resolving the model
            # — pass the header so the payload CRC runs once here
            # and once, authoritatively, at admission)
            header, _ = parse_lease(blob)
        extra = dict(header.get("extra") or {})
        prompt = np.asarray(extra.get("prompt", []),
                            np.int64).reshape(-1)
        n_tokens = int(extra.get("n_tokens", 0))
        if prompt.size == 0 or n_tokens < 1:
            raise KVLeaseError(
                "lease extra lacks the stream state (prompt / "
                "n_tokens) — not a stream export")
        if prompt.size + n_tokens > self.capacity:
            raise ValueError(
                f"imported stream's prompt ({prompt.size}) + "
                f"n_tokens ({n_tokens}) exceeds slot capacity "
                f"{self.capacity}")
        if not self.session.can_ever_fit(prompt.size, n_tokens):
            raise ValueError(
                f"imported stream needs more KV pages than the "
                f"whole pool ({self.session.pages_total()} pages of "
                f"{self.session.page_size} tokens)")
        deadline = (time.monotonic() + timeout
                    if timeout is not None else None)
        if ctx is None:
            ctx = RequestContext(route=self.name, deadline=deadline)
        req_tier = tiers.parse_tier(extra.get("tier")) \
            if extra.get("tier") else tier
        ctx.attrs["tier"] = req_tier
        ctx.phase_done("admission", now_in="queue_wait")
        r = _GenRequest(prompt, n_tokens,
                        float(extra.get("temperature", 0.0)),
                        int(extra.get("seed", 0)), deadline)
        r.ctx = ctx
        r.probe = probe
        r.tier = req_tier
        r.import_blob = blob
        r.import_state = {"pos": int(header.get("pos", 0)),
                          "out": extra.get("out") or [],
                          "rng_state": extra.get("rng_state")}
        return self._enqueue(r)

    def active_slots(self) -> int:
        return sum(1 for s in self._slots if s is not None)

    def _extra_depth(self) -> int:
        return len(self._pending)

    # ---- iteration-level scheduling ----
    def _pump(self, block: bool) -> None:
        """Move everything queued into the pending list (blocking
        briefly only when the batcher is otherwise idle)."""
        try:
            self._pending.append(
                self._queue.get(timeout=0.05 if block else 0.0))
        except queue.Empty:
            return
        while True:
            try:
                self._pending.append(self._queue.get_nowait())
            except queue.Empty:
                return

    def _expire_pending(self) -> None:
        """Deadline enforcement runs EVERY step, including while all
        slots are busy — a waiter must fail at its deadline, not when
        a slot finally frees."""
        now = time.monotonic()
        keep = []
        for r in self._pending:
            if r.deadline is not None and now > r.deadline:
                self._fail_expired(
                    r, "generate request deadline expired while "
                       "queued (decoding never started)")
            else:
                keep.append(r)
        self._pending = keep

    def _next_pending(self) -> int:
        """Index of the next request to slot: WEIGHTED-FAIR across
        the tiers present in the pending list, FIFO within a tier —
        the same smooth-WRR contract the TierQueue enforces on
        dequeue, re-applied here because ``_pump`` drains the queue
        into ``_pending`` wholesale (slots, not dequeues, are this
        backend's scarce resource). Strict priority would let a
        sustained gold stream starve an admitted best-effort
        request forever; the picker gives it the documented ~1/12
        share instead."""
        present = sorted({r.tier for r in self._pending},
                         key=lambda t: tiers.PRIORITY.get(t, 1))
        chosen = self._picker.pick(present)
        return next(i for i, r in enumerate(self._pending)
                    if r.tier == chosen)

    def _admit(self) -> None:
        while self._pending:
            free = [i for i, s in enumerate(self._slots) if s is None]
            if not free:
                return
            if (self._kv_blocked is not None
                    and self._kv_blocked not in self._pending):
                # the blocked request expired / was swept: release
                # the hold
                self._kv_blocked = None
            if self._kv_blocked is not None:
                # pool head-of-line: retry the SAME request until
                # completing slots free enough pages for it —
                # bypassing it would let smaller (or higher-tier)
                # requests eat every freed page and starve it
                nxt = self._pending.index(self._kv_blocked)
            else:
                nxt = self._next_pending()
            resume = 0
            slot_obj = None
            if self._paged and self._pending[nxt].import_blob \
                    is not None:
                # an exported stream re-entering: the lease rebuilds
                # into THIS pool (fresh pages, payload scattered in)
                # and decode resumes where the exporter stopped
                head = self._pending[nxt]
                try:
                    lease, _ = self.session.import_lease(
                        head.import_blob,
                        head.prompt.size + head.n_tokens)
                except KVPagePoolExhaustedError:
                    self._kv_blocked = head
                    return
                except Exception as e:
                    # the blob itself is bad (typed KVLeaseError) —
                    # or something the validators missed: either
                    # way /v1/kv/import is a public surface, and an
                    # escaped exception HERE would crash the worker
                    # loop and fail every active stream, so the
                    # request fails typed and admission continues
                    if not isinstance(e, KVLeaseError):
                        e = KVLeaseError(
                            f"lease import failed: {e!r}")
                    self._pending.pop(nxt)
                    if head is self._kv_blocked:
                        self._kv_blocked = None
                    self._endpoint.count_error()
                    self._deliver_failure(head, e)
                    continue
                r = self._pending.pop(nxt)
                if r is self._kv_blocked:
                    self._kv_blocked = None
                st = r.import_state or {}
                out_toks = st.get("out") or []
                pos_val = int(st.get("pos", lease.resume_pos))
                if not out_toks and pos_val >= r.prompt.size:
                    # an out-empty restore re-feeds prompt[pos]; a
                    # blob claiming more written positions than the
                    # prompt has would index past it — fail typed,
                    # give the reservation back
                    self.session.allocator.decref(lease.pages)
                    self._endpoint.count_error()
                    self._deliver_failure(r, KVLeaseError(
                        f"lease position {pos_val} exceeds the "
                        f"prompt length {r.prompt.size} with no "
                        "emitted tokens"))
                    continue
                self.session.bind(free[0], lease)
                try:
                    slot_obj = _Slot.restored(
                        r, pos_val, out_toks, st.get("rng_state"))
                except Exception as e:
                    # e.g. a malformed rng state: the slot is bound,
                    # so release() returns the pages; the request
                    # fails typed, the worker survives
                    self.session.release(free[0])
                    self._endpoint.count_error()
                    self._deliver_failure(r, KVLeaseError(
                        f"lease stream state failed to restore: "
                        f"{e!r}"))
                    continue
                self._sync_evictions()
                self._kv_imports.inc()
                resume = slot_obj.prefix_hit
                if r.ctx is not None:
                    r.ctx.attrs["kv_imported_tokens"] = resume
                    r.ctx.phase_done(
                        "queue_wait",
                        now_in="decode" if slot_obj.out
                        else "prefill",
                        attrs={"slot": free[0],
                               "kv_imported_tokens": resume})
                self._slots[free[0]] = slot_obj
                continue
            if self._paged:
                # admission asks the allocator: pages for this
                # request's worst case, reusing cached prefix pages.
                # Transient exhaustion parks the request as the
                # sticky pool head (no starvation of big requests —
                # see _kv_blocked); its deadline keeps being
                # enforced meanwhile
                try:
                    lease = self.session.reserve(
                        self._pending[nxt].prompt,
                        self._pending[nxt].n_tokens)
                except KVPagePoolExhaustedError:
                    self._kv_blocked = self._pending[nxt]
                    return
                r = self._pending.pop(nxt)
                if r is self._kv_blocked:
                    self._kv_blocked = None
                self.session.bind(free[0], lease)
                resume = lease.resume_pos
                if lease.prefix_hit_tokens:
                    self._prefix_hits.inc()
                self._sync_evictions()
            else:
                r = self._pending.pop(nxt)
                self.session.reset_slot(free[0])
            if r.ctx is not None:
                # slotted: queue_wait ends, prefill begins (prompt
                # tokens ride the pool's steps teacher-forced, up to
                # ``_chunk_t`` of them a step; a prefix-cache hit
                # resumes AFTER the cached tokens — the ledger
                # records how many were skipped)
                attrs = {"slot": free[0]}
                if resume:
                    attrs["prefix_hit_tokens"] = resume
                # the ledger attr ALSO lands on the context so the
                # /debug/requests completion ring can assert a
                # prefix hit without a sampled span
                r.ctx.attrs["prefix_hit_tokens"] = resume
                r.ctx.phase_done("queue_wait", now_in="prefill",
                                 attrs=attrs)
            slot_obj = _Slot(r, resume)
            self._slots[free[0]] = slot_obj
            if r.prefill_export and not slot_obj.prompt_left:
                # the whole prefill was covered by cached pages (or
                # a one-token prompt): the export point is already
                # here — no device step needed
                self._finish_prefill_export(free[0], slot_obj)

    @staticmethod
    def _sample(probs: np.ndarray, slot: _Slot) -> int:
        if not np.isfinite(probs).all():
            # np.argmax over an all-NaN row silently returns 0 — a
            # poisoned/diverged decode step must fail THIS request
            # loudly, not stream token 0 with a 200
            raise ValueError(_NON_FINITE)
        if slot.req.temperature <= 0:
            return int(np.argmax(probs))
        logits = np.log(probs + 1e-9) / slot.req.temperature
        p = np.exp(logits - logits.max())
        p = p / p.sum()
        return int(slot.rng.choice(p.size, p=p))

    # ---- drain migration (the fleet's zero-downtime replace) ----
    def _stream_extra(self, s: _Slot) -> dict:
        """The stream state a lease blob carries besides the pages:
        everything the importing batcher needs to resume decoding
        bit-identically."""
        extra = {"prompt": [int(t) for t in s.req.prompt],
                 "out": [int(t) for t in s.out],
                 "n_tokens": int(s.req.n_tokens),
                 "temperature": float(s.req.temperature),
                 "seed": int(s.req.seed),
                 "tier": s.req.tier}
        if s.rng is not None:
            extra["rng_state"] = s.rng.bit_generator.state
        if self.model_name is not None:
            extra["model"] = self.model_name
            try:
                extra["version"] = int(self.version)
            except (TypeError, ValueError):
                pass
        if s.req.export_extra:
            extra.update(s.req.export_extra)
        return extra

    def _finish_prefill_export(self, i: int, s: _Slot) -> None:
        """Complete a prefill-only request: serialize the slot's
        lease, donate the fully-written prompt pages to the local
        prefix cache (a later identical prompt prefills free here
        too), and recycle the slot. Runs on the worker thread at the
        export point — every prompt position except the last is in
        the KV cache."""
        ctx = s.req.ctx
        try:
            blob = _migrate_chaos(self.session.export_lease(
                i, extra=self._stream_extra(s)))
        except BaseException as e:
            self._endpoint.count_error()
            self._deliver_failure(s.req, e)
            self._release_slot(i)
            return
        self.session.register_written_prefix(i, s.req.prompt)
        self._kv_exports.inc()
        pos = int(self.session.slot_pos[i])
        s.req.result = blob
        if ctx is not None:
            ctx.attrs["kv_exported_tokens"] = pos
            ctx.phase_done("prefill", now_in="respond",
                           attrs={"kv_exported_tokens": pos})
        s.req.event.set()
        self._release_slot(i)

    def _offer_migration(self, i: int, s: _Slot) -> None:
        """Export one live stream and PARK its slot: the waiting
        request completes with a :class:`MigrationOffer` (the 202
        the router turns into an import-on-survivor), while the
        pages stay resident so a failed handoff can resume here. A
        chaos/export failure is silent: the stream simply keeps
        decoding on this backend — finish-on-incumbent."""
        try:
            blob = _migrate_chaos(self.session.export_lease(
                i, extra=self._stream_extra(s)))
        except BaseException:
            # one failed export decides the stream: it finishes on
            # this backend (re-trying every iteration would gather
            # the pages device→host once per step for nothing)
            s.no_migrate = True
            return
        handle = uuid.uuid4().hex
        with self._migrate_lock:
            self._parked[handle] = {"slot": i, "state": "parked",
                                    "t": time.monotonic()}
        s.parked = True
        self._kv_exports.inc()
        pos = int(self.session.slot_pos[i])
        ctx = s.req.ctx
        offer = MigrationOffer(handle, blob, pos, len(s.out))
        s.req.result = offer
        if ctx is not None:
            ctx.attrs["kv_migrated"] = True
            ctx.phase_done("decode" if s.out else "prefill",
                           now_in="respond",
                           attrs={"kv_migrated": True})
        s.req.event.set()

    def _service_migration(self) -> None:
        """Worker-side migration bookkeeping each iteration: free
        acked slots, un-park resumed or failsafe-expired ones, and
        offer every active stream once migration is armed."""
        if not self._paged:
            return
        now = time.monotonic()
        with self._migrate_lock:
            entries = list(self._parked.items())
        for handle, ent in entries:
            i = ent["slot"]
            s = self._slots[i]
            if s is None:
                with self._migrate_lock:
                    self._parked.pop(handle, None)
                continue
            if ent["state"] == "acked":
                # a survivor owns the stream now: drop the pages
                self._release_slot(i)
                with self._migrate_lock:
                    self._parked.pop(handle, None)
            elif ent["state"] == "resumed":
                # failed handoff: finish here. The original context
                # already closed with the offer response; the
                # resume caller owns the fresh waiter.
                s.req.ctx = None
                s.parked = False
                s.no_migrate = True
                with self._migrate_lock:
                    self._parked.pop(handle, None)
            elif now - ent["t"] > self.migrate_resume_timeout_s:
                # nobody claimed the offer (router died mid-drain, or
                # a non-router caller got the 202): finish the decode
                # so the pages free and the drain completes
                s.req.ctx = None
                s.parked = False
                s.no_migrate = True
                with self._migrate_lock:
                    self._parked.pop(handle, None)
        # an offer exports ``out``: none is made while a step's ids are
        # still on the device (a migration armed after ``_gather_step``
        # looked makes the next step synchronous and is offered then)
        if self._migrate.is_set() and self._inflight is None:
            for i, s in enumerate(self._slots):
                if s is not None and not s.parked \
                        and not s.no_migrate \
                        and not s.req.prefill_export \
                        and not s.req.event.is_set():
                    self._offer_migration(i, s)

    def request_migration(self) -> int:
        """Arm drain migration: every active stream is exported as a
        :class:`MigrationOffer` on the next worker iteration (new
        admissions keep being offered too until the backend stops).
        Returns how many streams were live at the call — dense
        backends return 0 and keep the PR-8 finish-in-place drain."""
        if not self._paged:
            return 0
        n = sum(1 for s in self._slots
                if s is not None and not s.parked)
        self._migrate.set()
        return n

    def resume_stream(self, handle: str):
        """Failed-handoff fallback: un-park the offered stream and
        finish it HERE, returning the completed token array. The
        caller (the router, after an import failed) blocks on the
        backend's usual heartbeat wait."""
        with self._migrate_lock:
            ent = self._parked.get(handle)
            if ent is None or ent["state"] != "parked":
                raise ValueError(
                    f"unknown or already-claimed migration handle "
                    f"{handle!r}")
            s = self._slots[ent["slot"]]
            if s is None:
                self._parked.pop(handle, None)
                raise ValueError(
                    f"migration handle {handle!r} no longer holds a "
                    "stream")
            r = s.req
            r.event = threading.Event()
            r.result = None
            r.error = None
            ent["state"] = "resumed"
        return self.wait(r)

    def has_migration(self, handle: str) -> bool:
        """Does this backend hold the parked stream behind
        ``handle`` (still unclaimed)?"""
        with self._migrate_lock:
            ent = self._parked.get(handle)
            return ent is not None and ent["state"] == "parked"

    def ack_migration(self, handle: str) -> bool:
        """Successful handoff: the survivor imported the lease, so
        the parked slot's pages free on the next worker iteration.
        False when the handle is unknown/claimed (the failsafe may
        have resumed it — the incumbent then finishes a stream the
        survivor also runs; idempotent for the client, who only ever
        sees the survivor's response)."""
        with self._migrate_lock:
            ent = self._parked.get(handle)
            if ent is None or ent["state"] != "parked":
                return False
            ent["state"] = "acked"
        return True

    def prefix_digest(self, limit: int = 512) -> Optional[dict]:
        """The replica-side advertisement for KV-aware routing: this
        backend's page size and the fingerprints of its cached
        prompt prefixes (None on the dense path)."""
        if not self._paged:
            return None
        return {"page_size": self.session.page_size,
                "prefixes":
                    self.session.prefix_cache.fingerprints(limit)}

    def _loop(self) -> None:
        """One pass per device step, and over a paged session one
        step AHEAD of the device: a pass plans step n+1 from counts
        (``_gather_step``), enqueues it, moves the slots on
        (``_advance``), and only then fetches and delivers step n
        (``_fetch``, ``_deliver``), whose ids step n+1 was fed on the
        device. A step that must be synchronous (``_Step.sync``) has
        the step in flight collected first and is delivered in its own
        pass. The parts are timed on every step into
        ``serving_step_seconds``: ``admit`` is the scheduling (both
        halves, before and after the enqueue), ``device`` the enqueue
        (alone in ``serving_step_enqueue_seconds``) and the wait for
        the ids that are due, ``sample`` their delivery; while the
        tracer is on they are ``serve_step/<part>`` spans under one
        ``serve_step``, with ``serve_step/enqueue`` and
        ``serve_step/fetch`` under ``serve_step/device`` (the few
        lines of ``_advance`` run between the two). A pass that found
        no live slot leaves neither."""
        while not self._stop.is_set():
            if self._inflight is None and not self._pending and not any(
                    s is not None and not s.parked for s in self._slots):
                # idle: wait for a request outside any step, so that
                # ``admit`` times work and never the wait
                self._pump(block=True)
            with trace.span("serve_step", annotate=False) as step:
                t0 = time.perf_counter()
                self._collected = [0.0, 0.0]
                with trace.span("serve_step/admit") as admit:
                    st = self._gather_step()
                    if st is None:
                        admit.discard()
                        step.discard()
                        continue
                # chaos site: crash kills the worker (active streams
                # fail with the crash error, the loop restarts), hang
                # stalls a step, poison NaNs this step's logits (each
                # active stream then fails per-slot, never the worker)
                try:
                    fault = chaos.step_fault("serving.worker.step")
                except BaseException as e:
                    self._fail_active(e)
                    raise
                st.poison = fault is not None and fault.kind == "poison"
                prev = self._inflight
                t1 = time.perf_counter()
                with trace.span("serve_step/device"):
                    try:
                        with trace.span("serve_step/enqueue"):
                            self._enqueue_step(st)
                        t_enq = time.perf_counter()
                        self._advance(st)
                        t_adv = time.perf_counter() - t_enq
                        self._inflight = None if st.sync else st
                        due = st if st.sync else prev
                        with trace.span("serve_step/fetch"):
                            got = (None if due is None
                                   else self._fetch(due))
                    except BaseException as e:
                        self._device_failed(e, st, prev)
                        continue
                t2 = time.perf_counter()
                with trace.span("serve_step/sample"):
                    if due is not None:
                        self._deliver(due, got)
                t3 = time.perf_counter()
                chunk = st.x.shape[1] > 1
                fetched, delivered = self._collected
                self._occupancy.record(len(st.live))
                self._steps.record(
                    t1 - t0 - fetched - delivered + t_adv,
                    t2 - t1 - t_adv + fetched, t3 - t2 + delivered,
                    st.n_prompt, len(st.emitters),
                    "chunk" if chunk else "single", st.prompt_tokens,
                    ahead=prev is not None, enqueue_s=t_enq - t1,
                    wide=st.x.shape[1] == self._wide_t,
                    grouped=st.x.shape[1] in self._grouped_t)
                if self._paged:
                    self._steps.record_kv_positions(
                        *self.session.step_kv_positions)
                    if any(self.session.step_ring_pages):
                        self._steps.record_kv_ring(
                            *self.session.step_ring_pages)
                    if self.session.state_pool_bytes:
                        self._steps.record_state_rows(
                            self.session.step_state_restarts,
                            self.session.state_pool_bytes)
                step.set("active", len(st.live))
                step.set("prompt_slots", st.n_prompt)
                step.set("decode_slots", len(st.emitters))
                step.set("rows", int(st.x.shape[1]))
                step.set("prompt_tokens", st.prompt_tokens)
                step.set("ahead", prev is not None)

    def _warm_programs(self) -> None:
        """Compile (or load) every width of the paged step ahead of
        the first step that feeds a token, on a batch whose slots all
        sit the step out (nothing but the scratch page is written):
        which program a step runs depends on who is in the pool, and
        none may compile under live traffic. These are the
        id-returning programs every greedy step runs, the wide one
        last; their row-returning siblings compile when first asked
        for (a request with a temperature). A batcher without a chunk
        program compiles its one step at the first request, as
        before."""
        self._warmed = True
        widths = dict.fromkeys(
            t for t in (self._chunk_t, 1, self._wide_t) if t)
        if len(widths) == 1:
            return
        idle = np.zeros((self.slots,), np.int32)
        try:
            with startup.span("setup/warm_programs",
                              {"widths": list(widths)}):
                for t in widths:
                    self.session.step_ids(
                        np.zeros((self.slots, t, 1), np.float32), idle,
                        idle > 0)
        except BaseException:
            # the step donates the pools: rebuild them, and let the
            # first real step surface a persistent fault to its
            # requests
            try:
                self.session.reinit_states()
            except BaseException:
                pass

    def _fail_active(self, e: BaseException, *steps) -> None:
        """Deliver ``e`` to every slotted stream, and to every stream
        that left its slot with a token still owed it by a step not
        yet delivered (the one in flight, and ``steps``), and recycle
        the slots."""
        for st in steps + (self._inflight,):
            for i, s in (st.emitters if st is not None else ()):
                if self._slots[i] is not s and not s.req.event.is_set():
                    self._endpoint.count_error()
                    s.req.error = e
                    s.req.event.set()
        self._inflight = None
        for i, s in enumerate(self._slots):
            if s is not None:
                self._endpoint.count_error()
                s.req.error = e
                s.req.event.set()
                self._release_slot(i)

    def _device_failed(self, e: BaseException, *steps) -> None:
        """A failed device step poisons every active stream and both
        steps that may be in flight: deliver the error, recycle the
        slots, and REBUILD the session carries: the jitted step
        donates them, so after a mid-call failure the old buffers may
        already be deleted and every later step would die with
        them."""
        self._fail_active(e, *steps)
        try:
            self.session.reinit_states()
        except BaseException:
            pass  # next step surfaces a persistent fault

    def _gather_step(self) -> Optional[_Step]:
        """Everything between two device steps: migration service,
        queue pump, deadline expiry, admission, and the plan of the
        next step (``_plan_step``), or None when no slot is live. The
        step in flight is collected first where what follows needs
        its tokens on the host: before a migration is serviced or a
        drain goes on (the offers export ``out``), before a
        synchronous step, and when the pool ran empty."""
        if self._inflight is not None and (
                self._migrate.is_set() or self._parked
                or self._draining.is_set()):
            self._collect()
        self._service_migration()
        self._pump(block=False)
        self._expire_pending()
        self._admit()
        st = self._plan_step()
        if self._inflight is not None and (st is None or st.sync):
            self._collect()
            st = self._plan_step()
        if st is None:
            if (self._draining.is_set() and self._queue.empty()
                    and not self._pending
                    and not any(s is not None for s in self._slots)):
                # parked slots count: a drain must not complete
                # while an un-acked offer still owns pages
                self._drained.set()
            return None
        if not self._warmed:
            self._warm_programs()
        return st

    def _plan_step(self) -> Optional[_Step]:
        """The next device step, from the slots as they stand and
        without touching them: ``x`` is (slots, rows, 1) and slot ``i``
        feeds its first ``n_valid[i]`` rows (0: free or parked).
        ``rows`` is 1 when no live slot has prompt tokens beyond its
        ``feed``: a pool that only decodes runs the single-token
        program. Else it is the chunk width, and the wide one where
        the pool holds a wide program and what that step would feed
        (a slot in decode 1 row, one in prefill up to the wide width
        of the rows it has left) would not fit in the narrow step's
        ``slots * t`` rows: at twice the width at least half of the
        wide step's rows then do work, at four times it (a pool whose
        session says its expert layers carry the rows) a quarter. A
        decoding slot feeds its last token: from ``out`` where it was
        delivered, else by ``use_prev`` from the ids the step in
        flight leaves on the device."""
        live = [(i, s) for i, s in enumerate(self._slots)
                if s is not None and not s.parked]
        if not live:
            return None
        # the rows a slot can feed; a prefill-only request stops one
        # token short: its export point is every prompt position but
        # the last
        need = lambda s: (1 + len(s.prompt_left)
                          - int(s.req.prefill_export))
        rows = 1
        if any(s.prompt_left for _, s in live):
            rows = self._chunk_t
            if self._wide_t and sum(
                    min(self._wide_t, need(s)) for _, s in live) \
                    >= self.slots * self._chunk_t:
                rows = self._wide_t
        st = _Step(np.zeros((self.slots, rows, 1), np.float32),
                   np.zeros((self.slots,), np.int32),
                   np.zeros((self.slots,), bool), live)
        st.rows = not self._paged
        st.sync = (not self._paged or self._draining.is_set()
                   or self._migrate.is_set() or bool(self._parked))
        for i, s in live:
            n = min(rows, need(s))
            if s.feed is not None:
                st.x[i, 0, 0] = s.feed
            elif len(s.out) == s.emitted:
                st.x[i, 0, 0] = s.out[-1]
            else:
                st.use_prev[i] = True
            st.x[i, 1:n, 0] = s.prompt_left[:n - 1]
            st.n_valid[i] = n
            if n > len(s.prompt_left):
                st.emitters.append((i, s))
                if s.req.temperature > 0:
                    # its seeded NumPy stream samples from the whole
                    # row, on the host and in this pass
                    st.rows = st.sync = True
            elif n == len(s.prompt_left) and s.req.prefill_export:
                st.sync = True      # reaches its export point
        return st

    def _enqueue_step(self, st: _Step) -> None:
        """Hand the planned step to the device; nothing is waited
        for. Greedy steps of a paged session return ids, any other
        the probability rows."""
        sess = self.session
        if st.rows:
            if st.x.shape[1] > 1:
                h = sess.step_chunk(st.x, st.n_valid)
            else:
                h = sess.step_slots(st.x, st.n_valid > 0)
            # an expert layer's counts come back with the rows, in
            # one transfer
            st.result = (h, getattr(sess, "step_aux", None))
        else:
            st.result = sess.step_ids(st.x, st.n_valid, st.use_prev) \
                + (sess.step_aux,)

    def _advance(self, st: _Step) -> None:
        """Move the slots past an enqueued step, from counts alone:
        a slot whose fed rows ended inside its prompt drops them; one
        whose rows carried its prompt's last token, or its last
        emitted token, has one more token scheduled, and at
        ``n_tokens`` of them its stream is over: the slot is
        recycled now, for the next admission, and the token itself
        arrives with ``_deliver``."""
        for i, s in st.live:
            n = int(st.n_valid[i])
            if not s.emitted:
                st.prompt_tokens += n
            if n <= len(s.prompt_left):
                # still prefilling: the next step starts at the
                # first prompt token this one did not feed
                s.feed = s.prompt_left[n - 1]
                del s.prompt_left[:n]
                st.n_prompt += 1
                if not s.prompt_left and s.req.prefill_export:
                    # the export point: every prompt position
                    # except the last is in the KV cache — the
                    # decode replica re-feeds the last token and
                    # samples, bit-identical to staying here
                    self._finish_prefill_export(i, s)
                continue
            s.prompt_left = []
            s.feed = None
            s.emitted += 1
            if s.emitted >= s.req.n_tokens:
                # a stream that ran to its end donates its
                # full-prompt pages to the prefix cache
                self._release_slot(i, register=True)

    def _fetch(self, st: _Step):
        """Wait for an enqueued step and bring back what the host
        reads of it: ids and finite flags, or the probability rows,
        and an expert layer's counts, in one transfer."""
        import jax
        got = jax.device_get(st.result)
        st.result = None
        return got

    def _collect(self) -> None:
        """Fetch and deliver the step in flight outside the pass that
        would have, timed into that pass's ``device`` and ``sample``
        parts."""
        st, self._inflight = self._inflight, None
        t0 = time.perf_counter()
        with trace.span("serve_step/device"):
            try:
                got = self._fetch(st)
            except BaseException as e:
                self._device_failed(e, st)
                return
        t1 = time.perf_counter()
        with trace.span("serve_step/sample"):
            self._deliver(st, got)
        self._collected[0] += t1 - t0
        self._collected[1] += time.perf_counter() - t1

    def _deliver(self, st: _Step, got) -> None:
        """A step's tokens, on the host at last: each emitter's id is
        appended to its stream with the TTFT / ITL stamps and phase
        marks, and a stream that has its ``n_tokens`` completes. An
        emitter whose row was not finite fails alone; one that an
        earlier delivery already failed is passed over."""
        if st.rows:
            (h, aux), ids, finite = got, None, None
            if st.poison:
                h = np.full_like(h, np.nan)
        else:
            ids, finite, aux = got
            if st.poison:
                finite = np.zeros_like(finite)
        for i, s in st.emitters:
            req = s.req
            if req.event.is_set():
                continue
            try:
                if st.rows:
                    nxt = self._sample(h[i, 0], s)
                elif finite[i]:
                    nxt = int(ids[i])
                else:
                    raise ValueError(_NON_FINITE)
            except BaseException as e:
                # per-slot failure (NaN output probabilities) fails
                # only this request — never the worker
                self._endpoint.count_error()
                req.error = e
                req.event.set()
                if self._slots[i] is s:
                    self._release_slot(i)
                elif self._paged:
                    # ``_advance`` took the stream for finished and
                    # donated its prompt's pages: nothing a faulted
                    # stream wrote may be served to a later prompt
                    self.session.prefix_cache.clear()
                continue
            s.out.append(nxt)
            now_t = time.monotonic()
            ctx = req.ctx
            tid = (ctx.trace_id
                   if ctx is not None and ctx.sampled else None)
            if len(s.out) == 1:
                # first emitted token: prefill ends, decode
                # begins; TTFT measured from admission (what the
                # caller actually waited for a first token).
                # Prefix-hit streams (cache hits AND imported
                # leases) land in their own TTFT population so
                # the hit-vs-cold split is scrapeable.
                if ctx is not None:
                    ctx.phase_done("prefill", now_in="decode")
                self._stream.record_ttft(
                    now_t - req.t_submit, trace_id=tid,
                    prefix_hit=s.prefix_hit > 0)
            elif s.t_last_token is not None:
                self._stream.record_itl(
                    now_t - s.t_last_token, trace_id=tid)
            s.t_last_token = now_t
            if len(s.out) >= req.n_tokens:
                req.result = np.asarray(s.out, np.int64)
                if ctx is not None:
                    # decode segment closes BEFORE the event: the
                    # waiter's respond stamp must come after
                    ctx.phase_done(
                        "decode", now_in="respond",
                        attrs={"tokens": len(s.out)})
                req.event.set()
        if aux is not None:
            self._steps.record_experts(aux)

    def slots_debug(self) -> List[dict]:
        """Per-slot state for ``/debug/slots``: what each KV-cache
        slot is doing right now, with the trace id to chase it by.
        Read from request threads while the worker mutates the slot
        list — the snapshot is best-effort, never blocking."""
        now = time.monotonic()
        out = []
        for i, s in enumerate(list(self._slots)):
            if s is None:
                out.append({"slot": i, "state": "free"})
                continue
            entry = {"slot": i,
                     "state": "parked" if s.parked
                     else "prefill" if s.prompt_left else "decode",
                     "tokens_out": len(s.out),
                     "prompt_left": len(s.prompt_left),
                     "prefix_hit_tokens": s.prefix_hit,
                     "age_ms": round((now - s.t_slotted) * 1e3, 3)}
            if self._paged:
                entry["kv_pages"] = self.session.slot_pages(i)
            if s.req.ctx is not None:
                entry["trace_id"] = s.req.ctx.trace_id
                entry["sampled"] = s.req.ctx.sampled
            out.append(entry)
        return out

    def kv_debug(self) -> Optional[dict]:
        """Pool + prefix-cache state for ``/debug/slots`` (None on
        the dense path)."""
        if not self._paged:
            return None
        sess = self.session
        return {"page_size": sess.page_size,
                "kv_pages_total": sess.pages_total(),
                "kv_pages_in_use": sess.pages_in_use(),
                "pages_per_slot": sess.pages_per_slot,
                "prefix_cache_entries": len(sess.prefix_cache),
                "prefix_cache_hits_total":
                    sess.prefix_cache.hits_total,
                "prefix_cache_evictions_total":
                    sess.prefix_cache.evictions_total}

    def _crash_casualties(self):
        # only streams mid-decode die with the crash; _pending
        # (admitted, never slotted — _pump drains the queue
        # aggressively, so queued work effectively lives here) is
        # served by the restarted loop. Their page leases are
        # released HERE (host-side bookkeeping, safe in the crash
        # handler) so refcounts cannot leak across a worker restart
        casualties = []
        st, self._inflight = self._inflight, None
        if st is not None:
            # streams that left their slot with the step in flight
            # still owing them their last token
            casualties.extend(s.req for i, s in st.emitters
                              if self._slots[i] is not s)
        for i, s in enumerate(self._slots):
            if s is not None:
                casualties.append(s.req)
                self._release_slot(i)
        return casualties

    def _abort_inflight(self):
        leftovers = self._crash_casualties()
        leftovers.extend(self._pending)
        self._pending = []
        return leftovers
