"""Paged (block) KV cache: allocator, prefix cache, slot session.

The dense ``SlotStreamingSession`` reserves ``capacity`` cache rows
per slot up front, so slot count is bounded by ``slots x capacity``
KV memory whether or not the streams use it — the shape-bucket
ceiling the ROADMAP "decode fast path" item names. This module is the
vLLM-style paged memory model over the same layer math:

- **PagedKVAllocator** — one physical pool of fixed-size pages per
  model (per attention layer: a ``(n_pages, page_size, H * Dh)``
  buffer, allocated once). Pages are refcounted; a request reserves
  only the pages its ``prompt + n_tokens`` worst case needs, so
  concurrent slot count is bounded by TOTAL KV memory, not by
  per-slot capacity. Exhaustion is a typed admission error
  (``KVPagePoolExhaustedError``, HTTP 429 + ``Retry-After``), never
  an OOM mid-decode: reservation is up-front.
- **PrefixCache** — prompt-prefix reuse across requests: when a
  stream completes, the pages FULLY covered by its prompt become
  immutable and are registered under the rolling hash chain of the
  prompt's page-aligned prefixes. A later request whose prompt starts
  with a cached prefix points its page table at the shared pages
  (refcounted) and resumes prefill AFTER them — repeated-prompt
  traffic skips prefill. Shared pages are read-only; the one write
  a resumed stream must make inside a shared page (re-feeding the
  last prompt token when the whole prompt was covered) triggers
  copy-on-write. Cache entries are LRU-evicted when the allocator
  runs dry.
- **PagedSlotSession** — the continuous-batching substrate over page
  tables: one jitted step at two widths. ``step_slots`` is the
  (slots, 1) decode step; ``step_chunk`` is (slots, t), chunked
  prefill: a slot feeds its next ``n_valid`` tokens, up to t of its
  prompt or the one it decodes, in one call. Each attention layer
  writes new k/v into the slot's pages and attends over the
  positions the slot holds (``apply_stream_paged``): on a TPU page by
  page through the slot's table and no further than its length
  (``ops/paged_attention.py``), elsewhere over the slot's GATHERED
  virtual cache. With
  ``pages_per_slot * page_size`` equal to the dense capacity the
  math is position-for-position identical to the dense path —
  greedy-token parity is tested, and so is the chunk step against
  the same tokens fed one by one. ``step_ids`` runs the same step at
  either width and returns, in place of the probability rows, each
  slot's greedy id and a flag that its row was finite, both left on
  the device; a slot may take the id the previous such step picked
  for it as its first row, so a decode loop need not wait for one
  step's ids before it enqueues the next.

Three kinds of cache live in one session: the allocator's pages, as
above, a ring of pages a slot owns, and one fixed-size row a slot.
Each layer DECLARES its kind (``nn.conf.layers.paged.PagedLayer``,
which says what a layer gives to be served and what each kind means);
the session reads the declaration once, when it is built.

Page id 0 is a reserved scratch page: a slot that sits a step out is
given an all-zero page-table row, and the chunk step sends every row
past a slot's ``n_valid`` there, so dummy writes land in scratch and
can never corrupt a live page. The allocator hands out ids
``1..n_pages``.
"""

from __future__ import annotations

import json
import struct
import threading
import zlib
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from deeplearning4j_tpu.nn.conf.layers.paged import (PAGES, RING, STATE,
                                                     PagedLayer)
from deeplearning4j_tpu.serving.errors import (KVLeaseCorruptError,
                                               KVLeaseVersionError,
                                               KVPagePoolExhaustedError)

__all__ = ["PagedKVAllocator", "PrefixCache", "PagedSlotSession",
           "prefix_fingerprint", "prefix_fingerprints", "parse_lease",
           "LEASE_WIRE_VERSION"]


# the step consumes the pools it is given and returns their successors
_DONATE_POOLS = {"donate_argnums": (2,)}

_NOT_CHUNKABLE = ("this network has a layer that is not pointwise in "
                  "time beside its caches; feed it through ")


def _pages_for(tokens: int, page_size: int) -> int:
    return -(-int(tokens) // int(page_size))


def _no_paged_analog(layer) -> bool:
    """Does ``layer`` carry state that this session has no pool for
    (an LSTM-style carry or a running statistic)?"""
    return not hasattr(layer, "apply_stream_paged") and (
        hasattr(layer, "zero_state") or hasattr(layer, "apply_stream"))


def _params_dtype(net):
    import jax
    import jax.numpy as jnp
    kinds = {leaf.dtype for leaf in jax.tree_util.tree_leaves(net.params)}
    return kinds.pop() if len(kinds) == 1 else jnp.float32


def _np_dtype(name: str) -> np.dtype:
    """A pool leaf's dtype from its name in a lease header; the half
    types numpy lacks (bfloat16) are jax's."""
    import jax.numpy as jnp
    return np.dtype(getattr(jnp, name, None) or name)


# ---------------------------------------------------------------------------
# prefix fingerprints — the router-side half of KV-aware routing
# ---------------------------------------------------------------------------

def _prefix_bytes(tokens, n_tokens: Optional[int] = None) -> bytes:
    arr = np.asarray(tokens).reshape(-1)
    if n_tokens is not None:
        arr = arr[:int(n_tokens)]
    return np.ascontiguousarray(arr, dtype=np.int64).tobytes()


def prefix_fingerprint(tokens, n_tokens: Optional[int] = None) -> str:
    """8-hex digest of a page-aligned token prefix — the SAME bytes
    :class:`PrefixCache` keys on, so a fingerprint computed by the
    fleet router from a request's prompt matches the one a replica
    advertises for its cached entry. A routing hint, not an identity
    check: a (1-in-4-billion) collision merely routes to a replica
    without the prefix, which then prefills cold."""
    return format(zlib.crc32(_prefix_bytes(tokens, n_tokens))
                  & 0xFFFFFFFF, "08x")


def prefix_fingerprints(tokens, page_size: int) -> List[Tuple[int, str]]:
    """``[(n_tokens, fingerprint)]`` for every page-aligned prefix of
    the prompt, LONGEST FIRST — the probe order for "which replica
    holds my longest cached prefix". Runs on the router's routing
    hot path, so the digests are computed in ONE pass with a running
    crc32 (a from-scratch hash per prefix would make routing
    O(prompt² / page_size))."""
    tokens = np.asarray(tokens).reshape(-1)
    ps = int(page_size)
    data = _prefix_bytes(tokens)
    stride = ps * 8                    # int64 bytes per page
    crc = 0
    out = []
    for n in range(1, tokens.size // ps + 1):
        crc = zlib.crc32(data[(n - 1) * stride:n * stride], crc)
        out.append((n * ps, format(crc & 0xFFFFFFFF, "08x")))
    out.reverse()
    return out


# ---------------------------------------------------------------------------
# lease wire format
# ---------------------------------------------------------------------------

_LEASE_MAGIC = b"DKVL"
LEASE_WIRE_VERSION = 1


def parse_lease(blob: bytes) -> Tuple[dict, bytes]:
    """Split and validate a serialized lease: ``(header, payload)``.
    Bad magic / truncation / CRC mismatch raise
    :class:`KVLeaseCorruptError`; an unknown wire version raises
    :class:`KVLeaseVersionError`. Schema-vs-session compatibility is
    the importing session's job (:meth:`PagedSlotSession
    .import_lease`) — this function needs no model."""
    if not isinstance(blob, (bytes, bytearray, memoryview)):
        raise KVLeaseCorruptError(
            f"lease blob must be bytes, got {type(blob).__name__}")
    blob = bytes(blob)
    if len(blob) < len(_LEASE_MAGIC) + 8 \
            or blob[:len(_LEASE_MAGIC)] != _LEASE_MAGIC:
        raise KVLeaseCorruptError(
            "not a KV lease blob (bad magic or truncated header)")
    frame, tail = blob[:-4], blob[-4:]
    (frame_crc,) = struct.unpack("<I", tail)
    computed = zlib.crc32(frame) & 0xFFFFFFFF
    if computed != frame_crc:
        raise KVLeaseCorruptError(
            f"lease frame CRC mismatch (stored {frame_crc}, "
            f"computed {computed}) — the blob was corrupted in "
            "transit")
    (hdr_len,) = struct.unpack_from("<I", frame, len(_LEASE_MAGIC))
    start = len(_LEASE_MAGIC) + 4
    if len(frame) < start + hdr_len:
        raise KVLeaseCorruptError("lease header truncated")
    try:
        header = json.loads(frame[start:start + hdr_len].decode())
    except (ValueError, UnicodeDecodeError) as e:
        raise KVLeaseCorruptError(
            f"lease header is not valid JSON: {e}") from e
    if not isinstance(header, dict):
        raise KVLeaseCorruptError("lease header is not an object")
    version = header.get("version")
    if version != LEASE_WIRE_VERSION:
        raise KVLeaseVersionError(
            f"lease wire version {version!r} != supported "
            f"{LEASE_WIRE_VERSION}")
    payload = frame[start + hdr_len:]
    crc = zlib.crc32(payload) & 0xFFFFFFFF
    if crc != header.get("payload_crc"):
        raise KVLeaseCorruptError(
            f"lease payload CRC mismatch (stored "
            f"{header.get('payload_crc')!r}, computed {crc}) — the "
            "blob was corrupted in transit")
    return header, payload


class PagedKVAllocator:
    """Refcounted free-list allocator over page ids ``1..n_pages``
    (id 0 is the session's scratch page). Thread-safe: admission
    checks read counts from request threads while the batcher worker
    allocates/frees."""

    def __init__(self, n_pages: int, page_size: int):
        if n_pages < 1:
            raise ValueError(f"n_pages must be >= 1, got {n_pages}")
        if page_size < 1:
            raise ValueError(
                f"page_size must be >= 1, got {page_size}")
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self._lock = threading.Lock()
        # LIFO free list: recently-freed pages are re-used first
        # (their pool rows are warm)
        self._free: List[int] = list(range(self.n_pages, 0, -1))
        self._ref = np.zeros(self.n_pages + 1, np.int32)

    # ---- queries ----
    def free_count(self) -> int:
        with self._lock:
            return len(self._free)

    def in_use(self) -> int:
        return self.n_pages - self.free_count()

    def refcount(self, page: int) -> int:
        with self._lock:
            return int(self._ref[page])

    # ---- alloc / refcount ----
    def alloc(self, n: int, evictor=None) -> List[int]:
        """Allocate ``n`` pages (refcount 1 each). When the free list
        is short and an ``evictor`` is given, it is asked to release
        ``needed`` pages (the prefix cache drops LRU entries there);
        still short afterwards raises
        :class:`KVPagePoolExhaustedError` with a backoff hint scaled
        to the shortfall — allocation is all-or-nothing."""
        n = int(n)
        if n < 0:
            raise ValueError(f"cannot allocate {n} pages")
        with self._lock:
            short = n - len(self._free)
        if short > 0 and evictor is not None:
            evictor.evict(short)
        with self._lock:
            if n > len(self._free):
                raise KVPagePoolExhaustedError(
                    f"KV page pool exhausted: {n} pages needed, "
                    f"{len(self._free)} free of {self.n_pages} — "
                    "active decodes free pages as they finish",
                    retry_after_s=max(0.1, 0.02 * n))
            pages = [self._free.pop() for _ in range(n)]
            for p in pages:
                self._ref[p] = 1
            return pages

    def incref(self, pages) -> None:
        with self._lock:
            for p in pages:
                if self._ref[p] <= 0:
                    raise ValueError(
                        f"incref on free page {p} (use-after-free)")
                self._ref[p] += 1

    def decref(self, pages) -> None:
        """Drop one reference per page; a page at refcount 0 returns
        to the free list."""
        with self._lock:
            for p in pages:
                if self._ref[p] <= 0:
                    raise ValueError(
                        f"decref on free page {p} (double free)")
                self._ref[p] -= 1
                if self._ref[p] == 0:
                    self._free.append(p)

    def reset(self) -> None:
        """Forget everything (worker-restart recovery: the pool
        buffers were rebuilt, so every outstanding reference is
        dead)."""
        with self._lock:
            self._free = list(range(self.n_pages, 0, -1))
            self._ref[:] = 0


class PrefixCache:
    """Page-granular prompt-prefix index with LRU eviction.

    Keys are the page-aligned token prefixes themselves (exact match,
    not a lossy hash): a registered prompt of ``m`` full pages adds
    one entry per prefix length ``1..m``, so a later prompt sharing
    only the first page still hits. Each entry owns one refcount on
    each of its pages; eviction (LRU, driven by the allocator running
    dry) drops entries and their references — a page frees only when
    no entry AND no live slot references it."""

    def __init__(self, allocator: PagedKVAllocator):
        self._alloc = allocator
        self._entries: "OrderedDict[bytes, List[int]]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits_total = 0
        self.evictions_total = 0

    @staticmethod
    def _key(tokens: np.ndarray, n_tokens: int) -> bytes:
        return np.ascontiguousarray(
            tokens[:n_tokens], dtype=np.int64).tobytes()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def register(self, tokens, pages: List[int]) -> int:
        """Register the chain of full-prompt pages ``pages`` (page i
        holds tokens ``[i*ps, (i+1)*ps)``). Returns how many new
        entries were added."""
        ps = self._alloc.page_size
        tokens = np.asarray(tokens).reshape(-1)
        added = 0
        with self._lock:
            for n in range(1, len(pages) + 1):
                key = self._key(tokens, n * ps)
                if key in self._entries:
                    self._entries.move_to_end(key)
                    continue
                chain = list(pages[:n])
                self._alloc.incref(chain)
                self._entries[key] = chain
                added += 1
        return added

    def lookup(self, tokens) -> List[int]:
        """Longest cached page chain matching the prompt's page-
        aligned prefix. The returned pages carry one NEW reference
        each (the caller's — release with ``decref``); empty list on
        miss. Counts a hit only when at least one page matched."""
        ps = self._alloc.page_size
        tokens = np.asarray(tokens).reshape(-1)
        with self._lock:
            for n in range(len(tokens) // ps, 0, -1):
                key = self._key(tokens, n * ps)
                chain = self._entries.get(key)
                if chain is not None:
                    self._entries.move_to_end(key)
                    self._alloc.incref(chain)
                    self.hits_total += 1
                    return list(chain)
        return []

    def evict(self, n_pages_needed: int) -> None:
        """Drop LRU entries until ``n_pages_needed`` PAGES came free
        (or the cache is empty). Called by the allocator
        mid-``alloc``; pages shared with live slots lose the cache's
        reference but stay resident. A prompt of m pages holds m
        entries over the same pages and a page frees only at its last
        reference, so the count is of pages freed, not of references
        dropped: an admission that is refused here is tried again a
        device step later."""
        with self._lock:
            want = self._alloc.free_count() + n_pages_needed
            while self._entries and self._alloc.free_count() < want:
                _, chain = self._entries.popitem(last=False)
                self._alloc.decref(chain)
                self.evictions_total += 1

    def clear(self) -> None:
        with self._lock:
            for chain in self._entries.values():
                self._alloc.decref(chain)
            self._entries.clear()

    def fingerprints(self, limit: int = 512) -> List[str]:
        """Digests of the (up to ``limit``) most-recently-used
        cached prefixes — the per-replica advertisement the fleet
        router's prober scrapes for KV-aware routing. Entry keys ARE
        the page-aligned token-prefix bytes, so hashing them here
        matches :func:`prefix_fingerprint` over the same tokens."""
        with self._lock:
            keys = list(self._entries.keys())
        keys = keys[-int(limit):]
        return [format(zlib.crc32(k) & 0xFFFFFFFF, "08x")
                for k in keys]


class _Lease:
    """One admitted stream's page reservation."""

    __slots__ = ("pages", "resume_pos", "prefix_hit_tokens",
                 "prompt_len", "ring_rows", "state_rows")

    def __init__(self, pages, resume_pos, prefix_hit_tokens,
                 prompt_len, ring_rows=None, state_rows=None):
        self.pages = pages                    # table order
        self.resume_pos = resume_pos          # first position to feed
        self.prefix_hit_tokens = prefix_hit_tokens
        self.prompt_len = prompt_len
        # an imported lease's ring rows, {layer: [leaf rows]} in
        # position order up to ``resume_pos``: a ring belongs to a
        # slot, so they reach the device at ``bind``
        self.ring_rows = ring_rows
        # an imported lease's state rows, {layer: [leaf row]}: they
        # too belong to a slot
        self.state_rows = state_rows


class PagedSlotSession:
    """Continuous-batching decode over a paged KV pool: the drop-in
    sibling of :class:`~deeplearning4j_tpu.models.streaming.
    SlotStreamingSession` whose per-slot state is a page table into
    one shared pool instead of a private ``capacity``-row cache.

    ``capacity`` still bounds ONE request's prompt+generation length
    (it is the page-table width in tokens); memory is bounded by
    ``n_pages * page_size`` total. Supported layers: those that
    declare a cache (``PagedLayer``; module docstring) and stateless
    layers — LSTM-style carries (``zero_state``) and running
    statistics have no paged analog; build the dense session for
    those models.
    """

    @staticmethod
    def supports(net) -> bool:
        """Can this model decode over page tables? False when any
        layer carries state with no paged analog (recurrent carry or
        running statistic) — the predicate the batcher's
        ``kv_mode="auto"`` fallback keys on, so that REAL
        construction errors (bad page_size/n_pages) are never
        mistaken for an unsupported model."""
        return not any(_no_paged_analog(layer) for layer in net.layers)

    def __init__(self, net, slots: int, capacity: int,
                 page_size: int = 16, n_pages: Optional[int] = None,
                 dtype=None):
        import jax
        from deeplearning4j_tpu.observability.tracing import startup
        with startup.span("setup/session", {
                "slots": int(slots), "capacity": int(capacity)}) as sp:
            self._build(net, slots, capacity, page_size, n_pages, dtype)
            sp.set("pool_bytes", sum(
                leaf.nbytes
                for leaf in jax.tree_util.tree_leaves(self._pools)))

    def _build(self, net, slots, capacity, page_size, n_pages, dtype):
        import jax
        import jax.numpy as jnp
        for i, layer in enumerate(net.layers):
            if _no_paged_analog(layer):
                raise ValueError(
                    f"layer {i} ({type(layer).__name__}) carries "
                    "state with no paged analog (recurrent carry or "
                    "running statistic); use the dense "
                    "SlotStreamingSession for this model")
        self.net = net
        self.slots = int(slots)
        self.capacity = int(capacity)
        self.page_size = int(page_size)
        self.pages_per_slot = _pages_for(capacity, page_size)
        if n_pages is None:
            # memory parity with the dense session by default: the
            # win then comes from reserving per-request actual need
            n_pages = self.slots * self.pages_per_slot
        # the cache rides the dtype the network's parameters were
        # built in (the dtype policy's): float32 unless they are all
        # of one half-precision type
        self._dtype = dtype or _params_dtype(net)
        self.allocator = PagedKVAllocator(n_pages, self.page_size)
        self.prefix_cache = PrefixCache(self.allocator)
        self.slot_pos = np.zeros((self.slots,), np.int32)
        self._table = np.zeros((self.slots, self.pages_per_slot),
                               np.int32)
        self._leases: Dict[int, _Lease] = {}
        # what each layer declares that it keeps between tokens; None
        # for a layer without a paged step. Everything below branches
        # on this reading
        self._caches = caches = [
            layer.paged_cache(self.page_size)
            if isinstance(layer, PagedLayer) else None
            for layer in net.layers]
        # pages of the ring a slot owns in each layer's pool; 0 for a
        # layer in the allocator's pages and for one without a cache
        self._ring = [c.ring_pages if c and c.kind == RING else 0
                      for c in caches]
        self._ring_sizes = sorted({r for r in self._ring if r})
        # layers that keep a fixed-size state in a row a slot
        self._state = [c is not None and c.kind == STATE for c in caches]
        # a ring or a state row belongs to its slot: no prefix is
        # shared over a network that has either
        self._slot_owned = bool(self._ring_sizes) or any(self._state)
        # slots whose state rows a request has written since the
        # pools were made (``_note_state``)
        self._state_used = np.zeros((self.slots,), bool)
        # the widest step a slot may be fed: a ring has a page beyond
        # its window, so up to ``page_size`` rows overwrite nothing a
        # row of the same step reads
        self.chunk_rows_max = (self.page_size if self._ring_sizes
                               else self.capacity)
        # some layer's step is unrolled over a chunk's rows: every
        # width of such a step is a long compile, so the batcher holds
        # one chunk program
        self.unrolls_chunk_rows = any(
            c.unrolls_chunk_rows for c in caches if c)
        self._pools = self._fresh_pools()
        # one jitted step: ``step_slots`` runs it at (slots, 1, C),
        # ``step_chunk`` at (slots, t, C), each shape its own program
        self._step = None
        # the same step with the greedy pick on the device
        # (``step_ids``), and the ids its latest call picked: unfetched,
        # the next call's ``prev_ids``
        self._step_ids = None
        # (kind, t) of the step programs that have run
        # (``_first_call``)
        self._registered = set()
        self._prev_ids = jnp.zeros((self.slots,), jnp.int32)
        cached = [i for i, c in enumerate(caches) if c]
        self._last_paged = cached[-1] if cached else -1
        # May ``step_chunk`` stand in for token-by-token steps? Only
        # where every layer without a cache is pointwise in time
        # (``seq_parallelizable``) and no preprocessor reshapes a
        # chunk on its way to a cache: a layer below the last cache
        # sees t rows of a slot where a single step shows it one, and
        # a layer above it is given a slot's last valid row alone,
        # which is the whole chunk's output at that row only if rows
        # do not mix.
        self.chunkable = bool(cached) and not any(
            i <= self._last_paged for i in net.conf.preprocessors
        ) and all(c or getattr(layer, "seq_parallelizable", False)
                  for layer, c in zip(net.layers, caches))
        self._copy_page = None
        # layers whose decode step returns counts beside its output
        # (an expert layer's tokens per expert); none: the step and
        # its program are what they were without this
        self._aux_layers = [i for i in cached
                            if net.layers[i].stream_aux]
        # the latest step's counts, (len(_aux_layers), ...) on the
        # device, unfetched (a dict of such arrays where the layers
        # return a dict of counts); None for a network that has none
        self.step_aux = None
        # the latest step's (read, spanned) KV positions: what its
        # attention layers read of each slot's cache, and the slots x
        # capacity the page tables span (host arithmetic, see
        # ``_note_kv_read``)
        self.step_kv_positions = (0, 0)
        self._by_table: Dict[int, bool] = {}
        # the latest step's ring pages (held, full, overwritten), see
        # ``_note_ring``; stays zero without a ring layer
        self.step_ring_pages = (0, 0, 0)
        # bytes of the state pools, and the slots of the latest step
        # that began a request on a row an earlier one had written
        # (``_note_state``); both stay zero without a state layer
        self.state_pool_bytes = sum(
            leaf.nbytes for pool, kept in zip(self._pools, self._state)
            if kept for leaf in jax.tree_util.tree_leaves(pool))
        self.step_state_restarts = 0

    # ---- pools ----
    def _fresh_pools(self):
        def rows(c):
            if c.kind == STATE:
                return self.slots
            # pages and one more: page id 0 is the scratch page
            return (self.slots * c.ring_pages if c.kind == RING
                    else self.allocator.n_pages) + 1

        return [None if c is None else layer.zero_pool(
                    rows(c), self.page_size, self._dtype)
                for layer, c in zip(self.net.layers, self._caches)]

    def pages_total(self) -> int:
        return self.allocator.n_pages

    def pages_in_use(self) -> int:
        return self.allocator.in_use()

    def slot_pages(self, slot: int) -> int:
        lease = self._leases.get(slot)
        return len(lease.pages) if lease is not None else 0

    def slot_prefix_hit(self, slot: int) -> int:
        lease = self._leases.get(slot)
        return lease.prefix_hit_tokens if lease is not None else 0

    # ---- admission-side API (batcher worker thread) ----
    def can_ever_fit(self, prompt_len: int, n_tokens: int) -> bool:
        """Could this request EVER be admitted (table width and whole
        pool permitting)? False means a client error, not transient
        pressure."""
        total = int(prompt_len) + int(n_tokens)
        return (total <= self.capacity
                and _pages_for(total, self.page_size)
                <= self.allocator.n_pages)

    def reserve(self, prompt, n_tokens: int) -> _Lease:
        """Reserve pages for one stream's ``prompt + n_tokens`` worst
        case, reusing cached prefix pages when the prompt matches.
        Raises :class:`KVPagePoolExhaustedError` (all-or-nothing)
        under transient pressure. The lease is not visible to the
        device until :meth:`bind`."""
        prompt = np.asarray(prompt).reshape(-1)
        T0 = prompt.size
        if T0 < 1:
            raise ValueError("prompt must contain at least one token")
        if T0 + int(n_tokens) > self.capacity:
            raise ValueError(
                f"prompt ({T0}) + n_tokens ({n_tokens}) exceeds the "
                f"page-table width (capacity {self.capacity})")
        total_pages = _pages_for(T0 + int(n_tokens), self.page_size)
        # a hit would resume behind an empty window: wrong logits,
        # not slow ones
        shared = ([] if self._slot_owned
                  else self.prefix_cache.lookup(prompt))
        # the LAST prompt token must be re-fed to produce the first
        # output logits, so a hit can cover at most T0 - 1 positions
        resume = min(len(shared) * self.page_size, T0 - 1)
        cow_idx = resume // self.page_size
        need_cow = cow_idx < len(shared)
        fresh_needed = total_pages - len(shared) + (
            1 if need_cow else 0)
        try:
            fresh = self.allocator.alloc(fresh_needed,
                                         evictor=self.prefix_cache)
        except KVPagePoolExhaustedError:
            if shared:
                self.allocator.decref(shared)
            raise
        if need_cow:
            # the resume position sits INSIDE a shared page (whole
            # prompt was covered): copy-on-write it so the re-fed
            # token's write cannot touch the shared original
            cow_page = fresh.pop()
            self._device_copy_page(cow_page, shared[cow_idx])
            self.allocator.decref([shared[cow_idx]])
            shared = shared[:cow_idx] + [cow_page]
        pages = shared + fresh
        return _Lease(pages, resume,
                      prefix_hit_tokens=resume, prompt_len=T0)

    def bind(self, slot: int, lease: _Lease) -> None:
        self._table[slot, :] = 0
        self._table[slot, :len(lease.pages)] = lease.pages
        self.slot_pos[slot] = lease.resume_pos
        self._leases[slot] = lease
        if lease.ring_rows:
            self._write_ring_rows(slot, lease.resume_pos,
                                  lease.ring_rows)
            lease.ring_rows = None
        if lease.state_rows:
            self._write_state_rows(slot, lease.state_rows)
            lease.state_rows = None

    def release(self, slot: int, register_prompt=None) -> None:
        """Recycle a slot: drop its page references; when the stream
        completed cleanly, first register its full-prompt pages in
        the prefix cache (the cache takes its own references)."""
        lease = self._leases.pop(slot, None)
        self._table[slot, :] = 0
        self.slot_pos[slot] = 0
        if lease is None:
            return
        if register_prompt is not None and not self._slot_owned:
            prompt = np.asarray(register_prompt).reshape(-1)
            n_full = prompt.size // self.page_size
            if n_full > 0:
                self.prefix_cache.register(prompt,
                                           lease.pages[:n_full])
        self.allocator.decref(lease.pages)

    def release_all(self) -> None:
        for slot in list(self._leases):
            self.release(slot)

    def register_written_prefix(self, slot: int, prompt) -> int:
        """Donate the slot's FULLY-WRITTEN prompt pages to the
        prefix cache without releasing the lease — the prefill-
        export path's registration, where only ``slot_pos``
        positions (all but the last prompt token) are in the cache
        and the boundary page may be half-written. Returns how many
        pages were registered."""
        lease = self._leases.get(slot)
        if lease is None or self._slot_owned:
            return 0
        pos = int(self.slot_pos[slot])
        prompt = np.asarray(prompt).reshape(-1)
        n_full = min(pos, prompt.size) // self.page_size
        if n_full > 0:
            self.prefix_cache.register(prompt, lease.pages[:n_full])
        return n_full

    # ---- lease serialization: the prefill→decode / drain-migration
    #      wire format. A slot's attention state is its page-table
    #      pages' contents plus its position; everything else about
    #      the stream (prompt, sampled tokens, rng) is the CALLER's
    #      ``extra`` dict, carried opaquely in the header ----
    def _pool_schema(self) -> List[Optional[List[dict]]]:
        """Per-layer leaf schema (page-row shape + dtype, the ring's
        pages for a layer that keeps one, ``state`` for a layer whose
        row is a slot's whole state) — what two replicas must agree
        on for a lease to be portable. None for stateless layers."""
        import jax
        schema: List[Optional[List[dict]]] = []
        for pool, ring, kept in zip(self._pools, self._ring,
                                    self._state):
            if pool is None:
                schema.append(None)
                continue
            leaves = jax.tree_util.tree_leaves(pool)
            kind = ({"ring": ring} if ring
                    else {"state": True} if kept else {})
            schema.append([dict(shape=list(leaf.shape[1:]),
                                dtype=str(leaf.dtype), **kind)
                           for leaf in leaves])
        return schema

    def _ring_span(self, ring: int, pos: int) -> np.ndarray:
        """The positions a ring of ``ring`` pages still holds of a
        stream at ``pos``, oldest first."""
        return np.arange(pos - min(pos, ring * self.page_size), pos)

    def _ring_place(self, slot: int, ring: int, positions):
        """(page ids, offsets) of ``positions`` in ``slot``'s ring."""
        row = positions % (ring * self.page_size)
        return (1 + slot * ring + row // self.page_size,
                row % self.page_size)

    def _write_ring_rows(self, slot: int, pos: int, ring_rows) -> None:
        """Put an imported lease's ring rows (``{layer: [leaf rows in
        position order up to pos]}``) into ``slot``'s rings."""
        import jax
        import jax.numpy as jnp
        for i, rows in ring_rows.items():
            pages, offs = self._ring_place(
                slot, self._ring[i], self._ring_span(self._ring[i], pos))
            leaves, treedef = jax.tree_util.tree_flatten(self._pools[i])
            self._pools[i] = jax.tree_util.tree_unflatten(
                treedef, [leaf.at[pages, offs].set(jnp.asarray(r))
                          for leaf, r in zip(leaves, rows)])

    def _write_state_rows(self, slot: int, state_rows) -> None:
        """Put an imported lease's state rows (``{layer: [leaf
        row]}``) into ``slot``'s row of each state pool."""
        import jax
        import jax.numpy as jnp
        for i, rows in state_rows.items():
            leaves, treedef = jax.tree_util.tree_flatten(self._pools[i])
            self._pools[i] = jax.tree_util.tree_unflatten(
                treedef, [leaf.at[slot].set(jnp.asarray(r))
                          for leaf, r in zip(leaves, rows)])
        self._state_used[slot] = True

    def export_lease(self, slot: int,
                     extra: Optional[dict] = None) -> bytes:
        """Serialize slot ``slot``'s attention state: a versioned
        header (wire version, page size, position, per-layer pool
        schema, the caller's ``extra``) followed by the raw contents
        of every page the stream has written, of a layer that keeps a
        ring the rows the slot's ring still holds, oldest position
        first, and of a layer that keeps a state the slot's row,
        CRC-tagged. The slot
        and its lease are left untouched — the caller decides
        whether the incumbent keeps decoding (failed handoff) or
        releases (acked migration). Device→host gather happens here,
        one fixed-shape fetch per (layer leaf, page)."""
        import jax
        lease = self._leases.get(slot)
        if lease is None:
            raise ValueError(f"slot {slot} holds no lease to export")
        pos = int(self.slot_pos[slot])
        # only pages with WRITTEN positions travel: [0, pos)
        pages_written = _pages_for(pos, self.page_size) if pos else 0
        page_ids = lease.pages[:pages_written]
        chunks: List[bytes] = []
        for pool, ring, kept in zip(self._pools, self._ring,
                                    self._state):
            if pool is None:
                continue
            for leaf in jax.tree_util.tree_leaves(pool):
                if kept:
                    chunks.append(np.ascontiguousarray(
                        np.asarray(leaf[slot])).tobytes())
                    continue
                if ring:
                    pages, offs = self._ring_place(
                        slot, ring, self._ring_span(ring, pos))
                    chunks.append(np.ascontiguousarray(
                        np.asarray(leaf[pages, offs])).tobytes())
                    continue
                for pid in page_ids:
                    chunks.append(np.ascontiguousarray(
                        np.asarray(leaf[pid])).tobytes())
        payload = b"".join(chunks)
        header = {
            "version": LEASE_WIRE_VERSION,
            "page_size": self.page_size,
            "pos": pos,
            "pages_written": pages_written,
            "layers": self._pool_schema(),
            "payload_crc": zlib.crc32(payload) & 0xFFFFFFFF,
            "extra": dict(extra or {}),
        }
        hdr = json.dumps(header).encode()
        frame = (_LEASE_MAGIC + struct.pack("<I", len(hdr)) + hdr
                 + payload)
        # trailing frame CRC over EVERYTHING (header included): the
        # payload CRC alone would let a bit flip inside the header —
        # pos, rng state, an emitted token — import silently-wrong
        # stream state instead of failing typed
        return frame + struct.pack("<I", zlib.crc32(frame)
                                   & 0xFFFFFFFF)

    def import_lease(self, blob: bytes,
                     total_tokens: int) -> Tuple[_Lease, dict]:
        """Rebuild an exported lease into THIS session's pool:
        validate the blob (magic/CRC → :class:`KVLeaseCorruptError`;
        wire version / page size / pool schema skew →
        :class:`KVLeaseVersionError`), reserve ``total_tokens``'
        worth of fresh pages (all-or-nothing, prefix cache evicted
        under pressure exactly like :meth:`reserve`), and scatter the
        payload pages into the physical pools — the rebuilt
        attention state is bit-identical to the exporter's (same
        bytes at the same in-page positions; everything past ``pos``
        is masked). Returns ``(lease, extra)``; bind the lease like
        any reservation."""
        import jax
        header, payload = parse_lease(blob)
        # every header field a crafted/corrupt blob controls is
        # validated TYPED here: this runs on the batcher worker
        # thread, and an untyped KeyError/IndexError would crash the
        # whole decode loop instead of failing one request
        try:
            page_size = int(header["page_size"])
            pos = int(header["pos"])
            pages_written = int(header["pages_written"])
            layers = header["layers"]
        except (KeyError, TypeError, ValueError) as e:
            raise KVLeaseCorruptError(
                f"lease header field missing or malformed: "
                f"{e!r}") from e
        if page_size != self.page_size:
            raise KVLeaseVersionError(
                f"lease page_size {page_size} != this "
                f"session's {self.page_size}")
        schema = self._pool_schema()
        if layers != schema:
            raise KVLeaseVersionError(
                "lease pool schema does not match this model's "
                "attention layers (different model or dtype)")
        if pos < 0 or pages_written != _pages_for(pos,
                                                  self.page_size):
            raise KVLeaseCorruptError(
                f"lease header inconsistent: pos {pos} does not "
                f"need {pages_written} page(s) of {self.page_size} "
                "tokens")
        if pos > int(total_tokens):
            raise KVLeaseCorruptError(
                f"lease position {pos} exceeds the request's token "
                f"budget {total_tokens}")
        total_pages = _pages_for(total_tokens, self.page_size)
        fresh = self.allocator.alloc(total_pages,
                                     evictor=self.prefix_cache)
        try:
            import jax.numpy as jnp
            n_leaf_rows = sum(len(s) for s in schema
                              if s is not None)
            # a page of a layer in the allocator's pages, a position
            # of a layer that keeps a ring, the row of one that keeps
            # a state
            expect = sum(
                _np_dtype(d["dtype"]).itemsize
                * (int(np.prod(d["shape"][1:]))
                   * self._ring_span(d["ring"], pos).size
                   if "ring" in d
                   else int(np.prod(d["shape"]))
                   * (1 if "state" in d else pages_written))
                for s in schema if s is not None for d in s)
            if len(payload) != expect:
                raise KVLeaseCorruptError(
                    f"lease payload is {len(payload)} bytes; schema "
                    f"demands {expect} ({n_leaf_rows} pool leaves x "
                    f"{pages_written} pages)")
            off = 0
            ring_rows: Dict[int, list] = {}
            state_rows: Dict[int, list] = {}
            for i, pool in enumerate(self._pools):
                if pool is None:
                    continue
                if self._state[i] or self._ring[i]:
                    # a slot's own rows: they wait in the lease for
                    # ``bind``. A state leaf is one row of the
                    # schema's shape, a ring leaf the positions its
                    # ring still holds
                    rows = state_rows if self._state[i] else ring_rows
                    held = self._ring_span(self._ring[i], pos).size
                    for spec in schema[i]:
                        dtype = _np_dtype(spec["dtype"])
                        shape = (tuple(spec["shape"]) if self._state[i]
                                 else (held,
                                       int(np.prod(spec["shape"][1:]))))
                        count = int(np.prod(shape))
                        rows.setdefault(i, []).append(np.frombuffer(
                            payload, dtype=dtype, count=count,
                            offset=off).reshape(shape))
                        off += count * dtype.itemsize
                    continue
                leaves, treedef = jax.tree_util.tree_flatten(pool)
                new_leaves = []
                for leaf, spec in zip(leaves, schema[i]):
                    shape = tuple(spec["shape"])
                    dtype = _np_dtype(spec["dtype"])
                    nb = dtype.itemsize * int(np.prod(shape))
                    for k in range(pages_written):
                        page = np.frombuffer(
                            payload, dtype=dtype, count=nb
                            // dtype.itemsize, offset=off
                        ).reshape(shape)
                        off += nb
                        leaf = leaf.at[fresh[k]].set(
                            jnp.asarray(page))
                    new_leaves.append(leaf)
                self._pools[i] = jax.tree_util.tree_unflatten(
                    treedef, new_leaves)
        except BaseException:
            self.allocator.decref(fresh)
            raise
        lease = _Lease(fresh, pos, prefix_hit_tokens=0,
                       prompt_len=pos, ring_rows=ring_rows,
                       state_rows=state_rows)
        return lease, dict(header.get("extra") or {})

    # ---- device step ----
    def _device_copy_page(self, dst: int, src: int) -> None:
        import jax
        if self._copy_page is None:
            def copy(pool, dst, src):
                row = jax.tree_util.tree_map(lambda b: b[src], pool)
                return jax.tree_util.tree_map(
                    lambda b, r: b.at[dst].set(r), pool, row)

            self._copy_page = jax.jit(copy, donate_argnums=(0,))
        import jax.numpy as jnp
        d, s = jnp.int32(dst), jnp.int32(src)
        for i, c in enumerate(self._caches):
            # page ids are the allocator's: a ring and a state pool
            # have none of them
            if c is not None and c.kind == PAGES:
                self._pools[i] = self._copy_page(self._pools[i], d, s)

    def _make_step(self):
        import jax
        import jax.numpy as jnp
        net = self.net
        layers = list(net.layers)
        preprocessors = dict(net.conf.preprocessors)

        aux_layers = set(self._aux_layers)
        cached = [c is not None for c in self._caches]
        last_paged = self._last_paged

        def step(params, layer_states, pools, table, pos, x,
                 active=None, n_valid=None):
            # ``n_valid`` makes this the chunk program: x is
            # (slots, t, C), a layer with a cache sends the rows past
            # a slot's n_valid to the scratch page and an expert
            # layer counts the valid rows; without it the program is
            # the (slots, 1, C) one, op for op what it was
            kw = {}
            chunk = n_valid is not None
            if chunk:
                kw["n_valid"] = n_valid
                active = (jnp.arange(x.shape[1])[None, :]
                          < n_valid[:, None])
            h = x
            new_pools = list(pools)
            aux = []
            for i, layer in enumerate(layers):
                if i in preprocessors:
                    h = preprocessors[i](h)
                # the layer's name on its device ops, as the
                # executors' forward has it (metadata only)
                with jax.named_scope(f"{i}_{type(layer).__name__}"):
                    if i in aux_layers:
                        h, new_pools[i], counts = \
                            layer.apply_stream_paged_aux(
                                params[i], pools[i], table, pos, h,
                                active, **kw)
                        aux.append(counts)
                    elif cached[i]:
                        h, new_pools[i] = layer.apply_stream_paged(
                            params[i], pools[i], table, pos, h, **kw)
                    else:
                        h, _ = layer.apply(params[i], layer_states[i],
                                           h, training=False)
                if chunk and i == last_paged:
                    # no later layer holds a cache and each is
                    # pointwise in time (``chunkable``), so only a
                    # slot's last valid row is ever looked at: the
                    # head, its softmax and the copy back stay
                    # (slots, 1, V)
                    h = jnp.take_along_axis(
                        h, jnp.maximum(n_valid - 1, 0)[:, None, None],
                        axis=1)
            if aux:
                # per layer a (held,) row, or a dict of counts with
                # that row among them: stacked leaf by leaf
                return h, new_pools, jax.tree_util.tree_map(
                    lambda *rows: jnp.stack(rows), *aux)
            return h, new_pools

        def step_ids(params, layer_states, pools, table, pos, x,
                     n_valid, prev_ids, use_prev):
            # row 0 of a slot in ``use_prev`` is the id the previous
            # call picked for it, which never left the device
            x = x.at[:, 0, 0].set(jnp.where(
                use_prev, prev_ids.astype(x.dtype), x[:, 0, 0]))
            if x.shape[1] == 1:
                out = step(params, layer_states, pools, table, pos, x,
                           n_valid > 0)
            else:
                out = step(params, layer_states, pools, table, pos, x,
                           None, n_valid)
            # (slots, V): the rows ``step_slots`` / ``step_chunk``
            # return. argmax takes the first index on ties, as
            # ``np.argmax`` over the fetched row does
            row = out[0][:, 0]
            ids = jnp.argmax(row, axis=-1).astype(jnp.int32)
            finite = jnp.all(jnp.isfinite(row), axis=-1)
            return (ids, finite) + tuple(out[1:])

        self._step = jax.jit(step, **_DONATE_POOLS)
        self._step_ids = jax.jit(step_ids, **_DONATE_POOLS)

    def runs_grouped_experts(self, t: int) -> bool:
        """Does the step program at ``t`` rows a slot run some expert
        layer's held experts as the grouped pass over the selected
        pairs (the block's ``experts_grouped`` of the ``slots * t``
        rows such a step carries)? The batcher asks, for
        ``serving_moe_grouped_steps_total``."""
        return any(
            self.net.layers[i].experts_grouped(self.slots * t,
                                               self._dtype)
            for i in self._aux_layers)

    def experts_carry_rows(self, t: int) -> bool:
        """Has the network expert layers, and does EVERY one of them
        carry the ``slots * t`` rows of a step at ``t`` rows a slot on
        weights its held experts' pass reads anyway (the block's
        ``experts_carry_rows``)? One layer that pays for its rows sets
        what the step's rows cost. The batcher asks, for the row
        budget of its wide chunk program."""
        return bool(self._aux_layers) and all(
            self.net.layers[i].experts_carry_rows(self.slots * t,
                                                  self._dtype)
            for i in self._aux_layers)

    def _first_call(self, kind: str, t: int, jitted, args):
        """The first call of a step program, whole, under one
        ``setup/program`` span of the set-up timeline:
        ``observability.programs`` is told of it as ``<kind>/t=<rows
        a slot>``, then ``jitted(*args)`` runs it (trace, lowering,
        compile or load, dispatch). The closures of ``_make_step``
        hold the layers and nothing of ``net.params``, so the registry
        keeps no array alive through them."""
        from deeplearning4j_tpu.observability import programs
        from deeplearning4j_tpu.observability.tracing import startup
        name = f"{kind}/t={t}"
        with startup.span("setup/program", {"program": name}):
            self._registered.add((kind, t))
            programs.register(name, jitted.__wrapped__, _DONATE_POOLS,
                              args)
            return jitted(*args)

    def _note_kv_read(self, t: int, lengths) -> None:
        """``step_kv_positions`` of a step at ``t`` rows a slot, from
        the ``lengths`` its attention is given (``pos`` plus the rows
        a slot feeds). This is the ACCOUNTING the layers' predicate
        implies, host arithmetic and no measurement, of the layers
        whose pages the allocator hands out (a ring layer reads its
        own ring whatever the table spans: ``_note_ring`` counts
        those; a state layer reads no position at all): layers that
        read by table (``paged_reads_by_table``: all of them must)
        fetch of each slot the pages up to the one its length ends in
        (``ops.paged_attention.pages_read``, the kernel's own rule);
        layers that gather read every slot's whole table. What the
        device moved is in its trace."""
        spanned = self.slots * self.pages_per_slot * self.page_size
        if t not in self._by_table:
            self._by_table[t] = all(
                layer.paged_reads_by_table(self.page_size, t,
                                           self._dtype)
                for layer, c in zip(self.net.layers, self._caches)
                if c is not None and c.kind == PAGES)
        read = spanned
        if self._by_table[t]:
            from deeplearning4j_tpu.ops.paged_attention import pages_read
            read = int(pages_read(lengths, self.page_size).sum()) \
                * self.page_size
        self.step_kv_positions = (read, spanned)

    def _note_ring(self, pos, n_valid) -> None:
        """``step_ring_pages`` of a step that fed slot ``s`` the
        positions ``pos[s] .. pos[s] + n_valid[s] - 1``, over the
        slots it fed and once for each size of ring the network has
        (its layers of one window are one kind): ``held``, the pages
        of their rings that hold a position the ring still keeps;
        ``full``, the pages the same slots would hold had that kind
        kept every position; ``overwritten``, the ring pages a write
        of this step began to reuse. Host arithmetic from the
        positions, as ``_note_kv_read`` is."""
        if not self._ring_sizes:
            return
        fed = n_valid > 0
        start, end = pos[fed], (pos + n_valid)[fed]
        ps = self.page_size
        pages = lambda n: -(-n // ps)
        held = full = over = 0
        for ring in self._ring_sizes:
            full += int(pages(end).sum())
            held += int(np.minimum(pages(end), ring).sum())
            over += int((pages(end) - pages(np.maximum(
                start, ring * ps))).clip(min=0).sum())
        self.step_ring_pages = (held, full, over)

    def _note_state(self, n_valid) -> None:
        """``step_state_restarts`` of a step about to feed slot ``s``
        ``n_valid[s]`` rows from ``slot_pos[s]``: the slots that begin
        a request (position 0) on a state row an earlier request has
        written. Such a row is neither zeroed nor read: the layer
        starts from zeros by position. Host arithmetic."""
        if not self.state_pool_bytes:
            return
        fed = n_valid > 0
        self.step_state_restarts = int(
            (fed & (self.slot_pos == 0) & self._state_used).sum())
        self._state_used |= fed

    def step_slots(self, x, active):
        """One decode step for every slot at once — the
        ``SlotStreamingSession.step_slots`` contract: ``x`` is
        (slots, 1, C), free slots carry a dummy row (their write
        lands in the scratch page and their ``pos`` stays put).
        Returns the (slots, 1, V) output for the new step."""
        import jax.numpy as jnp
        x = jnp.asarray(x)
        active = np.asarray(active, bool)
        if x.shape[0] != self.slots:
            raise ValueError(f"x has {x.shape[0]} rows; session has "
                             f"{self.slots} slots")
        if active.any() and int(self.slot_pos[active].max()) >= \
                self.capacity:
            raise ValueError(
                f"slot overflow: an active slot is at pos "
                f"{int(self.slot_pos[active].max())} with capacity "
                f"{self.capacity} — admit shorter requests or build "
                "the session with a larger capacity")
        if self._step is None:
            self._make_step()
        # inactive slots step with pos 0 over an all-zero table row:
        # the write targets scratch, never a live page (a slot that
        # is bound but sits a step out, as a parked one does, would
        # otherwise have its position 0 overwritten)
        pos = np.where(active, self.slot_pos, 0).astype(np.int32)
        table = np.where(active[:, None], self._table, 0)
        args = (self.net.params, self.net.state, self._pools,
                jnp.asarray(table), jnp.asarray(pos), x)
        if self._aux_layers:
            args += (jnp.asarray(active),)
        if ("paged_step", 1) not in self._registered:
            out = self._first_call("paged_step", 1, self._step, args)
        else:
            out = self._step(*args)
        if self._aux_layers:
            h, self._pools, self.step_aux = out
        else:
            h, self._pools = out
        self._note_state(active)
        self.slot_pos = self.slot_pos + active.astype(
            self.slot_pos.dtype)
        # a slot that sits the step out has length 1: its dummy row,
        # in the scratch page
        self._note_kv_read(1, pos + 1)
        self._note_ring(pos, active.astype(np.int32))
        return h

    def _check_rows(self, x, n_valid):
        """Shapes and bounds of a step that feeds slot ``s`` the rows
        ``x[s, :n_valid[s]]``: ``(t, live)``, or the ValueError."""
        if x.ndim != 3 or x.shape[0] != self.slots \
                or n_valid.shape != (self.slots,):
            raise ValueError(
                f"x {x.shape} / n_valid {n_valid.shape}: want "
                f"({self.slots}, t, C) and ({self.slots},)")
        t = int(x.shape[1])
        if n_valid.min() < 0 or n_valid.max() > t:
            raise ValueError(f"n_valid must lie in [0, {t}]")
        live = n_valid > 0
        if live.any() and int((self.slot_pos + n_valid)[live].max()) \
                > self.capacity:
            raise ValueError(
                f"slot overflow: a step ends at pos "
                f"{int((self.slot_pos + n_valid)[live].max())} with "
                f"capacity {self.capacity}")
        return t, live

    def step_chunk(self, x, n_valid):
        """One device step that feeds slot ``s`` its next
        ``n_valid[s]`` tokens, the rows ``x[s, :n_valid[s]]`` of a
        (slots, t, C) ``x``: up to t prompt tokens for a slot in
        prefill, 1 for a slot in decode, 0 for a free or parked slot
        (nothing of it is touched). The same positions of the same
        pages hold afterwards what ``n_valid[s]`` calls of
        :meth:`step_slots` would have left, the rows past ``n_valid``
        having gone to the scratch page, and ``slot_pos`` has advanced
        by ``n_valid``. Returns (slots, 1, V): the output at each
        slot's LAST valid row (row 0 of a slot that had none). One
        program per ``t``, compiled when first called at that width."""
        import jax.numpy as jnp
        x = jnp.asarray(x)
        n_valid = np.asarray(n_valid, np.int32)
        t, live = self._check_rows(x, n_valid)
        if not self.chunkable:
            raise ValueError(_NOT_CHUNKABLE + "step_slots")
        if self._step is None:
            self._make_step()
        pos = np.where(live, self.slot_pos, 0).astype(np.int32)
        args = (self.net.params, self.net.state, self._pools,
                jnp.asarray(self._table), jnp.asarray(pos), x, None,
                jnp.asarray(n_valid))
        if ("paged_step_chunk", t) not in self._registered:
            out = self._first_call("paged_step_chunk", t, self._step,
                                   args)
        else:
            out = self._step(*args)
        if self._aux_layers:
            h, self._pools, self.step_aux = out
        else:
            h, self._pools = out
        self._note_state(n_valid)
        self.slot_pos = self.slot_pos + n_valid
        self._note_kv_read(t, pos + n_valid)
        self._note_ring(pos, n_valid)
        return h

    def step_ids(self, x, n_valid, use_prev):
        """The step of :meth:`step_slots` (``x`` (slots, 1, 1)) or
        :meth:`step_chunk` (``x`` (slots, t, 1)) with the greedy pick
        made on the device: returns ``(ids, finite)``, both (slots,)
        and both still on the device, in place of the (slots, 1, V)
        rows. ``ids[s]`` is the argmax of slot ``s``'s last valid row
        (the first index on ties) and ``finite[s]`` says that every
        value of that row is finite: an id whose flag is false is
        worth nothing. A slot in ``use_prev`` feeds, as row 0, the id
        the PREVIOUS call of this method picked for it, whether or
        not anyone fetched it; every other row is ``x``'s. ``n_valid``
        is :meth:`step_chunk`'s at either width (1 or 0 a slot at
        t = 1). Pools, ``slot_pos`` and ``step_aux`` move as under the
        row-returning calls."""
        x = np.asarray(x, np.float32)
        n_valid = np.asarray(n_valid, np.int32)
        use_prev = np.asarray(use_prev, bool)
        t, live = self._check_rows(x, n_valid)
        if x.shape[2] != 1 or use_prev.shape != (self.slots,):
            raise ValueError(
                f"x {x.shape} / use_prev {use_prev.shape}: want "
                f"({self.slots}, t, 1) token ids and ({self.slots},)")
        if t > 1 and not self.chunkable:
            raise ValueError(_NOT_CHUNKABLE + "one row a step")
        if self._step_ids is None:
            self._make_step()
        pos = np.where(live, self.slot_pos, 0).astype(np.int32)
        # the single-token program trusts the table (``step_slots``):
        # a slot that sits the step out gets the all-zero row. A COPY
        # either way: the call returns before the device has read its
        # operands, and ``release`` / ``bind`` write ``_table`` in
        # place while the step is still in flight
        table = self._table.copy() if t > 1 else np.where(
            live[:, None], self._table, 0)
        args = (self.net.params, self.net.state, self._pools, table,
                pos, x, n_valid, self._prev_ids, use_prev)
        if ("paged_step_ids", t) not in self._registered:
            out = self._first_call("paged_step_ids", t, self._step_ids,
                                   args)
        else:
            out = self._step_ids(*args)
        if self._aux_layers:
            ids, finite, self._pools, self.step_aux = out
        else:
            ids, finite, self._pools = out
        self._prev_ids = ids
        self._note_state(n_valid)
        self.slot_pos = self.slot_pos + n_valid
        self._note_kv_read(t, pos + (n_valid if t > 1 else 1))
        self._note_ring(pos, n_valid)
        return ids, finite

    def reinit_states(self) -> None:
        """Post-crash recovery: the jitted step donates the pools, so
        after a failed step the buffers may be deleted device arrays.
        Rebuild them AND forget every page reference — the prefix
        cache's entries point at contents that no longer exist, so it
        must flush (its counters survive for the metrics)."""
        self._leases.clear()
        self.prefix_cache.clear()
        self.allocator.reset()
        self.slot_pos = np.zeros((self.slots,), np.int32)
        self._table = np.zeros((self.slots, self.pages_per_slot),
                               np.int32)
        self._pools = self._fresh_pools()
        self._state_used[:] = False
