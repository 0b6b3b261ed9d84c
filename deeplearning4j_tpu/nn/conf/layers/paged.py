"""What a layer gives to be served over page tables, stated once.

``models.paged_kv.PagedSlotSession`` runs one jitted step over a
network's layers. A layer that keeps something between the tokens of a
stream is a ``PagedLayer``; the session asks each of them ONE thing
when it is built, ``paged_cache(page_size)``, and branches on the
answer from then on. Every other layer runs its ``apply``.
"""

from __future__ import annotations

import dataclasses

__all__ = ["PagedCache", "PagedLayer", "MixerCacheLayer", "PAGES", "RING",
           "STATE"]

# the kinds of cache a session holds pools for
PAGES = "pages"     # the allocator's pages, named by the slot's table
RING = "ring"       # a ring of pages a slot owns, outside the allocator
STATE = "state"     # one row of fixed size a slot: no pages, no positions


@dataclasses.dataclass(frozen=True)
class PagedCache:
    """What a layer keeps between tokens. ``ring_pages`` (``RING``):
    the pages of the ring a slot owns. ``unrolls_chunk_rows``: the
    layer's step is unrolled over a chunk's rows, so every width of
    it is a long compile and the batcher holds one chunk program."""

    kind: str
    ring_pages: int = 0
    unrolls_chunk_rows: bool = False


class PagedLayer:
    """A layer with a paged step. It gives:

    - ``paged_cache(page_size) -> PagedCache``: the kind of its cache.
      ``PAGES``: every earlier position, in pages the allocator hands
      out and the slot's table names (shared by prefix, copied on
      write, leased page by page). ``RING``: the last positions of a
      window, in ``ring_pages`` pages slot ``s`` owns for good (pages
      ``1 + s * R .. (s + 1) * R`` of a pool of its own; position ``p``
      at ring row ``p mod (R * page_size)``; the layer tells by
      position which rows a query may see). ``STATE``: one fixed-size
      row a slot (a slot that feeds position 0 starts from zeros
      whatever its row holds). A ring and a state row belong to their
      slot: nothing is allocated, shared or zeroed for them, and a
      network that has either takes no prefix hit.
    - ``zero_pool(n, page_size, dtype)``: the pool, a dict of arrays
      whose leading axis is ``n``: pages (``PAGES``, ``RING``; page 0
      is the scratch page) or slots (``STATE``).
    - ``apply_stream_paged(params, pool, table, pos, x, n_valid=None)
      -> (out, pool)``: one step for all slots, ``x`` (slots, t, C)
      from positions ``pos`` (slots,), of which slot ``s`` feeds its
      first ``n_valid[s]`` rows (None: the single-row program, where
      an all-zero ``table`` row marks a slot that sits the step out).
    - ``paged_reads_by_table(page_size, t, dtype)`` (``PAGES``): does
      that step at ``t`` rows a slot read each slot's live pages by
      table, or gather its whole table? The session's accounting asks.
    - ``stream_aux``: the step returns counts beside its output,
      through ``apply_stream_paged_aux(params, pool, table, pos, x,
      active=None, n_valid=None) -> (out, pool, counts)``; such a
      layer also says ``experts_grouped(rows, dtype)`` and
      ``experts_carry_rows(rows, dtype)``.

    A block around a mixer is a ``MixerCacheLayer``; a decoder block
    is ``decoder_blocks._NormedBlock``'s fields, key, scope and
    parts. A new configuration touches its mixer's module, that one
    and a builder; ``models/paged_kv.py`` and ``serving/`` only for a
    new KIND of cache."""

    stream_aux = False

    def paged_cache(self, page_size: int) -> PagedCache:
        raise NotImplementedError

    def zero_pool(self, n: int, page_size: int, dtype):
        raise NotImplementedError

    def paged_reads_by_table(self, page_size: int, t: int, dtype) -> bool:
        return False


class MixerCacheLayer(PagedLayer):
    """A block around ONE sequence mixer (``_mixer()``): what it keeps
    between tokens is what its mixer keeps."""

    def _mixer(self) -> PagedLayer:
        raise NotImplementedError

    def paged_cache(self, page_size: int) -> PagedCache:
        return self._mixer().paged_cache(page_size)

    def zero_pool(self, n: int, page_size: int, dtype):
        return self._mixer().zero_pool(n, page_size, dtype)

    def paged_reads_by_table(self, page_size: int, t: int, dtype) -> bool:
        return self._mixer().paged_reads_by_table(page_size, t, dtype)
