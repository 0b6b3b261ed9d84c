"""Ring pages the window's steps held over the pages a whole-length
cache of the same layers would have held
(``serving_kv_ring_pages_held_total`` over
``serving_kv_ring_pages_full_total``, both over the slots a step fed):
the share of a whole-length window cache that the slot-owned rings
keep. 100 while no request has outgrown its ring, lower the longer the
requests. The session's host arithmetic from the positions it feeds,
as ``kv_read_pct.serve`` is; nothing to read where the program has no
such counters (a network without a window layer, a program before
them)."""

from benchmark.harness import readers

_KEY = r"serving_kv_ring_pages_%s_total\{"


def read(obs):
    held = readers.counter_delta(obs, _KEY % "held")
    full = readers.counter_delta(obs, _KEY % "full")
    return 100.0 * held / full if held is not None and full else None
