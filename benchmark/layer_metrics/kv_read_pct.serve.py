"""KV positions the window's steps read over the positions their page
tables span (``serving_kv_positions_read_total`` over
``serving_kv_positions_spanned_total``, slots x capacity a step): about
the share of the pool's span the slots hold where attention reads each
slot's live pages by table, 100 where it gathers every slot's whole
capacity. The counters are the session's ACCOUNTING of what its
layers' dispatch implies (host arithmetic from the lengths the step is
given), not a measurement of what the device moved: that is the
trace's (``paged_attn_time_pct.serve``, ``%copy``). Says where the
by-table read is absent; nothing to read where the program has no such
counters."""

from benchmark.harness import readers

_KEY = r"serving_kv_positions_%s_total\{"


def read(obs):
    got = readers.counter_delta(obs, _KEY % "read")
    spanned = readers.counter_delta(obs, _KEY % "spanned")
    if got is None or not spanned:
        return None
    return 100.0 * got / spanned
