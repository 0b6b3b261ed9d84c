"""The program's own spans (``observability/tracing.py``) beside the
device trace, for the readers of benchmark/layer_metrics.

An event of ``obs["spans"]`` carries ``t_ns`` (its start in raw
``perf_counter_ns``), ``dur_us``, ``span_id`` and ``parent_id``; a
program older than those keys gives events without them, and every
function here then finds nothing to read and says so with None.

The device trace runs on another clock. ``anchor_offset`` reads the
exact distance from the ``dl4j/clock_anchor/<perf_counter_ns>`` host
event the tracer writes when it is switched on, where the mix traces
the host; ``fitted_offset`` estimates it from the run where the mix
traces the device alone: an ``h2d_wait`` span ends when the batch has
landed, which is when the device can start the step, so the start of
the device's burst of ops after each long gap is that span's end on
the trace's clock.
"""

import statistics

from . import xplane

ANCHOR = "dl4j/clock_anchor/"


def timed(obs):
    """The events that carry the raw clock."""
    return [e for e in obs.get("spans", ()) if "t_ns" in e]


def _interval(e):
    return e["t_ns"], e["t_ns"] + int(round(e["dur_us"] * 1e3))


def in_whole_iterations(obs, name):
    """The ``name`` spans of iterations whose ``step`` span is there
    (the tracer is switched inside the iterator, so the first traced
    iteration has none and the last is cut short)."""
    events = timed(obs)
    by_id = {e["span_id"]: e for e in events if "span_id" in e}
    out = []
    for e in events:
        if e["name"] != name:
            continue
        up = e
        while up is not None and up["name"] != "step":
            up = by_id.get(up.get("parent_id"))
        if up is not None and "iteration" in (up.get("args") or {}):
            out.append(e)
    return out


def mean_ms(obs, name):
    d = [e["dur_us"] for e in in_whole_iterations(obs, name)]
    return sum(d) / len(d) / 1e3 if d else None


# ---- the offset from ``t_ns`` to the trace's clock

def anchor_offset(tr):
    """Exact, from the tracer's anchor event; None where the trace
    holds no host events or none of that name."""
    for _, name, start, _ in tr.get("host", ()):
        if name.startswith(ANCHOR):
            return start - int(name[len(ANCHOR):])
    return None


def burst_starts(ops, min_gap_ns):
    """Starts of the runs of device ops that a gap of at least
    ``min_gap_ns`` precedes, the first op's among them."""
    starts, end = [], None
    for _, s, d in sorted(ops, key=lambda o: o[1]):
        if end is None or s - end >= min_gap_ns:
            starts.append(s)
        end = s + d if end is None else max(end, s + d)
    return starts


def _spread(values):
    if len(values) >= 4:
        q = statistics.quantiles(values, n=4)
        return q[2] - q[0]
    return max(values) - min(values)


def fitted_offset(tr, events):
    """The offset fitted from the run: the median, over the traced
    steps, of (start of the device's burst after a gap - end of the
    step's ``h2d_wait``). A burst starts when the last device starts
    (a sharded batch lands shard by shard). Returns a dict with
    ``offset_ns``, ``spread_ns`` (quartile distance of the per-step
    residuals), ``worst_ns`` and ``steps``, or None with fewer than
    three steps to pair."""
    waits = sorted(_interval(e) for e in events if e["name"] == "h2d_wait")
    if len(waits) < 3 or not tr["devices"]:
        return None
    ends = [e for _, e in waits]
    min_gap = max(100_000, min(e - s for s, e in waits) // 2)
    per_dev = [burst_starts(d["ops"], min_gap) for d in tr["devices"]
               if d["ops"]]
    if not per_dev:
        return None
    if len({len(b) for b in per_dev}) == 1:
        bursts = [max(col) for col in zip(*per_dev)]
    else:
        bursts = per_dev[0]
    first = min(e["t_ns"] for e in events)
    last_start = max(s for d in tr["devices"] for _, s, _ in d["ops"])
    best = None
    for drop_h, drop_b in ((0, 0), (1, 0), (0, 1)):
        pairs = list(zip(ends[drop_h:], bursts[drop_b:]))
        if len(pairs) < 3:
            continue
        offset = int(statistics.median(b - h for h, b in pairs))
        # the profiler started before the tracer, and a step follows
        # every whole ``h2d_wait``: a pairing off by a whole step
        # breaks one of the two
        if first + offset < 0 or ends[-1] + offset > last_start + 1e6:
            continue
        resid = [b - h - offset for h, b in pairs]
        cand = {"offset_ns": offset, "spread_ns": _spread(resid),
                "worst_ns": max(abs(r) for r in resid),
                "steps": len(pairs), "bursts": len(bursts),
                "waits": len(ends), "dropped": (drop_h, drop_b)}
        if best is None or cand["spread_ns"] < 0.5 * best["spread_ns"]:
            best = cand
    return best


# ---- device idle time by the span the host was in

def self_intervals(events):
    """{name: disjoint intervals on the raw clock} of each span less
    what its children cover; a span with children is listed as
    ``<name> (self)``."""
    kids = {}
    for e in events:
        if e.get("parent_id") is not None:
            kids.setdefault(e["parent_id"], []).append(_interval(e))
    out = {}
    for e in events:
        mine = kids.get(e.get("span_id"))
        if mine:
            name = e["name"] + " (self)"
            own = xplane.subtract([list(_interval(e))], xplane.union(mine))
        else:
            name, own = e["name"], [list(_interval(e))]
        out.setdefault(name, []).extend(own)
    return {k: xplane.union(v) for k, v in out.items()}


def intersect(a, b):
    """Both sorted disjoint."""
    return xplane.subtract(a, xplane.subtract(a, b))


def idle_by_span(tr, events, offset_ns):
    """(idle seconds of the first device, {span name: idle seconds
    inside it}, seconds inside any span that has no child there)."""
    gaps = sorted(xplane.idle_gaps(tr))
    split, leaves = {}, []
    for name, own in self_intervals(events).items():
        own = [[s + offset_ns, e + offset_ns] for s, e in own]
        sec = xplane.length(intersect(gaps, own)) / 1e9
        if sec > 0:
            split[name] = sec
        if not name.endswith(" (self)"):
            leaves += own
    named = xplane.length(intersect(gaps, xplane.union(leaves))) / 1e9
    return xplane.length(gaps) / 1e9, split, named
