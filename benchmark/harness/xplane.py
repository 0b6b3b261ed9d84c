"""From a JAX profiler trace (``*.xplane.pb``) to numbers.

``load_dir`` turns the trace into plain lists (the form the recorded
test trace beside benchmark/tests is kept in):

    {"devices": [{"name": "/device:TPU:0",
                  "ops":   [[name, start_ns, dur_ns], ...],   "XLA Ops"
                  "async": [[name, start_ns, dur_ns], ...]}], "Async XLA Ops"
     "host": [[line, name, start_ns, dur_ns], ...]}

An op's name is the HLO instruction text up to `` = `` (for example
``%pallas_flash_attention_bwd.3``); its full text is kept as ``text``
in a side table so a reader can parse operand shapes.

Busy time is the union of the intervals of the "XLA Ops" line of a
device (the ops the core runs one after another); the asynchronous
line holds copies and collectives in flight, which overlap them.
"""

import glob
import os
import re

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
# host events that say nothing about what the host was doing
_DULL = ("$", "Acquire semaphore", "MemoryAllocation",
         "MemoryDeallocation")


def load_dir(trace_dir):
    import jax
    files = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        raise RuntimeError(f"no xplane file under {trace_dir}")
    return from_profile(jax.profiler.ProfileData.from_file(files[0]))


def from_profile(pd):
    out = {"devices": [], "host": [], "text": {}}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"name": plane.name, "ops": [], "async": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "Async XLA Ops": "async"}.get(
                    line.name)
                if key is None:
                    continue
                for e in line.events:
                    short = e.name.split(" = ")[0]
                    out["text"].setdefault(short, e.name)
                    dev[key].append([short, int(e.start_ns),
                                     int(e.duration_ns)])
            out["devices"].append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        out["host"].append([line.name, e.name,
                                            int(e.start_ns),
                                            int(e.duration_ns)])
    out["devices"].sort(key=lambda d: d["name"])
    return out


# ---- intervals

def union(intervals):
    """Sorted disjoint [start, end) from any (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def length(disjoint):
    return sum(e - s for s, e in disjoint)


def subtract(a, b):
    """a minus b, both sorted disjoint."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _spans(events):
    return [(s, s + d) for _, s, d in events]


def device_span(tr):
    """[first op start, last op end) over all devices, ns."""
    starts = [d["ops"][0][1] for d in tr["devices"] if d["ops"]]
    ends = [max(s + du for _, s, du in d["ops"])
            for d in tr["devices"] if d["ops"]]
    if not starts:
        raise RuntimeError("the trace holds no device operation")
    return min(starts), max(ends)


def busy_and_window(tr):
    """(busy seconds averaged over devices, window seconds)."""
    t0, t1 = device_span(tr)
    busy = [length(union(_spans(d["ops"]))) for d in tr["devices"]]
    return sum(busy) / len(busy) / 1e9, (t1 - t0) / 1e9


def op_time(tr, pattern):
    """Seconds (averaged over devices) and calls (on the first device)
    of the ops whose name matches ``pattern``; and their names."""
    rx = re.compile(pattern)
    per_dev, names = [], []
    for d in tr["devices"]:
        hit = [(n, du) for n, _, du in d["ops"] if rx.search(n)]
        per_dev.append(sum(du for _, du in hit))
        if not names:
            names = [n for n, _ in hit]
    return sum(per_dev) / max(len(per_dev), 1) / 1e9, names


def exposed_collective(tr):
    """Seconds (averaged over devices) in which a collective is in
    flight or waited for and no other op runs on that device."""
    total = []
    for d in tr["devices"]:
        coll = union(_spans([e for e in d["ops"] + d["async"]
                             if COLLECTIVE.search(e[0])]))
        compute = union(_spans([e for e in d["ops"]
                                if not COLLECTIVE.search(e[0])]))
        total.append(length(subtract(coll, compute)))
    return sum(total) / max(len(total), 1) / 1e9


def idle_gaps(tr):
    """Gaps of the first device inside the device span, longest first:
    [[start_ns, end_ns], ...]."""
    d = tr["devices"][0]
    t0, t1 = device_span(tr)
    gaps = subtract([[t0, t1]], union(_spans(d["ops"])))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def _host_table(tr):
    """The host events that can name a gap, as columns."""
    import numpy as np
    rows = [(name, hs, hs + hd) for _, name, hs, hd in tr["host"]
            if not name.startswith(_DULL)]
    return {"name": [r[0] for r in rows],
            "start": np.array([r[1] for r in rows], dtype=np.int64),
            "end": np.array([r[2] for r in rows], dtype=np.int64),
            "bench": np.array([r[0].startswith("bench/") for r in rows],
                              dtype=bool)}


def _host_name(table, gap):
    """What the host was doing in a gap: the benchmark's own
    annotation (``bench/...``) that covers most of it, then the
    runtime event that covers most of it; of two that cover it alike,
    the narrower."""
    import numpy as np
    s, e = gap
    ov = np.minimum(e, table["end"]) - np.maximum(s, table["start"])
    parts = []
    for kind in (table["bench"], ~table["bench"]):
        idx = np.flatnonzero((ov > 0) & kind)
        if idx.size:
            dur = (table["end"] - table["start"])[idx]
            best = idx[np.lexsort((dur, -ov[idx]))[0]]
            parts.append(table["name"][best])
    if not table["name"]:
        return "(host not traced)"
    return " > ".join(parts) if parts else "(no host event)"


def breakdown(tr, top=10):
    """The contract's ``breakdown``: device ops by total seconds on
    the first device, idle gaps summed by what the host was doing."""
    ops = {}
    for n, _, du in tr["devices"][0]["ops"]:
        base = re.sub(r"\.\d+$", "", n)
        ops[base] = ops.get(base, 0) + du
    gaps, table = {}, _host_table(tr)
    for g in idle_gaps(tr)[:200]:
        name = _host_name(table, g)
        gaps[name] = gaps.get(name, 0) + (g[1] - g[0])
    rank = lambda m: [[k, v / 1e9] for k, v in sorted(
        m.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(ops), "idle_gaps": rank(gaps)}
