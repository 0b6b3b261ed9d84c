"""The first device's busy time by the ``jax.named_scope`` the program
put on each op.

The TPU's trace names a device op by its HLO instruction
(``%fusion.12``) and carries no ``op_name``; the program publishes,
for every step program it has built, the table from instruction to
``op_name`` (``deeplearning4j_tpu.observability.programs.scope_tables``).
Three steps from a trace to a number:

``assign``  walks the device's ops in start order and gives each its
            program and ``op_name`` by matching RUNS: from an op whose
            name a registered program has, the ops that follow must
            come in that program's order (instructions a trace omits
            are skipped; forward, but for ``SLACK`` positions the
            trace itself reorders). Of the programs that have the
            name, the one whose order the most ops follow gets the
            run: two programs with the same instruction names (the
            two widths of the paged step) are told apart by what
            follows. An op in no run of two ops or more is outside
            every registered program (a page copy, a transfer).
``group``   the groups an ``op_name`` counts towards, ONE table of
            expressions for both executors and every block. How
            scopes are grouped is the benchmark's and not the
            program's, so no change to the program moves it.
``busy_by`` ``{group: seconds}`` of the first device's busy time,
            memoised on ``obs``, printing one table a run. Where more
            than ``MAX_OUTSIDE`` of the busy time lies outside every
            registered program, or the program has no registry, every
            reader gets None and the print says why: never a number
            from a table that did not match.
"""

import re
import time

MAX_OUTSIDE = 0.05
# How far behind the run's furthest instruction an op may sit. The
# trace starts a zero-duration ``custom-call`` up to 5 instructions
# after the schedule lists it (my chip runs, PR 37: `gpt2m_train`,
# `gpt2m_serve_closed`, `mimo_serve_mixedlen`); forward only, a step
# broke into 4 to 49 runs; with 8, 32 or 128 every run is one whole
# step of 948, 9,663, 1,988 or 1,926 ops.
SLACK = 8
OUTSIDE = "(outside any registered program)"
NO_SCOPE = "(no scope)"

# <i>_<Class> of both executors' layer loops and the paged step's,
# then the block's own name for its part
_LAYER = re.compile(
    r"[(/]\d+_([A-Za-z0-9]+)\)*"
    r"(?:/(attn/global|attn/window|attn|mla0|mla1|mla|moe/router"
    r"|moe/experts|moe/shared|moe/zero|mlp0|mlp1|mlp|ln1|ln2)"
    r"(?=[/)]|$))?")
# a function jitted inside the step (``jit(_where)``): no scope
_CALL = re.compile(r"^p?jit\(")
# a transform around a scope (``transpose(jvp(3_Block))``): none itself
_WRAPPER = re.compile(r"\w+\(|\)")
# a vertex of the zoo's ResNet50 by its stage and kind
_VERTEX = re.compile(r"^(stem|s\d+)(?:b\d+)?_(?:[a-z]+_)?([a-z]+)$")
_ATTENTION_PARTS = {"attn", "attn/global", "attn/window", "mla", "mla0",
                    "mla1"}
_ATTENTION_LAYERS = {"SelfAttentionLayer", "LatentAttentionLayer",
                     "GroupedQueryAttentionLayer"}
_EXPERT_PARTS = {"moe/experts", "moe/shared", "moe/zero"}
_MLP_PARTS = {"mlp", "mlp0", "mlp1"}


def scope_path(op_name):
    """The named scopes of an ``op_name`` without what wraps them and
    without the primitive at its end:
    ``jit(train_step)/transpose(jvp(3_Block))/mlp/dot_general`` ->
    ``3_Block/mlp``; "" where the op was traced under no scope."""
    parts = []
    for part in op_name.split("/")[:-1]:
        if _CALL.match(part):
            continue
        part = _WRAPPER.sub("", part)
        if part:
            parts.append(part)
    return "/".join(parts)


def label(op_name):
    """The row of the printed table an op counts towards: a layer's
    class with the block's part (every index together), a ResNet50
    vertex by stage and kind, any other scope by its path."""
    m = _LAYER.search(op_name)
    if m:
        return "/".join(x for x in m.groups() if x)
    path = scope_path(op_name)
    v = _VERTEX.match(path.split("/")[0]) if path else None
    if v:
        return f"{v.group(1)} {v.group(2)}"
    return path or NO_SCOPE


def group(op_name):
    """The groups (metrics) an op of a registered program counts
    towards. Norms, router, head, loss and embedding are in the
    printed table and in no group."""
    out = set()
    if "transpose(" in op_name:
        out.add("backward")
    if "/updater/" in op_name or op_name.endswith("/updater"):
        out.add("updater")
        return out
    m = _LAYER.search(op_name)
    if m is None:
        if not scope_path(op_name):
            out.add("unscoped")
        return out
    layer, part = m.groups()
    if part in _ATTENTION_PARTS or (part is None
                                    and layer in _ATTENTION_LAYERS):
        out.add("attention")
    if part == "attn/window":
        out.add("window_attention")
    if part in _EXPERT_PARTS:
        out.add("experts")
    if part in _MLP_PARTS:
        out.add("mlp")
    return out


def own_ns(ops):
    """For ops in start order, the nanoseconds of the device's busy
    time that are each op's own: its interval less what ops that
    began inside it cover (a loop's body inside its ``while``). Sums
    to the union of the intervals, which is what
    ``xplane.busy_and_window`` calls busy."""
    own, stack, at = [0] * len(ops), [], 0
    for i, (_, s, d) in enumerate(ops):
        while stack and stack[-1][0] <= s:
            end, j = stack.pop()
            own[j] += max(0, end - at)
            at = max(at, end)
        if stack:
            own[stack[-1][1]] += max(0, s - at)
        at = max(at, s)
        stack.append((s + d, i))
    while stack:
        end, j = stack.pop()
        own[j] += max(0, end - at)
        at = max(at, end)
    return own


def _follow(index, names, i):
    """How many of ``names[i:]`` come in the order of the program
    whose instruction positions are ``index``, and the furthest table
    position reached. An op may sit up to ``SLACK`` positions behind
    the furthest, once: the trace's own order."""
    at, j = index[names[i]], i + 1
    seen = {at}
    while j < len(names):
        nxt = index.get(names[j])
        if nxt is None or nxt in seen or nxt <= at - SLACK:
            break
        seen.add(nxt)
        at, j = max(at, nxt), j + 1
    return j - i, at


def assign(tr, tables):
    """``(ops, rows, runs)``: the first device's ``ops`` in start
    order, ``rows[k] = (program or None, op_name)`` for the k-th of
    them, and the runs matched, by program."""
    ops = sorted(tr["devices"][0]["ops"], key=lambda o: (o[1], -o[2]))
    names = [o[0].lstrip("%") for o in ops]
    index = {p: {n: k for k, (n, _) in enumerate(t)}
             for p, t in tables.items()}
    rows, runs, i = [], {p: 0 for p in tables}, 0
    while i < len(names):
        best = None
        for p in sorted(index):
            if names[i] in index[p]:
                n, last = _follow(index[p], names, i)
                # the longest run; of two alike, the one that skipped
                # the fewest instructions
                key = (n, index[p][names[i]] - last)
                if best is None or key > best[0]:
                    best = (key, p, n)
        if best is None or best[2] < 2:
            rows.append((None, ""))
            i += 1
            continue
        _, p, n = best
        table = tables[p]
        rows += [(p, table[index[p][name]][1]) for name in names[i:i + n]]
        runs[p] += 1
        i += n
    return ops, rows, runs


def _tables():
    try:
        from deeplearning4j_tpu.observability.programs import scope_tables
    except ImportError:
        return None
    t0 = time.perf_counter()
    tables = scope_tables()
    print(f"scopes: the tables of {len(tables)} registered programs "
          f"({', '.join(f'{p} {len(t)}' for p, t in sorted(tables.items()))}"
          f" instructions) took {time.perf_counter() - t0:.2f} s",
          flush=True)
    return tables


def busy_by(obs, tables=None):
    """``{group: seconds}`` with ``"busy"`` the whole, or None (see the
    module's docstring). ``tables`` stands in for the program's."""
    if "busy_by_scope" in obs:
        return obs["busy_by_scope"]
    obs["busy_by_scope"] = out = _busy_by(obs.get("trace"), tables)
    return out


def _busy_by(tr, tables):
    if tr is None:
        return None
    tables = _tables() if tables is None else tables
    if not tables:
        print("scopes: nothing to read: the program "
              + ("has no registry of step programs" if tables is None
                 else "registered no step program"), flush=True)
        return None
    ops, rows, runs = assign(tr, tables)
    own = own_ns(ops)
    busy = sum(own)
    # a few thousand distinct rows stand for millions of ops
    by_row = {}
    for ns, row in zip(own, rows):
        by_row[row] = by_row.get(row, 0) + ns
    by_label, by_group = {}, {}
    for (program, op_name), ns in by_row.items():
        if program is None:
            key, groups = OUTSIDE, ("unscoped",)
        else:
            key = label(op_name) + (
                " backward" if "transpose(" in op_name else "")
            groups = group(op_name)
        by_label[key] = by_label.get(key, 0) + ns
        for g in groups:
            by_group[g] = by_group.get(g, 0) + ns
    outside = by_label.get(OUTSIDE, 0)
    print(f"scopes: {len(ops)} ops on the first device, busy "
          f"{busy / 1e9:.4f} s; runs matched: "
          + ", ".join(f"{p} {n}" for p, n in sorted(runs.items()))
          + f"; outside every registered program "
          f"{100.0 * outside / max(busy, 1):.2f} %", flush=True)
    for key, ns in sorted(by_label.items(), key=lambda kv: -kv[1])[:40]:
        print(f"scopes:   {ns / 1e9:9.4f} s {100.0 * ns / busy:5.1f} %  "
              f"{key}", flush=True)
    if busy <= 0 or outside > MAX_OUTSIDE * busy:
        print(f"scopes: nothing to read: more than "
              f"{100 * MAX_OUTSIDE:.0f} % of busy time matched no "
              f"registered program", flush=True)
        return None
    out = {g: ns / 1e9 for g, ns in by_group.items()}
    out["busy"] = busy / 1e9
    return out


def share_pct(obs, which):
    """Percent of the first device's busy time in group ``which``; 0
    where the table matched and nothing of that group ran."""
    by = busy_by(obs)
    if by is None:
        return None
    return 100.0 * by.get(which, 0.0) / by["busy"]
