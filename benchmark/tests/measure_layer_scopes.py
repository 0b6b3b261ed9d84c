#!/usr/bin/env python3
"""Runs one cell once with ``--trace 1`` and, from the same profiler
trace before the session deletes it, splits the first device's op time
by the ``jax.named_scope`` the program put on each op (the layer loops
of both executors, the loss and the updater: ISSUE 24):

    python3 benchmark/tests/measure_layer_scopes.py <workload> <seed> \\
        [seconds]

The TPU's trace events carry no ``op_name`` (their stats are the
device's offsets alone: probed on a v5e, PR 24), so the scope of an op
is read from the compiled train step's own HLO text, instruction by
instruction name, taken through the driver's ``break_step`` hook the
first time the step is called. Training cells on one chip only. No
per-layer metric reads the scopes; this is the by-hand reading PERF.md
section 5 quotes. The result line of the run is printed as
``benchmark/run.py`` prints it; the table follows on standard output
and goes to ``chiprun_out/layer_scopes.<workload>.json`` with the op
count a step.
"""

import bisect
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# an instruction of the compiled module with its metadata
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?(%?[\w.\-]+) = .*?metadata=\{[^}]*?op_name=\"([^\"]*)\"",
    re.M)
LAYER = re.compile(
    r"[(/](\d+)_([A-Za-z0-9]+)\)*(?:/(ln1|attn|mlp|ln2)\b)?")
VERTEX = re.compile(
    r"[(/]((?:stem|s\d+b\d+)_[a-z]+(?:_[a-z]+)?|avgpool|out)\)*/")


def scope_of(op_name):
    """(scope, direction) from an op's ``op_name`` metadata, such as
    ``jit(train_step)/transpose(jvp(3_TransformerEncoderLayer))/mlp/dot_general``."""
    direction = "backward" if "transpose(" in op_name else "forward"
    if "/updater/" in op_name or op_name.endswith("/updater"):
        return "updater", "update"
    m = LAYER.search(op_name)
    if m:
        return "_".join(x for x in (m.group(2), m.group(3)) if x), direction
    m = VERTEX.search(op_name)
    if m:
        return m.group(1), direction
    return "(no scope)", direction


def kind_of(scope):
    """A vertex of the zoo's ResNet50 by what it is: its stage and the
    layer kind (``s2 conv``); other scopes stand for themselves."""
    m = re.match(r"(stem|s\d+)(?:b\d+)?_(?:[a-z]+_)?([a-z]+)$", scope)
    return f"{m.group(1)} {m.group(2)}" if m else scope


def op_names(hlo_text):
    """{instruction name without ``%``: its ``op_name``}."""
    return {m.group(1).lstrip("%"): m.group(2)
            for m in INSTRUCTION.finditer(hlo_text)}


def grab_hlo(found):
    """A ``break_step`` hook: the first call of the net's train step
    also keeps the compiled module's text."""
    def hook(net):
        real = net._make_train_step

        def make():
            step = real()

            class Step:
                lower = step.lower

                def __call__(self, *args):
                    if "hlo" not in found:
                        found["hlo"] = step.lower(
                            *args).compile().as_text()
                    return step(*args)
            return Step()
        net._make_train_step = make
        net._jit_train_step = None
    return hook


def reduce_profile(pd, names):
    """{table rows, ops on the first device, ops a step}."""
    plane = sorted((p for p in pd.planes
                    if p.name.startswith("/device:TPU:")),
                   key=lambda p: p.name)[0]
    line = next(l for l in plane.lines if l.name == "XLA Ops")
    by, missed, examples, starts = {}, 0, {}, []
    for e in line.events:
        short = e.name.split(" = ")[0].lstrip("%")
        op_name = names.get(short, "")
        missed += short not in names
        scope, direction = scope_of(op_name)
        base = re.sub(r"\.\d+$", "", short)
        what = ("flash" if "flash" in base else
                "fusion" if base == "fusion" else "other op")
        key = (kind_of(scope), direction, what)
        by[key] = by.get(key, 0) + e.duration_ns
        examples.setdefault(key, op_name[-160:])
        starts.append(("", int(e.start_ns), int(e.duration_ns)))
    rows = [{"scope": k[0], "direction": k[1], "op": k[2],
             "seconds": v / 1e9, "example": examples[k]}
            for k, v in sorted(by.items(), key=lambda kv: -kv[1])]
    # ops a step: between two gaps of 5 ms or more
    from benchmark.harness import program_spans
    bursts = program_spans.burst_starts(starts, 5_000_000)
    at = sorted(s for _, s, _ in starts)
    cuts = [bisect.bisect_left(at, b) for b in bursts]
    return {"rows": rows, "ops": len(starts),
            "ops_without_metadata": missed,
            "ops_per_step": [b - a for a, b in zip(cuts, cuts[1:])]}


def main(workload, seed, seconds=45.0):
    from benchmark import run as bench_run
    from benchmark.harness import xplane
    import jax
    found = {}
    real = xplane.from_profile

    def from_profile(pd):
        found.update(reduce_profile(pd, op_names(found.get("hlo", ""))))
        return real(pd)

    xplane.from_profile = from_profile
    result = bench_run.main(["--workload", workload, "--seed", str(seed),
                             "--seconds", str(seconds), "--trace", "1"],
                            break_step=grab_hlo(found))
    found.pop("hlo", None)
    total = sum(r["seconds"] for r in found["rows"])
    print(f"layer scopes of {workload}: {found['ops']} ops on the first "
          f"device ({found['ops_without_metadata']} not in the compiled "
          f"step's text), {total:.4f} s busy; ops a step (between gaps "
          f"of 5 ms): {found['ops_per_step']}", flush=True)
    for r in found["rows"][:60]:
        print(f"  {r['seconds']:9.4f} s {100 * r['seconds'] / total:5.1f} % "
              f" {r['scope']:34s} {r['direction']:8s} {r['op']:8s} "
              f"{r['example'][-70:]}", flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"layer_scopes.{workload}.json"),
              "w") as f:
        json.dump({"workload": workload, "seed": seed,
                   "device": jax.devices()[0].device_kind,
                   "result": result, **found}, f, indent=1)
    return found


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]),
         float(sys.argv[3]) if len(sys.argv) > 3 else 45.0)
