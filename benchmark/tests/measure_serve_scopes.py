#!/usr/bin/env python3
"""``measure_layer_scopes.py`` for a serving cell: runs the cell once
with ``--trace 1`` and splits the first device's op time by the
``jax.named_scope`` the program put on each op of the paged decode
step (the layer's ``<i>_<Class>`` and, inside a block, ``mla``,
``moe/router``, ``moe/experts``, ``moe/shared``, ``mlp``, ``attn``):

    python3 benchmark/tests/measure_serve_scopes.py <workload> <seed> \\
        [seconds]

The scope of an op is read from the compiled step's own HLO text,
taken through the driver's ``break_token`` hook the first time the
paged session builds its step. The table follows the run's result
line and goes to ``chiprun_out/serve_scopes.<workload>.json``.
"""

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.tests import measure_layer_scopes as train_scopes

SCOPE = re.compile(
    r"[(/](\d+)_([A-Za-z0-9]+)\)*"
    r"(?:/(mla|moe/router|moe/experts|moe/shared|mlp|attn|ln1|ln2)\b)?")


def scope_of(op_name):
    m = SCOPE.search(op_name)
    if not m:
        return "(no scope)"
    return m.group(3) or m.group(2)


def grab_hlo(found):
    """A ``break_token`` hook: the paged session's step, the first
    time it is called, also keeps its compiled module's text."""
    def hook(server):
        from deeplearning4j_tpu.models.paged_kv import PagedSlotSession
        real = PagedSlotSession._make_step

        def make(self):
            step = real(self)

            def call(*args):
                if "hlo" not in found:
                    found["hlo"] = step.lower(*args).compile().as_text()
                return step(*args)
            return call
        PagedSlotSession._make_step = make
    return hook


def reduce_profile(pd, names):
    plane = sorted((p for p in pd.planes
                    if p.name.startswith("/device:TPU:")),
                   key=lambda p: p.name)[0]
    line = next(l for l in plane.lines if l.name == "XLA Ops")
    by, n, missed = {}, 0, 0
    for e in line.events:
        short = e.name.split(" = ")[0].lstrip("%")
        missed += short not in names
        base = re.sub(r"[.\d]+$", "", short)
        key = (scope_of(names.get(short, "")), base)
        by[key] = by.get(key, 0) + e.duration_ns
        n += 1
    return {"rows": [{"scope": k[0], "op": k[1], "seconds": v / 1e9}
                     for k, v in sorted(by.items(),
                                        key=lambda kv: -kv[1])],
            "ops": n, "ops_without_metadata": missed}


def main(workload, seed, seconds=45.0):
    import jax
    from benchmark import run as bench_run
    from benchmark.harness import xplane
    found = {}
    real = xplane.from_profile

    def from_profile(pd):
        found.update(reduce_profile(
            pd, train_scopes.op_names(found.get("hlo", ""))))
        return real(pd)

    xplane.from_profile = from_profile
    result = bench_run.main(["--workload", workload, "--seed", str(seed),
                             "--seconds", str(seconds), "--trace", "1"],
                            break_token=grab_hlo(found))
    found.pop("hlo", None)
    total = sum(r["seconds"] for r in found["rows"])
    scopes = {}
    for r in found["rows"]:
        scopes[r["scope"]] = scopes.get(r["scope"], 0.0) + r["seconds"]
    print(f"scopes of {workload}: {found['ops']} ops on the first device "
          f"({found['ops_without_metadata']} not in the compiled step's "
          f"text), {total:.4f} s busy", flush=True)
    for k, v in sorted(scopes.items(), key=lambda kv: -kv[1]):
        print(f"  {v:9.4f} s {100 * v / total:5.1f} %  {k}", flush=True)
    print("by scope and op:", flush=True)
    for r in found["rows"][:40]:
        print(f"  {r['seconds']:9.4f} s {100 * r['seconds'] / total:5.1f} % "
              f" {r['scope']:24s} {r['op']}", flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"serve_scopes.{workload}.json"),
              "w") as f:
        json.dump({"workload": workload, "seed": seed,
                   "device": jax.devices()[0].device_kind,
                   "result": result, "scopes": scopes, **found}, f,
                  indent=1)
    return found


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]),
         float(sys.argv[3]) if len(sys.argv) > 3 else 45.0)
