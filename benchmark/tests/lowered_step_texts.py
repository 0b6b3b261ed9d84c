#!/usr/bin/env python3
"""Are the paged step programs of the serving configurations what
they were at another commit? On the CPU, at the tiny presets:

    python3 benchmark/tests/lowered_step_texts.py <other checkout> \\
        [cell ...]

For each serving cell (all that both trees have, or those named) the
id-returning step of ``PagedSlotSession`` (what the batcher runs) is
lowered at t = 1 and at the batcher's chunk width, in this tree and in
the other, each in a process of its own whose only ``sys.path`` entry
for the program is that tree; the texts (``jit(...).lower(...)
.as_text()``: no source locations) are compared byte for byte. Exits
1 where one differs, printing which. ``git archive <commit> | tar -x
-C <dir>`` makes the other checkout."""

import hashlib
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def _merge(dst, src):
    for k, v in src.items():
        if isinstance(v, dict) and isinstance(dst.get(k), dict):
            _merge(dst[k], v)
        else:
            dst[k] = v


def serving_cells(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    serve = next(m for m in bench["end_to_end"]
                 if m["name"] == "serve_tokens_per_s")["workloads"]
    return [w["name"] for w in bench["workloads"] if w["name"] in serve]


def texts(root, cells):
    """``{"<cell> t=<t>": sha256 of the lowered text}`` in the tree at
    ``root`` (this process must not have imported another tree)."""
    sys.path.insert(0, root)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import numpy as np
    from benchmark.harness import spec
    from deeplearning4j_tpu.models.paged_kv import PagedSlotSession
    from deeplearning4j_tpu.serving.continuous import chunk_width
    out = {}
    for name in cells:
        cell = spec.load(name)
        with open(os.path.join(root, "benchmark", "tests", "tiny",
                               name + ".json")) as f:
            over = json.load(f)
        _merge(cell.config, over.get("config", {}))
        _merge(cell.traffic, over.get("traffic", {}))
        config, sv = cell.config, cell.traffic["server"]
        builder = spec.load_module("builders", config["builder"])
        with builder.policy(config):
            net = builder.build(config).init()   # parameters as shapes
            sess = PagedSlotSession(net, sv["slots"], sv["capacity"],
                                    sv["page_size"])
        sess._make_step()
        slots = sv["slots"]
        wide = chunk_width(slots, min(sv["capacity"],
                                      sess.chunk_rows_max))
        for t in sorted({1, wide}):
            ints = np.zeros((slots,), np.int32)
            text = sess._step_ids.lower(
                net.params, net.state, sess._pools,
                np.zeros((slots, sess.pages_per_slot), np.int32), ints,
                np.zeros((slots, t, 1), np.float32), ints, ints,
                ints > 0).as_text()
            out[f"{name} t={t}"] = hashlib.sha256(
                text.encode()).hexdigest()
    return out


def _in_a_process_of_its_own(root, cells):
    got = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--dump", root]
        + cells, cwd=root, check=True, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""))
    return json.loads(got.stdout.strip().splitlines()[-1])


def main(argv):
    if argv[0] == "--dump":
        print(json.dumps(texts(argv[1], argv[2:])))
        return 0
    here = os.path.dirname(os.path.dirname(HERE))
    other = os.path.abspath(argv[0])
    cells = argv[1:] or [c for c in serving_cells(here)
                         if c in serving_cells(other)]
    mine = _in_a_process_of_its_own(here, cells)
    theirs = _in_a_process_of_its_own(other, cells)
    differ = [k for k in mine if mine[k] != theirs.get(k)]
    for k in sorted(mine):
        print(("DIFFERS  " if k in differ else "identical") + "  " + k)
    print(f"{len(mine) - len(differ)} of {len(mine)} lowered step "
          f"programs are text-identical to {other}'s")
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
