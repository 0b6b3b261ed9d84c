#!/usr/bin/env python3
"""Are the k=1 train programs of the training configurations what they
were at another commit? On the CPU, at the tiny presets:

    python3 benchmark/tests/lowered_train_step_texts.py <other checkout> \\
        [cell ...]

``lowered_step_texts.py`` for the training cells: for each one-chip
training cell both trees have (or those named) the executor's
``_jit_train_step`` is lowered on the cell's own first batch, in this
tree and in the other, each in a process of its own whose only
``sys.path`` entry for the program is that tree; the texts
(``jit(...).lower(...).as_text()``: no source locations) are compared
byte for byte. Exits 1 where one differs, printing which."""

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


def training_cells(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    train = next(m for m in bench["end_to_end"] if m["name"]
                 == "train_samples_per_s_per_chip")["workloads"]
    return [w["name"] for w in bench["workloads"]
            if w["name"] in train and w["chips"] == 1]


def texts(root, cells):
    """``{cell: sha256 of the lowered train step}`` in the tree at
    ``root`` (this process must not have imported another tree)."""
    sys.path.insert(0, root)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np
    from benchmark.harness import inputs, spec
    out = {}
    for name in cells:
        cell = spec.load(name)
        with open(os.path.join(root, "benchmark", "tests", "tiny",
                               name + ".json")) as f:
            over = json.load(f)
        _merge(cell.config, over.get("config", {}))
        _merge(cell.traffic, over.get("traffic", {}))
        config, traffic = cell.config, cell.traffic
        builder = spec.load_module("builders", config["builder"])
        from deeplearning4j_tpu.data.dataset import DataSet
        with builder.policy(config):
            net = builder.build(
                config, traffic["inputs"].get("seq_len")).init()
            x, y = inputs.train_pool(dict(traffic, pool_batches=1),
                                     config, 0)[0]
            batch = net._batch_tuple(net._coerce_fit_batch(DataSet(x, y)))
            text = net._make_train_step().lower(
                net.params, net.state, net.opt_state, batch,
                net._rng_key, np.int32(0)).as_text()
        out[name] = hashlib.sha256(text.encode()).hexdigest()
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
    cells = argv[1:] or [c for c in training_cells(here)
                         if c in training_cells(other)]
    mine = _in_a_process_of_its_own(here, cells)
    theirs = _in_a_process_of_its_own(other, cells)
    differ = [k for k in mine if mine[k] != theirs.get(k)]
    for k in sorted(mine):
        print(("DIFFERS  " if k in differ else "identical") + "  " + k)
    print(f"{len(mine) - len(differ)} of {len(mine)} lowered train "
          f"steps are text-identical to {other}'s")
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
