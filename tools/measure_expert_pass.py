#!/usr/bin/env python3
"""One expert layer at a configuration's own widths, on the chip: the
dense pass against the program's grouped pass (ISSUE 43):

    python3 tools/measure_expert_pass.py <config> [rows ...] \\
        [--valid SHARE]

``dense`` is ``SparseExpertsLayer.apply_tallied`` as every call off a
serving step runs it (every row through every held expert);
``grouped`` is the same method as a serving step runs it
(``stream=True``) with ``ops.grouped_experts.grouped_pass`` held True,
so that the kernel is timed at row counts the predicate leaves to the
dense pass too: what is timed is the program's own code, imported.
The layer is the configuration's first expert layer, its weights made
from the configuration's ``init`` (routing over random rows is near
uniform: a share that holds a sixteenth of the router's width is
picked by a sixteenth of the pairs). ``--valid`` marks that share of
the rows as carrying a token (a chunk step's ragged rows; default 1).

One JSON line per (rows, path): the mean milliseconds of a call (30
calls enqueued back to back, so the device's time and not the host's
dispatch), the (row, held expert) pairs and the held experts hit, and
the time the held and the HIT experts' weights alone need at the
chip's memory bandwidth; then a line with the widest absolute gap
between the two outputs and whether the two tallies are equal.
``benchmark/tests/measure_expert_paths.py`` is the benchmark's older
twin (the dense pass against ``jax.lax.ragged_dot``) and is left as
it is. PERF.md section 6 quotes the readings.
"""

import inspect
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CALLS = 30


def expert_layer(config_name):
    """(layer, parameter shapes, configuration) of ``config_name``'s
    first expert layer, built by the benchmark's builder under its
    dtype policy."""
    import jax
    from benchmark.harness import spec
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    bench = spec._json(os.path.join(ROOT, "BENCHMARK.json"))
    conf = next(c for c in bench["configs"] if c["name"] == config_name)
    config = spec._json(os.path.join(ROOT, conf["file"]))
    builder = spec.load_module("builders", config["builder"])
    by_layer = len(inspect.signature(builder.block).parameters) > 1
    first = config.get("first_layer", 0)
    with builder.policy(config):
        for i in range(first, first + 64):
            block = builder.block(config, i) if by_layer \
                else builder.block(config)
            block.n_in = config["hidden_size"]
            layer = block._ensure_parts()[1]
            if layer is not None:
                break
        shapes = jax.eval_shape(lambda: layer.initialize(
            jax.random.PRNGKey(0),
            InputType.recurrent(config["hidden_size"]))[0])
    return layer, shapes, config


def measure(config_name, rows, valid=1.0, out=print):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.harness import peaks, weights
    from deeplearning4j_tpu.ops import grouped_experts
    layer, shapes, config = expert_layer(config_name)
    dev = jax.devices()[0]
    pk = peaks.peaks_for(dev.device_kind)
    params = weights.maker(shapes, config["init"])(7)
    held = layer.held_range()[1]
    expert_bytes = sum(int(np.prod(shapes[k].shape)) * 2
                       for k in ("Wg", "Wu", "Wd")) / held
    grouped_experts.grouped_pass = lambda *a: True
    paths = {name: jax.jit(lambda p, x, a, s=stream: layer.apply_tallied(
                 p, x, a, s))
             for name, stream in (("dense", False), ("grouped", True))}
    for n in rows:
        x = jax.random.normal(jax.random.PRNGKey(n),
                              (n, config["hidden_size"]), jnp.bfloat16)
        active = jnp.arange(n) < round(valid * n)
        got = {}
        for name, fn in paths.items():
            y, tally = fn(params, x, active)
            got[name] = (np.asarray(y, np.float32),
                         jax.tree_util.tree_map(np.asarray, tally))
            t0 = time.perf_counter()
            for _ in range(CALLS):
                y, _ = fn(params, x, active)
            y.block_until_ready()
            ms = (time.perf_counter() - t0) / CALLS * 1e3
            counts = got[name][1]["held"]
            hit = int((counts > 0).sum())
            out(json.dumps({
                "config": config_name, "rows": n, "valid": valid,
                "path": name, "ms": ms, "held_pairs": int(counts.sum()),
                "held": held, "hit": hit,
                "held_weights_ms":
                    held * expert_bytes / pk["bytes_per_s"] * 1e3,
                "hit_weights_ms":
                    hit * expert_bytes / pk["bytes_per_s"] * 1e3,
                "device": dev.device_kind}), flush=True)
        (yd, td), (yg, tg) = got["dense"], got["grouped"]
        out(json.dumps({
            "config": config_name, "rows": n,
            "max_abs_difference": float(np.abs(yd - yg).max()),
            "max_abs": float(np.abs(yd).max()),
            "tallies_equal": all(np.array_equal(td[k], tg[k])
                                 for k in td)}), flush=True)


if __name__ == "__main__":
    args = sys.argv[1:]
    share = 1.0
    if "--valid" in args:
        i = args.index("--valid")
        share = float(args[i + 1])
        del args[i:i + 2]
    measure(args[0], [int(r) for r in args[1:]] or [256], share)
