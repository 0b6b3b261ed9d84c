#!/usr/bin/env python3
"""Times, on the chip and at a configuration's own widths, the two
ways one chip's held experts can serve a decode batch (ISSUE 26):

    python3 benchmark/tests/measure_expert_paths.py <config> [rows ...]

``dense``: the program's ``SparseExpertsLayer`` as it stands (every
row through every held expert, the combine weight picks). ``grouped``:
the held (token, expert) pairs sorted by expert and run as
``jax.lax.ragged_dot`` over the experts' stacked weights, written here
and nowhere in the program. Both give the same numbers (checked).
Prints one JSON line per (rows, path) with the mean milliseconds of a
call and the time the held experts' weights alone need at the chip's
memory bandwidth. PERF.md section 5 quotes the reading.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def grouped(layer, params, x):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    n, k = x.shape[0], layer.top_k
    first, count = layer.held_range()
    ids, w = layer.route(params, x)
    local = (ids - first).reshape(-1)
    mine = (local >= 0) & (local < count)
    key = jnp.where(mine, local, count)
    order = jnp.argsort(key)
    token = (jnp.arange(n * k) // k)[order]
    sizes = jnp.bincount(key, length=count + 1)[:count].astype(jnp.int32)
    xs = x[token]
    dot = lambda a, b: jax.lax.ragged_dot(
        a, b, sizes, preferred_element_type=f32)
    a = (jax.nn.silu(dot(xs, params["Wg"])) * dot(xs, params["Wu"])
         ).astype(x.dtype)
    y = dot(a, params["Wd"])
    wk = jnp.where(mine, w.reshape(-1), 0.0)[order]
    out = jnp.zeros((n, x.shape[1]), f32).at[token].add(y * wk[:, None])
    from deeplearning4j_tpu.nn.conf.layers.moe import swiglu
    out = out + swiglu(x, params["Wsg"], params["Wsu"],
                       params["Wsd"]).astype(f32)
    return out.astype(x.dtype)


def main(config_name, rows):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.harness import peaks, spec, weights
    bench = spec._json(os.path.join(ROOT, "BENCHMARK.json"))
    conf = next(c for c in bench["configs"] if c["name"] == config_name)
    config = spec._json(os.path.join(ROOT, conf["file"]))
    builder = spec.load_module("builders", config["builder"])
    dev = jax.devices()[0]
    pk = peaks.peaks_for(dev.device_kind)
    with builder.policy(config):
        block = builder.block(config, config["first_k_dense_replace"])
        block.n_in = config["hidden_size"]
        layer = block._ensure_parts()[1]
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        shapes = jax.eval_shape(lambda: layer.initialize(
            jax.random.PRNGKey(0),
            InputType.recurrent(config["hidden_size"]))[0])
    params = weights.maker(shapes, config["init"])(7)
    held_bytes = sum(int(np.prod(shapes[k].shape)) * 2
                     for k in ("Wg", "Wu", "Wd"))
    paths = {"dense": jax.jit(
        lambda p, x: layer.apply_counted(p, x)[0]),
        "grouped": jax.jit(lambda p, x: grouped(layer, p, x))}
    for n in rows:
        x = jax.random.normal(jax.random.PRNGKey(n),
                              (n, config["hidden_size"]), jnp.bfloat16)
        outs = {}
        for name, fn in paths.items():
            outs[name] = np.asarray(fn(params, x), np.float32)
            fn(params, x).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(30):
                y = fn(params, x)
            y.block_until_ready()
            ms = (time.perf_counter() - t0) / 30 * 1e3
            print(json.dumps({
                "rows": n, "path": name, "ms": ms,
                "held_weights_ms": held_bytes / pk["bytes_per_s"] * 1e3,
                "device": dev.device_kind}), flush=True)
        print(json.dumps({"rows": n, "max_abs_difference": float(
            np.abs(outs["dense"] - outs["grouped"]).max()),
            "max_abs": float(np.abs(outs["dense"]).max())}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], [int(r) for r in sys.argv[2:]] or [64])
