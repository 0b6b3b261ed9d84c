#!/usr/bin/env python3
"""Reads, on the chip and at ``trinity_train_8k``'s own shapes, one
layer's forward + backward by each route the program could take
(PERF.md gives the readings):

    python3 benchmark/tests/measure_trinity_paths.py [repeats]

- attention, a window layer and a full layer: the flash kernels' band
  over all 32 query heads on 4 key heads; and, on ONE key head's group
  of 8 (the einsum's (8, T, T) float32 scores of all four groups do
  not fit beside their gradient), the band beside the exact einsum
  (``GroupedQueryAttentionLayer._attend``'s mathematics,
  ``ops.attention._exact_band``);
- the held experts' part of one expert layer at 8,192 rows under
  uniform routing: the dense pass and the pairs pass
  (``jax.lax.ragged_dot``); and the pairs pass under a skew that
  sends every row to two held experts.

Every timing is the mean of ``repeats`` runs of one jitted
value-and-gradient, after a warm-up, ended by ``block_until_ready``.
It times a chip: off a TPU the flash route would be the einsum under
another name, so it exits 2 before building anything, and it checks
that each route it labels is the route the program takes.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def timed(fn, args, repeats):
    """Milliseconds a call, or why it could not run (out of memory)."""
    import jax
    try:
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = fn(*args)
        jax.block_until_ready(out)
    except Exception as e:          # noqa: BLE001 -- recorded, not hidden
        return f"failed: {type(e).__name__}: {str(e)[:160]}"
    return 1e3 * (time.perf_counter() - t0) / repeats


def main(repeats=5):
    import jax
    import jax.numpy as jnp
    from benchmark.harness import peaks, spec
    from deeplearning4j_tpu.ops import attention, grouped_experts
    dev = jax.devices()[0]
    if jax.default_backend() != "tpu":
        print(f"benchmark/tests/measure_trinity_paths.py times a chip "
              f"and found {dev.device_kind!r}: run it through chiprun",
              file=sys.stderr)
        raise SystemExit(2)
    peaks.peaks_for(dev.device_kind)     # a chip the benchmark knows
    cell = spec.load("trinity_train_8k")
    c, t = cell.config, cell.traffic["inputs"]["seq_len"]
    H, K, d = (c["num_attention_heads"], c["num_key_value_heads"],
               c["head_dim"])
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    out = {"device": dev.device_kind}
    block = attention._auto_block(t, d)
    assert attention._use_pallas(t, block, block), "flash = the kernels"
    assert grouped_experts.pairs_pass(t), "the layer takes the pairs pass"

    def grad_of(f):
        return jax.jit(jax.grad(lambda *a: jnp.sum(f(*a) ** 2),
                                argnums=(0, 1, 2)))

    for name, window in (("window", c["sliding_window"]), ("full", None)):
        flash = lambda q, k, v: attention.flash_attention(
            q, k, v, causal=True, window=window)
        exact = lambda q, k, v: attention._exact_band(q, k, v, window)
        for heads, kv, routes in ((H, K, {"flash": flash}),
                                  (H // K, 1, {"flash": flash,
                                               "einsum": exact})):
            q = jax.random.normal(keys[0], (1, t, heads, d))
            k = jax.random.normal(keys[1], (1, t, kv, d))
            v = jax.random.normal(keys[2], (1, t, kv, d))
            for route, f in routes.items():
                out[f"attn_{name}_{heads}on{kv}_{route}_ms"] = timed(
                    grad_of(f), (q, k, v), repeats)
                print(json.dumps(out), flush=True)

    n, D, W = t, c["hidden_size"], c["moe_intermediate_size"]
    held, E, top = (c["num_experts"], c["router_experts"],
                    c["num_experts_per_tok"])
    x = jax.random.normal(keys[3], (n, D))
    wg, wu = (0.02 * jax.random.normal(keys[i], (held, D, W))
              for i in (4, 5))
    wd = 0.02 * jax.random.normal(keys[6], (held, W, D))
    cw = jax.random.uniform(keys[7], (n, top))
    scores = jax.random.uniform(keys[0], (n, E))
    uniform = jax.lax.top_k(scores, top)[1]
    skewed = jax.lax.top_k(scores.at[:, :2].add(2.0), top)[1]

    def dense(x, cw, wg, wu, wd, ids):
        from deeplearning4j_tpu.dtypes import einsum_f32
        hit = ids[:, :, None] == jnp.arange(held)
        comb = jnp.sum(jnp.where(hit, cw[:, :, None], 0.0), axis=1)
        g = einsum_f32("nd,edw->enw", x, wg)
        u = einsum_f32("nd,edw->enw", x, wu)
        y = einsum_f32("enw,ewd->end",
                       (jax.nn.silu(g) * u).astype(x.dtype), wd)
        return jnp.einsum("end,ne->nd", y, comb)

    def pairs(x, cw, wg, wu, wd, ids):
        return grouped_experts.pairs_experts(
            x, jnp.minimum(ids, held), cw, wg, wu, wd)

    def run(f, ids):
        return timed(jax.jit(jax.grad(
            lambda *a: jnp.sum(f(*a, ids) ** 2), argnums=(0, 1, 2, 3, 4))),
            (x, cw, wg, wu, wd), repeats)

    for label, ids in (("uniform", uniform), ("skewed", skewed)):
        out[f"pairs_held_{label}"] = int(jnp.sum(ids < held))
        out[f"experts_{label}_pairs_ms"] = run(pairs, ids)
        print(json.dumps(out), flush=True)
    out["experts_uniform_dense_ms"] = run(dense, uniform)
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main(*(int(a) for a in sys.argv[1:2]))
