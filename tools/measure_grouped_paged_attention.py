#!/usr/bin/env python3
"""The grouped by-table paged attention kernel against the gather and
``_attend``, on the chip, at a global layer of ``mimo_serve_mixedlen``
(ISSUE 35; ``benchmark/tests/measure_paged_attention.py`` is its twin
for the multi-head kernel):

    python3 tools/measure_grouped_paged_attention.py [seed]

64 slots of 128 pages of 16 tokens, 64 query heads of 192 over 4 key
heads of 192 and value heads of 128, a bfloat16 pool, at t = 1 and
t = 2 (the cell's two step programs). Tier-1 holds the kernel to
``_attend`` in Pallas' interpret mode; this is the real (Mosaic)
kernel. One JSON line per (t, lengths): the widest absolute gap of
``GroupedQueryAttentionLayer.apply_stream_paged`` between the two
paths over the rows that carry a token, each path's gap to a float64
reference over the same bfloat16 inputs, and the mean microseconds of
the layer's step by either path (the pool donated and threaded, as the
session's step does) and of the kernel alone (``CHAIN`` calls in one
program, so that the host's dispatch is not what is timed).
``lengths``:
``mix`` is ragged like the cell's traffic (lognormal, median 256,
clipped 64-1536, with a free slot, a slot of one token and a slot that
sits the step out), ``full`` every slot at capacity.
"""

import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SLOTS, PAGES, PAGE, D = 64, 128, 16, 4096
H, K, DQ, DV = 64, 4, 192, 128


def reference(layer, params, q, k_pool, v_pool, table, pos, n_valid):
    """float64 on the host from the rotated queries and the pool as
    written, slot by slot over its own length only."""
    import numpy as np
    S, t = q.shape[:2]
    out = np.zeros((S, t, H * DV))
    for s in range(S):
        n = int(pos[s] + n_valid[s])
        if not n:
            continue
        pages = table[s, :-(-n // PAGE)]
        k = k_pool[pages].reshape(-1, K, DQ)[:n].astype(float)
        v = v_pool[pages].reshape(-1, K, DV)[:n].astype(float)
        qs = q[s].reshape(t, K, H // K, DQ).astype(float)
        logits = np.einsum("tkgd,nkd->kgtn", qs, k) * DQ ** -0.5
        seen = np.arange(n)[None, :] <= (pos[s] + np.arange(t))[:, None]
        logits = np.where(seen[None, None], logits, -np.inf)
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[s] = np.einsum("kgtn,nkd->tkgd", p, v).reshape(t, H * DV)
    return out @ np.asarray(params["Wo"], float)


CHAIN = 16


def timed_us(fn, *args, calls=20):
    """Mean microseconds of ``fn(*args)``; a ``fn`` that returns
    ``(out, pool)`` is fed its own pool again (``args[1]``, donated)."""
    import jax
    args = list(args)

    def call():
        out = fn(*args)
        if isinstance(out, tuple):
            args[1] = out[1]
        return out
    jax.block_until_ready(call())
    t0 = time.perf_counter()
    for _ in range(calls):
        out = call()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e6


def main(seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import GroupedQueryAttentionLayer
    from deeplearning4j_tpu.ops import paged_attention as PA
    if jax.default_backend() != "tpu":
        sys.exit("needs the chip: the kernel runs in tier-1 in "
                 "interpret mode, this script is for the Mosaic one")
    bf16 = jnp.bfloat16
    rng = np.random.default_rng(seed)
    layer = GroupedQueryAttentionLayer(
        n_in=D, n_heads=H, n_kv_heads=K, qk_head_dim=DQ, v_head_dim=DV,
        rotary_dim=64, rope_theta=1e7, value_scale=0.707)
    params = jax.tree_util.tree_map(
        lambda w: w.astype(bf16),
        layer.initialize(jax.random.PRNGKey(seed % 2**31),
                         InputType.recurrent(D))[0])
    cap, n_pages = PAGES * PAGE, SLOTS * PAGES + 1
    pool = {"k": jnp.asarray(rng.normal(size=(n_pages, PAGE, K * DQ)), bf16),
            "v": jnp.asarray(rng.normal(size=(n_pages, PAGE, K * DV)), bf16)}
    table_np = rng.permutation(np.arange(1, n_pages)).reshape(
        SLOTS, PAGES).astype(np.int32)
    table = jnp.asarray(table_np)
    by_kernel = jax.jit(layer.apply_stream_paged, donate_argnums=(1,))
    if not all(layer.paged_reads_by_table(PAGE, t, bf16) for t in (1, 2)):
        sys.exit("the layer's predicate refuses these shapes: nothing "
                 "here would run the kernel")

    @functools.partial(jax.jit, donate_argnums=(1,))
    def by_gather(*args):
        """The same step traced with the shapes' predicate off."""
        holds = PA.grouped_reads_by_table
        PA.grouped_reads_by_table = lambda *a: False
        try:
            return layer.apply_stream_paged(*args)
        finally:
            PA.grouped_reads_by_table = holds

    for t in (1, 2):
        mix = np.exp(rng.normal(np.log(256), 1.0, SLOTS)).clip(64, 1536)
        mix = mix.astype(np.int32) + rng.integers(0, 96, SLOTS)
        mix[:3] = (0, 0, 5 * PAGE + 3)
        fed = np.full(SLOTS, t)
        fed[:3] = (0, 1, 0)
        for name, (pos, n_valid) in {
                "mix": (mix, fed),
                "full": (np.full(SLOTS, cap - t), np.full(SLOTS, t))}.items():
            pos, n_valid = pos.astype(np.int32), n_valid.astype(np.int32)
            x = jnp.asarray(rng.normal(size=(SLOTS, t, D)), bf16)
            fresh = lambda: (params, jax.tree_util.tree_map(jnp.copy, pool),
                             table, jnp.asarray(pos), x,
                             jnp.asarray(n_valid))
            want, want_pool = by_gather(*fresh())
            got, got_pool = by_kernel(*fresh())
            wpos = jnp.asarray(pos)[:, None] + jnp.arange(t)[None]
            q = layer._project(params, x, wpos)[0]
            ref = reference(
                layer, params, np.asarray(q, np.float32),
                np.asarray(got_pool["k"], np.float32),
                np.asarray(got_pool["v"], np.float32), table_np, pos,
                n_valid)
            rows = np.arange(t)[None, :] < n_valid[:, None]
            gap = lambda a, b: float(np.abs(
                np.asarray(a, np.float32) - np.asarray(b, np.float32)
            )[rows].max())
            @jax.jit
            def kernel(q, k_pool, v_pool, lengths, pos):
                return sum(PA.pallas_paged_attention_grouped(
                    q * (1 + i / 64), k_pool, v_pool, table, lengths, pos,
                    n_heads=H, n_kv_heads=K) for i in range(CHAIN))
            print(json.dumps({
                "t": t, "lengths": name,
                "positions_held": int((pos + n_valid).sum()),
                "finite": bool(np.isfinite(np.asarray(
                    got, np.float32)).all()),
                "pool_equal": all(bool(jnp.array_equal(
                    got_pool[n], want_pool[n])) for n in pool),
                "out_scale": float(np.abs(ref[rows]).max()),
                "gap_kernel_gather": gap(got, want),
                "gap_kernel_float64": gap(got, ref),
                "gap_gather_float64": gap(want, ref),
                "kernel_alone_us": timed_us(
                    kernel, q, got_pool["k"], got_pool["v"],
                    jnp.asarray(pos + n_valid), jnp.asarray(pos)) / CHAIN,
                "layer_kernel_us": timed_us(by_kernel, *fresh()),
                "layer_gather_us": timed_us(by_gather, *fresh()),
                "device": jax.devices()[0].device_kind}), flush=True)


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 0)
