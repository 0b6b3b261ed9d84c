#!/usr/bin/env python3
"""The by-table paged attention kernel against the gather path, on the
chip and at ``gpt2m_serve_closed``'s own shapes (ISSUE 29):

    python3 benchmark/tests/measure_paged_attention.py [seed]

8 slots of 64 pages of 16 tokens, 16 heads of 64, a float32 pool, at
t = 1 and t = 16 (the two step programs of the cell). Tier-1 holds the
kernel to the gather in Pallas' interpret mode; this is the real
(Mosaic) kernel. One JSON line per (t, lengths): the widest absolute
gap between the two paths over the rows that carry a token (the gather
as XLA compiles its einsums, the kernel's dots at the precision that
comes to: ``_dot_precision``), each path's gap to a float64 reference, and
the mean microseconds of a call of each alone (24 calls chained in one
program, as a step's 24 layers are, so that the host's dispatch is not
what is timed). ``lengths``: ``mix`` is
ragged like the cell's traffic (0, 1, a page boundary, mid-page,
about a hundred, full capacity), ``full`` every slot at capacity.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

SLOTS, PAGES, PAGE, HEADS, HEAD_DIM = 8, 64, 16, 16, 64


def reference(q, k_pool, v_pool, table, pos, n_valid):
    """float64 on the host, slot by slot over its own length only."""
    import numpy as np
    S, t, HD = q.shape
    out = np.zeros((S, t, HD))
    for s in range(S):
        n = int(pos[s] + n_valid[s])
        if not n:
            continue
        pages = table[s, :-(-n // PAGE)]
        k = k_pool[pages].reshape(-1, HEADS, HEAD_DIM)[:n].astype(float)
        v = v_pool[pages].reshape(-1, HEADS, HEAD_DIM)[:n].astype(float)
        qs = q[s].reshape(t, HEADS, HEAD_DIM).astype(float)
        logits = np.einsum("qhd,khd->hqk", qs, k) * HEAD_DIM ** -0.5
        seen = np.arange(n)[None, :] <= (pos[s] + np.arange(t))[:, None]
        logits = np.where(seen[None], logits, -np.inf)
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[s] = np.einsum("hqk,khd->qhd", p, v).reshape(t, HD)
    return out


LAYERS = 24


def timed_us(fn, pools, table, q, *args, dispatches=20):
    """Mean microseconds of one call of ``fn`` among ``LAYERS`` chained
    in one program, each over a pool of its own (one shared pool would
    let XLA gather once for all) and fed the last one's output."""
    import jax

    @jax.jit
    def chain(pools, table, q, *args):
        for k_pool, v_pool in pools:
            q = fn(q, k_pool, v_pool, table, *args)
        return q

    chain(pools, table, q, *args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(dispatches):
        out = chain(pools, table, q, *args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / (dispatches * LAYERS) * 1e6


def main(seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.ops import paged_attention as PA
    if jax.default_backend() != "tpu":
        sys.exit("needs the chip: the kernel runs in tier-1 in "
                 "interpret mode, this script is for the Mosaic one")
    rng = np.random.default_rng(seed)
    HD = HEADS * HEAD_DIM
    cap = PAGES * PAGE
    n_pages = SLOTS * PAGES + 1
    k_np = rng.normal(size=(n_pages, PAGE, HD)).astype(np.float32)
    v_np = rng.normal(size=(n_pages, PAGE, HD)).astype(np.float32)
    table_np = rng.permutation(np.arange(1, n_pages)).reshape(
        SLOTS, PAGES).astype(np.int32)
    k_pool, v_pool, table = map(jnp.asarray, (k_np, v_np, table_np))
    pools = [(k_pool * (1 + i / 1024), v_pool * (1 - i / 1024))
             for i in range(LAYERS)]

    def kernel(q, k_pool, v_pool, table, lengths, pos):
        return PA.pallas_paged_attention(q, k_pool, v_pool, table,
                                         lengths, pos, n_heads=HEADS)

    def gather(q, k_pool, v_pool, table, lengths, pos):
        return PA.paged_attention_gather(q, k_pool, v_pool, table, pos,
                                         HEADS)

    for t in (1, 16):
        mixes = {
            "mix": (np.array([0, 0, PAGE - t, PAGE, 100, 117,
                              cap - t, 3 * PAGE + 5]),
                    np.array([0, 1, t, t, t, max(t - 3, 1), t, 0])),
            "full": (np.full(SLOTS, cap - t), np.full(SLOTS, t)),
        }
        for name, (pos, n_valid) in mixes.items():
            pos = pos.astype(np.int32)
            n_valid = n_valid.astype(np.int32)
            q_np = rng.normal(size=(SLOTS, t, HD)).astype(np.float32)
            args = (jnp.asarray(q_np), jnp.asarray(pos + n_valid),
                    jnp.asarray(pos))
            got = np.asarray(kernel(args[0], k_pool, v_pool, table,
                                    *args[1:]))
            want = np.asarray(gather(args[0], k_pool, v_pool, table,
                                     *args[1:]))
            ref = reference(q_np, k_np, v_np, table_np, pos, n_valid)
            rows = np.arange(t)[None, :] < n_valid[:, None]
            print(json.dumps({
                "t": t, "lengths": name,
                "finite": bool(np.isfinite(got).all()),
                "gap_kernel_gather": float(
                    np.abs(got - want)[rows].max()),
                "gap_kernel_float64": float(
                    np.abs(got - ref)[rows].max()),
                "gap_gather_float64": float(
                    np.abs(want - ref)[rows].max()),
                "kernel_us": timed_us(kernel, pools, table, *args),
                "gather_us": timed_us(gather, pools, table, *args),
                "device": jax.devices()[0].device_kind}), flush=True)


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 0)
