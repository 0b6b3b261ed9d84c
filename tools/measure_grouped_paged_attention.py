#!/usr/bin/env python3
"""The by-table paged attention kernels of the expert cells against the
gather and ``_attend``, on the chip
(``benchmark/tests/measure_paged_attention.py`` is their twin for the
multi-head kernel):

    python3 tools/measure_grouped_paged_attention.py [seed] \\
        [--kind grouped|latent]

``--kind grouped`` (the default; ISSUE 35): a global layer of
``mimo_serve_mixedlen``. 64 slots of 128 pages of 16 tokens, 64 query
heads of 192 over 4 key heads of 192 and value heads of 128, a
bfloat16 pool, at t = 1 and t = 2 (the cell's two step programs).
``lengths``: ``mix`` is ragged like the cell's traffic (lognormal,
median 256, clipped 64-1536, with a free slot, a slot of one token and
a slot that sits the step out), ``full`` every slot at capacity.

``--kind latent`` (ISSUE 38): a latent attention of
``axk1_serve_decode`` (64 slots, hidden 7168, YaRN, t = 2 and 1) and
of ``longcat_serve_tooluse`` (32 slots, hidden 6144, the scaled
bottlenecks, t = 4 and 1): 64 heads over ONE shared key head of 512 +
64, 64 pages of 16 a slot, a bfloat16 pool. ``mix`` draws each slot's
request as the cell's traffic file does and stops it at a uniform
point of its life (with the same three odd slots), ``full`` is every
slot at capacity.

Tier-1 holds the kernels to ``_attend`` in Pallas' interpret mode;
this is the real (Mosaic) kernel. One JSON line per (t, lengths): the
positions the slots hold and the blocks of 128 keys the kernel walks
for them, the widest absolute gap of the layer's
``apply_stream_paged`` between the two paths over the rows that carry
a token, each path's gap to a
float64 reference over the same bfloat16 inputs, and the mean
microseconds of the layer's step by either path (the pool donated and
threaded, as the session's step does) and of the kernel alone
(``CHAIN`` calls in one program, so that the host's dispatch is not
what is timed).
"""

import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

PAGE, H = 16, 64
CHAIN = 16


def softmax_float64(logits, seen):
    """The softmax of ``logits`` (..., t, n) over the keys each row
    has ``seen`` (t, n)."""
    import numpy as np
    logits = np.where(seen, logits, -np.inf)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    return p / p.sum(-1, keepdims=True)


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


def measure(rng, tag, layer, params, pool, table_np, predicate, ts,
            positions, queries_of, reference, kernel_call):
    """The lines of one layer over one pool. ``predicate``: the name
    in ``ops.paged_attention`` that the gather's program is traced
    without; ``positions(t)``: ``{lengths: (pos, n_valid)}``;
    ``queries_of(projected)``: the kernel's query operands from
    ``layer._project``'s; ``reference(queries, pool, pos, n_valid)``:
    the layer's output in float64 from float32 copies of them and of
    the pool as written; ``kernel_call(queries, pool, table, lengths,
    pos)``: the kernel alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.ops import paged_attention as PA
    bf16 = jnp.bfloat16
    slots = table_np.shape[0]
    table = jnp.asarray(table_np)
    by_kernel = jax.jit(layer.apply_stream_paged, donate_argnums=(1,))
    if not all(layer.paged_reads_by_table(PAGE, t, bf16) for t in ts):
        sys.exit("the layer's predicate refuses these shapes: nothing "
                 "here would run the kernel")

    @functools.partial(jax.jit, donate_argnums=(1,))
    def by_gather(*args):
        """The same step traced with the shapes' predicate off."""
        holds = getattr(PA, predicate)
        setattr(PA, predicate, lambda *a: False)
        try:
            return layer.apply_stream_paged(*args)
        finally:
            setattr(PA, predicate, holds)

    as_f32 = lambda tree: jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float32), tree)
    for t in ts:
        for name, (pos, n_valid) in positions(t).items():
            pos, n_valid = pos.astype(np.int32), n_valid.astype(np.int32)
            x = jnp.asarray(rng.normal(size=(slots, t, layer.n_in)), bf16)
            fresh = lambda: (params, jax.tree_util.tree_map(jnp.copy, pool),
                             table, jnp.asarray(pos), x,
                             jnp.asarray(n_valid))
            want, want_pool = by_gather(*fresh())
            got, got_pool = by_kernel(*fresh())
            wpos = jnp.asarray(pos)[:, None] + jnp.arange(t)[None]
            queries = queries_of(layer._project(params, x, wpos))
            ref = reference(as_f32(queries), as_f32(got_pool), pos, n_valid)
            rows = np.arange(t)[None, :] < n_valid[:, None]
            gap = lambda a, b: float(np.abs(
                np.asarray(a, np.float32) - np.asarray(b, np.float32)
            )[rows].max())

            @jax.jit
            def kernel(queries, pool, lengths, pos):
                return sum(kernel_call(
                    (queries[0] * (1 + i / 64),) + queries[1:], pool, table,
                    lengths, pos) for i in range(CHAIN))
            lengths = pos + n_valid
            blocks = int((-(-PA.pages_read(lengths, PAGE)
                            // (128 // PAGE))).sum())
            alone = timed_us(kernel, queries, got_pool,
                             jnp.asarray(lengths), jnp.asarray(pos)) / CHAIN
            print(json.dumps({
                **tag, "t": t, "lengths": name,
                "positions_held": int(lengths.sum()), "blocks": blocks,
                "finite": bool(np.isfinite(np.asarray(
                    got, np.float32)).all()),
                "pool_equal": all(bool(jnp.array_equal(
                    got_pool[n], want_pool[n])) for n in pool),
                "out_scale": float(np.abs(ref[rows]).max()),
                "gap_kernel_gather": gap(got, want),
                "gap_kernel_float64": gap(got, ref),
                "gap_gather_float64": gap(want, ref),
                "kernel_alone_us": alone,
                "kernel_us_a_block": alone / max(blocks, 1),
                "layer_kernel_us": timed_us(by_kernel, *fresh()),
                "layer_gather_us": timed_us(by_gather, *fresh()),
                "device": jax.devices()[0].device_kind}), flush=True)


def odd_slots(pos, t):
    """``pos`` with a free slot, a slot of one token and a slot that
    sits the step out in front, and the rows each slot feeds."""
    import numpy as np
    pos[:3] = (0, 0, 5 * PAGE + 3)
    fed = np.full(len(pos), t)
    fed[:3] = (0, 1, 0)
    return pos, fed


def setup(seed, layer, d, slots, pages, fill):
    """(rng, bfloat16 params, pool filled by ``fill(leaf name, array,
    rng)``, a shuffled table) for ``layer`` over ``slots`` x ``pages``
    pages."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    if jax.default_backend() != "tpu":
        sys.exit("needs the chip: the kernel runs in tier-1 in "
                 "interpret mode, this script is for the Mosaic one")
    bf16 = jnp.bfloat16
    rng = np.random.default_rng(seed)
    params = jax.tree_util.tree_map(
        lambda w: w.astype(bf16),
        layer.initialize(jax.random.PRNGKey(seed % 2**31),
                         InputType.recurrent(d))[0])
    n_pages = slots * pages + 1
    pool = {}
    for name, leaf in layer.zero_pool(n_pages, PAGE, bf16).items():
        rows = np.zeros(leaf.shape, np.float32)
        fill(name, rows, rng)
        pool[name] = jnp.asarray(rows, bf16)
    table = rng.permutation(np.arange(1, n_pages)).reshape(
        slots, pages).astype(np.int32)
    return rng, params, pool, table


# ---- --kind grouped -------------------------------------------------

def main_grouped(seed):
    import numpy as np
    from deeplearning4j_tpu.nn.conf.layers import GroupedQueryAttentionLayer
    from deeplearning4j_tpu.ops import paged_attention as PA
    slots, pages, d = 64, 128, 4096
    K, DQ, DV = 4, 192, 128
    layer = GroupedQueryAttentionLayer(
        n_in=d, n_heads=H, n_kv_heads=K, qk_head_dim=DQ, v_head_dim=DV,
        rotary_dim=64, rope_theta=1e7, value_scale=0.707)

    def fill(name, rows, rng):
        rows[:] = rng.normal(size=rows.shape)
    rng, params, pool, table = setup(seed, layer, d, slots, pages, fill)
    cap = pages * PAGE

    def positions(t):
        mix = np.exp(rng.normal(np.log(256), 1.0, slots)).clip(64, 1536)
        mix = mix.astype(np.int32) + rng.integers(0, 96, slots)
        return {"mix": odd_slots(mix, t),
                "full": (np.full(slots, cap - t), np.full(slots, t))}

    def reference(queries, pool, pos, n_valid):
        """Slot by slot over its own length only."""
        q, = queries
        S, t = q.shape[:2]
        out = np.zeros((S, t, H * DV))
        for s in range(S):
            n = int(pos[s] + n_valid[s])
            if not n:
                continue
            held = table[s, :-(-n // PAGE)]
            k = pool["k"][held].reshape(-1, K, DQ)[:n].astype(float)
            v = pool["v"][held].reshape(-1, K, DV)[:n].astype(float)
            qs = q[s].reshape(t, K, H // K, DQ).astype(float)
            seen = np.arange(n)[None, :] <= (
                pos[s] + np.arange(t))[:, None]
            p = softmax_float64(
                np.einsum("tkgd,nkd->kgtn", qs, k) * DQ ** -0.5, seen)
            out[s] = np.einsum("kgtn,nkd->tkgd", p, v).reshape(t, H * DV)
        return out @ np.asarray(params["Wo"], float)

    measure(rng, {}, layer, params, pool, table, "grouped_reads_by_table",
            (1, 2), positions, lambda projected: projected[:1], reference,
            lambda queries, pool, table, lengths, pos:
                PA.pallas_paged_attention_grouped(
                    queries[0], pool["k"], pool["v"], table, lengths, pos,
                    n_heads=H, n_kv_heads=K))


# ---- --kind latent --------------------------------------------------

LATENT_CELLS = {
    # slots, hidden, the chunk program's t, the layer's own fields,
    # the traffic file's lengths: (median, sigma, min, max) of the
    # prompts and of the answers
    "axk1_serve_decode": (64, 7168, 2, dict(
        rope_scaling={"type": "yarn", "factor": 32, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 4096}),
        (48, 0.8, 16, 192), (192, 0.6, 64, 448)),
    "longcat_serve_tooluse": (32, 6144, 4, dict(
        rope_theta=1e7, eps=1e-5, scale_q_lora=True, scale_kv_lora=True),
        (320, 0.6, 128, 768), (64, 0.6, 16, 192)),
}


def main_latent(seed):
    import numpy as np
    from deeplearning4j_tpu.nn.conf.layers import LatentAttentionLayer
    from deeplearning4j_tpu.ops import paged_attention as PA
    pages, RKV, DR = 64, 512, 64
    cap = pages * PAGE
    for cell, (slots, d, t_chunk, fields, prompts,
               answers) in LATENT_CELLS.items():
        layer = LatentAttentionLayer(
            n_in=d, n_heads=H, q_lora_rank=1536, kv_lora_rank=RKV,
            qk_nope_head_dim=128, qk_rope_head_dim=DR, v_head_dim=128,
            **fields)

        def fill(name, rows, rng):
            # the rotary key's row: zeros past the key
            width = RKV if name == "ckv" else DR
            rows[..., :width] = rng.normal(size=rows.shape[:2] + (width,))
        rng, params, pool, table = setup(seed, layer, d, slots, pages, fill)
        draw = lambda median, sigma, lo, hi: np.clip(np.rint(np.exp(
            rng.normal(np.log(median), sigma, slots))), lo, hi)

        def positions(t):
            # a request at a uniform point of its life
            mix = (rng.uniform(size=slots)
                   * (draw(*prompts) + draw(*answers))).astype(np.int32)
            return {"mix": odd_slots(mix, t),
                    "full": (np.full(slots, cap - t), np.full(slots, t))}

        def queries_of(projected):
            q_nope, q_rope = projected[:2]
            return layer._absorb(params, q_nope), q_rope

        def reference(queries, pool, pos, n_valid):
            """Slot by slot over its own length only."""
            q_lat, q_rope = queries
            S, t = q_lat.shape[:2]
            o_lat = np.zeros(q_lat.shape)
            for s in range(S):
                n = int(pos[s] + n_valid[s])
                if not n:
                    continue
                held = table[s, :-(-n // PAGE)]
                ckv = pool["ckv"][held].reshape(-1, RKV)[:n].astype(float)
                kr = pool["kr"][held].reshape(
                    -1, pool["kr"].shape[-1])[:n, :DR].astype(float)
                seen = np.arange(n)[None, :] <= (
                    pos[s] + np.arange(t))[:, None]
                p = softmax_float64(
                    (np.einsum("thr,nr->htn", q_lat[s].astype(float), ckv)
                     + np.einsum("thd,nd->htn", q_rope[s].astype(float), kr))
                    * layer._softmax_scale(), seen)
                o_lat[s] = np.einsum("htn,nr->thr", p, ckv)
            wv = np.asarray(layer._kvb(params)[1], float)
            o = np.einsum("bthr,rhd->bthd", o_lat, wv).reshape(S, t, -1)
            return o @ np.asarray(params["Wo"], float)

        measure(rng, {"cell": cell}, layer, params, pool, table,
                "latent_reads_by_table", (t_chunk, 1), positions,
                queries_of, reference,
                lambda queries, pool, table, lengths, pos:
                    PA.pallas_paged_attention_latent(
                        *queries, pool["ckv"], pool["kr"], table, lengths,
                        pos, scale=layer._softmax_scale()))


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("seed", nargs="?", type=int, default=0)
    ap.add_argument("--kind", choices=("grouped", "latent"),
                    default="grouped")
    args = ap.parse_args()
    {"grouped": main_grouped, "latent": main_latent}[args.kind](args.seed)
