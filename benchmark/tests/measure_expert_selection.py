#!/usr/bin/env python3
"""Reads, on the chip and at the configuration's own widths, how often
the program's router selects other experts than the plain reference's
float32 router does (a sigmoid near-tie flips on bfloat16 rounding):

    python3 benchmark/tests/measure_expert_selection.py <workload> \\
        <rows> <tokens> <seed> ...

For each seed: weights from the seed as the driver makes them, ``rows``
sequences of ``tokens`` random ids through the program's own layers
(full sequence), the selection of every expert block read at the
block's router, and the reference's selection on the same ids. Prints
one JSON line per seed with the share of (token, layer) whose sets of
selected experts differ, and of (token, layer, expert) pairs that
differ. The limits of ``correct`` in the mix's file are read beside
this number (PERF.md section 2).
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def program_selection(net, params, ids):
    """Per expert block, the (rows, tokens, k) experts the program's
    router selects, ascending; the blocks are walked as
    ``LatentDecoderBlock.apply`` walks them."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf.layers.normalization import rms_norm
    h, chosen = ids[..., None].astype(jnp.float32), []
    for layer, p in zip(net.layers, params):
        if getattr(layer, "stream_aux", False):
            attn, moe = layer._ensure_parts()
            x = h.astype(p["norm1_gain"].dtype)
            a, _ = attn.apply(p["attn"], {}, rms_norm(
                x, p["norm1_gain"], layer.eps))
            z = rms_norm(x + a, p["norm2_gain"], layer.eps)
            sel, _ = moe.route(p["moe"], z.reshape(-1, z.shape[-1]))
            chosen.append(jnp.sort(sel, axis=-1).reshape(
                ids.shape + (-1,)))
        h, _ = layer.apply(p, {}, h)
    return chosen


def main(workload, rows, tokens, seeds):
    import jax
    import numpy as np
    from benchmark.harness import session, spec, weights
    cell = spec.load(workload)
    config = cell.config
    session.find_devices(cell.chips)
    builder = spec.load_module("builders", config["builder"])
    ref = spec.load_module("reference", config["reference"])
    with builder.policy(config):
        net = builder.build(config).init()
        maker = weights.maker(net.params, config["init"])
        select = jax.jit(lambda p, ids: program_selection(net, p, ids))
        for seed in seeds:
            params = maker(int(np.random.SeedSequence(
                [seed, 0]).generate_state(1)[0] >> 1))
            ids = np.random.default_rng([seed, 9]).integers(
                0, config["vocab_size"], (rows, tokens))
            got = [np.asarray(s) for s in select(params, ids)]
            sets = pairs = total = 0
            for r in range(rows):
                want = ref.selected_experts(params, ids[r], config)
                for g, w in zip(got, want):
                    w = np.asarray(w)
                    differ = (g[r] != w).any(axis=-1)
                    sets += int(differ.sum())
                    pairs += sum(len(set(a) - set(b))
                                 for a, b in zip(g[r][differ], w[differ]))
                    total += differ.size
            print(json.dumps({
                "seed": seed, "token_layers": total,
                "selection_differs_share": sets / total,
                "pairs_differ_share": pairs / (
                    total * config["num_experts_per_tok"]),
                "device": jax.devices()[0].device_kind}), flush=True)
            del params


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), int(sys.argv[3]),
         [int(x) for x in sys.argv[4:]])
