#!/usr/bin/env python3
"""One gated delta-rule mixer's serving step at a configuration's own
widths, on the chip: the ``jax.numpy`` form against the program's
kernel (ISSUE 47):

    python3 tools/measure_delta_state.py [config] [--slots N] \\
        [--rows T ...] [--blocks B ...]

``numpy`` is ``GatedDeltaMixerLayer.apply_stream_paged`` with
``ops.delta_state.delta_state_pass`` held False (XLA's two passes over
the pool), ``kernel`` the same method with it held True: what is timed
is the program's own code, imported, the pool donated as the paged
step donates it. The layer is the configuration's first linear layer's
mixer, its weights made from the configuration's ``init``; the state
starts from random values, every slot live at a position past 0 and
feeding all its rows.

One JSON line per (rows, path): the mean milliseconds of a step (30
steps enqueued back to back, each on the pool the one before left, so
the device's time and not the host's dispatch) and the same for the
mixer's weights alone at the chip's bandwidth; then, the kernel alone
(``pallas_delta_state`` on made-up rows) at each block of head-packs:
milliseconds a call and GB/s of the bytes the call NEEDS, the state
read once and written once; then a line with the widest absolute gap
between the two steps' outputs and states. PERF.md section 6 quotes
the readings.

That last line is a CHECK, the one place the kernel's float32 reads
are held to on a chip (the configuration states float32 state
products; the kernel's reads are an MXU product that is float32 only
while Mosaic honours ``Precision.HIGHEST``, and every test of the
kernel runs interpreted on the CPU, where the product is float32
whatever the flag): the tool exits 1 where the two states differ by
more than ``STATE_GAP`` of the state's largest value. Read on a v5e
(my chip runs, PR 47): 1.5e-7 at most as the kernel stands, 1.6e-3
with its product at the default precision (one bfloat16 pass; Mosaic
lowers no precision between the two). It needs a TPU and exits 2 on
anything else: a time from the CPU's interpreter under these names
would read as a chip's (``tests/test_delta_state_kernel.py`` is the
kernel's rehearsal here).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CALLS = 30
# the widest gap between the kernel's state and the ``jax.numpy``
# form's, as a share of the state's largest value, that float32 reads
# leave: a float32 sum's order (1.5e-7 read); one bfloat16 pass leaves
# 1.6e-3
STATE_GAP = 1e-6


def mixer(config_name):
    """(mixer, parameter shapes, configuration) of ``config_name``'s
    first linear-attention layer, built by the benchmark's builder
    under its dtype policy."""
    import jax
    from benchmark.harness import spec
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    bench = spec._json(os.path.join(ROOT, "BENCHMARK.json"))
    conf = next(c for c in bench["configs"] if c["name"] == config_name)
    config = spec._json(os.path.join(ROOT, conf["file"]))
    builder = spec.load_module("builders", config["builder"])
    with builder.policy(config):
        block = builder.block(
            config, config["layer_types"].index("linear_attention"))
        block.n_in = config["hidden_size"]
        layer = block._ensure_parts()[0]
        shapes = jax.eval_shape(lambda: layer.initialize(
            jax.random.PRNGKey(0),
            InputType.recurrent(config["hidden_size"]))[0])
    return layer, shapes, config


def _timed(step, pool, *args):
    """Mean ms of ``step(pool, *args) -> (out, pool)`` over ``CALLS``
    steps, each on the pool the one before left."""
    import jax
    out, pool = step(pool, *args)           # compiles
    jax.block_until_ready(pool)
    t0 = time.perf_counter()
    for _ in range(CALLS):
        out, pool = step(pool, *args)
    jax.block_until_ready((out, pool))
    return (time.perf_counter() - t0) / CALLS * 1e3


def measure(config_name, slots, rows, blocks, out=print):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.harness import peaks, weights
    from deeplearning4j_tpu.ops import delta_state
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"tools/measure_delta_state.py times a chip and found "
              f"{dev.device_kind!r}: run it through chiprun",
              file=sys.stderr)
        raise SystemExit(2)
    pk = peaks.peaks_for(dev.device_kind)
    layer, shapes, config = mixer(config_name)
    params = weights.maker(shapes, config["init"])(7)
    weight_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                       for s in jax.tree_util.tree_leaves(shapes))
    zero = layer.zero_pool(slots, 16, jnp.bfloat16)
    state_bytes = 2 * zero["state"].size * 4
    d = config["hidden_size"]
    line = lambda **kw: out(json.dumps(dict(
        kw, config=config_name, slots=slots, device=dev.device_kind)),
        flush=True)

    def fresh_pool(seed):
        key = jax.random.PRNGKey(seed)
        return {"state": 0.1 * jax.random.normal(
                    key, zero["state"].shape, jnp.float32),
                "conv": jax.random.normal(
                    key, zero["conv"].shape, jnp.float32
                ).astype(zero["conv"].dtype)}

    table = jnp.ones((slots, 4), jnp.int32)
    pos = jnp.full((slots,), 7, jnp.int32)
    for t in rows:
        x = jax.random.normal(jax.random.PRNGKey(t), (slots, t, d),
                              jnp.bfloat16)
        n_valid = jnp.full((slots,), t, jnp.int32)
        got = {}
        for name, takes in (("numpy", False), ("kernel", True)):
            delta_state.delta_state_pass = lambda *a, takes=takes: takes
            step = jax.jit(
                lambda pool, p, x, n: layer.apply_stream_paged(
                    p, pool, table, pos, x, n), donate_argnums=(0,))
            # one step from the same pool for the comparison
            y, after = step(fresh_pool(3), params, x, n_valid)
            got[name] = (np.asarray(y, np.float32),
                         np.asarray(after["state"]))
            ms = _timed(step, fresh_pool(3), params, x, n_valid)
            line(rows=t, path=name, ms=ms,
                 weights_ms=weight_bytes / pk["bytes_per_s"] * 1e3,
                 state_ms=state_bytes / pk["bytes_per_s"] * 1e3)
        (yn, sn), (yk, sk) = got["numpy"], got["kernel"]
        gap = float(np.abs(sn - sk).max())
        line(rows=t, out_max_abs_difference=float(np.abs(yn - yk).max()),
             out_max_abs=float(np.abs(yn).max()),
             state_max_abs_difference=gap,
             state_max_abs=float(np.abs(sn).max()))
        if not gap <= STATE_GAP * float(np.abs(sn).max()):
            print(f"rows {t}: the kernel's state is {gap:.3g} from the "
                  f"jax.numpy form's, past {STATE_GAP:g} of the state's "
                  "largest value: its reads are not float32 products",
                  file=sys.stderr)
            raise SystemExit(1)
        # the kernel alone
        S, packs, dk, w = zero["state"].shape
        H = layer.n_heads
        key = jax.random.split(jax.random.PRNGKey(5), 8)
        unit = lambda k, shape: (lambda y: y / jnp.linalg.norm(
            y, axis=-1, keepdims=True))(
                jax.random.normal(k, shape, jnp.float32))
        kr, qr = unit(key[0], (S, t, H, dk)), unit(key[1], (S, t, H, dk))
        small = (kr, qr, jax.random.normal(key[2], (S, t, packs, w)),
                 jax.random.uniform(key[3], (S, t, H), minval=0.3),
                 jax.random.uniform(key[4], (S, t, H)),
                 jnp.einsum("sjhd,sihd->sjih", kr, kr),
                 jnp.einsum("sjhd,sihd->sjih", kr, qr),
                 jnp.zeros((S,), bool), jnp.ones((S,), bool))
        for block in blocks:
            if packs % block:
                continue
            call = jax.jit(
                lambda st, *a, block=block: delta_state.pallas_delta_state(
                    st, *a, heads_block=block), donate_argnums=(0,))
            ms = _timed(call, fresh_pool(4)["state"], *small)
            line(rows=t, path="kernel alone", heads_block=block, ms=ms,
                 needed_gb_per_s=state_bytes / ms / 1e6,
                 state_ms=state_bytes / pk["bytes_per_s"] * 1e3)


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config", nargs="?", default="olmo_hybrid_7b")
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--rows", type=int, nargs="+", default=[1, 2])
    ap.add_argument("--blocks", type=int, nargs="+", default=[15, 5, 3, 1])
    a = ap.parse_args()
    measure(a.config, a.slots, a.rows, a.blocks)
