#!/usr/bin/env python3
"""The flash kernels' compiled length, with no chip:

    python3 tools/measure_flash_bundles.py [--T 8192 --H 32 --K 4 --D 128
        --window 2048 --block 512] [--tree <other checkout>]

Compiles ``pallas_flash_attention`` and ``pallas_flash_attention_bwd``
(the dq kernel and the dk/dv kernel) of ``ops/attention.py`` at one
shape (default: ``trinity_train_8k``'s window layer, float32) for a
DESCRIBED v5e, each direction in a process of its own with the
compiler's bundle dump switched on (``LIBTPU_INIT_ARGS=
--xla_jf_dump_to=<dir> --xla_jf_dump_llo_text=true``; the dumper aborts
the process once a compile's kernels are written, over a report
template this installation lacks: the files are whole), and prints for
each kernel

- the whole program's bundles (a bundle is a cycle: the kernel's
  length at 1.5 GHz, pipeline prologue and every branch included);
- the tile's region: the innermost region of the final bundles that
  holds every MXU use, i.e. the body that runs once a (q tile, k tile)
  pair, as ``last bundle - first bundle``;
- the nine slot sums of ``*final_hlo-static-per-bundle-utilization.txt``
  over the whole program (slots a bundle: 4 MXU, 3 XLU, 4 VALU, 1 EUP,
  3 loads, 1 store, 2 scalar), and the MXU floor ``MXU uses / 4``.

Tiles x whole-program bundles / 1.5 GHz is the kernels' time a step
within a few per cent for the backward (PERF.md section 6, PR 49).
``--tree`` reads another checkout's kernels (the parent's: 2,962 /
2,116 / 2,998 bundles at the default shape, commit 18c95b8). It times
nothing and needs no chip; ``--window 0`` is a full layer."""

import argparse
import glob
import json
import os
import re
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLOTS = ("MXU", "XLU", "VALU", "EUP", "VLOAD", "VLOAD:FILL", "VSTORE",
         "VSTORE:SPILL", "SALU")

_COMPILE = """
import os, sys
sys.path.insert(0, {tree!r})
os.environ.setdefault("TPU_LOG_DIR", "disabled")
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
jax.config.update("jax_enable_compilation_cache", False)
one = SingleDeviceSharding(topologies.get_topology_desc(
    platform="tpu", topology_name="v5e:2x2").devices[0])
from deeplearning4j_tpu.ops import attention as A
T, H, K, D = {T}, {H}, {K}, {D}
sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)
q, k, lse = sds(1, T, H, D), sds(1, T, K, D), sds(1, H, T)
kw = dict(causal=True, block_q={block}, block_k={block}, window={window})
if {forward}:
    jax.jit(lambda q, k, v: A.pallas_flash_attention(
        q, k, v, return_lse=True, **kw)).lower(q, k, k).compile()
else:
    jax.jit(lambda q, k, v, o, l, do: A.pallas_flash_attention_bwd(
        q, k, v, o, l, do, **kw)).lower(q, k, k, q, lse, q).compile()
"""


def _dumped(dump, kernel, outputs):
    """The utilization and final-bundle files of the dumped ``kernel``
    with so many outputs (the dq kernel has one, the dk/dv kernel two;
    the dump's order is the compiler's, not the program's)."""
    for bundles in glob.glob(os.path.join(
            dump, f"*-{kernel}.*-final_bundles.txt")):
        stem = re.match(rf"(.*-{re.escape(kernel)}\.\d+)-\d+-final_bundles"
                        r"\.txt$", bundles)
        if not stem:        # schedule-analysis_final_bundles and others
            continue
        with open(bundles) as f:
            if f.read().count("kind: output") == outputs:
                return glob.glob(stem.group(1) + "-*-final_hlo-static-per-"
                                 "bundle-utilization.txt")[0], bundles
    raise SystemExit(f"no dump of {kernel} with {outputs} output(s)")


def _tile_region(bundles_text, mxu_bundles):
    """(first, last) bundle of the innermost region that holds every
    MXU use."""
    opened, regions = {}, []
    for line in bundles_text.splitlines():
        m = re.match(r"\s*(0x[0-9a-f]+|\d+)\s+:", line)
        if not m:
            continue
        at = int(m.group(1), 0)
        for kind, n in re.findall(r"(Start|End) region (\d+)", line):
            if kind == "Start":
                opened[n] = at
            elif n in opened:
                regions.append((opened.pop(n), at))
    lo, hi = min(mxu_bundles), max(mxu_bundles)
    return min((r for r in regions if r[0] <= lo and hi <= r[1]),
               key=lambda r: r[1] - r[0])


def read(dump, kernel, outputs):
    util, bundles = _dumped(dump, kernel, outputs)
    with open(util) as f:
        rows = [list(map(int, l.split())) for l in
                f.read().split("== UTILIZATION:\n")[1].strip().splitlines()]
    with open(bundles) as f:
        first, last = _tile_region(
            f.read(), [i for i, r in enumerate(rows) if r[0]])
    sums = dict(zip(SLOTS, (sum(c) for c in zip(*rows))))
    return {"bundles": len(rows), "tile_region": last - first,
            "tile_region_at": [first, last], "slot_uses": sums,
            "mxu_floor": sums["MXU"] // 4}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    for name, default in (("T", 8192), ("H", 32), ("K", 4), ("D", 128),
                          ("window", 2048), ("block", 512)):
        ap.add_argument(f"--{name}", type=int, default=default)
    ap.add_argument("--tree", default=ROOT)
    a = ap.parse_args(argv)
    with tempfile.TemporaryDirectory() as dump:
        for forward in (True, False):
            code = _COMPILE.format(
                tree=os.path.abspath(a.tree), T=a.T, H=a.H, K=a.K, D=a.D,
                block=a.block, window=a.window or None, forward=forward)
            got = subprocess.run(
                [sys.executable, "-c", code], capture_output=True, text=True,
                env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="",
                         LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={dump} "
                                          "--xla_jf_dump_llo_text=true"))
            if not glob.glob(os.path.join(
                    dump, "*pallas_flash_attention*final_bundles.txt")):
                sys.stderr.write(got.stderr[-4000:])    # no kernel compiled
                return 1
        out = {"shape": {k: getattr(a, k) for k in
                         ("T", "H", "K", "D", "window", "block")},
               "tree": os.path.abspath(a.tree)}
        for label, kernel, outputs in (
                ("forward", "pallas_flash_attention", 2),
                ("backward_dq", "pallas_flash_attention_bwd", 1),
                ("backward_dkdv", "pallas_flash_attention_bwd", 2)):
            out[label] = r = read(dump, kernel, outputs)
            print(f"{label:14s} {r['bundles']:5d} bundles, tile region "
                  f"{r['tile_region']:5d}, MXU {r['slot_uses']['MXU']} -> "
                  f"floor {r['mxu_floor']}, VALU {r['slot_uses']['VALU']}, "
                  f"XLU {r['slot_uses']['XLU']}, EUP {r['slot_uses']['EUP']}")
        print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
