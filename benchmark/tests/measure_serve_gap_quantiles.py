#!/usr/bin/env python3
"""``measure_serve_limits.py`` with the served-token gap read token by
token, for a model whose widest gap a router flip decides:

    python3 benchmark/tests/measure_serve_gap_quantiles.py <workload> \\
        <seconds> <seed> ...

For each seed one short window through the driver itself; then, on the
requests the run compared, one pass of the reference and one of its
CONTROL (``logits(..., control=True)``). Per seed one JSON line: the
driver's two numbers for the program and for the control (as
``measure_serve_limits.py`` gives them) and, for both, quantiles of
the per-token gap (how far the token lies below the reference's best)
and the share of tokens that are not the reference's best. The
driver's ``correct`` reads only the widest gap and the divergence; a
quantile as a third number would need an edit to
``drivers/serve_closed_loop.py`` (PERF.md section 7).
"""

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

QUANTILES = (0.5, 0.9, 0.99, 0.999)


def describe(gaps):
    g = np.concatenate(gaps)
    out = {f"p{100 * q:g}": float(np.quantile(g, q)) for q in QUANTILES}
    return dict(out, widest=float(g.max()),
                not_best_share=float((g > 0).mean()))


def main(workload, seconds, seeds):
    import time
    from benchmark.harness import session, spec
    cell = spec.load(workload)
    config = cell.config
    driver = spec.load_module("drivers", cell.traffic["driver"])
    ref = spec.load_module("reference", config["reference"])
    rows = []
    for seed in seeds:
        s = session.Session(cell, seed, seconds, 0, time.perf_counter())
        result = driver.run(s)
        sample, pad_to, make_params, _ = s.obs["check_sample"]
        params = make_params()
        prog, ctrl, kl, n = [], [], 0.0, 0
        for rec in sample:
            seq = np.zeros((pad_to,), np.int32)
            seq[:len(rec[4]) + len(rec[5])] = list(rec[4]) + list(rec[5])
            pos = driver.served_positions(rec)
            z = np.asarray(ref.logits(params, seq, config))[pos]
            zc = np.asarray(ref.logits(params, seq, config,
                                       control=True))[pos]
            at = np.arange(len(pos))
            prog.append(z.max(axis=-1) - z[at, np.asarray(rec[5])])
            ctrl.append(z.max(axis=-1) - z[at, zc.argmax(axis=-1)])
            lp = driver.log_softmax(z)
            kl += float((np.exp(lp) * (lp - driver.log_softmax(zc))).sum())
            n += len(pos)
        del params
        checks = {c["name"]: c["value"] for c in s.checks}
        row = {"seed": seed, "tokens": n,
               "program_kl": checks["served_logprob_kl"],
               "control_kl": kl / n,
               "program_gap": describe(prog),
               "control_gap": describe(ctrl),
               "correct": result["correct"],
               "metrics": result["metrics"],
               "peak": result["device"]["memory_peak_bytes"]}
        print(json.dumps(row), flush=True)
        rows.append(row)
    print(f"kl: program {min(r['program_kl'] for r in rows):.4g}.."
          f"{max(r['program_kl'] for r in rows):.4g}, control "
          f"{min(r['control_kl'] for r in rows):.4g}.."
          f"{max(r['control_kl'] for r in rows):.4g}")
    for k in ("widest", "p99.9", "p99", "p90", "not_best_share"):
        p = [r["program_gap"][k] for r in rows]
        c = [r["control_gap"][k] for r in rows]
        print(f"gap {k}: program {min(p):.4g}..{max(p):.4g}, control "
              f"{min(c):.4g}..{max(c):.4g}")
    return rows


if __name__ == "__main__":
    main(sys.argv[1], float(sys.argv[2]), [int(x) for x in sys.argv[3:]])
