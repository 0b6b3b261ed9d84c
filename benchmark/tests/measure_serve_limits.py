#!/usr/bin/env python3
"""Reads, on the chip and at the cell's own load, the numbers that a
serving cell's limits are set from (PERF.md gives the readings):

    python3 benchmark/tests/measure_serve_limits.py <workload> \\
        <seconds> <seed> ...

For each seed one short window through the driver itself; then, on the
very requests the run compared, the CONTROL: the reference computed in
the precision below the configuration's (``logits(..., control=True)``
of the reference file), put in the program's place. It need not
decode: at each served position its distribution is held against the
reference's, and the token IT puts first against the reference's best.
"""

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def control_numbers(driver, ref, config, params, sample, pad_to):
    logp, first = [], []
    for rec in sample:
        seq = np.zeros((pad_to,), np.int32)
        seq[:len(rec[4]) + len(rec[5])] = list(rec[4]) + list(rec[5])
        z = np.asarray(ref.logits(params, seq, config, control=True))[
            driver.served_positions(rec)]
        logp.append(driver.log_softmax(z))
        first.append(z.argmax(axis=-1))
    return driver.reference_numbers(ref, config, params, sample, pad_to,
                                    logp, tokens=first)


def main(workload, seconds, seeds, find=None):
    import time
    from benchmark.harness import session, spec
    cell = spec.load(workload)
    driver = spec.load_module("drivers", cell.traffic["driver"])
    ref = spec.load_module("reference", cell.config["reference"])
    rows = []
    for seed in seeds:
        s = session.Session(cell, seed, seconds, 0, time.perf_counter(),
                            **({} if find is None else {"find": find}))
        result = driver.run(s)
        sample, pad_to, make_params, _ = s.obs["check_sample"]
        ctrl = control_numbers(driver, ref, cell.config, make_params(),
                               sample, pad_to)
        prog = {c["name"]: c["value"] for c in s.checks}
        row = {"seed": seed, "tokens": ctrl["tokens"],
               "program_gap": prog["served_token_widest_logit_gap"],
               "control_gap": ctrl["gap"],
               "program_kl": prog["served_logprob_kl"],
               "control_kl": ctrl["kl"],
               "correct": result["correct"],
               "metrics": result["metrics"],
               "peak": result["device"]["memory_peak_bytes"]}
        print(json.dumps(row), flush=True)
        rows.append(row)
    for k in ("gap", "kl"):
        print(f"{k}: program largest "
              f"{max(r['program_' + k] for r in rows):.4g}, control "
              f"smallest {min(r['control_' + k] for r in rows):.4g}")
    return rows


if __name__ == "__main__":
    main(sys.argv[1], float(sys.argv[2]), [int(x) for x in sys.argv[3:]])
