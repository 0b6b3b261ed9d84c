#!/usr/bin/env python3
"""Reads, on the chip and at the cell's own size, the numbers that the
limits of a training cell are set from (PERF.md gives the readings):

    python3 benchmark/tests/measure_train_limits.py <workload> <seed> ...

For each seed: the plain reference (float32, highest), the CONTROL (the
same reference computed in the precision below the one the configuration
states, as the reference file's ``control`` defines it) and the program,
through the driver's own ``program_numbers``. Prints the program's and
the control's gap to the reference for every compared number. No
measured window: training's readings need none.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def gaps(got, want, train_check):
    out = {"loss_rel_gap": max(abs(a - b) / abs(b) for a, b in zip(
        got["losses"], want["losses"]))}
    out["grad_norm_worst_leaf_gap"], out["grad_leaf"] = \
        train_check.worst_leaf_gap(got["grad_norms"], want["grad_norms"])
    out["param_change_worst_leaf_gap"], out["delta_leaf"] = \
        train_check.worst_leaf_gap(got["delta_norms"],
                                   want["delta_norms"])
    # steadier statistics, read beside the worst leaf
    import numpy as np
    for key, name in (("grad_norms", "grad"), ("delta_norms", "delta")):
        g, w = (np.asarray(x[key], np.float64) for x in (got, want))
        rel = np.abs(g - w) / np.maximum(w, np.median(w))
        out[name + "_leaf_gap_median"] = float(np.median(rel))
        out[name + "_leaf_gap_p90"] = float(np.quantile(rel, 0.9))
        out[name + "_global_norm_gap"] = float(
            abs(np.sqrt((g ** 2).sum()) - np.sqrt((w ** 2).sum()))
            / np.sqrt((w ** 2).sum()))
        big = w >= np.quantile(w, 0.5)
        out[name + "_worst_gap_larger_half"] = float(rel[big].max())
    return out


def main(workload, seeds, find=None):
    import jax
    from benchmark.harness import (inputs, session, spec, train_check,
                                   weights)
    from deeplearning4j_tpu.train.listeners import (
        CollectScoresIterationListener)
    cell = spec.load(workload)
    config, traffic = cell.config, cell.traffic
    driver = spec.load_module("drivers", traffic["driver"])
    builder = spec.load_module("builders", config["builder"])
    ref = spec.load_module("reference", config["reference"])
    rows = []
    for seed in seeds:
        s = session.Session(cell, seed, 0, 0, 0.0,
                            **({} if find is None else {"find": find}))
        pool = inputs.train_pool(traffic, config, seed)
        n = traffic["check_steps"]
        with builder.policy(config):
            seq = traffic["inputs"].get("seq_len")
            shapes = jax.eval_shape(
                lambda: builder.build(config, seq).init().params)
            maker = weights.maker(shapes, config["init"])
            make = lambda: maker(s.seed31())
            batches = [ref.batch_of(*pool[i % len(pool)])
                       for i in range(n)]
            want = train_check.reference_steps(ref, config, make, batches)
            ctrl = train_check.reference_steps(
                ref, config, make, batches, control=True)
            net = builder.build(config, seq).init()
            net.params = make()
            rec = CollectScoresIterationListener(1)
            net.set_listeners(rec)
            it = driver._iterator_class()(pool)
            got = driver.program_numbers(
                net, it, rec, dict(traffic.get("fit_kwargs", {})),
                config, make, n)
            del net, it
        row = {"seed": seed, "program": gaps(got, want, train_check),
               "control": gaps(ctrl, want, train_check),
               "ref_losses": want["losses"],
               "program_losses": got["losses"],
               "control_losses": ctrl["losses"]}
        print(json.dumps(row), flush=True)
        rows.append(row)
    for k in ("loss_rel_gap", "grad_norm_worst_leaf_gap",
              "param_change_worst_leaf_gap"):
        print(f"{k}: program largest "
              f"{max(r['program'][k] for r in rows):.4g}, control "
              f"smallest {min(r['control'][k] for r in rows):.4g}")
    return rows


if __name__ == "__main__":
    main(sys.argv[1], [int(x) for x in sys.argv[2:]])
