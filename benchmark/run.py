#!/usr/bin/env python3
"""Runs one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are read
from BENCHMARK.json and the files it names (benchmark/README.md). The
last line of standard output is the result as one JSON object. Exits
non-zero, printing no result, without a TPU or with fewer chips than
the cell asks for.
"""

import time

T_START = time.perf_counter()

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None, find_devices=None, **driver_kwargs):
    from benchmark.harness import session, spec
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load(args.workload)
    kw = {} if find_devices is None else {"find": find_devices}
    s = session.Session(cell, args.seed, args.seconds, args.trace,
                        T_START if argv is None else time.perf_counter(),
                        **kw)
    driver = spec.load_module("drivers", cell.traffic["driver"])
    result = driver.run(s, **driver_kwargs)
    session.emit(result)
    return result


if __name__ == "__main__":
    main()
    sys.exit(0)
