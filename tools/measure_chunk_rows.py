#!/usr/bin/env python3
"""A serving cell read at several row budgets of the chunked-prefill
step (``deeplearning4j_tpu.serving.continuous.CHUNK_ROWS``,
``WIDE_CHUNK_ROWS`` and ``GROUPED_CHUNK_ROWS``: a slot in prefill
feeds up to t tokens a step, slots * t <= rows, and a pool may hold a
second program at a wide budget, run only in the steps whose prompt
rows fill it):

    python3 tools/measure_chunk_rows.py <workload> \\
        <seconds> <seed> auto|<rows>[:<wide rows>] [...]

``auto`` leaves the program's own choice: 128 rows, and the wide
budget ``continuous.wide_chunk_rows`` reads off the cell's session
(256, or 512 where every expert layer carries the rows). ``<rows>``
alone sets every budget to it (one chunk program at that width in
every chunk step, as before PR 42); ``128:512`` forces the pair,
whatever the session says. For each one untraced window through the
driver itself, in one process (one set-up of the chip, the weights
made anew each time), and one JSON line: the budgets as asked, the
widths ``t_lo`` and ``t_hi`` the batcher gave the cell's pool
(``t_hi`` 0: no wide program) and the rows a step of each carries,
the driver's result, and the batcher's own counters over the window
(the readers of benchmark/layer_metrics that need no trace;
``wide_steps_pct.serve`` is the wide steps' share). The program has no
option for the budgets; this script sets the module's constants, which
is how PERF.md's readings were taken. A budget of 1 is token-by-token
prefill.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

COUNTERS = ("chunk_steps_pct.serve", "wide_steps_pct.serve",
            "prompt_slot_steps_pct.serve",
            "step_device_ms.serve", "step_host_ms.serve",
            "prefill_ms.serve", "queue_wait_ms.serve",
            "batch_occupancy_pct.serve",
            "moe_local_pairs_per_step.serve",
            "moe_grouped_steps_pct.serve")


def main(workload, seconds, seed, budgets):
    from benchmark.harness import session, spec
    from deeplearning4j_tpu.serving import continuous
    cell = spec.load(workload)
    driver = spec.load_module("drivers", cell.traffic["driver"])
    # the widths are the batcher's own: it knows its session's caps
    widths, warm = [], continuous.ContinuousBatcher._warm_programs

    def noting(cb):
        widths.append((cb._chunk_t, cb._wide_t))
        return warm(cb)

    continuous.ContinuousBatcher._warm_programs = noting
    own = (continuous.CHUNK_ROWS, continuous.WIDE_CHUNK_ROWS,
           continuous.GROUPED_CHUNK_ROWS)
    for budget in budgets:
        forced = budget != "auto"
        rows, wide = budget if forced else (own[0], "auto")
        # a forced wide budget is both of the session's answers
        (continuous.CHUNK_ROWS, continuous.WIDE_CHUNK_ROWS,
         continuous.GROUPED_CHUNK_ROWS) = (
            (rows, wide, wide) if forced else own)
        s = session.Session(cell, seed, seconds, 0, time.perf_counter())
        result = driver.run(s)
        read = {}
        for name in COUNTERS:
            v = spec.load_module("layer_metrics", name).read(s.obs)
            if v is not None:
                read[name] = v
        t_lo, t_hi = widths[-1]
        slots = cell.traffic["server"]["slots"]
        print(json.dumps({
            "rows": rows, "wide_rows": wide, "t_lo": t_lo,
            "t_hi": t_hi, "step_rows": [slots * t_lo, slots * t_hi],
            "seed": seed,
            "correct": result["correct"], "failed": result["failed"],
            "checks": {c["name"]: c["value"] for c in s.checks},
            "metrics": {k: v["value"]
                        for k, v in result["metrics"].items()},
            "counters": read,
            "peak": result["device"]["memory_peak_bytes"]}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], float(sys.argv[2]), int(sys.argv[3]),
         [a if a == "auto" else
          tuple(int(r) for r in (a.split(":") * 2)[:2])
          for a in sys.argv[4:]])
