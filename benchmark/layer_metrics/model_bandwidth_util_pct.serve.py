"""Bytes a decode step reads (``serve_step_bytes`` of the file the
configuration's key of that name gives, at the window's own mean live
slots) over the device's own time for a step x the chip's memory
bandwidth. The time is the device trace's: the first device's busy
seconds over the steps that ran while the profiler was on, so the
host's turn between two steps is not in it."""

import collections

from benchmark.harness import counts, peaks, readers, spec, xplane


def steps_in_trace(tr):
    """Runs of the step program inside the traced part: each op of a
    compiled step runs once a step, so the count that most op names
    share (the few ops of a page copy run less often)."""
    runs = collections.Counter(op[0] for op in tr["devices"][0]["ops"])
    return collections.Counter(runs.values()).most_common(1)[0][0]


def read(obs):
    cell, tr = obs["cell"], obs.get("trace")
    count = spec.load_module(
        "counts", cell.config.get("serve_step_bytes", ""))
    items = readers.counter_delta(obs, "serving_batch_items_total")
    batches = readers.counter_delta(obs, "serving_batches_total")
    if count is None or tr is None or not batches:
        return None
    need = count.serve_step_bytes(cell.config, cell.traffic,
                                  items / batches)
    busy, _ = xplane.busy_and_window(tr)
    pk = peaks.peaks_for(obs["device"].device_kind)
    return counts.share_pct(need / pk["bytes_per_s"],
                            busy / steps_in_trace(tr),
                            "model bandwidth utilization of a step")
