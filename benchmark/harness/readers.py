"""Helpers shared by the one-file readers of benchmark/layer_metrics.
A reader gets ``obs`` (what the run observed) and returns a number, or
None when there is nothing to read in this cell."""

import re

from . import counts, peaks, spec, xplane


def span_mean_ms(obs, name):
    d = [e["dur_us"] for e in obs.get("spans", ()) if e["name"] == name]
    return sum(d) / len(d) / 1e3 if d else None


def counter_delta(obs, key_pattern, field=None):
    """after - before of the program's metric whose flat key
    (``name{labels}``) matches; ``field`` picks ``sum``/``count`` of a
    histogram."""
    rx = re.compile(key_pattern)
    before, after = obs["counters"].get("before", {}), obs[
        "counters"].get("after", {})
    total, found = 0.0, False
    for k, v in after.items():
        if not rx.search(k):
            continue
        b = before.get(k, 0 if field is None else {field: 0})
        total += (v[field] - b[field]) if field else (v - b)
        found = True
    return total if found else None


def histogram_mean_ms(obs, key_pattern):
    s = counter_delta(obs, key_pattern, "sum")
    n = counter_delta(obs, key_pattern, "count")
    return None if not n else 1e3 * s / n


def idle_pct(obs):
    tr = obs.get("trace")
    if tr is None:
        return None
    busy, span = xplane.busy_and_window(tr)
    return 100.0 * (1.0 - busy / span)


def peak_gb(obs):
    return obs["peak_bytes"] / 1e9


def kernel_time_pct(obs, pattern):
    tr = obs.get("trace")
    if tr is None:
        return None
    t, names = xplane.op_time(tr, pattern)
    if not names:
        return None
    busy, _ = xplane.busy_and_window(tr)
    return 100.0 * t / busy


_SHAPE = re.compile(r"(f32|bf16|f16)\[(\d+),(\d+),(\d+)\]")
_SIZE = {"f32": 4, "bf16": 2, "f16": 2}


def flash_roofline_pct(obs, pattern="pallas_flash_attention"):
    """Needed time of every flash call in the trace (forward: names
    without ``_bwd``; backward: the ``_bwd`` kernels of one attention
    call together need one backward) over their summed device time.
    Shapes are read from each call's own HLO text."""
    tr = obs.get("trace")
    if tr is None:
        return None
    t, names = xplane.op_time(tr, pattern)
    if not names:
        return None
    pk = peaks.peaks_for(obs["device"].device_kind)
    need, bound = 0.0, {}
    n_bwd_kernels = len({n for n in names if "_bwd" in n})
    n_fwd_kernels = len({n for n in names if "_bwd" not in n}) or 1
    per_bwd = n_bwd_kernels / n_fwd_kernels     # kernels per backward
    for n in names:
        m = _SHAPE.search(tr["text"][n].split("custom-call(", 1)[-1])
        bh, t_, dh = int(m.group(2)), int(m.group(3)), int(m.group(4))
        size = _SIZE[m.group(1)]
        if "_bwd" in n:
            f, b = counts.flash_bwd(bh, t_, dh, size)
            f, b = f / per_bwd, b / per_bwd
        else:
            f, b = counts.flash_fwd(bh, t_, dh, size)
        sec, which = counts.roofline_seconds(f, b, pk)
        need += sec
        bound[which] = bound.get(which, 0) + 1
    obs["flash_bound"] = max(bound, key=bound.get)
    print(f"flash roofline: bound by {obs['flash_bound']} in "
          f"{bound}", flush=True)
    return counts.share_pct(need, t, "flash kernels' roofline share")


def model_flops_util_pct(obs):
    """Required forward+backward FLOPs per sample x samples/s/chip
    over one chip's peak. The count is ``train_flops(config,
    traffic)`` of ``benchmark/counts/<name>.py``, the file the
    configuration's ``train_flops`` key names; a configuration
    without one has nothing to read."""
    cell = obs["cell"]
    count = spec.load_module("counts", cell.config.get("train_flops", ""))
    t = obs.get("traced")
    if count is None or t is None:
        return None
    per_sample = count.train_flops(cell.config, cell.traffic)
    pk = peaks.peaks_for(obs["device"].device_kind)
    # the traced run's own rate, over the steps that ran while the
    # profiler was on (starting and stopping it stalls the window)
    rate = (t["steps"] * obs["samples_per_step"] / t["seconds"]
            / obs["n_devices"])
    return counts.share_pct(per_sample * rate / pk["flops_per_s"], 1.0,
                            "model FLOP/s utilization")
