"""Roofline share of the flash attention kernels over a band
(``pallas_flash_attention`` / ``_bwd`` with a window or grouped heads,
``deeplearning4j_tpu/ops/attention.py``): the least time the chip
could take over the traced steps' attention calls, from
``counts/<train_flops>.py`` ``flash_needed_seconds`` (the band a
window lets a query see and not the triangle, key/value bytes once a
KEY head, every layer's forward and backward once a step), over the
device time of ALL their calls, the forward's second run under
recomputation included: needed work over spent time.

A step is told by its backward calls, two kernels (dq, dk/dv) a layer
a step. Nothing to read where the trace has no such call or the
configuration's counts file has no band (``flash_roofline_pct.train``
reads the cells whose every call is a whole triangle)."""

import re

from benchmark.harness import counts, peaks, spec, xplane

_DTYPE = re.compile(r"custom-call\((f32|bf16|f16)\[")
_SIZE = {"f32": 4, "bf16": 2, "f16": 2}


def read(obs):
    tr = obs.get("trace")
    if tr is None:
        return None
    cell = obs["cell"]
    count = spec.load_module("counts", cell.config.get("train_flops", ""))
    if count is None or not hasattr(count, "flash_needed_seconds"):
        return None
    seconds, calls = xplane.op_time(tr, "pallas_flash_attention")
    backward = [n for n in calls if "_bwd" in n]
    if not backward:
        return None
    m = _DTYPE.search(tr["text"][backward[0]])
    itemsize = _SIZE[m.group(1)] if m else 4
    layers = cell.config["num_hidden_layers"]
    steps = len(backward) / (2.0 * layers)
    need, bound = count.flash_needed_seconds(
        cell.config, cell.traffic["inputs"]["seq_len"], itemsize,
        peaks.peaks_for(obs["device"].device_kind))
    print(f"flash band roofline: {len(calls)} calls over {steps:.2f} "
          f"steps, {1e3 * seconds / steps:.2f} ms a step against "
          f"{1e3 * need:.2f} ms needed, bound by {bound}", flush=True)
    return counts.share_pct(need * steps, seconds,
                            "flash band kernels' roofline share")
