"""Of the traced steps' forward flash calls (``pallas_flash_attention``
names without ``_bwd``), the share beyond one a layer a step: the
forward kernel's second run in the backward pass of a layer that is
recomputed whole (``recompute_layers`` around a flash call whose ``o``
and ``lse`` were not kept). A layer-step is two ``_bwd`` calls (dq,
dk/dv), as ``flash_gqa_roofline_pct.train`` counts them. 50 where
every layer's forward runs twice, 0 where it runs once.

Whole steps alone are counted: the calls from one call of an
instruction to its last (a step calls each of its instructions once,
so any stretch between two calls of one name is whole steps, wherever
in the step the trace began). Nothing to read where the trace has no
``_bwd`` call."""

from benchmark.harness import xplane


def reruns_pct(calls):
    """``calls``: the flash ops' instruction names in time order."""
    last = max(i for i, name in enumerate(calls) if name == calls[0])
    whole = calls[:last] or calls
    backward = sum("_bwd" in name for name in whole)
    if not backward:
        return None
    forward = len(whole) - backward
    return 100.0 * max(forward - backward / 2.0, 0.0) / max(forward, 1)


def read(obs):
    tr = obs.get("trace")
    if tr is None:
        return None
    _, calls = xplane.op_time(tr, "pallas_flash_attention")
    return reruns_pct(calls) if calls else None
