"""Roofline share of the delta rule's state kernel
(``pallas_delta_state``, ``deeplearning4j_tpu/ops/delta_state.py``):
the bytes its calls NEED at the chip's memory bandwidth over their
device time. A call needs every slot's state of one linear layer read
once and written once, ``2 x slots x 4 x 30 x 96 x 192`` bytes in
``olmo_hybrid_7b`` (the state term of ``counts/olmo_hybrid.py``
``state_bytes``; the convolutions' window is not the kernel's), the
slots the traffic file's ``server`` gives: the kernel passes every
slot's row, live or not. The small operands (keys, queries, values,
the rows' scalars, the output) are under 3 % of that and are not
counted. The kernel is bound by memory: 0.7 FLOP a byte.

Nothing to read where no such kernel is in the trace (a program
without the kernel, a cell without the layer): the metric is also the
kernel's engagement counter."""

from benchmark.harness import counts, peaks, xplane

STATE_ITEM = 4      # float32


def read(obs):
    tr = obs.get("trace")
    if tr is None:
        return None
    seconds, calls = xplane.op_time(tr, "pallas_delta_state")
    if not calls:
        return None
    cell = obs["cell"]
    c = cell.config
    need = (len(calls) * 2 * cell.traffic["server"]["slots"] * STATE_ITEM
            * c["linear_num_value_heads"] * c["linear_key_head_dim"]
            * c["linear_value_head_dim"])
    pk = peaks.peaks_for(obs["device"].device_kind)
    return counts.share_pct(need / pk["bytes_per_s"], seconds,
                            "delta state kernel's roofline share")
