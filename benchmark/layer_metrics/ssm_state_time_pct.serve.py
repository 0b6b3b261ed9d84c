"""Share of the first device's busy time under ``ssm/state``: what a
state-space mixer does between its two projections, the convolution
over the carried window, the recurrence over every slot's state row,
the write of both back, the gate and its norm. The rest of
``ssm_time_pct.serve`` is the two matrix products. Read by
``ssm_time_pct.serve.py``'s walk of the trace."""

from benchmark.harness import spec


def read(obs):
    return spec.load_module("layer_metrics", "ssm_time_pct.serve"
                            ).share_pct(obs, "ssm/state")
