"""Share of the first device's busy time under ``delta/state``: what a
gated delta-rule mixer does between its input projections and ``Wo``,
the convolutions over the carried window, the L2 norms and the gates,
the two passes over every slot's state row (the reads ``S^T k`` and
``S^T q``, then the write), the carried window's write and the gated
norm. The rest of ``delta_time_pct.serve`` is the matrix products and
the block's norm. Read by ``delta_time_pct.serve.py``'s walk of the
trace."""

from benchmark.harness import spec


def read(obs):
    return spec.load_module("layer_metrics", "delta_time_pct.serve"
                            ).share_pct(obs, "delta/state")
