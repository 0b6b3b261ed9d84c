"""Share of the first device's busy time under ``attn/window``,
forward, recomputation and backward: the sliding-window layers of a
grouped-query block, projections, gate and the flash kernels' band
(harness/scopes.py)."""

from benchmark.harness import scopes


def read(obs):
    return scopes.share_pct(obs, "window_attention")
