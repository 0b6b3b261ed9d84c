"""Share of the first device's busy time under a block's ``attn`` scope
or a bare attention layer, forward and backward: projections, head
split and merge, the flash kernels (harness/scopes.py)."""

from benchmark.harness import scopes


def read(obs):
    return scopes.share_pct(obs, "attention")
