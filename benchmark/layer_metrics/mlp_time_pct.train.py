"""Share of the first device's busy time under a block's ``mlp`` scope,
forward and backward (harness/scopes.py)."""

from benchmark.harness import scopes


def read(obs):
    return scopes.share_pct(obs, "mlp")
