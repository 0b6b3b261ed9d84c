"""Share of the first device's busy time in ops whose ``op_name`` has a
``transpose(``: the backward pass of every layer
(harness/scopes.py)."""

from benchmark.harness import scopes


def read(obs):
    return scopes.share_pct(obs, "backward")
