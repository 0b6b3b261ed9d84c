"""Share of the first device's busy time under ``mlp``, ``mlp0`` or
``mlp1``: the dense feed-forward halves (harness/scopes.py)."""

from benchmark.harness import scopes


def read(obs):
    return scopes.share_pct(obs, "mlp")
