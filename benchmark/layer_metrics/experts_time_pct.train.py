"""Share of the first device's busy time under ``moe/experts`` or
``moe/shared``, forward, recomputation and backward: the held experts'
pairs pass and the shared expert (harness/scopes.py)."""

from benchmark.harness import scopes


def read(obs):
    return scopes.share_pct(obs, "experts")
