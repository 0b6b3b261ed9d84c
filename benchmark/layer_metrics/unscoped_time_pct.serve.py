"""Share of the first device's busy time in ops that carry no named
scope (the copies the compiler puts between layers, the greedy pick)
or lie outside every registered step program (harness/scopes.py)."""

from benchmark.harness import scopes


def read(obs):
    return scopes.share_pct(obs, "unscoped")
