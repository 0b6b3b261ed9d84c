"""Share of the first device's busy time under the executors'
``updater`` scope: gradient normalisation, the optimizer's rule, the
parameters' update and constraints (harness/scopes.py)."""

from benchmark.harness import scopes


def read(obs):
    return scopes.share_pct(obs, "updater")
