"""Share of the first device's busy time under ``moe/experts``,
``moe/shared`` or ``moe/zero``: the held experts' pass
(harness/scopes.py)."""

from benchmark.harness import scopes


def read(obs):
    return scopes.share_pct(obs, "experts")
