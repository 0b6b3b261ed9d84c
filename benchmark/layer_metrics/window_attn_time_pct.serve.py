"""Share of the first device's busy time under ``attn/window``: the
sliding-window layers of a grouped-query block, their ring slice and
``_attend`` (harness/scopes.py)."""

from benchmark.harness import scopes


def read(obs):
    return scopes.share_pct(obs, "window_attention")
