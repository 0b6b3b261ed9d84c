"""Share of the first device's busy time under an attention scope of the
paged step (``attn``, ``attn/global``, ``attn/window``, ``mla``,
``mla0``, ``mla1``, or a bare attention layer): projections, the
cache write, the read and the head merge (harness/scopes.py). None
where the trace did not match the program's own tables."""

from benchmark.harness import scopes


def read(obs):
    return scopes.share_pct(obs, "attention")
