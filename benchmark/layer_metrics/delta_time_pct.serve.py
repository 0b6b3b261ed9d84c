"""Share of the first device's busy time under a gated delta-rule
mixer's scope (``delta``: the six input projections, the convolutions,
the recurrence over every slot's state row, the write of it, the gated
norm, the output projection and, the block's norm sitting behind the
mixer, that norm, of every ``DeltaRuleDecoderBlock``).

``harness/scopes.py``'s ``group`` knows no such part, so this file
walks the same assignment itself, as ``ssm_time_pct.serve.py`` walks
``ssm`` (``scopes.assign`` and ``scopes.own_ns`` over the program's
own ``scope_tables``), and keeps the seconds under ``delta`` and under
``delta/state`` on ``obs`` for the reader beside it. None where
``scopes.busy_by`` gives None or where no op lies under ``delta`` (a
program without the layer): never a number from a table that did not
match."""

from benchmark.harness import scopes

_KEY = "busy_under_delta"
_PARTS = ("delta", "delta/state")


def busy_under(obs):
    """``{"busy", "delta", "delta/state"}`` in nanoseconds, or None."""
    if _KEY not in obs:
        obs[_KEY] = _busy_under(obs)
    return obs[_KEY]


def _busy_under(obs):
    if scopes.busy_by(obs) is None:
        return None
    from deeplearning4j_tpu.observability.programs import scope_tables
    ops, rows, _ = scopes.assign(obs["trace"], scope_tables())
    out = dict.fromkeys(("busy",) + _PARTS, 0)
    for ns, (program, op_name) in zip(scopes.own_ns(ops), rows):
        out["busy"] += ns
        path = "/" + scopes.scope_path(op_name) + "/" if program else ""
        for part in _PARTS:
            if f"/{part}/" in path:
                out[part] += ns
    return out if out["delta"] else None


def share_pct(obs, part):
    by = busy_under(obs)
    return None if by is None else 100.0 * by[part] / by["busy"]


def read(obs):
    return share_pct(obs, "delta")
