"""Share of the first device's busy time under a state-space mixer's
scope (``ssm``: the input projection, the convolution, the recurrence,
the state write, the gated norm and the output projection of every
``StateSpaceDecoderBlock``).

``harness/scopes.py``'s ``group`` knows no such part, so this file
walks the same assignment itself (``scopes.assign`` and
``scopes.own_ns`` over the program's own ``scope_tables``) and keeps
the seconds under ``ssm`` and under ``ssm/state`` on ``obs`` for the
reader beside it. None where ``scopes.busy_by`` gives None: never a
number from a table that did not match."""

from benchmark.harness import scopes

_KEY = "busy_under_ssm"


def busy_under(obs):
    """``{"busy", "ssm", "ssm/state"}`` in nanoseconds, or None."""
    if _KEY not in obs:
        obs[_KEY] = _busy_under(obs)
    return obs[_KEY]


def _busy_under(obs):
    if scopes.busy_by(obs) is None:
        return None
    from deeplearning4j_tpu.observability.programs import scope_tables
    ops, rows, _ = scopes.assign(obs["trace"], scope_tables())
    out = {"busy": 0, "ssm": 0, "ssm/state": 0}
    for ns, (program, op_name) in zip(scopes.own_ns(ops), rows):
        out["busy"] += ns
        path = "/" + scopes.scope_path(op_name) + "/" if program else ""
        for part in ("ssm", "ssm/state"):
            if f"/{part}/" in path:
                out[part] += ns
    return out


def share_pct(obs, part):
    by = busy_under(obs)
    return None if by is None else 100.0 * by[part] / by["busy"]


def read(obs):
    return share_pct(obs, "ssm")
