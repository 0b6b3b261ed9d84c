"""Share of the first device's busy time under a gated short
convolution's scope (``conv``: the input projection, the two gates,
the convolution over the carried window, the window's write and the
output projection of every ``ShortConvDecoderBlock``).

``harness/scopes.py``'s ``group`` knows no such part, so this file
walks the same assignment itself, as ``ssm_time_pct.serve.py`` does
for its scope (``scopes.assign`` and ``scopes.own_ns`` over the
program's own ``scope_tables``). None where ``scopes.busy_by`` gives
None, and where the program has no such scope table: never a number
from a table that did not match."""

from benchmark.harness import scopes


def read(obs):
    if scopes.busy_by(obs) is None:
        return None
    from deeplearning4j_tpu.observability.programs import scope_tables
    ops, rows, _ = scopes.assign(obs["trace"], scope_tables())
    busy = under = 0
    for ns, (program, op_name) in zip(scopes.own_ns(ops), rows):
        busy += ns
        if program and "/conv/" in "/" + scopes.scope_path(op_name) + "/":
            under += ns
    return 100.0 * under / busy
