#!/usr/bin/env python3
"""``measure_serve_scopes.py`` for a block that numbers its parts: the
same traced run, with the scopes of ``ShortcutExpertBlock`` (``mla0``,
``mla1``, ``mlp0``, ``mlp1``, ``moe/zero``) beside those that script
knows:

    python3 benchmark/tests/measure_block_scopes.py <workload> <seed> \\
        [seconds]
"""

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tests import measure_serve_scopes as scopes  # noqa: E402

scopes.SCOPE = re.compile(
    r"[(/](\d+)_([A-Za-z0-9]+)\)*"
    r"(?:/(mla[01]?|mlp[01]?|moe/router|moe/experts|moe/shared|moe/zero"
    r"|attn|ln1|ln2)\b)?")

if __name__ == "__main__":
    scopes.main(sys.argv[1], int(sys.argv[2]),
                float(sys.argv[3]) if len(sys.argv) > 3 else 45.0)
