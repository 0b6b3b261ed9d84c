#!/usr/bin/env python3
"""``measure_serve_scopes.py`` for ``GroupedQueryDecoderBlock``: the
same traced run, with the block's scopes ``attn/global`` and
``attn/window`` beside those that script knows:

    python3 benchmark/tests/measure_gqa_scopes.py <workload> <seed> \\
        [seconds]

The compiled step's text is taken from the id-returning step
(``PagedSlotSession._step_ids``, what the batcher has run since it
went one step ahead) the first time it is called at a chunk's width:
``measure_serve_scopes.py`` hooks the return value of ``_make_step``,
which has none since then, and reads every op as "(no scope)".
"""

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tests import measure_serve_scopes as scopes  # noqa: E402

scopes.SCOPE = re.compile(
    r"[(/](\d+)_([A-Za-z0-9]+)\)*"
    r"(?:/(attn/global|attn/window|mla|moe/router|moe/experts"
    r"|moe/shared|mlp|attn|ln1|ln2)\b)?")


def grab_hlo(found):
    """A ``break_token`` hook: the session's id-returning step also
    keeps its compiled module's text, the first time it runs at a
    chunk's width (or at 1 where the batcher has no chunk program)."""
    def hook(server):
        from deeplearning4j_tpu.models.paged_kv import PagedSlotSession
        real = PagedSlotSession._make_step

        def make(self):
            real(self)
            step = self._step_ids

            def call(*args):
                if "hlo" not in found:
                    found["hlo"] = step.lower(*args).compile().as_text()
                return step(*args)
            self._step_ids = call
        PagedSlotSession._make_step = make
    return hook


scopes.grab_hlo = grab_hlo

if __name__ == "__main__":
    scopes.main(sys.argv[1], int(sys.argv[2]),
                float(sys.argv[3]) if len(sys.argv) > 3 else 45.0)
