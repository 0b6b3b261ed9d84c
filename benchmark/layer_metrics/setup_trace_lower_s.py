"""Seconds of Python tracing and of lowering to MLIR during set-up:
the ``xla/trace`` and ``xla/lower`` spans ``compile_watch`` writes, the
outermost a thread only (a jitted function traced inside another's
trace is inside that span already), summed. A warm persistent cache
does not save them: its key is computed from the lowered text.
``setup_named_pct.py`` says how the set-up is bounded and when there is
nothing to read."""

from benchmark.harness import spec


def read(obs):
    events = spec.load_module(
        "layer_metrics", "setup_named_pct").setup_events(obs)
    if events is None:
        return None
    return sum(e["dur_us"] for e in events
               if e["name"] in ("xla/trace", "xla/lower")) / 1e6
