"""Seconds the persistent compile cache took to hand back executables
during set-up: ``load_s`` (jax's ``cache_retrieval_time_sec``) of the
``xla/compile`` spans whose ``cache`` says ``hit``. On a warm cache this
is what ``setup_compile_s`` reads, less jax's bookkeeping around each
load; on a cold one it is 0 and ``setup_compile_s`` is compile seconds.
``setup_named_pct.py`` says how the set-up is bounded and when there is
nothing to read."""

from benchmark.harness import spec


def read(obs):
    events = spec.load_module(
        "layer_metrics", "setup_named_pct").setup_events(obs)
    if events is None:
        return None
    return sum(e["args"]["load_s"] for e in events
               if e["name"] == "xla/compile"
               and e["args"]["cache"] == "hit")
