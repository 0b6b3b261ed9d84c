"""Seconds inside ``setup/init`` spans during set-up: both executors'
``init()`` (parameters, then ``setup/init/optimizer``: the updater's
state), every call of it, the one under ``jax.eval_shape`` that only
asks for shapes among them. ``setup_named_pct.py`` says how the set-up
is bounded and when there is nothing to read."""

from benchmark.harness import spec


def read(obs):
    timeline = spec.load_module("layer_metrics", "setup_named_pct")
    return timeline.union_s(timeline.setup_events(obs), ("setup/init",))
