"""Seconds inside ``setup/program`` spans during set-up: the first
call of each step program, whole (its registration, Python tracing,
lowering, the compile or the load from the persistent cache, the
dispatch), and the ahead-of-time compiles of ``warmup_train_programs``.
``setup_named_pct.py`` says how the set-up is bounded and when there is
nothing to read."""

from benchmark.harness import spec


def read(obs):
    timeline = spec.load_module("layer_metrics", "setup_named_pct")
    return timeline.union_s(timeline.setup_events(obs),
                            ("setup/program",))
