"""Share of the window's device steps that ran the WIDE chunk program
(``serving_wide_steps_total`` over both programs of
``serving_steps_total``; a wide step counts under ``program="chunk"``
too): how often the batcher's second, wider chunk width engages. The
batcher runs it only in a step whose slots have enough prompt rows on
offer to fill it, so a pool that mostly decodes holds the program and
reads near 0. Nothing to read where the pool holds no wide program
(few slots, a layer whose step unrolls over the chunk's rows) or the
program has no such counter."""

from benchmark.harness import readers

_STEPS = r'serving_steps_total\{.*program="%s"'


def read(obs):
    wide = readers.counter_delta(obs, r"serving_wide_steps_total\{")
    chunk = readers.counter_delta(obs, _STEPS % "chunk")
    single = readers.counter_delta(obs, _STEPS % "single")
    if wide is None or chunk is None or single is None \
            or chunk + single <= 0:
        return None
    return 100.0 * wide / (chunk + single)
