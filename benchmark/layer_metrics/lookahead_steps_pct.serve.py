"""Share of the window's device steps that were enqueued while the
previous step's ids were not yet on the host
(``serving_lookahead_steps_total`` over both programs of
``serving_steps_total``): how often the batcher's one-step lookahead
engages. A step taken synchronously (a request with a temperature
emits in it, a drain, a migration, a prefill export's last chunk, the
dense session) and the first step after the pool ran empty do not
count. Nothing to read where the program has no such counter."""

from benchmark.harness import readers

_STEPS = r'serving_steps_total\{.*program="%s"'


def read(obs):
    ahead = readers.counter_delta(obs, r"serving_lookahead_steps_total\{")
    chunk = readers.counter_delta(obs, _STEPS % "chunk")
    single = readers.counter_delta(obs, _STEPS % "single")
    if ahead is None or chunk is None or single is None \
            or chunk + single <= 0:
        return None
    return 100.0 * ahead / (chunk + single)
