"""Share of the window's device steps that ran the chunk program
(``serving_steps_total{program="chunk"}``: some slot had prompt tokens
to spare, so the step was (slots, t)) rather than the single-token one
(``program="single"``: every live slot was decoding). How often
chunked prefill engages; nothing to read where the program has no
such counter."""

from benchmark.harness import readers

_KEY = r'serving_steps_total\{.*program="%s"'


def read(obs):
    chunk = readers.counter_delta(obs, _KEY % "chunk")
    single = readers.counter_delta(obs, _KEY % "single")
    if chunk is None or single is None or chunk + single <= 0:
        return None
    return 100.0 * chunk / (chunk + single)
