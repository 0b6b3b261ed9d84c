"""Share of the window's slot-steps that consumed a prompt token
(``serving_slot_steps_total{kind="prompt"}``) rather than emitted one
(``kind="decode"``): what token-by-token prefill takes of the pool."""

from benchmark.harness import readers

_KEY = r'serving_slot_steps_total\{.*kind="%s"'


def read(obs):
    prompt = readers.counter_delta(obs, _KEY % "prompt")
    decode = readers.counter_delta(obs, _KEY % "decode")
    if prompt is None or decode is None or prompt + decode <= 0:
        return None
    return 100.0 * prompt / (prompt + decode)
