"""Mean of ``serving_phase_seconds{phase="prefill"}`` over the window:
server-side time to first token less queueing."""

from benchmark.harness import readers


def read(obs):
    return readers.histogram_mean_ms(
        obs, r'serving_phase_seconds\{.*phase="prefill"')
