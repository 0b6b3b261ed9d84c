"""Mean of ``serving_phase_seconds{phase="queue_wait"}`` over the window
(sum and count are exact; the bucketed quantiles are not used)."""

from benchmark.harness import readers


def read(obs):
    return readers.histogram_mean_ms(
        obs, r'serving_phase_seconds\{.*phase="queue_wait"')
