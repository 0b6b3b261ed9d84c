"""Mean of ``serving_step_seconds{part="device"}`` over the window: one
batcher step from ``step_slots`` to the logits' arrival on the host
(enqueue, the device's step, the copy back)."""

from benchmark.harness import readers


def read(obs):
    return readers.histogram_mean_ms(
        obs, r'serving_step_seconds\{.*part="device"')
