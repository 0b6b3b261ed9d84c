"""Ring pages the window's steps began to reuse, a batcher step
(``serving_kv_ring_wraps_total`` over the steps of the window): 0
means the traffic never outgrew a ring and the mechanism slept.
Nothing to read where the program has no such counter."""

from benchmark.harness import readers


def read(obs):
    wraps = readers.counter_delta(obs, r"serving_kv_ring_wraps_total\{")
    steps = readers.counter_delta(
        obs, r'serving_step_seconds\{.*part="device"', "count")
    return wraps / steps if wraps is not None and steps else None
