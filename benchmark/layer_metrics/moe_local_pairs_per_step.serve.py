"""(token, held expert) pairs this chip's expert layers computed, a
batcher step: ``serving_moe_local_pairs_total`` over the steps of the
window. Nothing to read where the program has no such counter."""

from benchmark.harness import readers


def read(obs):
    pairs = readers.counter_delta(obs, "serving_moe_local_pairs_total")
    steps = readers.counter_delta(
        obs, r'serving_step_seconds\{.*part="device"', "count")
    return pairs / steps if pairs is not None and steps else None
