"""Token ids (B, T) as float32 (what the program's embedding layer
takes) and dense one-hot next-token labels (B, T, V) float32."""

import numpy as np

from benchmark.harness.inputs import one_hot


def batch(rng, config, spec, b):
    v, t = config["vocab_size"], spec["seq_len"]
    ids = rng.integers(0, v, (b, t + 1))
    return ids[:, :-1].astype(np.float32), one_hot(ids[:, 1:], v)
