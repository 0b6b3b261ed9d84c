"""float32 images (B, H, W, 3) ~ N(0, 1) and one-hot class labels
(B, classes)."""

import numpy as np

from benchmark.harness.inputs import one_hot


def batch(rng, config, spec, b):
    s, c = config["image_size"], config["num_classes"]
    x = rng.standard_normal((b, s, s, 3), np.float32)
    return x, one_hot(rng.integers(0, c, (b,)), c)
