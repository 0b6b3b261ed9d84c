"""The general generators: traffic parameters + seed -> inputs.

Training pools: ``inputs.kind`` names the file
``benchmark/inputs/<kind>.py`` whose ``batch(rng, config, spec, b)``
makes one (features, labels) pair of ``b`` distinct rows; a new kind
is a new file. Serving requests (``lengths``): a fixed set of
lognormal prompt and output lengths, clipped; the seed orders them
and draws the token ids.
"""

import numpy as np

from . import spec as spec_mod


def one_hot(targets, width):
    """Dense float32 one-hot by scatter into zeros -- never
    ``np.eye(width)``, which is 10 GB at a 50k vocabulary."""
    y = np.zeros(targets.shape + (width,), np.float32)
    np.put_along_axis(y, targets[..., None], 1.0, axis=-1)
    return y


def train_pool(traffic, config, seed):
    """``pool_batches`` distinct (features, labels) pairs."""
    rng = np.random.default_rng([seed, 1])
    kind = traffic["inputs"]["kind"]
    gen = spec_mod.load_module("inputs", kind)
    if gen is None:
        raise SystemExit(f"inputs.kind {kind!r} has no generator "
                         f"benchmark/inputs/{kind}.py")
    return [gen.batch(rng, config, traffic["inputs"], traffic["batch"])
            for _ in range(traffic["pool_batches"])]


def _lognormal_int(rng, n, median, sigma, lo, hi):
    x = np.exp(rng.normal(np.log(median), sigma, n))
    return np.clip(np.rint(x), lo, hi).astype(int)


def serve_lengths(traffic, seed, n_clients):
    """Per client, the (prompt length, n_tokens) pairs it cycles
    through. The SET of pairs is fixed by the mix (``lengths.set_seed``
    and ``lengths.set_size``): every seed serves the same sizes, in
    another order and on other clients, so that seeds do not change
    the work."""
    L = traffic["lengths"]
    fixed = np.random.default_rng([L["set_seed"], 7])
    n = L["set_size"]
    p = _lognormal_int(fixed, n, L["prompt_median"], L["prompt_sigma"],
                       L["prompt_min"], L["prompt_max"])
    o = _lognormal_int(fixed, n, L["output_median"], L["output_sigma"],
                       L["output_min"], L["output_max"])
    o = np.minimum(o, traffic["server"]["capacity"] - p)
    order = np.random.default_rng([seed, 2]).permutation(n)
    pairs = [(int(p[i]), int(o[i])) for i in order]
    return [pairs[c::n_clients] for c in range(n_clients)]


def serve_prompt(config, seed, client, k, length):
    """Token ids of client ``client``'s k-th request."""
    rng = np.random.default_rng([seed, 3, client, k])
    return rng.integers(0, config["vocab_size"], length).tolist()
