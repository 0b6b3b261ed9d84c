"""1 - union of device-op intervals over the traced window."""

from benchmark.harness import readers


def read(obs):
    return readers.idle_pct(obs)
