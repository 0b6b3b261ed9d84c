"""Mean of the program's ``train_step`` host span (kstep.py)."""

from benchmark.harness import readers


def read(obs):
    return readers.span_mean_ms(obs, "train_step")
