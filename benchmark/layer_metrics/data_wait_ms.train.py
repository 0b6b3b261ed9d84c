"""Mean of the program's ``data_wait`` host span (kstep.py)."""

from benchmark.harness import readers


def read(obs):
    return readers.span_mean_ms(obs, "data_wait")
