"""Device time of the by-table paged attention kernel over device busy
time; nothing to read where no such kernel ran."""

from benchmark.harness import readers


def read(obs):
    return readers.kernel_time_pct(obs, "pallas_paged_attention")
