"""Device time of the flash attention kernels over device busy time."""

from benchmark.harness import readers


def read(obs):
    return readers.kernel_time_pct(obs, "pallas_flash_attention")
