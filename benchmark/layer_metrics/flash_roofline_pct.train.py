"""Roofline share of the flash attention kernels (says which bound)."""

from benchmark.harness import readers


def read(obs):
    return readers.flash_roofline_pct(obs, "pallas_flash_attention")
