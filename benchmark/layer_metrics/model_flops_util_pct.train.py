"""Model FLOP/s utilization: required FLOPs per sample x throughput
over the chip's bf16 peak."""

from benchmark.harness import readers


def read(obs):
    return readers.model_flops_util_pct(obs)
