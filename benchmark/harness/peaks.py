"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports
it. A device that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s interconnect.
"""

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "memory_bytes": 16e9, "ici_bits_per_s": 1600e9},
    "TPU v5e": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                "memory_bytes": 16e9, "ici_bits_per_s": 1600e9},
}


def peaks_for(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/harness/"
            f"peaks.py (known: {sorted(PEAKS)}); add it with its source")
    return PEAKS[device_kind]
