"""CPU tests of the benchmark's harness, run by hand:

    python -m pytest benchmark/tests -q

They are not part of the repository's tier-1 suite. They hold JAX to
the CPU, keep its compile cache out of the checkout's TPU cache, and
give the cells a rehearsal hook that skips the look for a chip."""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".jax_cache", "cpu_tests"))
sys.path.insert(0, ROOT)

import pytest


def _merge(dst, src):
    for k, v in src.items():
        if isinstance(v, dict) and isinstance(dst.get(k), dict):
            _merge(dst[k], v)
        else:
            dst[k] = v


@pytest.fixture
def tiny(monkeypatch):
    """Make ``spec.load`` return each cell at its tiny preset
    (benchmark/tests/tiny/<cell>.json over the real files): the real
    sizes need the chip."""
    from benchmark.harness import spec
    real = spec.load

    def load(workload):
        cell = real(workload)
        with open(os.path.join(HERE, "tiny", workload + ".json")) as f:
            over = json.load(f)
        _merge(cell.config, over.get("config", {}))
        _merge(cell.traffic, over.get("traffic", {}))
        return cell

    monkeypatch.setattr(spec, "load", load)
    return load


def cpu_devices(chips):
    import jax
    return jax.devices()[:chips]
