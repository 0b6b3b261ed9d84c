import os

# Tests run on a virtual 8-device CPU mesh: sharding/collective tests
# exercise real multi-device code paths without TPU hardware.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def own_programs():
    """``observability.programs``' registry is the process's: a step
    program an earlier test of this worker registered and never built
    is built by the next ``scope_tables()``, and one registered for a
    step that raised while tracing raises again there. A test that
    reads the registry, or leaves such a program in it, starts from an
    empty one and leaves an empty one."""
    from deeplearning4j_tpu.observability import programs
    programs.PROGRAMS.clear()
    yield programs
    programs.PROGRAMS.clear()


# ---------------------------------------------------------------------------
# test tiering: smoke (`pytest -m "not slow"`) vs full. Heavy files are
# marked wholesale; a few heavyweight classes are marked in place.
# ---------------------------------------------------------------------------
_SLOW_FILES = {
    "test_examples.py",        # subprocess examples recompile everything
    "test_end_to_end.py",      # full train/checkpoint/resume cycles
    "test_gradientcheck.py",   # float64 central differences
    "test_zoo.py",             # builds all 13 archs + goldens
    "test_computation_graph_parity.py",   # tBPTT training to accuracy
    "test_keras_import.py",    # live keras forward goldens
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if os.path.basename(str(item.fspath)) in _SLOW_FILES:
            item.add_marker(pytest.mark.slow)
