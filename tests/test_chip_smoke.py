"""``chip_smoke.py`` away from the chip, and the compile-cache helper
it shares with the benchmark's session and the CLI.

The smoke script proves the main path on a TPU; here on the CPU it has
to FAIL — quickly, with no result line — and the one helper that
places the persistent compilation cache has to obey
``JAX_COMPILATION_CACHE_DIR`` and otherwise pick one fixed path inside
the checkout, whatever the working directory.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **extra)
    return env


# ---- chip_smoke.py without a chip ----------------------------------------

@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one_chip", "four_chips"])
def test_chip_smoke_fails_on_the_cpu(tmp_path, argv):
    r = subprocess.run([sys.executable, SMOKE, *argv],
                       capture_output=True, text=True, timeout=120,
                       env=_env(), cwd=str(tmp_path))
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    # no phase passed, so no line at all — least of all a result
    assert r.stdout.strip() == ""
    assert '"ok": true' not in r.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """The script with nothing else of the repo beside it must not
    report success (the driver runs it so)."""
    alone = tmp_path / "chip_smoke.py"
    alone.write_bytes(open(SMOKE, "rb").read())
    env = _env()
    env.pop("PYTHONPATH")
    r = subprocess.run([sys.executable, str(alone)],
                       capture_output=True, text=True, timeout=120,
                       env=env, cwd=str(tmp_path))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


# ---- the compile-cache helper --------------------------------------------

@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them: the
    helper's decisions are what is under test, and the test process
    must keep compiling as every other test expects."""
    import jax
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    return calls


def test_cache_dir_from_the_environment_wins(tmp_path, monkeypatch,
                                             config_updates):
    from deeplearning4j_tpu.util.platform import setup_compile_cache
    placed = tmp_path / "placed" / "from" / "outside"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(placed))
    used = setup_compile_cache(str(tmp_path / "asked_by_the_caller"))
    assert used == str(placed) and placed.is_dir()
    # jax reads the variable itself: the code sets NO directory
    assert "jax_compilation_cache_dir" not in config_updates
    assert not (tmp_path / "asked_by_the_caller").exists()
    # ... but the thresholds are the helper's to set, always
    assert config_updates == {
        "jax_persistent_cache_min_entry_size_bytes": 0,
        "jax_persistent_cache_min_compile_time_secs": 0.0}


def test_caller_directory_when_the_variable_is_unset(
        tmp_path, monkeypatch, config_updates):
    from deeplearning4j_tpu.util.platform import setup_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    asked = tmp_path / "xla_cache_flag"
    assert setup_compile_cache(str(asked)) == str(asked)
    assert asked.is_dir()
    assert config_updates["jax_compilation_cache_dir"] == str(asked)


def test_default_is_one_fixed_path_in_the_checkout(
        tmp_path, monkeypatch, config_updates):
    from deeplearning4j_tpu.util import platform
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    # the real default lives in the checkout; point it into tmp so
    # the test leaves no directory behind
    assert platform.REPO_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    fixed = tmp_path / "checkout" / ".jax_cache"
    monkeypatch.setattr(platform, "REPO_CACHE_DIR", str(fixed))
    seen = set()
    for cwd in (tmp_path, tmp_path / "checkout"):
        monkeypatch.chdir(cwd)
        seen.add(platform.setup_compile_cache())
        seen.add(config_updates["jax_compilation_cache_dir"])
    assert seen == {str(fixed)}       # never cwd, pid or time


_COMPILE_ONCE = """
import json, sys
from deeplearning4j_tpu.observability.compile_watch import (
    install_global_watch)
from deeplearning4j_tpu.util.platform import setup_compile_cache
used = setup_compile_cache(sys.argv[1] or None)
stats = install_global_watch()
import jax, jax.numpy as jnp
jax.jit(lambda x: jnp.tanh(x) * 3 + 1)(jnp.ones((7, 5))).block_until_ready()
s = stats.summary()
print(json.dumps({"dir": used, "hits": s["persistent_cache_hits"],
                  "requests": s["cache_requests"],
                  "events": s["backend_compiles"],
                  "cold": s["cold_compiles"], "cache_hit": s["cache_hit"],
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def _compile_once(cwd, directory="", **env):
    r = subprocess.run([sys.executable, "-c", _COMPILE_ONCE, directory],
                       capture_output=True, text=True, timeout=120,
                       env=_env(**env), cwd=str(cwd))
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_second_run_from_another_directory_hits_the_cache(tmp_path):
    """End to end, in real processes: the cache placed from outside
    is used although the caller names another directory (the CLI's
    --xla-cache), and a second run from a different working directory
    is served from it."""
    placed = tmp_path / "placed"
    a, b = tmp_path / "a", tmp_path / "b"
    a.mkdir()
    b.mkdir()
    first = _compile_once(a, str(tmp_path / "other"),
                          JAX_COMPILATION_CACHE_DIR=str(placed))
    second = _compile_once(b, str(tmp_path / "other"),
                           JAX_COMPILATION_CACHE_DIR=str(placed))
    assert first["dir"] == second["dir"] == str(placed)
    assert first["config"] == str(placed)
    assert not (tmp_path / "other").exists()
    assert first["requests"] >= 1 and first["hits"] == 0
    assert second["hits"] >= 1
    # ``backend_compiles`` counts the loads too (jax fires the event
    # around a hit as well): ``cold_compiles`` and ``cache_hit`` are
    # what tell a warm start from a cold one
    assert first["events"] == first["cold"] == first["requests"]
    assert first["cache_hit"] is False
    assert second["events"] == first["events"] and second["cold"] == 0
    assert second["cache_hit"] is True
    assert any(placed.iterdir())
