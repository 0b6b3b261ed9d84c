"""Process set-up before first backend use: the persistent XLA
compilation cache, placed in ONE way by everything that wants one.

The cache key includes the cache directory's path, so a directory
that moves between runs never hits. Hence one rule, applied here and
nowhere else:

- where ``JAX_COMPILATION_CACHE_DIR`` is set, that is the directory —
  jax reads the variable itself, this module only makes the directory
  and sets NO cache directory in code (whoever runs the program can
  then place the cache, e.g. on a disk that outlives the machine);
- where it is not set, the caller's directory (the CLI's
  ``--xla-cache DIR``), or by default one fixed path inside the
  checkout, ``<repo>/.jax_cache`` — never a temporary name, a pid or
  a time.

``chip_smoke.py`` and the benchmark's session
(``benchmark/harness/session.py``) call :func:`setup_compile_cache`
before they touch a device; the CLI calls it only when
``--xla-cache`` is given (no persistent cache unless asked).
"""

from __future__ import annotations

import os
from typing import Optional

__all__ = ["setup_compile_cache", "REPO_CACHE_DIR"]

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def setup_compile_cache(directory: Optional[str] = None) -> str:
    """Switch the persistent compilation cache on and return the
    directory in use: ``$JAX_COMPILATION_CACHE_DIR`` when set (it
    wins over ``directory``), else ``directory``, else
    :data:`REPO_CACHE_DIR`. Must run before the first compile — the
    cache is consulted at compile time, AOT warmup included."""
    import jax
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    cache_dir = os.path.abspath(from_env or directory or REPO_CACHE_DIR)
    os.makedirs(cache_dir, exist_ok=True)
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache every executable: the small ones are most of a cold start
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
