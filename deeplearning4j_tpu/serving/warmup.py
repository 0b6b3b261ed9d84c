"""Serving-side AOT warmup: zero post-startup compiles.

The TF-Serving pattern (arXiv:1605.08695): a replica that compiles on
its first real request serves that request seconds late — and a
pow2-bucketed scheduler compiles once per BUCKET, so the tail of slow
first requests stretches across the whole warm-up period of a fresh
replica. ``serve --aot-warmup`` runs :func:`warmup_server` at boot:
every hosted model's serving executables are pre-built by driving
representative zero inputs through the REAL serving entry points —

- **predict**: ``model.output`` over every power-of-two batch bucket
  up to the scheduler's ``max_batch_size`` (the exact shapes
  ``pow2_pad_rows`` produces), per-item shape derived from the
  model's configured ``InputType``;
- **generate**: one short dummy request through the continuous
  batcher (prefill + fused decode-step programs for the default
  ``n_tokens``), for models that support streaming.

After warmup a steady-state request burst compiles ZERO times —
``observability.compile_watch.zero_compile_scope`` proves it
(``tests/test_kstep.py``).

Predict warmup drives ``model.output`` directly (the scheduler's own
device call, bypassing its queue), so it leaves NO trace in serving
metrics; the generate pass goes through the continuous batcher's real
request path and does count — dashboards may see one boot-time
generate per streaming model.
"""

from __future__ import annotations

import logging
import time
from typing import Dict, Optional, Tuple

import numpy as np

logger = logging.getLogger("deeplearning4j_tpu")

__all__ = ["warmup_server"]


def _pow2_sizes(max_batch_size: int):
    """The batch buckets the scheduler's padding can produce, derived
    from ``pow2_pad_rows`` ITSELF (not a re-derivation of its rule —
    if the bucketing policy ever changes, warmup follows it instead
    of silently warming the wrong set)."""
    from deeplearning4j_tpu.parallel.inference import pow2_pad_rows
    return sorted({pow2_pad_rows(np.zeros((n, 1), np.float32)).shape[0]
                   for n in range(1, max_batch_size + 1)})


def _per_item_shape(model) -> Optional[Tuple[int, ...]]:
    """The per-item feature shape a /v1/predict request carries,
    derived from the model's configured InputType; None when the
    config doesn't pin it (multi-input graphs, unknown-length
    sequences) — those models skip predict warmup with a log line."""
    conf = getattr(model, "conf", None)
    t = getattr(conf, "input_type", None)
    if t is None:
        types = getattr(conf, "input_types", None)
        if types and len(types) == 1:
            t = types[0]
    if t is None:
        return None
    try:
        shape = tuple(t.array_shape(1))[1:]
    except Exception:
        return None
    if any(d is None or d < 0 for d in shape):
        return None
    return shape


def warmup_server(server, *, generate: bool = True,
                  prompt_tokens: int = 8,
                  n_tokens: int = 16) -> Dict[str, dict]:
    """Pre-compile every hosted model's serving executables (see
    module docstring). ``server`` is a
    :class:`~deeplearning4j_tpu.serving.http.ModelServer`; call
    before (or right after) ``start()``. Returns per-model
    ``{"version", "predict_buckets", "generate", "seconds",
    "skipped"}``."""
    report: Dict[str, dict] = {}
    for entry in server.registry.models():
        name = entry["name"]
        # resolve THROUGH the server: a mesh-sharded server serves
        # the tensor-parallel proxy, so warmup compiles the sharded
        # per-bucket executables the real traffic will hit
        model, version = server.resolve_serving_model(name)
        r = {"version": version, "predict_buckets": [],
             "generate": False, "seconds": 0.0, "skipped": []}
        t0 = time.perf_counter()
        shape = _per_item_shape(model)
        if shape is None:
            r["skipped"].append(
                "predict: per-item input shape not derivable from "
                "the model's InputType config")
            logger.info("aot warmup: skipping predict warmup for "
                        "%s (no concrete input shape)", name)
        else:
            server.scheduler_for(name)    # build the backend up front
            try:
                for b in _pow2_sizes(server.max_batch_size):
                    x = np.zeros((b,) + shape, np.float32)
                    # the scheduler's device call is model.output on
                    # the pow2-padded batch — drive it directly and
                    # block so the compile lands before traffic does
                    np.asarray(model.output(x))
                    r["predict_buckets"].append(b)
            except Exception as e:
                # e.g. integer-input (embedding/token-id) models
                # reject float zeros — a warmup miss must not stop
                # the server from booting
                r["skipped"].append(f"predict: {e}")
                logger.info("aot warmup: predict warmup skipped for "
                            "%s: %s", name, e)
        if generate and hasattr(model, "slot_streaming_session"):
            try:
                batcher, _ = server.batcher_for(name)
                n = max(1, min(prompt_tokens,
                               server.capacity - n_tokens - 1))
                toks = max(1, min(n_tokens, server.capacity - n - 1))
                batcher.generate(np.zeros(n, dtype=np.int64), toks)
                r["generate"] = True
            except Exception as e:
                # token-id streaming is model-shape-specific; a model
                # whose generate path can't take the dummy prompt
                # skips with the reason on record
                r["skipped"].append(f"generate: {e}")
                logger.info("aot warmup: generate warmup skipped for "
                            "%s: %s", name, e)
        r["seconds"] = round(time.perf_counter() - t0, 3)
        report[name] = r
    return report
