"""Tensor-parallel predict backend: one model sharded over a mesh.

The serving-side half of the mesh-spec work (``parallel/mesh_spec.py``):
a :class:`TensorParallelModel` wraps a hosted model for serving with
its params sharded over the mesh's ``model`` axis (the Megatron rule
table from ``parallel/tensor_parallel.py``; a ``dp`` axis additionally
splits the request batch), exposing the same ``output()`` surface the
``BatchScheduler`` drives — so the whole existing serving stack
(dynamic batching, admission control, the fleet router) runs
tensor-parallel without knowing it.

Executables are AOT-compiled PER POW2 BUCKET (the exact shapes
``pow2_pad_rows`` produces — requests are padded up and sliced back,
so the executable cache is bounded by the bucket set, never by
request-shape churn; GL002) with output shardings pinned to
replicated, so a result fetch is one local copy and the warmed steady
state compiles zero times (``serve --aot-warmup`` +
``zero_compile_scope`` prove it, same contract as the train path).
"""

from __future__ import annotations

import logging
import threading
from typing import Dict, Optional, Tuple

import numpy as np

logger = logging.getLogger("deeplearning4j_tpu")

__all__ = ["TensorParallelModel"]


class TensorParallelModel:
    """Serving proxy: ``model`` with params sharded over ``mesh_spec``.

    Supports executors exposing the sequential ``_forward`` contract
    (MultiLayerNetwork); raises for models the rule table cannot
    place. The proxy owns the placement — construct it from the
    replica's own model instance (the serving factory contract: each
    replica owns its models outright)."""

    def __init__(self, model, mesh_spec, devices=None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from deeplearning4j_tpu.parallel.mesh_spec import (
            build_mesh_context)
        from deeplearning4j_tpu.parallel.seq_context import gspmd_mesh
        from deeplearning4j_tpu.serving.errors import ServingError

        if not hasattr(model, "_forward"):
            raise ServingError(
                "tensor-parallel serving supports sequential "
                f"executors (MultiLayerNetwork); got "
                f"{type(model).__name__}")
        self.model = model
        self.ctx = build_mesh_context(mesh_spec, model, devices)
        if self.ctx.plan.sp > 1:
            raise ServingError(
                "serving meshes take dp/tp axes only; sp belongs to "
                "training")
        if model.params is None:
            model.init()
        self.ctx.place_model(model)
        self._repl = NamedSharding(self.ctx.mesh, P())
        dp = self.ctx.plan.dp
        self._in_sharding_of = (
            lambda ndim: NamedSharding(
                self.ctx.mesh,
                P("data" if dp > 1 else None, *([None] * (ndim - 1)))))
        # compiled forward executables per (shape, dtype) bucket —
        # bounded because every entry key comes out of _bucket_key
        # (pow2-padded rows), never a raw request shape
        self._compiled: Dict[Tuple, object] = {}
        self._lock = threading.Lock()

        def fwd(params, state, x):
            with gspmd_mesh(self.ctx.mesh):
                y, _, _, _ = model._forward(params, state, x,
                                            training=False, rng=None)
            return y

        self._jit_fwd = jax.jit(fwd, out_shardings=self._repl)

    # ---- the scheduler-facing surface ----
    @property
    def conf(self):
        # the warmup path derives per-item shapes from model config
        return self.model.conf

    def mesh_desc(self) -> dict:
        return self.ctx.describe()

    def _bucket_key(self, x: np.ndarray) -> Tuple:
        # rows already pow2-padded by the caller path (scheduler /
        # output below) — the key is the bucketed shape + dtype
        return (tuple(x.shape), str(x.dtype))

    def _executable_for(self, xp) -> object:
        import jax
        key = self._bucket_key(xp)
        with self._lock:
            exe = self._compiled.get(key)
        if exe is not None:
            return exe
        abstract = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            (self.model.params, self.model.state, xp))
        exe = self._jit_fwd.lower(*abstract).compile()
        with self._lock:
            return self._compiled.setdefault(key, exe)

    def output(self, x, training: bool = False):
        """Sharded forward pass, same contract as ``model.output``:
        rows are pow2-padded (then sliced back) so every executable
        comes from the bounded bucket set; the padded batch is
        device_put from host with the batch dim over 'data' (when
        dp > 1) and the replicated result fetches with one local
        copy."""
        import jax
        from deeplearning4j_tpu.parallel.inference import pow2_pad_rows

        x = np.asarray(x, np.float32)
        n = x.shape[0]
        xp = pow2_pad_rows(x)
        dp = self.ctx.plan.dp
        if xp.shape[0] % dp:
            # pow2 buckets below dp (a 1-row request on dp=4): pad up
            # to the mesh's data degree so the split stays even
            pad = dp - (xp.shape[0] % dp)
            xp = np.concatenate([xp, np.zeros((pad,) + xp.shape[1:],
                                              xp.dtype)])
        xd = jax.device_put(xp, self._in_sharding_of(xp.ndim))
        y = self._executable_for(xd)(self.model.params,
                                     self.model.state, xd)
        return np.asarray(y)[:n]

    def warmup_bucket(self, batch_rows: int,
                      per_item_shape: Tuple[int, ...]) -> float:
        """AOT-compile the executable for one pow2 bucket without
        serving a request; returns compile seconds (0.0 when the
        bucket was already warm)."""
        import time
        import jax
        x = np.zeros((batch_rows,) + tuple(per_item_shape),
                     np.float32)
        dp = self.ctx.plan.dp
        if x.shape[0] % dp:
            x = np.concatenate([x, np.zeros(
                (dp - x.shape[0] % dp,) + x.shape[1:], x.dtype)])
        key = self._bucket_key(x)
        with self._lock:
            if key in self._compiled:
                return 0.0
        t0 = time.perf_counter()
        xd = jax.device_put(x, self._in_sharding_of(x.ndim))
        self._executable_for(xd)
        return time.perf_counter() - t0

    def shutdown(self, drain: bool = True,
                 timeout: float = 30.0) -> bool:
        """Backend-lifecycle no-op: the proxy owns no worker threads
        or queues — only compiled executables, which the allocator
        reclaims with the object (ModelServer's get-or-create calls
        this on the draining race path)."""
        return True

    # streaming generate stays on the unsharded model (the decode
    # fast path has its own KV-cache device story); expose the
    # capability honestly so batcher_for() routes around the proxy
    def __getattr__(self, name):
        # only NON-streaming attributes delegate: the proxy must not
        # advertise slot_streaming_session and then serve it
        # unsharded behind the operator's back
        if name in ("slot_streaming_session",
                    "paged_slot_streaming_session",
                    "streaming_session"):
            raise AttributeError(name)
        return getattr(self.model, name)
