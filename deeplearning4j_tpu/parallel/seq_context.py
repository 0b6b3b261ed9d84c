"""Trace-time mesh context: which seq axis a step shards time over,
and which mesh a GSPMD-partitioned step runs on.

When ``ParallelWrapper`` trains over a mesh with a ``seq`` axis it
shards the time dimension of every (B, T, ...) activation across
devices and traces the model's loss INSIDE a ``shard_map``. Layers
whose math spans timesteps (attention) must then compute over the
distributed sequence rather than their local chunk. This module is the
signal: the wrapper activates the context around tracing, and
``SelfAttentionLayer.apply`` consults it to route through the ring
flash attention path (``parallel/ring_attention.py``) instead of the
single-device kernel.

This is the seam that makes sequence parallelism reachable from the
framework surface — the config-built network stays unchanged; only the
wrapper's mesh decides the execution strategy (reference bar: the
wrapper runs any Model, deeplearning4j-scaleout-parallelwrapper/
ParallelWrapper.java:58).

The same seam carries the MESH of a plain-jit (GSPMD) step
(``gspmd_mesh``; entered by both executors' traced bodies under
``fit(mesh_spec=)``, by the wrapper around its plain data step and by
the tp serving backend): the Pallas attention kernels cannot be
partitioned automatically, so ``ops.attention.flash_attention`` wraps
itself in a shard_map on the announced mesh.

A thread-local suffices because the context only needs to be live
while JAX traces the step (tracing is single-threaded per step build);
the traced computation itself carries no Python state.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

__all__ = ["sequence_parallel", "sequence_parallel_gspmd",
           "gspmd_mesh", "current_seq_axis", "current_mesh",
           "current_loss_axes"]

_tls = threading.local()


def current_seq_axis() -> Optional[str]:
    """Mesh axis name the sequence dim is sharded over, or None."""
    return getattr(_tls, "axis", None)


def current_mesh():
    """The mesh of a GSPMD-partitioned trace, or None.

    A step is traced in one of two modes:

    - **manual** (``sequence_parallel``): the WRAPPER traces the whole
      step inside one shard_map; layer code sees local chunks and the
      attention layer calls ``ring_self_attention`` directly (it is
      already inside the manual region). ``current_mesh()`` is None.
    - **GSPMD** (``gspmd_mesh``, ``sequence_parallel_gspmd``): the
      step is a plain jit over global logical arrays and GSPMD
      partitions every axis. What GSPMD cannot partition opens its
      own fully manual shard_map island on this mesh: the Pallas
      kernels (``ops/attention.flash_attention`` — a Mosaic call has
      no partitioning rule), and, when a seq axis is active, the
      ring's collectives (``SelfAttentionLayer``). Everything outside
      the islands stays automatic, which is what lets
      Megatron-sharded projections compose with them.
    """
    return getattr(_tls, "mesh", None)


def current_loss_axes():
    """Mesh axes the BATCH is sharded over (e.g. ('data', 'seq')), or
    None outside a sequence-parallel trace. Masked time-distributed
    losses consult this: the masked mean's denominator is a GLOBAL
    count (shards hold different numbers of unmasked steps), so the
    loss layer psums the count over these axes and scales so that the
    wrapper's mean-of-local-losses equals the global masked mean.
    (GSPMD mode leaves this None on purpose: the loss computes on
    global logical arrays and XLA already yields the global mean.)"""
    return getattr(_tls, "loss_axes", None)


@contextlib.contextmanager
def _scope(axis, loss_axes, mesh):
    prev = (getattr(_tls, "axis", None),
            getattr(_tls, "loss_axes", None),
            getattr(_tls, "mesh", None))
    _tls.axis, _tls.loss_axes, _tls.mesh = axis, loss_axes, mesh
    try:
        yield
    finally:
        _tls.axis, _tls.loss_axes, _tls.mesh = prev


def sequence_parallel(axis_name: str, loss_axes=None):
    """Activate MANUAL sequence-parallel routing while tracing a step
    (inside the wrapper's shard_map)."""
    return _scope(axis_name, loss_axes, None)


def sequence_parallel_gspmd(mesh, axis_name: str = "seq"):
    """Activate GSPMD-mode sequence-parallel routing: the attention
    layers open shard_map islands on ``mesh`` that ride the ring over
    ``axis_name``; everything else partitions automatically (composes
    with dp/tp)."""
    return _scope(axis_name, None, mesh)


def gspmd_mesh(mesh):
    """Announce the mesh a plain-jit (GSPMD) step is partitioned
    over, with no seq axis active: single-device kernel calls wrap
    themselves in a shard_map on it (see :func:`current_mesh`)."""
    return _scope(None, None, mesh)
