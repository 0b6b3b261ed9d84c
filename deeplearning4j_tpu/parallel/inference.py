"""ParallelInference: high-throughput serving with dynamic batching.

Mirrors deeplearning4j-scaleout-parallelwrapper's ``ParallelInference``
(ParallelInference.java:32) and its observables
(BatchedInferenceObservable.java): concurrent callers submit inputs;
in BATCHED mode a collector thread coalesces up to ``max_batch_size``
requests into one device call (dynamic batching — the TPU loves big
batches); SEQUENTIAL mode serves each request directly. Shapes are
bucketed by padding the coalesced batch to the next power of two so
XLA sees few distinct shapes (no retrace storms).
"""

from __future__ import annotations

import itertools
import queue
import threading
import weakref
from typing import List, Optional

import numpy as np

from deeplearning4j_tpu.serving.errors import QueueFullError

__all__ = ["InferenceMode", "ParallelInference", "QueueFullError",
           "pow2_pad_rows", "serve_batch_with_retry"]

_INSTANCE_IDS = itertools.count()
_SHARED_METRICS = None
_SHARED_LOCK = threading.Lock()


def _shared_metrics():
    """Default ServingMetrics bound to the process-wide registry, so
    ParallelInference's shed counts and queue-depth gauges report
    through the same pipe as training and serving (lazy: importing
    this module must stay cheap)."""
    global _SHARED_METRICS
    with _SHARED_LOCK:
        if _SHARED_METRICS is None:
            from deeplearning4j_tpu.observability.registry import REGISTRY
            from deeplearning4j_tpu.serving.metrics import ServingMetrics
            _SHARED_METRICS = ServingMetrics(registry=REGISTRY)
        return _SHARED_METRICS


def pow2_pad_rows(x: np.ndarray) -> np.ndarray:
    """Pad axis 0 up to the next power of two (shape bucketing: a
    batch of 1..max rows compiles to ~log2(max) executables, not max).
    Shared by this collector and the serving scheduler built on it."""
    target = 1
    while target < x.shape[0]:
        target *= 2
    if target == x.shape[0]:
        return x
    pad = np.zeros((target - x.shape[0],) + x.shape[1:], x.dtype)
    return np.concatenate([x, pad], axis=0)


def serve_batch_with_retry(output_fn, batch, count_error=None,
                           before_complete=None) -> None:
    """Serve one coalesced batch of waitable requests (items with
    ``.x``/``.result``/``.error``/``.event``), with the poison-request
    recovery policy shared by this collector and the serving
    scheduler (one copy, so a fix to the policy cannot miss a
    backend): if the coalesced call fails, retry each item ALONE so a
    poison request fails only its own caller — but cap the cascade:
    two CONSECUTIVE per-item failures mean the device, not an input,
    is broken, and serially hammering it once per waiter would wedge
    the collector for the whole outage. Retries are pow2-padded: the raw row count may be a
    shape the bucketing never compiled, and a cold compile
    mid-recovery would wedge the collector.

    ``before_complete(r)`` (optional) runs right before each item's
    ``event.set()`` — the serving scheduler closes the request's
    device-step trace segment there, which must happen before the
    waiter thread can wake and stamp the respond segment."""
    def _done(r):
        if before_complete is not None:
            try:
                before_complete(r)
            except Exception:
                pass      # instrumentation must not fail delivery
        r.event.set()

    try:
        x = np.concatenate([r.x for r in batch], axis=0)
        out = np.asarray(output_fn(pow2_pad_rows(x)))
        off = 0
        for r in batch:
            n = r.x.shape[0]
            r.result = out[off:off + n]
            off += n
            _done(r)
    except BaseException as batch_err:
        consecutive = 0
        for r in batch:
            if consecutive >= 2:
                r.error = batch_err
                if count_error is not None:
                    count_error()
                _done(r)
                continue
            try:
                out = np.asarray(output_fn(pow2_pad_rows(r.x)))
                r.result = out[:r.x.shape[0]]
                consecutive = 0
            except BaseException as e:
                consecutive += 1
                r.error = e
                if count_error is not None:
                    count_error()
            _done(r)


class InferenceMode:
    SEQUENTIAL = "sequential"
    BATCHED = "batched"


class _Pending:
    def __init__(self, x):
        self.x = x
        self.event = threading.Event()
        self.result: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None


class ParallelInference:
    def __init__(self, model, mode: str = InferenceMode.BATCHED,
                 max_batch_size: int = 32, queue_limit: int = 64,
                 wait_ms: float = 2.0, metrics=None):
        self.model = model
        self.mode = mode
        self.max_batch_size = max_batch_size
        self.wait_ms = wait_ms
        self._queue: "queue.Queue[_Pending]" = queue.Queue(queue_limit)
        self._stop = threading.Event()
        self._worker = None
        # shed/request/error accounting through the unified registry
        # (metrics: a ServingMetrics; default = the process-wide one,
        # where counters aggregate safely across instances). The
        # per-instance queue-depth gauge holds only a WEAKREF to the
        # queue: instances dropped without shutdown() (ad-hoc
        # SEQUENTIAL-mode uses) stay GC-able, and a dead gauge
        # callback returns None, which exposition skips.
        self.metrics = metrics if metrics is not None \
            else _shared_metrics()
        self._endpoint = self.metrics.endpoint("parallel_inference")
        self._gauge_name = (
            f"parallel_inference_{next(_INSTANCE_IDS)}_queue_depth")
        qref = weakref.ref(self._queue)

        def _depth():
            q = qref()
            return None if q is None else q.qsize()

        self.metrics.register_gauge(self._gauge_name, _depth)
        if mode == InferenceMode.BATCHED:
            self._worker = threading.Thread(target=self._collector,
                                            daemon=True)
            self._worker.start()

    # ---- builder parity (ParallelInference.Builder) ----
    class Builder:
        def __init__(self, model):
            self._model = model
            self._mode = InferenceMode.BATCHED
            self._bs = 32
            self._ql = 64
            self._metrics = None

        def inference_mode(self, m):
            self._mode = m
            return self

        def batch_limit(self, n):
            self._bs = n
            return self

        def queue_limit(self, n):
            self._ql = n
            return self

        def metrics(self, m):
            self._metrics = m
            return self

        def build(self):
            return ParallelInference(self._model, self._mode, self._bs,
                                     self._ql, metrics=self._metrics)

    @staticmethod
    def builder(model):
        return ParallelInference.Builder(model)

    # ---- serving ----
    def output(self, x) -> np.ndarray:
        """Blocking inference call, safe from many threads.

        Backpressure is EXPLICIT: when ``queue_limit`` pending requests
        are already waiting, this raises :class:`QueueFullError`
        immediately instead of blocking the caller indefinitely — the
        reference's ObservablesProvider drops to the caller the same
        way, and the serving scheduler reuses this fail-fast path.
        """
        x = np.asarray(x)
        if self.mode == InferenceMode.SEQUENTIAL:
            t0 = _now()
            out = np.asarray(self.model.output(x))
            self._endpoint.observe(_now() - t0)
            return out
        if self._stop.is_set():
            raise RuntimeError("ParallelInference is shut down")
        t0 = _now()
        p = _Pending(x)
        try:
            self._queue.put_nowait(p)
        except queue.Full:
            self._endpoint.count_shed()
            raise QueueFullError(
                f"inference queue is at its limit "
                f"({self._queue.maxsize} pending requests); shed the "
                "request and retry with backoff") from None
        if self._stop.is_set() and not p.event.is_set():
            # raced with shutdown's drain: serve directly rather than
            # waiting on a collector that already exited
            try:
                p.result = np.asarray(self.model.output(x))
            except BaseException as e:
                p.error = e
            p.event.set()
        p.event.wait()
        if p.error is not None:
            raise p.error
        # successes must be observed, or the endpoint's requests
        # counter equals its errors and reads as a 100% error rate
        self._endpoint.observe(_now() - t0)
        return p.result

    def _collector(self):
        self._carry = None                    # dequeued but over-limit
        while not self._stop.is_set():
            if self._carry is not None:
                first, self._carry = self._carry, None
            else:
                try:
                    first = self._queue.get(timeout=0.1)
                except queue.Empty:
                    continue
            batch: List[_Pending] = [first]
            total = first.x.shape[0]
            deadline = self.wait_ms / 1000.0
            t_end = _now() + deadline
            while total < self.max_batch_size:
                remaining = t_end - _now()
                if remaining <= 0:
                    break
                try:
                    nxt = self._queue.get(timeout=remaining)
                except queue.Empty:
                    break
                if total + nxt.x.shape[0] > self.max_batch_size:
                    self._carry = nxt    # would exceed cap: next round
                    break
                batch.append(nxt)
                total += nxt.x.shape[0]
            self._serve(batch, total)

    def _serve(self, batch: List[_Pending], total: int):
        serve_batch_with_retry(self.model.output, batch,
                               count_error=self._endpoint.count_error)

    def shutdown(self):
        self._stop.set()
        self.metrics.unregister_gauge(self._gauge_name)
        if self._worker is not None:
            self._worker.join(timeout=1.0)
        # fail any requests still queued so their callers don't block
        # forever on event.wait()
        err = RuntimeError("ParallelInference shut down before request "
                           "was served")
        carry = getattr(self, "_carry", None)
        if carry is not None:
            carry.error = err
            carry.event.set()
            self._carry = None
        while True:
            try:
                p = self._queue.get_nowait()
            except queue.Empty:
                break
            p.error = err
            p.event.set()


def _now() -> float:
    import time
    return time.monotonic()
