"""Recompile watchdog: compile logging, cache accounting, trip-wire.

The round-5 verdict's unverifiable failure was a *suspected* XLA
compile-cache miss (a 441 s headline leg ≈ warm estimate + cold
compile) that nothing could confirm — compiles were invisible. This
module makes them visible two ways:

1. ``watch(fn)`` wraps a jitted callable. Every call samples the
   executable cache size (``fn._cache_size()``) before/after: a delta
   is a compile — logged with the call's arg shapes and elapsed time,
   counted as a miss (vs a hit). A configurable **trip-wire** fires on
   recompile storms: N compiles of the SAME function within a window,
   the shape-churn bug class (a new batch shape every step silently
   recompiling forever).

2. ``install_global_watch()`` hooks ``jax.monitoring`` so every
   backend compile in the process — watched or not — is counted, with
   persistent-compilation-cache hits/misses split out. The
   benchmark's ``setup_compile_s`` and ``chip_smoke.py``'s per-phase
   ``cache_hit`` read it.

Both report through the unified metrics registry and (optionally)
drop ``xla_compile`` instants on the tracer so compiles show up in
the Perfetto timeline.
"""

from __future__ import annotations

import collections
import contextlib
import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional, Tuple

logger = logging.getLogger("deeplearning4j_tpu")

__all__ = ["RecompileStormError", "SteadyStateCompileError",
           "CompileEvent", "CompileWatcher", "watch",
           "install_global_watch", "GlobalCompileStats"]


class RecompileStormError(RuntimeError):
    """Raised when a watched function recompiles ``storm_threshold``
    times inside ``storm_window_s`` seconds — almost always shape
    churn: un-bucketed batch sizes, python scalars promoted to fresh
    weak types, or a config rebuilt per step."""

    def __init__(self, msg: str, events: List["CompileEvent"]):
        super().__init__(msg)
        self.events = events


class SteadyStateCompileError(RuntimeError):
    """Raised by :meth:`GlobalCompileStats.zero_compile_scope` when a
    scope that promised zero compiles (the post-AOT-warmup steady
    state) compiled anyway — a shape escaped the warmup set, or a
    program was invalidated after warming (listener/health toggle,
    optimizer rebuild)."""

    def __init__(self, msg: str, stats: dict):
        super().__init__(msg)
        self.stats = stats


def _describe(x) -> str:
    shape = getattr(x, "shape", None)
    if shape is None:
        return type(x).__name__
    dtype = getattr(x, "dtype", "?")
    return f"{dtype}{list(shape)}"


def arg_signature(args: tuple, kwargs: dict) -> str:
    """Human-readable shapes/dtypes of a call's arguments (pytrees
    flattened), the thing you need to SEE to spot shape churn."""
    try:
        import jax
        leaves = jax.tree_util.tree_leaves((args, kwargs))
    except Exception:
        leaves = list(args) + list(kwargs.values())
    parts = [_describe(l) for l in leaves[:16]]
    if len(leaves) > 16:
        parts.append(f"...+{len(leaves) - 16}")
    return "(" + ", ".join(parts) + ")"


@dataclass
class CompileEvent:
    name: str
    signature: str
    elapsed_s: float
    t: float = field(default_factory=time.monotonic)


class _WatchedFunction:
    """Callable proxy sampling the jit executable-cache size around
    each call."""

    def __init__(self, fn, name: str, watcher: "CompileWatcher"):
        if not hasattr(fn, "_cache_size"):
            raise TypeError(
                "watch() needs a jitted callable (jax.jit result with "
                f"_cache_size); got {type(fn).__name__}. Wrap the "
                "function with jax.jit first.")
        self.__wrapped__ = fn
        self._name = name
        self._watcher = watcher
        self._storm: Deque[CompileEvent] = collections.deque(maxlen=256)
        self._lock = threading.Lock()
        self.compiles = 0
        self.hits = 0

    def __call__(self, *args, **kwargs):
        fn = self.__wrapped__
        before = fn._cache_size()
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        elapsed = time.perf_counter() - t0
        if fn._cache_size() > before:
            self._on_compile(args, kwargs, elapsed)
        else:
            with self._lock:
                self.hits += 1
            self._watcher._count_hit()
        return out

    def _on_compile(self, args, kwargs, elapsed):
        ev = CompileEvent(self._name, arg_signature(args, kwargs),
                          elapsed)
        with self._lock:
            self.compiles += 1
            self._storm.append(ev)
            w = self._watcher
            recent = [e for e in self._storm
                      if e.t >= ev.t - w.storm_window_s]
        w._count_compile(ev)
        if len(recent) >= w.storm_threshold:
            msg = (f"recompile storm: {self._name!r} compiled "
                   f"{len(recent)} times in the last "
                   f"{w.storm_window_s:.0f}s — shape churn? recent "
                   "signatures:\n  " +
                   "\n  ".join(f"{e.signature} ({e.elapsed_s:.3f}s)"
                               for e in recent[-8:]))
            if w.on_storm == "raise":
                raise RecompileStormError(msg, recent)
            logger.warning(msg)

    def cache_stats(self) -> dict:
        with self._lock:
            return {"name": self._name, "compiles": self.compiles,
                    "cache_hits": self.hits}

    def __getattr__(self, item):
        # lower/trace/clear_cache etc. pass through to the jit object
        return getattr(self.__wrapped__, item)


class CompileWatcher:
    """Factory for watched callables sharing one storm policy +
    registry wiring. The module-level ``watch()`` uses a default
    instance (warn-only, so production training never dies to its own
    telemetry); tests construct a raising one."""

    def __init__(self, registry=None, tracer=None,
                 storm_threshold: int = 8, storm_window_s: float = 30.0,
                 on_storm: str = "warn", log_compiles: bool = True):
        if on_storm not in ("raise", "warn"):
            raise ValueError("on_storm must be 'raise' or 'warn'")
        if registry is None:
            from deeplearning4j_tpu.observability.registry import REGISTRY
            registry = REGISTRY
        self.registry = registry
        self.tracer = tracer
        self.storm_threshold = storm_threshold
        self.storm_window_s = storm_window_s
        self.on_storm = on_storm
        self.log_compiles = log_compiles
        # bounded: under a warn-mode storm (compile-per-step churn)
        # an unbounded log would itself become the leak
        self.log: Deque[CompileEvent] = collections.deque(maxlen=4096)
        self._lock = threading.Lock()
        self._compiles = registry.counter(
            "xla_watched_compiles_total",
            help="compiles observed by compile_watch.watch()")
        self._hits = registry.counter(
            "xla_watched_cache_hits_total",
            help="watched calls served from the jit executable cache")

    def watch(self, fn, name: Optional[str] = None) -> _WatchedFunction:
        if name is None:
            name = getattr(fn, "__name__", None) or repr(fn)
        return _WatchedFunction(fn, name, self)

    def _count_compile(self, ev: CompileEvent) -> None:
        self._compiles.inc()
        with self._lock:
            self.log.append(ev)
        if self.log_compiles:
            logger.info("XLA compile: %s args=%s (%.3fs)", ev.name,
                        ev.signature, ev.elapsed_s)
        if self.tracer is not None:
            self.tracer.instant("xla_compile",
                                {"fn": ev.name,
                                 "signature": ev.signature,
                                 "elapsed_s": round(ev.elapsed_s, 4)})

    def _count_hit(self) -> None:
        self._hits.inc()


_DEFAULT_WATCHER: Optional[CompileWatcher] = None
_DEFAULT_LOCK = threading.Lock()


def _default_watcher() -> CompileWatcher:
    global _DEFAULT_WATCHER
    with _DEFAULT_LOCK:
        if _DEFAULT_WATCHER is None:
            from deeplearning4j_tpu.observability.tracing import trace
            _DEFAULT_WATCHER = CompileWatcher(tracer=trace)
        return _DEFAULT_WATCHER


def watch(fn, name: Optional[str] = None) -> _WatchedFunction:
    """Wrap a jitted callable with the default (warn-on-storm)
    watcher: per-call hit/miss accounting, compile logging with arg
    shapes, storm warnings."""
    return _default_watcher().watch(fn, name)


# ---------------------------------------------------------------------------
# process-wide compile accounting via jax.monitoring
# ---------------------------------------------------------------------------

class GlobalCompileStats:
    """Totals fed by jax.monitoring events:

    - ``backend_compiles`` / ``compile_secs``: actual XLA backend
      compiles (a persistent-cache hit does NOT fire this).
    - ``cache_requests``: compile requests eligible for the
      persistent compilation cache.
    - ``persistent_cache_hits``: requests served from it.

    ``cache_hit`` answers: did this process reuse compiled
    artifacts instead of cold-compiling?
    """

    def __init__(self, registry=None, tracer=None):
        if registry is None:
            from deeplearning4j_tpu.observability.registry import REGISTRY
            registry = REGISTRY
        self._lock = threading.Lock()
        self.backend_compiles = 0
        self.compile_secs = 0.0
        self.cache_requests = 0
        self.persistent_cache_hits = 0
        self.tracer = tracer
        self._c_compiles = registry.counter(
            "xla_backend_compiles_total",
            help="XLA backend compiles in this process")
        self._c_secs = registry.counter(
            "xla_backend_compile_seconds_total",
            help="wall seconds spent in XLA backend compiles")
        self._c_hits = registry.counter(
            "xla_persistent_cache_hits_total",
            help="compiles served from the persistent XLA cache")

    def mark(self) -> dict:
        """Snapshot for delta accounting (``summary(since=mark)``)."""
        with self._lock:
            return {"backend_compiles": self.backend_compiles,
                    "compile_secs": self.compile_secs,
                    "cache_requests": self.cache_requests,
                    "persistent_cache_hits": self.persistent_cache_hits}

    def summary(self, since: Optional[dict] = None) -> dict:
        cur = self.mark()
        if since:
            cur = {k: (round(cur[k] - since[k], 3)
                       if isinstance(cur[k], float)
                       else cur[k] - since[k]) for k in cur}
        else:
            cur["compile_secs"] = round(cur["compile_secs"], 3)
        cur["cache_hit"] = self._cache_hit(cur)
        return cur

    @staticmethod
    def _cache_hit(s: dict) -> Optional[bool]:
        """True = every compile request was served from cache (zero
        cold backend compiles); None when nothing compiled at all (no
        evidence either way)."""
        if s["backend_compiles"] == 0 and s["cache_requests"] == 0:
            return None
        return s["backend_compiles"] == 0

    @property
    def cache_hit(self) -> Optional[bool]:
        return self._cache_hit(self.mark())

    @contextlib.contextmanager
    def zero_compile_scope(self, what: str = "steady state"):
        """Assert that NOTHING in the scope triggers an XLA backend
        compile — the post-AOT-warmup contract: after
        ``model.warmup()`` / ``ModelServer.warmup()`` pre-built every
        expected program, the fit loop or a serving request burst
        must run entirely on compiled executables. Raises
        :class:`SteadyStateCompileError` with the compile deltas
        otherwise."""
        mark = self.mark()
        yield self
        s = self.summary(mark)
        if s["backend_compiles"]:
            raise SteadyStateCompileError(
                f"{what}: {s['backend_compiles']} XLA backend "
                f"compile(s) ({s['compile_secs']:.2f}s) inside a "
                "scope that promised zero after AOT warmup — a shape "
                "escaped the warmup set or a warmed program was "
                "invalidated", s)

    # ---- listeners ----
    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.persistent_cache_hits += 1
            self._c_hits.inc()
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            with self._lock:
                self.cache_requests += 1

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.backend_compiles += 1
                self.compile_secs += duration
            self._c_compiles.inc()
            self._c_secs.inc(duration)
            if self.tracer is not None:
                self.tracer.instant(
                    "xla_backend_compile",
                    {"elapsed_s": round(duration, 4)})


_GLOBAL_STATS: Optional[GlobalCompileStats] = None


def install_global_watch(registry=None) -> GlobalCompileStats:
    """Idempotently hook jax.monitoring and return the process-wide
    compile stats. jax's listener list has no per-listener removal, so
    this installs exactly once per process."""
    global _GLOBAL_STATS
    with _DEFAULT_LOCK:
        if _GLOBAL_STATS is None:
            from deeplearning4j_tpu.observability.tracing import trace
            stats = GlobalCompileStats(registry=registry, tracer=trace)
            import jax.monitoring as monitoring
            monitoring.register_event_listener(stats._on_event)
            monitoring.register_event_duration_secs_listener(
                stats._on_duration)
            _GLOBAL_STATS = stats
        return _GLOBAL_STATS
