"""Stdlib HTTP front end for the serving stack.

The same ``ThreadingHTTPServer`` idiom as ``ui/server.py`` (the
reference's Play-based servers become stdlib http.server + JSON), in
front of the registry + schedulers:

- ``POST /v1/predict``  {"model", "version"?, "inputs", "timeout_ms"?,
  "tier"?} → {"outputs", "model_version"}
- ``POST /v1/generate`` {"model", "version"?, "prompt", "n_tokens",
  "temperature"?, "seed"?, "timeout_ms"?, "tier"?} →
  {"ids", "model_version"}

``tier`` is the priority-admission tier (``gold`` / ``standard`` /
``best_effort``, default standard — see ``serving/tiers.py``): under
queue pressure the cheapest backlogged tier is shed first and 429/503
``Retry-After`` hints are priced by tier.
Retrieval (``serve --index``; see ``serving/retrieval_backend.py``):

- ``POST /v1/embed``    {"texts" | "text", "timeout_ms"?, "tier"?} →
  {"embeddings", "dim", "model_version"} — the embedder is a
  registered model ("embedder"), batched by the ordinary scheduler
- ``POST /v1/search``   {"query" (text) | "vector"/"vectors", "k"?,
  "nprobe"?, "filter_ids"?, "timeout_ms"?, "tier"?} → {"results":
  [[{"id", "score"}...]...], "generation"} — text queries embed
  first, then search; both hops share one deadline budget
- ``POST /v1/index/{upsert,delete,compact,stats}`` — admin verbs,
  single-writer serialized on the service's admin lock
- ``GET  /v1/models``   → registry listing
- ``GET  /healthz``     → {"status": "ok" | "degraded" | "draining"}
  — always 200 for humans; the STATUS field carries the judgement
- ``GET  /readyz`` (or ``/healthz?ready``) → the same payload, but
  503 when draining or degraded: the form a dumb load-balancer
  check (and the fleet router's prober) consumes — ready means
  "send me traffic", not "the process is up"
- ``GET  /metrics``     → ServingMetrics snapshot (JSON), or
  Prometheus text exposition when the client asks for it —
  ``?format=prometheus``, or an ``Accept`` header naming
  ``text/plain`` / ``openmetrics`` (what Prometheus scrapers send).
  The JSON default preserves the pre-observability contract.
- ``GET  /debug/startup`` → the set-up timeline: the ``setup/*`` and
  ``xla/*`` spans of ``observability.tracing.startup`` and the
  compile seconds by function (``compile_watch.by_function()``)

Error mapping is the typed-error contract from ``serving/errors.py``:
QueueFullError → 429, DeadlineExceededError → 504, ModelNotFoundError
→ 404, ServerClosedError (draining) → 503, bad request → 400.
``stop(drain=True)`` is the graceful path: /healthz flips to
"draining", new work is refused, queued + in-flight work completes,
then the listener stops.
"""

from __future__ import annotations

import base64
import binascii
import collections
import functools
import itertools
import json
import logging
import threading
import socket
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional, Tuple
from urllib.parse import parse_qs, urlparse

import numpy as np

from deeplearning4j_tpu.observability.compile_watch import (
    install_global_watch)
from deeplearning4j_tpu.observability.tracing import (RequestContext,
                                                      Sampler,
                                                      get_tracer,
                                                      startup)
from deeplearning4j_tpu.serving.continuous import (ContinuousBatcher,
                                                   MigrationOffer)
from deeplearning4j_tpu.serving.errors import (CircuitOpenError,
                                               DeadlineExceededError,
                                               KVLeaseCorruptError,
                                               KVLeaseError,
                                               ModelNotFoundError,
                                               QueueFullError,
                                               ServerClosedError,
                                               ServingError)
from deeplearning4j_tpu.serving.metrics import ServingMetrics
from deeplearning4j_tpu.serving.registry import ModelRegistry
from deeplearning4j_tpu.serving.scheduler import BatchScheduler

logger = logging.getLogger("deeplearning4j_tpu")

__all__ = ["ModelServer"]


def _retry_after_header(seconds: float) -> str:
    """RFC-compliant delta-seconds (integer, >= 1): routers and the
    in-repo loadgen parse it numerically; standard LBs expect an
    int."""
    return str(max(1, int(-(-float(seconds) // 1))))


class _JsonRequestHandler(BaseHTTPRequestHandler):
    """Shared base for the serving listeners (ModelServer and the
    fleet Router): quiet logging, the Nagle fix, and a JSON/bytes
    response helper — one copy, so a transport fix lands on both."""

    # headers and body go out as two small writes; with Nagle on,
    # the second stalls until the client ACKs the first, and the
    # client's delayed-ACK timer makes that ~40ms PER HOP — at a
    # router in front, ~80ms on every request. TCP_NODELAY removes
    # the stall outright.
    disable_nagle_algorithm = True

    # every read on the connection is bounded: a half-open peer (or
    # one partitioned away mid-request) must cost ONE handler thread
    # 30s, not wedge it forever. StreamRequestHandler.setup applies
    # this to the socket; header reads already honor it, body reads
    # go through _read_body below.
    timeout = 30.0

    def log_message(self, fmt, *args):
        pass

    def _send(self, code, obj, headers=None):
        data = obj if isinstance(obj, bytes) \
            else json.dumps(obj).encode()
        self.send_response(code)
        if not (headers or {}).get("Content-Type"):
            self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(data)

    def _send_text(self, code, text, content_type):
        data = text.encode()
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def _metrics_mode(self) -> str:
        # "json" | "text" (classic 0.0.4) | "openmetrics".
        # Exemplars are only legal in OpenMetrics, so a scraper
        # that wants them must say so (format=openmetrics or
        # the Accept header real Prometheus sends).
        q = parse_qs(urlparse(self.path).query)
        fmt = (q.get("format") or [None])[0]
        if fmt == "openmetrics":
            return "openmetrics"
        if fmt == "prometheus":
            return "text"
        if fmt == "json":
            return "json"
        accept = self.headers.get("Accept", "")
        if "openmetrics" in accept:
            return "openmetrics"
        if "text/plain" in accept:
            return "text"
        return "json"

    def _content_length(self) -> int:
        n = int(self.headers.get("Content-Length", 0))
        if n < 0:
            # rfile.read(-1) would read to EOF — on a keep-alive
            # connection that blocks forever, wedging the handler
            raise ValueError(f"negative Content-Length: {n}")
        return n

    def _read_body(self, n: int) -> bytes:
        """Read exactly the advertised body under the socket
        deadline. A peer that stops sending mid-body (partition,
        half-open) surfaces as ValueError — the callers' existing
        bad-request (400) path — instead of a wedged thread or a
        raw socket.timeout unwinding the handler."""
        try:
            data = self.rfile.read(n)
        except socket.timeout as e:
            raise ValueError(
                f"body read timed out after {self.timeout}s "
                f"({n} byte(s) advertised)") from e
        if len(data) < n:
            raise ValueError(
                f"body truncated: Content-Length {n} but only "
                f"{len(data)} byte(s) arrived")
        return data


def _make_listener(host: str, port: int, handler_cls):
    """ThreadingHTTPServer with a raised listen backlog: the stdlib
    default of 5 drops SYNs under connection-churn load (a
    closed-loop client pool opening a fresh connection per request);
    the dropped SYN retries after ~1s — a hard 1s floor on the
    latency tail."""
    class _Httpd(ThreadingHTTPServer):
        request_queue_size = 128

    return _Httpd((host, port), handler_cls)


class ModelServer:
    """Registry + per-model schedulers behind one HTTP listener.

    Schedulers are created lazily per (model name, version) on first
    use, so registering a new version swaps serving onto a fresh
    scheduler while the old version's in-flight batches complete.
    """

    def __init__(self, registry: Optional[ModelRegistry] = None,
                 port: int = 0, host: str = "127.0.0.1",
                 max_batch_size: int = 32, queue_limit: int = 256,
                 wait_ms: float = 2.0, slots: int = 4,
                 capacity: int = 256,
                 metrics: Optional[ServingMetrics] = None,
                 alerts=None, sample_rate: float = 0.01,
                 sample_routes: Optional[Dict[str, float]] = None,
                 slow_ms: float = 250.0, slos=None, tracer=None,
                 kv_mode: str = "auto", page_size: int = 16,
                 kv_pages: Optional[int] = None, mesh=None,
                 retrieval=None):
        self.registry = registry or ModelRegistry()
        self.metrics = metrics or ServingMetrics()
        # last good /metrics payload per mode — served when a rebuild
        # raises mid-drain so a collector's final scrape still lands
        self._last_exposition: Dict[str, object] = {}
        # mesh: a declarative serving mesh spec ("tp=2" |
        # "dp=2,tp=2" | dict — parallel/mesh_spec.py). Predict
        # backends then run TENSOR-PARALLEL: each hosted model is
        # wrapped in serving/tp_backend.TensorParallelModel (params
        # sharded over the 'model' axis, request batches over
        # 'data'), with one AOT-compilable executable per pow2
        # bucket. Parsed NOW so a typo'd spec kills boot, not the
        # first request; surfaced on /healthz ("mesh") and the
        # serving_mesh_devices gauge. Generate/streaming stays on
        # the unsharded model (the paged-KV decode path has its own
        # device story) — the proxy refuses to advertise streaming.
        self.mesh_plan = None
        self._tp_models: Dict[Tuple[str, int], object] = {}
        if mesh is not None:
            from deeplearning4j_tpu.parallel.mesh_spec import (
                build_mesh_context, parse_mesh_spec)
            self.mesh_plan = parse_mesh_spec(mesh)
            if self.mesh_plan.sp > 1:
                raise ServingError(
                    "serving meshes take dp/tp axes only; sp "
                    "belongs to training")
            # full validation at boot, not first traffic: device
            # count and pp rejection (build_mesh_context raises with
            # the fix in the message; the context itself is rebuilt
            # per model by the tp proxy), plus executor
            # compatibility for every model ALREADY registered — a
            # graph model would otherwise boot healthy and 500 every
            # predict (models registered later still fail lazily)
            build_mesh_context(self.mesh_plan)
            for entry in self.registry.models():
                mdl, _ = self.registry.resolve(entry["name"])
                if not hasattr(mdl, "_forward"):
                    raise ServingError(
                        f"model {entry['name']!r} "
                        f"({type(mdl).__name__}) cannot serve "
                        "tensor-parallel (sequential executors "
                        "only); drop --mesh or host it on an "
                        "unsharded server")
            _help = ("serving mesh shape per axis (absent = "
                     "unsharded serving)")
            axes = self.mesh_plan.describe()["axes"]
            reg = self.metrics.registry
            reg.gauge("serving_mesh_devices", help=_help,
                      labels={"axis": "dp"}).set(axes["dp"])
            reg.gauge("serving_mesh_devices", help=_help,
                      labels={"axis": "tp"}).set(axes["tp"])
        # optional observability.AlertManager: while any rule fires,
        # /healthz reports "degraded" + the firing alerts instead of
        # an unconditional "ok" (load balancers and pagers see the
        # p99/queue/shed blow-up without polling /metrics)
        self.alerts = alerts
        # optional observability.slo.SLOMonitor: burn rates are
        # re-evaluated on every /healthz poll so a breach degrades
        # health even without the background alert thread
        self.slos = slos
        # request-scoped tracing: head-based sampling decided at
        # admission (default 1%, per-route overrides, always-sample
        # on error), spans recorded on the process tracer
        self.sampler = Sampler(rate=sample_rate, routes=sample_routes)
        self.tracer = tracer if tracer is not None else get_tracer()
        # every compile of the process by function, from here on
        # (/debug/startup)
        self.compiles = install_global_watch()
        self.slow_ms = float(slow_ms)
        self._inflight: Dict[int, dict] = {}
        self._inflight_lock = threading.Lock()
        self._req_seq = itertools.count()
        # completed-request ring for /debug/traces (slow + errored
        # requests stay inspectable after the fact)
        self._recent: collections.deque = collections.deque(
            maxlen=256)
        self.host = host
        self.port = port
        self.max_batch_size = max_batch_size
        self.queue_limit = queue_limit
        self.wait_ms = wait_ms
        self.slots = slots
        self.capacity = capacity
        # paged-KV decode knobs (models/paged_kv.py): "auto" gives
        # transformer models the paged session + prefix cache and
        # falls back to dense for recurrent models
        self.kv_mode = kv_mode
        self.page_size = page_size
        self.kv_pages = kv_pages
        self._schedulers: Dict[Tuple[str, int], BatchScheduler] = {}
        self._batchers: Dict[Tuple[str, int], ContinuousBatcher] = {}
        # batchers mid-drain: stop() clears _batchers before the
        # concurrent drains, but /v1/kv/resume and /v1/kv/ack must
        # still find a draining backend's parked streams — that is
        # exactly when they arrive
        self._stopping_batchers: List[ContinuousBatcher] = []
        self._lock = threading.Lock()
        self._create_locks: Dict[tuple, threading.Lock] = {}
        self._draining = threading.Event()
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        # Retry-After hint on the draining 503: a drained server is
        # being replaced, so "come back soon" is measured in seconds
        self.drain_retry_after_s = 2.0
        # chaos hook (site serving.replica, kind hang/slow): every
        # handler — health probes included — stalls this long, so a
        # hung replica looks to the router exactly like a real one:
        # probe timeouts, rising latency, passive ejection
        self.chaos_delay_s = 0.0
        # retrieval: a RetrievalService (or a callable building one —
        # the in-process-fleet shape, so each replica owns fresh
        # search backends) hosting /v1/search + /v1/index. Its
        # embedder registers as the "embedder" model, so /v1/embed is
        # literally the predict path over a different model.
        self.retrieval = None
        if retrieval is not None:
            if self.mesh_plan is not None:
                raise ServingError(
                    "retrieval serving does not compose with --mesh "
                    "(the embedder/search models are not "
                    "tensor-parallel); host the index on an "
                    "unsharded replica")
            self.retrieval = retrieval(self.metrics) \
                if callable(retrieval) \
                else retrieval.attach_metrics(self.metrics)
            emb = self.retrieval.embedder
            if emb is not None and "embedder" not in self.registry:
                self.registry.register("embedder", emb)

    # ---- backend resolution ----
    def _get_or_create(self, cache: dict, key: tuple, factory,
                       kind: Optional[str] = None):
        """Resolve-or-build a backend WITHOUT holding the global lock
        through construction (building allocates device buffers and
        must not stall unrelated models), serialized per key so a
        thundering first-request herd builds exactly one backend.
        Draining is re-checked after the build: a backend created
        behind stop()'s back would leak its worker thread + gauge."""
        if kind is None:
            kind = "sched" if cache is self._schedulers else "batch"
        with self._lock:
            b = cache.get(key)
            if b is not None:
                return b
            if self._draining.is_set():
                raise ServerClosedError(
                    "server is draining; not creating new backends",
                    retry_after_s=self.drain_retry_after_s)
            create_lock = self._create_locks.setdefault(
                (kind,) + key, threading.Lock())
        with create_lock:
            with self._lock:
                b = cache.get(key)
                if b is not None:
                    return b
            b = factory()
            with self._lock:
                if not self._draining.is_set():
                    cache[key] = b
                    return b
        b.shutdown(drain=False)
        raise ServerClosedError(
            "server is draining; not creating new backends",
            retry_after_s=self.drain_retry_after_s)

    def resolve_serving_model(self, name: str,
                              version: Optional[int] = None):
        """(model, version) as the predict path serves it: the
        registry's model, wrapped tensor-parallel per the server's
        mesh spec when one is configured (wrap cached per
        name/version — the proxy owns the sharded placement and the
        per-bucket executables)."""
        model, version = self.registry.resolve(name, version)
        if self.mesh_plan is None:
            return model, version

        def build():
            from deeplearning4j_tpu.serving.tp_backend import (
                TensorParallelModel)
            return TensorParallelModel(model, self.mesh_plan)

        # the shared double-checked-locking helper: one proxy per
        # name/version even under a first-request herd (construction
        # re-places the registry model's params — two concurrent
        # builds would race that), and draining refuses cleanly
        tp = self._get_or_create(self._tp_models, (name, version),
                                 build, kind="tp")
        return tp, version

    def scheduler_for(
            self, name: str, version: Optional[int] = None
    ) -> Tuple[BatchScheduler, int]:
        """(scheduler, served version) — the single resolution point
        for a predict request."""
        model, version = self.resolve_serving_model(name, version)
        s = self._get_or_create(
            self._schedulers, (name, version),
            lambda: BatchScheduler(
                model, max_batch_size=self.max_batch_size,
                queue_limit=self.queue_limit, wait_ms=self.wait_ms,
                metrics=self.metrics,
                name=f"predict/{name}/v{version}"))
        return s, version

    def batcher_for(
            self, name: str, version: Optional[int] = None
    ) -> Tuple[ContinuousBatcher, int]:
        """(batcher, served version)."""
        if self.mesh_plan is not None:
            raise ServingError(
                "generate is not supported on a mesh-sharded server "
                "yet (the tp proxy re-places params; the decode KV "
                "path is single-device) — serve streaming models "
                "from an unsharded replica")
        model, version = self.registry.resolve(name, version)
        if not hasattr(model, "slot_streaming_session"):
            raise ServingError(
                f"model {name!r} does not support streaming "
                "generation (no slot_streaming_session)")
        def build():
            with startup.span("setup/batcher", {"model": name}):
                return ContinuousBatcher(
                    model, slots=self.slots, capacity=self.capacity,
                    queue_limit=self.queue_limit, metrics=self.metrics,
                    name=f"generate/{name}/v{version}",
                    version=str(version), kv_mode=self.kv_mode,
                    page_size=self.page_size, kv_pages=self.kv_pages,
                    model_name=name)

        b = self._get_or_create(self._batchers, (name, version), build)
        return b, version

    def warmup(self, **kwargs) -> Dict[str, dict]:
        """AOT warmup for every hosted model: pre-compile the predict
        pow2 batch buckets and (optionally) the generate prefill +
        decode programs, so the first real request — and every later
        one landing in a warmed bucket — never pays an XLA compile
        (see serving/warmup.py). Call before serving traffic."""
        from deeplearning4j_tpu.serving.warmup import warmup_server
        report = warmup_server(self, **kwargs)
        if self.retrieval is not None:
            # the search buckets compile too (one executable per
            # (k_pad, nprobe) pair) — warm the default so first-query
            # latency is a queue wait, not an XLA compile
            report["_search"] = {
                "buckets": self.retrieval.warmup()}
        return report

    # ---- HTTP plumbing ----
    def start(self) -> "ModelServer":
        server = self

        class Handler(_JsonRequestHandler):
            def _body(self):
                n = self._content_length()
                return json.loads(self._read_body(n).decode()
                                  or "{}")

            def do_GET(self):
                path = urlparse(self.path).path
                if server.chaos_delay_s:
                    # chaos hang: the whole replica stalls, health
                    # probes included — the router must see it
                    time.sleep(server.chaos_delay_s)
                if path in ("/healthz", "/readyz"):
                    payload = server.health_payload()
                    q = parse_qs(urlparse(self.path).query,
                                 keep_blank_values=True)
                    ready = path == "/readyz" or "ready" in q
                    if ready and payload["status"] != "ok":
                        # the load-balancer form: draining/degraded IS
                        # a 503 (stop sending), with a backoff hint
                        self._send(503, payload, headers={
                            "Retry-After": _retry_after_header(
                                server._unready_retry_after_s(
                                    payload))})
                    else:
                        self._send(200, payload)
                elif path == "/metrics":
                    # observability endpoints stay up THROUGH a
                    # drain: the fleet collector's last scrape of a
                    # retiring replica must succeed, so a rebuild
                    # that trips over mid-teardown registry churn
                    # serves the last good exposition instead of
                    # failing the scrape
                    mode = self._metrics_mode()
                    try:
                        if mode == "openmetrics":
                            out = server.metrics.prometheus_text(
                                openmetrics=True)
                        elif mode == "text":
                            out = server.metrics.prometheus_text()
                        else:
                            out = server.metrics.snapshot()
                        server._last_exposition[mode] = out
                    except Exception:
                        out = server._last_exposition.get(mode)
                        if out is None:
                            raise
                    if mode == "openmetrics":
                        self._send_text(
                            200, out,
                            "application/openmetrics-text; "
                            "version=1.0.0; charset=utf-8")
                    elif mode == "text":
                        self._send_text(
                            200, out,
                            "text/plain; version=0.0.4; "
                            "charset=utf-8")
                    else:
                        self._send(200, out)
                elif path == "/debug/trace-export":
                    q = parse_qs(urlparse(self.path).query)
                    since = int((q.get("since") or ["0"])[0])
                    limit = int((q.get("limit") or ["10000"])[0])
                    self._send(200, server.tracer.export_since(
                        since=since, limit=limit))
                elif path == "/debug/bundle":
                    from deeplearning4j_tpu.observability.fleetobs \
                        import local_bundle_payload
                    q = parse_qs(urlparse(self.path).query)
                    reason = (q.get("reason") or ["manual"])[0]
                    self._send(200, local_bundle_payload(
                        registry=server.metrics.registry,
                        tracer=server.tracer, reason=reason))
                elif path == "/v1/models":
                    self._send(200, {"models":
                                     server.registry.models()})
                elif path == "/v1/kv/prefixes":
                    self._send(200, server.kv_prefixes())
                elif path == "/debug/requests":
                    self._send(200, server.debug_requests())
                elif path == "/debug/slots":
                    self._send(200, server.debug_slots())
                elif path == "/debug/traces":
                    self._send(200, server.debug_traces())
                elif path == "/debug/startup":
                    self._send(200, server.debug_startup())
                else:
                    self._send(404, {"error": "not found"})

            def do_POST(self):
                path = urlparse(self.path).path
                if path == "/v1/predict":
                    self._serve_request(server._handle_predict, path)
                elif path == "/v1/generate":
                    self._serve_request(server._handle_generate, path)
                elif path == "/v1/embed":
                    self._serve_request(server._handle_embed, path)
                elif path == "/v1/search":
                    self._serve_request(server._handle_search, path)
                elif path in ("/v1/index/upsert", "/v1/index/delete",
                              "/v1/index/compact", "/v1/index/stats"):
                    verb = path.rsplit("/", 1)[1]
                    self._serve_request(
                        functools.partial(server._handle_index,
                                          verb), path)
                elif path == "/v1/kv/export":
                    self._serve_request(server._handle_kv_export,
                                        path)
                elif path == "/v1/kv/import":
                    self._serve_request(server._handle_kv_import,
                                        path)
                elif path in ("/v1/kv/migrate", "/v1/kv/resume",
                              "/v1/kv/ack"):
                    # migration control plane: these three MUST work
                    # while the server drains (that is exactly when
                    # they fire), so they bypass _serve_request's
                    # draining refusal
                    self._kv_control(path)
                else:
                    self._send(404, {"error": "not found"})

            def _kv_control(self, path):
                if server.chaos_delay_s:
                    time.sleep(server.chaos_delay_s)
                try:
                    body = self._body()
                except (ValueError, json.JSONDecodeError) as e:
                    self._send(400, {"error": f"bad JSON: {e}"})
                    return
                try:
                    if path == "/v1/kv/migrate":
                        self._send(200, {"parked":
                                         server.migrate_streams()})
                    elif path == "/v1/kv/ack":
                        self._send(200, {"acked":
                                         server.kv_ack(
                                             body.get("handle"))})
                    else:
                        self._send(200,
                                   server.kv_resume(
                                       body.get("handle")))
                except (ValueError, KeyError, TypeError) as e:
                    # an unknown/claimed handle is the caller's
                    # answer, not a server fault: it falls back
                    self._send(404, {"error": str(e)})
                except Exception as e:
                    logger.exception("kv control error")
                    self._send(500, {"error": str(e)})

            def _serve_request(self, handler, route):
                if server.chaos_delay_s:
                    time.sleep(server.chaos_delay_s)
                if server._draining.is_set():
                    self._send(503, {"error": "server is draining"},
                               headers={"Retry-After":
                                        _retry_after_header(
                                            server.drain_retry_after_s
                                        )})
                    return
                try:
                    body = self._body()
                except (ValueError, json.JSONDecodeError) as e:
                    self._send(400, {"error": f"bad JSON: {e}"})
                    return
                # admission: adopt the upstream trace (router hop) or
                # mint a fresh one; the head sampling decision is
                # made here and rides the context end to end. Bad
                # client input (e.g. a non-numeric timeout_ms) must
                # still produce a 400, not a dropped connection.
                try:
                    ctx = server._mint_ctx(self.headers, route, body)
                except (ValueError, KeyError, TypeError) as e:
                    self._send(400, {"error": str(e)})
                    return
                key = server._track_request(ctx, body)
                code = 500
                hdrs = {"traceparent": ctx.traceparent()}

                def send(c, obj):
                    nonlocal code
                    code = c
                    self._send(c, obj, headers=hdrs)

                def err(c, e):
                    ctx.set_error(e)
                    # the promoted sampling decision must reach the
                    # next hop's response header too
                    hdrs["traceparent"] = ctx.traceparent()
                    if c in (429, 503):
                        # backpressure responses carry the raiser's
                        # backoff hint (breaker cooldown remaining,
                        # queue-depth estimate, drain default)
                        ra = getattr(e, "retry_after_s", None)
                        hdrs["Retry-After"] = _retry_after_header(
                            server.drain_retry_after_s
                            if ra is None else ra)
                    send(c, {"error": str(e),
                             "trace_id": ctx.trace_id})

                try:
                    # attach() scopes the context to THIS handler
                    # thread only, restored on exit — pooled HTTP
                    # threads cannot leak a request's context
                    with ctx.attach():
                        rv = handler(body, ctx=ctx)
                    if isinstance(rv, tuple):
                        # handlers may override the status (the 202
                        # migration-offer shape)
                        send(rv[0], rv[1])
                    else:
                        send(200, rv)
                except QueueFullError as e:
                    err(429, e)
                except DeadlineExceededError as e:
                    err(504, e)
                except ModelNotFoundError as e:
                    err(404, e)
                except KVLeaseError as e:
                    # the lease blob itself is bad (corrupt bytes /
                    # version skew): re-sending it anywhere cannot
                    # help — 422 tells the router to fall back to
                    # recompute/resume instead of retrying
                    err(422, e)
                except (ServerClosedError, CircuitOpenError) as e:
                    # both are "this backend cannot take work right
                    # now, retry later" — 503 for the load balancer
                    err(503, e)
                except ServingError as e:
                    # remaining typed serving errors (e.g. generate
                    # against a model with no streaming session) are
                    # client mistakes, not server faults
                    err(400, e)
                except (ValueError, KeyError, TypeError) as e:
                    err(400, e)
                except Exception as e:    # keep the listener alive
                    logger.exception("serving error")
                    err(500, e)
                finally:
                    server._finish_request(key, ctx, code, body)

        # cheap pre-check before binding the socket: a second start()
        # on a live server must not try to re-bind its own port
        with self._lock:
            if self._draining.is_set():
                raise ServerClosedError(
                    "server was stopped; not starting listener")
            if self._httpd is not None:
                return self
        httpd = _make_listener(self.host, self.port, Handler)
        # publish under the lock so a concurrent stop() either sees
        # None or the live server, and re-check draining there: a
        # stop() that already returned must not leave this listener
        # running ownerless. Double start() is idempotent.
        with self._lock:
            if self._draining.is_set():
                httpd.server_close()
                raise ServerClosedError(
                    "server was stopped; not starting listener")
            if self._httpd is not None:
                httpd.server_close()
                return self
            self._httpd = httpd
            self.port = httpd.server_address[1]
            self._thread = threading.Thread(
                target=httpd.serve_forever, daemon=True,
                name="model-server")
            self._thread.start()
        logger.info("model server on http://%s:%d/", self.host,
                    self.port)
        return self

    # ---- endpoint handlers (also the in-process API) ----
    @staticmethod
    def _timeout_s(body) -> Optional[float]:
        t = body.get("timeout_ms")
        return None if t is None else float(t) / 1e3

    def _handle_predict(self, body: dict, ctx=None) -> dict:
        if "model" not in body or "inputs" not in body:
            raise ValueError('predict body needs "model" and "inputs"')
        sched, version = self.scheduler_for(body["model"],
                                            body.get("version"))
        x = np.asarray(body["inputs"], np.float32)
        if x.ndim == 1:
            x = x[None, :]
        if ctx is not None:
            ctx.attrs["model_version"] = version
        out = sched.predict(x, timeout=self._timeout_s(body), ctx=ctx,
                            tier=body.get("tier"))
        return {"outputs": np.asarray(out).tolist(),
                "model_version": version}

    @staticmethod
    def _offer_payload(offer: MigrationOffer, version) -> Tuple[int,
                                                                dict]:
        """The 202 body a :class:`MigrationOffer` result becomes:
        the router imports ``blob`` on a survivor and acks, or
        resumes ``handle`` here."""
        return 202, {"migration": {
            "handle": offer.handle,
            "blob": base64.b64encode(offer.blob).decode(),
            "pos": offer.pos,
            "tokens_out": offer.tokens_out,
            "model_version": version}}

    def _handle_generate(self, body: dict, ctx=None):
        if "model" not in body or "prompt" not in body:
            raise ValueError('generate body needs "model" and '
                             '"prompt"')
        batcher, version = self.batcher_for(body["model"],
                                            body.get("version"))
        if ctx is not None:
            ctx.attrs["model_version"] = version
        ids = batcher.generate(
            body["prompt"], int(body.get("n_tokens", 16)),
            temperature=float(body.get("temperature", 0.0)),
            seed=int(body.get("seed", 0)),
            timeout=self._timeout_s(body), ctx=ctx,
            tier=body.get("tier"))
        if isinstance(ids, MigrationOffer):
            # the backend started draining mid-stream and exported
            # this stream's lease instead of finishing it
            return self._offer_payload(ids, version)
        return {"ids": np.asarray(ids).tolist(),
                "model_version": version}

    # ---- retrieval: embed + search + index admin ----
    def _require_retrieval(self):
        if self.retrieval is None:
            raise ModelNotFoundError(
                "no index hosted on this server (start it with "
                "serve --index)")
        return self.retrieval

    @staticmethod
    def _texts_of(body: dict, plural: str = "texts",
                  singular: str = "text"):
        texts = body.get(plural, body.get(singular))
        if texts is None:
            raise ValueError(f'body needs "{plural}" (list) or '
                             f'"{singular}" (string)')
        if isinstance(texts, str):
            texts = [texts]
        if not texts or not all(isinstance(t, str) for t in texts):
            raise ValueError(f'"{plural}" must be a non-empty list '
                             "of strings")
        return texts

    def _embed_sched(self, texts, timeout, ctx, tier):
        """Embed texts through the REGISTERED embedder's scheduler
        (the predict path, not a host-side shortcut): returns the
        (B, D) query matrix + the served model version."""
        r = self._require_retrieval()
        if r.embedder is None:
            raise ValueError(
                "this index has no embedder — send raw vectors")
        sched, version = self.scheduler_for("embedder")
        packed = r.embedder.encode(texts)
        out = sched.predict(packed, timeout=timeout, ctx=ctx,
                            tier=tier)
        return np.asarray(out), version

    def _handle_embed(self, body: dict, ctx=None) -> dict:
        texts = self._texts_of(body)
        out, version = self._embed_sched(
            texts, self._timeout_s(body), ctx, body.get("tier"))
        if ctx is not None:
            ctx.attrs["model_version"] = version
        return {"embeddings": out.tolist(),
                "dim": int(out.shape[1]),
                "model_version": version}

    def _handle_search(self, body: dict, ctx=None) -> dict:
        r = self._require_retrieval()
        has_text = "query" in body or "queries" in body
        has_vec = "vector" in body or "vectors" in body
        if has_text == has_vec:
            raise ValueError(
                'search body needs exactly one of "query"/"queries" '
                '(text) or "vector"/"vectors" (raw floats)')
        k = int(body.get("k", 10))
        nprobe = body.get("nprobe")
        if nprobe is not None:
            nprobe = int(nprobe)
        filter_ids = body.get("filter_ids")
        if filter_ids is not None and not isinstance(
                filter_ids, (list, tuple)):
            raise ValueError('"filter_ids" must be a list of ids')
        tier = body.get("tier")
        timeout = self._timeout_s(body)
        deadline = None if timeout is None \
            else time.monotonic() + timeout
        embedder_version = None
        if has_text:
            texts = self._texts_of(body, "queries", "query")
            q, embedder_version = self._embed_sched(
                texts, timeout, ctx, tier)
        else:
            q = np.asarray(body.get("vectors", body.get("vector")),
                           np.float32)
            if q.ndim == 1:
                q = q[None, :]
        # one deadline budget across both hops: the search leg gets
        # whatever the embed leg left, so "timeout_ms" bounds the
        # request, not each stage
        remaining = None if deadline is None \
            else deadline - time.monotonic()
        ids, scores = r.search(q, k=k, nprobe=nprobe,
                               filter_ids=filter_ids,
                               timeout=remaining, ctx=ctx, tier=tier)
        results = [[{"id": int(i), "score": float(s)}
                    for i, s in zip(row_ids, row_scores) if i >= 0]
                   for row_ids, row_scores in zip(ids, scores)]
        out = {"results": results, "k": k,
               "generation": r.index.generation}
        if embedder_version is not None:
            out["embedder_version"] = embedder_version
        if ctx is not None:
            ctx.attrs["index_generation"] = r.index.generation
        return out

    def _handle_index(self, verb: str, body: dict, ctx=None) -> dict:
        r = self._require_retrieval()
        if verb == "upsert":
            if "ids" not in body:
                raise ValueError('index upsert body needs "ids"')
            return r.upsert(body["ids"],
                            vectors=body.get("vectors"),
                            texts=body.get("texts"))
        if verb == "delete":
            if "ids" not in body:
                raise ValueError('index delete body needs "ids"')
            return r.delete(body["ids"])
        if verb == "compact":
            return r.compact()
        return r.stats()

    # ---- disaggregated prefill/decode + drain migration ----
    def _handle_kv_export(self, body: dict, ctx=None):
        """``POST /v1/kv/export`` — the prefill half: run the
        prompt's prefill here, return the serialized lease for a
        decode replica's ``/v1/kv/import``. Body = the generate
        body."""
        if "model" not in body or "prompt" not in body:
            raise ValueError('kv export body needs "model" and '
                             '"prompt"')
        batcher, version = self.batcher_for(body["model"],
                                            body.get("version"))
        if ctx is not None:
            ctx.attrs["model_version"] = version
        blob = batcher.prefill_export(
            body["prompt"], int(body.get("n_tokens", 16)),
            temperature=float(body.get("temperature", 0.0)),
            seed=int(body.get("seed", 0)),
            timeout=self._timeout_s(body), ctx=ctx,
            tier=body.get("tier"),
            export_extra={"model": body["model"],
                          "version": version})
        if isinstance(blob, MigrationOffer):
            return self._offer_payload(blob, version)
        return {"blob": base64.b64encode(blob).decode(),
                "model_version": version}

    def _handle_kv_import(self, body: dict, ctx=None):
        """``POST /v1/kv/import`` — rebuild an exported stream into
        this replica's page pool and stream it to completion. The
        lease's ``extra`` names the model; version/page/CRC skew
        fail typed (422)."""
        from deeplearning4j_tpu.models.paged_kv import parse_lease
        if "blob" not in body:
            raise ValueError('kv import body needs "blob"')
        try:
            blob = base64.b64decode(body["blob"], validate=True)
        except (binascii.Error, ValueError, TypeError) as e:
            raise KVLeaseCorruptError(
                f"lease blob is not valid base64: {e}") from e
        header, _ = parse_lease(blob)
        extra = dict(header.get("extra") or {})
        model = extra.get("model")
        if not model:
            raise KVLeaseError(
                "lease extra names no model — exported outside the "
                "serving stack?")
        batcher, version = self.batcher_for(model,
                                            extra.get("version"))
        if ctx is not None:
            ctx.attrs["model_version"] = version
        ids = batcher.wait(batcher.import_stream(
            blob, timeout=self._timeout_s(body), ctx=ctx,
            tier=body.get("tier"), header=header))
        if isinstance(ids, MigrationOffer):
            return self._offer_payload(ids, version)
        return {"ids": np.asarray(ids).tolist(),
                "model_version": version}

    # ---- request-scoped tracing plumbing ----
    def _mint_ctx(self, headers, route: str,
                  body: dict) -> RequestContext:
        t = self._timeout_s(body)
        deadline = time.monotonic() + t if t is not None else None
        ctx = RequestContext.from_traceparent(
            headers.get("traceparent"), route, self.sampler,
            deadline=deadline, tracer=self.tracer)
        if ctx is None:
            ctx = RequestContext.new(route, self.sampler,
                                     deadline=deadline,
                                     tracer=self.tracer)
        # announce the root span to the sinks: a crash bundle lists
        # this request as an unclosed span until finish() closes it
        ctx.open_root()
        return ctx

    def _track_request(self, ctx: RequestContext, body: dict) -> int:
        key = next(self._req_seq)
        with self._inflight_lock:
            self._inflight[key] = {"ctx": ctx,
                                   "model": body.get("model")}
        return key

    def _finish_request(self, key: int, ctx: RequestContext,
                        code: int, body: dict) -> None:
        with self._inflight_lock:
            self._inflight.pop(key, None)
        total_s = ctx.finish(attrs={"http_status": code})
        entry = {"trace_id": ctx.trace_id, "route": ctx.route,
                 "model": body.get("model"), "status": code,
                 "duration_ms": round(total_s * 1e3, 3),
                 "phases_ms": {k: round(v * 1e3, 3)
                               for k, v in ctx.phases.items()},
                 # scalar phase attrs (slot, prefix_hit_tokens,
                 # model_version, ...) make the completion ring
                 # assertable: "did the second identical prompt skip
                 # prefill" is attrs["prefix_hit_tokens"], not a
                 # timing heuristic
                 "attrs": {k: v for k, v in ctx.attrs.items()
                           if isinstance(v, (int, float, str, bool))},
                 "sampled": ctx.sampled,
                 "slow": total_s * 1e3 >= self.slow_ms
                 or code >= 400,
                 "t_end": time.time()}
        if ctx.error is not None:
            entry["error"] = ctx.error
        with self._inflight_lock:
            self._recent.append(entry)

    # ---- /debug payloads ----
    def debug_requests(self) -> dict:
        """In-flight requests (current phase + age + deadline), the
        most recent completions, per-backend queue depth by
        priority tier, and the latency-attribution report — the
        first page an operator opens for a slow server."""
        with self._inflight_lock:
            inflight = [dict(v["ctx"].to_debug(), model=v["model"])
                        for v in self._inflight.values()]
            recent = list(self._recent)[-20:]
        with self._lock:
            backends = (list(self._schedulers.values())
                        + list(self._batchers.values()))
        # which tiers are backlogged where: the page that answers
        # "is the spike degrading best-effort first" directly
        by_tier = {b.name: d for b in backends
                   for d in [b._queue.depth_by_tier()] if d}
        return {"in_flight": inflight,
                "in_flight_count": len(inflight),
                "recent": recent,
                "queue_by_tier": by_tier,
                "latency_attribution":
                    self.metrics.latency_attribution()}

    def debug_slots(self) -> dict:
        """Continuous-batching slot states per generate backend,
        with the paged-KV pool and prefix-cache state when the
        backend decodes over page tables."""
        with self._lock:
            batchers = dict(self._batchers)
        out = {}
        for b in batchers.values():
            entry = {"active_slots": b.active_slots(),
                     "pending": len(b._pending),
                     "slots": b.slots_debug()}
            kv = b.kv_debug()
            if kv is not None:
                entry["kv"] = kv
            out[b.name] = entry
        return {"backends": out}

    # ---- disaggregation / migration control plane ----
    def _all_batchers(self) -> List[ContinuousBatcher]:
        """Live + mid-drain generate backends — the handle-lookup
        set for the migration control plane."""
        with self._lock:
            return (list(self._batchers.values())
                    + list(self._stopping_batchers))

    def migrate_streams(self) -> int:
        """Arm drain migration on every paged generate backend:
        active streams complete with 202 migration offers the fleet
        router re-homes onto survivors. Returns how many live
        streams will be offered. The fleet calls this right before
        a retire/replace drain; ``POST /v1/kv/migrate`` is the
        same verb for subprocess replicas."""
        return sum(b.request_migration()
                   for b in self._all_batchers())

    def kv_ack(self, handle) -> bool:
        """``POST /v1/kv/ack`` — a survivor imported the offered
        stream; the parked pages free."""
        if not handle:
            raise ValueError('kv ack body needs "handle"')
        return any(b.ack_migration(str(handle))
                   for b in self._all_batchers())

    def kv_resume(self, handle) -> dict:
        """``POST /v1/kv/resume`` — the handoff failed; finish the
        parked stream HERE and return its completed ids (the
        generate response shape, so the router can hand it straight
        to the client)."""
        if not handle:
            raise ValueError('kv resume body needs "handle"')
        for b in self._all_batchers():
            if b.has_migration(str(handle)):
                ids = b.resume_stream(str(handle))
                return {"ids": np.asarray(ids).tolist(),
                        "model_version": b.version}
        raise ValueError(f"unknown migration handle {handle!r}")

    def kv_prefixes(self, limit: int = 512) -> dict:
        """``GET /v1/kv/prefixes`` — this replica's prefix-cache
        advertisement for KV-aware routing: page size + cached
        prefix fingerprints, merged over the paged generate
        backends."""
        page_size = None
        prefixes: List[str] = []
        for b in self._all_batchers():
            d = b.prefix_digest(limit)
            if d is None:
                continue
            page_size = d["page_size"]
            prefixes.extend(d["prefixes"])
        return {"page_size": page_size,
                "prefixes": prefixes[-int(limit):]}

    def debug_traces(self) -> dict:
        """Recent slow/errored traces with their phase breakdown —
        what an exemplar trace id from /metrics resolves to."""
        with self._inflight_lock:
            recent = list(self._recent)
        slow = [e for e in recent if e.get("slow")]
        return {"slow": slow[-50:],
                "sample_rate": self.sampler.rate,
                "slow_ms": self.slow_ms}

    def debug_startup(self) -> dict:
        """The set-up timeline: the ``setup/*`` and ``xla/*`` spans of
        ``tracing.startup`` (constructors, warm-ups, each step
        program's first call, every trace / lowering / compile with
        its function and whether the persistent cache served it) and
        the compile seconds by function."""
        return {"events": startup.events(),
                "dropped": startup.dropped,
                "compiles": self.compiles.summary(),
                "by_function": self.compiles.by_function()}

    # ---- health ----
    def health_payload(self) -> dict:
        """The /healthz body — status ``ok`` | ``degraded`` |
        ``draining`` plus the evidence (firing alerts, non-closed
        circuits, SLO breaches). ``/healthz`` serves it with 200
        always (humans read the status field); ``/readyz`` and
        ``/healthz?ready`` turn a non-ok status into a 503 so dumb
        LB checks and the fleet router's prober work unmodified."""
        if self._draining.is_set():
            return {"status": "draining"}
        firing = []
        if self.alerts is not None:
            try:
                self.alerts.evaluate()
                firing = self.alerts.firing()
            except Exception:
                logger.exception("alert evaluation failed")
        slo_status = None
        if self.slos is not None:
            try:
                self.slos.evaluate()
                slo_status = self.slos.status()
            except Exception:
                logger.exception("SLO evaluation failed")
        # non-closed circuit breakers degrade health: a crash-looping
        # backend must be visible to load balancers without polling
        # /metrics
        circuits = self._circuit_states()
        breached = [s for s in (slo_status or [])
                    if s.get("breached")]
        if firing or circuits or breached:
            payload = {"status": "degraded"}
            if firing:
                payload["alerts"] = firing
            if circuits:
                payload["circuits"] = circuits
            if breached:
                payload["slo_breaches"] = breached
        else:
            payload = {"status": "ok"}
        if slo_status is not None:
            payload["slos"] = slo_status
        if self.mesh_plan is not None:
            # operators (and the fleet router's prober) see the
            # serving mesh shape next to health, not buried in logs
            payload["mesh"] = self.mesh_plan.describe()
        if self.retrieval is not None:
            # index generation + size ride the health payload: the
            # fleet's convergence checks (did the upsert land on
            # every replica) read them here, not via a scrape
            payload["index"] = self.retrieval.describe()
        # version provenance: which model versions this replica
        # actually serves, straight from the registry — a rollout
        # operator (or the fleet prober) reads the canary's version
        # off /healthz instead of trusting deployment intent
        try:
            payload["models"] = self.registry.models()
        except Exception:
            logger.exception("model provenance listing failed")
        return payload

    def _unready_retry_after_s(self, payload: dict) -> float:
        """Backoff hint for a not-ready 503: the longest breaker
        cooldown still running when circuits degraded us, else the
        drain default."""
        if payload.get("circuits"):
            with self._lock:
                backends = (list(self._schedulers.values())
                            + list(self._batchers.values()))
            cooldowns = [b.breaker.cooldown_remaining()
                         for b in backends]
            longest = max(cooldowns, default=0.0)
            if longest > 0:
                return longest
        return self.drain_retry_after_s

    def _circuit_states(self) -> Dict[str, str]:
        """Backend name -> breaker state, for every backend whose
        circuit is NOT closed (the /healthz payload)."""
        with self._lock:
            backends = (list(self._schedulers.values())
                        + list(self._batchers.values()))
        out = {}
        for b in backends:
            state = b.breaker.state
            if state != "closed":
                out[b.name] = state
        if self.retrieval is not None:
            out.update(self.retrieval.breaker_states())
        return out

    # ---- lifecycle ----
    def evict_model(self, name: str, version: Optional[int] = None,
                    drain: bool = True, timeout: float = 30.0) -> bool:
        """Release the scheduler/batcher backing a swapped-out model
        version (every version of ``name`` when ``version`` is None):
        their collector threads and compiled executables live until
        evicted, so pair this with ``registry.unregister`` on
        long-running servers."""
        ok = True
        with self._lock:
            keys = [k for k in set(self._schedulers) |
                    set(self._batchers)
                    if k[0] == name and (version is None
                                         or k[1] == version)]
            backends = ([self._schedulers.pop(k) for k in keys
                         if k in self._schedulers]
                        + [self._batchers.pop(k) for k in keys
                           if k in self._batchers])
            # drop the tensor-parallel wraps too: a re-registered
            # version must re-place and re-compile, not serve a
            # stale proxy's executables
            for k in [k for k in self._tp_models
                      if k[0] == name and (version is None
                                           or k[1] == version)]:
                self._tp_models.pop(k, None)
        for b in backends:
            ok = b.shutdown(drain=drain, timeout=timeout) and ok
            # drop the evicted version's metric labels with its
            # backend: hot-swapping versions on a long-running
            # server must not accrete dead
            # ``serving_*{endpoint=predict/name/vN}`` series forever
            # (the _sync_views leak class, for versions)
            try:
                self.metrics.evict_endpoint(b.name)
            except Exception:
                logger.exception("metrics eviction for %s failed",
                                 b.name)
        return ok

    def stop(self, drain: bool = True, timeout: float = 30.0) -> bool:
        """Graceful by default: refuse new work, complete queued and
        in-flight requests, then stop the listener. Backends drain
        CONCURRENTLY, so the wall-clock bound is one ``timeout``, not
        one per hosted model version."""
        self._draining.set()
        with self._lock:
            backends = (list(self._schedulers.values())
                        + list(self._batchers.values()))
            # parked-stream lookups (/v1/kv/resume, /v1/kv/ack) must
            # keep working through the concurrent drains below
            self._stopping_batchers = list(self._batchers.values())
            self._schedulers.clear()
            self._batchers.clear()
            self._tp_models.clear()
        oks = {}
        threads = [threading.Thread(
            target=lambda b=b: oks.__setitem__(
                b, b.shutdown(drain=drain, timeout=timeout)),
            daemon=True) for b in backends]
        retrieval = self.retrieval
        if retrieval is not None:
            # the search backends drain in the same concurrent wave
            # (close() also releases the retrieval gauges)
            threads.append(threading.Thread(
                target=lambda: oks.__setitem__(
                    "retrieval", retrieval.close(drain=drain,
                                                 timeout=timeout)),
                daemon=True))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout + 10.0)
        with self._lock:
            self._stopping_batchers = []
        ok = all(oks.get(b, False) for b in backends) \
            and (retrieval is None or oks.get("retrieval", False))
        # swap under the lock: two racing stop() calls must not both
        # pass the None test (the loser would call shutdown() on a
        # dead server or on None) — found by graftlint GL004; the
        # blocking shutdown() itself runs outside the lock
        with self._lock:
            httpd, self._httpd = self._httpd, None
            thread, self._thread = self._thread, None
        if httpd is not None:
            httpd.shutdown()
            try:
                # release the bound port NOW, not at GC: fleet
                # replicas cycle on loopback ports, and an embedder
                # restarting on the same port would hit EADDRINUSE
                httpd.server_close()
            except OSError:
                pass
        if thread is not None:
            # join the listener thread (GL007): stop() returning
            # while serve_forever still winds down would let a
            # restart race the old generation for the port
            thread.join(timeout=5.0)
        return ok
