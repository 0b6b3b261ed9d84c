"""Load generator for the serving fleet (and single servers).

The in-repo harness that turns "the router survives faults" into a
measured claim: drive ``/v1/predict`` (or ``/v1/generate``) at a
target rate or at fixed concurrency, record every latency in the
SAME histogram implementation the serving stack exposes
(``observability.registry.Histogram`` — percentiles come from the
metrics registry, not a side array), honor ``Retry-After`` backoff
on 429/503, and report exactly what the soak acceptance needs:
how many requests were sent, how many ever failed to get a
successful response (``failed`` — the "dropped requests" count),
and the latency distribution. Every error is also CLASSIFIED
(``error_classes`` in the report: ``connect_refused`` / ``reset`` /
``timeout`` / ``bad_body`` / ``5xx`` / ``4xx`` / ``shed_429_503`` /
``neterr``), retried or not — a network-chaos soak asserts WHICH
failure mode occurred, not just how many requests it cost.

Two loop disciplines (the classic load-testing split):

- **closed loop** (``qps=None``): N workers fire back-to-back; the
  system's completion rate gates the arrival rate. Measures peak
  sustainable throughput, hides queueing delay.
- **open loop** (``qps=R``): arrivals are scheduled at R/s no matter
  how slow responses are (coordinated-omission-resistant); a bounded
  backlog models client impatience — overflow counts as
  ``not_sent`` rather than silently stretching the schedule.

Streaming mode (``--mode generate``) drives ``/v1/generate`` with a
configurable **duplicate-prompt ratio**: that fraction of requests
reuses one shared prompt, the rest get unique prompts — the traffic
shape that makes prefix-cache wins measurable through the router.
After the run the report includes TTFT / inter-token percentiles
scraped from the server's own ``serving_ttft_seconds`` /
``serving_itl_seconds`` histograms (``--metrics-url``, defaulting to
the target), so the latency attribution comes from the serving
stack's instruments, not a client-side proxy.

Usage (library)::

    from tools.loadgen import LoadGen
    report = LoadGen(url, concurrency=16, total=2000).run()

Autoscaler-soak extensions: ``--profile step:LOW:HIGH:AT`` /
``ramp:LOW:HIGH`` schedule the open-loop QPS over the run (the
traffic spike the autoscaler must absorb), and ``--tier-mix
gold=0.2,standard=0.5,best_effort=0.3`` stamps each request with a
deterministic priority tier — the report then carries per-tier
latency and outcome percentiles (sent/ok/failed/shed per tier), the
evidence for "zero gold dropped, best-effort degraded first".

CLI::

    python -m tools.loadgen --url http://127.0.0.1:8080 \
        --qps 200 --duration 30 --concurrency 32
    python -m tools.loadgen --url http://127.0.0.1:8080 \
        --mode generate --dup-ratio 0.5 --total 200 --n-tokens 16
    python -m tools.loadgen --url http://127.0.0.1:8080 \
        --profile step:20:80:5 --duration 20 \
        --tier-mix gold=0.2,standard=0.5,best_effort=0.3
"""

from __future__ import annotations

import argparse
import http.client
import json
import queue
import socket
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Callable, Dict, Optional

# the serving stack's tier literals, from their one authoritative
# home (a stdlib-only leaf module — loadgen already depends on the
# package for the registry histogram, so mirroring them here would
# only add drift risk)
from deeplearning4j_tpu.serving.tiers import TIERS as _TIERS

__all__ = ["LoadGen", "SearchWorkload", "generate_body_fn",
           "scrape_streaming_latency", "parse_profile",
           "parse_tier_mix", "tiered_body_fn"]


def _default_body(i: int) -> dict:
    return {"model": "default", "inputs": [[0.0, 1.0, 2.0, 3.0]]}


def parse_profile(spec):
    """Open-loop QPS schedule from a compact spec — the soak
    driver's traffic shape:

    - ``step:LOW:HIGH:AT`` (or ``...:AT:UNTIL``) — LOW q/s until
      ``AT`` seconds into the run, then HIGH (until ``UNTIL``, then
      back to LOW): the spike the autoscaler must absorb.
    - ``ramp:LOW:HIGH`` — linear from LOW to HIGH over the run.

    Returns ``qps_at(t_seconds, duration_s) -> float``; None for no
    profile (constant ``--qps``)."""
    if spec is None:
        return None
    parts = str(spec).split(":")
    kind = parts[0]
    try:
        nums = [float(x) for x in parts[1:]]
    except ValueError:
        raise ValueError(f"bad profile numbers in {spec!r}") from None
    if kind == "step":
        if len(nums) not in (3, 4):
            raise ValueError(
                f"step profile wants step:LOW:HIGH:AT[:UNTIL], got "
                f"{spec!r}")
        low, high, at = nums[:3]
        until = nums[3] if len(nums) == 4 else float("inf")

        def qps_at(t, duration_s=None):
            return high if at <= t < until else low
    elif kind == "ramp":
        if len(nums) != 2:
            raise ValueError(
                f"ramp profile wants ramp:LOW:HIGH, got {spec!r}")
        low, high = nums

        def qps_at(t, duration_s=None):
            if not duration_s:
                return high
            frac = min(1.0, max(0.0, t / duration_s))
            return low + (high - low) * frac
    else:
        raise ValueError(
            f"unknown profile kind {kind!r}; known: step, ramp")
    return qps_at


def parse_tier_mix(spec):
    """``gold=0.2,standard=0.5,best_effort=0.3`` -> dict (fractions
    normalised to sum 1). None/empty -> None (untiered traffic)."""
    if not spec:
        return None
    mix = {}
    for part in str(spec).split(","):
        name, _, frac = part.partition("=")
        name = name.strip().replace("-", "_")
        if name not in _TIERS:
            raise ValueError(
                f"unknown tier {name!r} in mix; known: {_TIERS}")
        mix[name] = float(frac)
    total = sum(mix.values())
    if total <= 0:
        raise ValueError(f"tier mix {spec!r} sums to zero")
    return {t: v / total for t, v in mix.items()}


def tiered_body_fn(base_fn, mix):
    """Wrap a body factory to stamp a deterministic per-ordinal
    ``tier`` drawn from ``mix`` (same spread idiom as the
    duplicate-prompt mix: replayable, no rng)."""
    tiers_sorted = [t for t in _TIERS if t in mix]
    edges = []
    acc = 0.0
    for t in tiers_sorted:
        acc += mix[t]
        edges.append((acc * 100.0, t))

    def body(i: int) -> dict:
        b = dict(base_fn(i))
        spread = (i * 37) % 100
        for edge, t in edges:
            if spread < edge:
                b["tier"] = t
                break
        else:
            b["tier"] = tiers_sorted[-1]
        return b

    return body


def generate_body_fn(model: str = "default", prompt_len: int = 16,
                     n_tokens: int = 16, vocab: int = 64,
                     dup_ratio: float = 0.0) -> Callable[[int], dict]:
    """Body factory for ``/v1/generate`` streaming load:
    deterministically, ``dup_ratio`` of requests (by ordinal) send
    ONE shared prompt — prefix-cache hits after the first completes
    — and the rest send unique prompts (cold prefill). Prompt ids
    stay in ``[1, vocab)``."""
    dup_per_100 = int(round(max(0.0, min(1.0, dup_ratio)) * 100))
    span = max(1, vocab - 1)
    shared = [1 + (7 * j) % span for j in range(prompt_len)]

    def body(i: int) -> dict:
        if (i * 37) % 100 < dup_per_100:     # deterministic spread
            prompt = shared
        else:
            prompt = [1 + (i + 3 * j) % span
                      for j in range(prompt_len)]
        return {"model": model, "prompt": prompt,
                "n_tokens": n_tokens}

    return body


class SearchWorkload:
    """``--mode search``: a Zipf-skewed query stream over a corpus
    plus the client-side recall@k oracle.

    A fixed pool of queries (corpus vectors + gaussian noise) is
    ranked by a seeded popularity permutation; request ordinal ``i``
    maps DETERMINISTICALLY to a pool rank through the Zipf CDF (same
    replayable-spread idiom as the duplicate-prompt mix), so head
    queries repeat the way real retrieval traffic does — the shape
    that makes batching and cache effects measurable. The exact
    brute-force top-k over the corpus is computed host-side up
    front; every 200 response's ids score against it, and the report
    carries the measured ``recall_at_k``.
    """

    def __init__(self, vectors, ids=None, k: int = 10,
                 nprobe: Optional[int] = None,
                 metric: str = "cosine", pool: int = 256,
                 zipf_s: float = 1.1, noise: float = 0.05,
                 seed: int = 0):
        import numpy as np
        self._np = np
        vectors = np.asarray(vectors, np.float32)
        self._ids = (np.arange(vectors.shape[0]) if ids is None
                     else np.asarray(ids))
        self.k = int(k)
        self.nprobe = nprobe
        rng = np.random.default_rng(seed)
        pool = min(int(pool), vectors.shape[0])
        picks = rng.choice(vectors.shape[0], size=pool,
                           replace=False)
        self.queries = (vectors[picks]
                        + noise * rng.standard_normal(
                            (pool, vectors.shape[1]))
                        ).astype(np.float32)
        # Zipf CDF over pool ranks: rank r has mass 1/(r+1)^s
        w = 1.0 / np.power(np.arange(1, pool + 1, dtype=np.float64),
                           float(zipf_s))
        self._cdf = np.cumsum(w) / np.sum(w)
        self._oracle = self._exact_topk(vectors, metric)

    def _exact_topk(self, corpus, metric):
        np = self._np
        q = self.queries.astype(np.float64)
        m = corpus.astype(np.float64)
        if metric == "cosine":
            qn = q / np.maximum(
                np.linalg.norm(q, axis=1, keepdims=True), 1e-12)
            mn = m / np.maximum(
                np.linalg.norm(m, axis=1, keepdims=True), 1e-12)
            scores = qn @ mn.T
        elif metric == "dot":
            scores = q @ m.T
        else:                                   # euclidean
            scores = (2.0 * (q @ m.T)
                      - np.sum(m * m, axis=1)[None, :]
                      - np.sum(q * q, axis=1)[:, None])
        order = np.argsort(-scores, axis=1, kind="stable")
        return [set(int(self._ids[p]) for p in row[:self.k])
                for row in order]

    def rank_of(self, i: int) -> int:
        """ordinal -> Zipf-drawn pool rank, replayable (golden-ratio
        low-discrepancy spread through the CDF, no rng at request
        time)."""
        u = ((i * 2654435761) % (1 << 32)) / float(1 << 32)
        return int(self._np.searchsorted(self._cdf, u,
                                         side="right"))

    def body(self, i: int) -> dict:
        r = min(self.rank_of(i), len(self.queries) - 1)
        b = {"vector": [float(x) for x in self.queries[r]],
             "k": self.k}
        if self.nprobe is not None:
            b["nprobe"] = int(self.nprobe)
        return b

    def make_response_cb(self, lock: threading.Lock,
                         acc: Dict[str, float]):
        """Recall accumulator fed by LoadGen's response hook: the
        ordinal recomputes its pool rank deterministically, so no
        state rides in the request."""
        def cb(i: int, data: bytes) -> None:
            r = min(self.rank_of(i), len(self.queries) - 1)
            got = json.loads(data.decode())
            ids = {int(e["id"]) for e in got["results"][0]}
            hits = len(ids & self._oracle[r])
            with lock:
                acc["hits"] = acc.get("hits", 0.0) + hits
                acc["total"] = acc.get("total", 0.0) + self.k
        return cb

    def recall(self, acc: Dict[str, float]) -> Optional[float]:
        if not acc.get("total"):
            return None
        return round(acc["hits"] / acc["total"], 4)


def _histogram_quantiles(buckets: Dict[float, float], count: float):
    """p50/p95/p99 from cumulative Prometheus buckets (upper-edge
    estimate, matching how coarse scrape-side quantiles are always
    read)."""
    out = {}
    edges = sorted(buckets)
    finite = [e for e in edges if e != float("inf")]
    for name, q in (("p50", 0.5), ("p95", 0.95), ("p99", 0.99)):
        target = q * count
        val = None
        for e in edges:
            if buckets[e] >= target:
                val = e
                break
        if val is None or val == float("inf"):
            # an observation above every finite bucket: report the
            # highest finite edge (the standard scrape-side clamp)
            val = finite[-1] if finite else 0.0
        out[name] = round(val * 1e3, 3)
    return out


def _label_value(line: str, label: str) -> Optional[str]:
    marker = label + '="'
    at = line.find(marker)
    if at < 0:
        return None
    return line[at + len(marker):line.index('"', at + len(marker))]


def _accumulate_histogram(text: str, metric: str,
                          buckets: Dict[float, float],
                          counts: Dict[str, float],
                          pop_buckets: Dict[str, Dict[float, float]],
                          pop_counts: Dict[str, float]) -> None:
    """Fold one Prometheus exposition's ``metric`` histogram lines
    into running bucket/count accumulators (overall + split by the
    ``population`` label): the parser behind the per-server scrape
    below."""
    for line in text.splitlines():
        if not line.startswith(metric):
            continue
        rest = line[len(metric):]
        try:
            value = float(line.rsplit(" ", 1)[1])
        except (IndexError, ValueError):
            continue
        pop = _label_value(line, "population")
        if rest.startswith("_bucket"):
            le = _label_value(line, "le")
            if le is None:
                continue
            edge = float("inf") if le in ("+Inf", "inf") \
                else float(le)
            buckets[edge] = buckets.get(edge, 0.0) + value
            if pop is not None:
                pb = pop_buckets.setdefault(pop, {})
                pb[edge] = pb.get(edge, 0.0) + value
        elif rest.startswith("_count"):
            counts["total"] = counts.get("total", 0.0) + value
            if pop is not None:
                pop_counts[pop] = pop_counts.get(pop, 0.0) + value


def _quantile_entry(buckets: Dict[float, float],
                    count: float) -> dict:
    entry = {"count": int(count)}
    entry.update(_histogram_quantiles(buckets, count)
                 if count else {"p50": 0.0, "p95": 0.0,
                                "p99": 0.0})
    return entry


def _fetch_exposition(url: str, timeout_s: float) -> str:
    req = urllib.request.Request(
        url.rstrip("/") + "/metrics?format=prometheus")
    with urllib.request.urlopen(req, timeout=timeout_s) as r:
        return r.read().decode()


def scrape_streaming_latency(url: str,
                             timeout_s: float = 5.0) -> dict:
    """TTFT / inter-token latency percentiles from a server's OWN
    metrics: parses the Prometheus exposition's
    ``serving_ttft_seconds`` / ``serving_itl_seconds`` histograms
    (buckets summed across model versions). Returns
    ``{metric: {count, p50, p95, p99}}`` in milliseconds; TTFT is
    ADDITIONALLY split by the ``population`` label into ``cold``
    vs ``prefix_hit`` sub-entries — the headline ratio of prefix
    caching / KV-aware routing, measurable without
    post-processing."""
    text = _fetch_exposition(url, timeout_s)
    out = {}
    for metric in ("serving_ttft_seconds", "serving_itl_seconds"):
        buckets: Dict[float, float] = {}
        counts: Dict[str, float] = {}
        pop_buckets: Dict[str, Dict[float, float]] = {}
        pop_counts: Dict[str, float] = {}
        _accumulate_histogram(text, metric, buckets, counts,
                              pop_buckets, pop_counts)
        entry = _quantile_entry(buckets, counts.get("total", 0.0))
        for pop, pc in pop_counts.items():
            entry[pop] = _quantile_entry(pop_buckets[pop], pc)
        out[metric] = entry
    return out


def scrape_version_breakdown(url: str,
                             timeout_s: float = 5.0) -> dict:
    """Per-MODEL-VERSION outcome split from the router's own
    per-version accounting (``router_version_requests_total`` /
    ``router_version_errors_total`` /
    ``router_version_latency_seconds``, all labeled ``version``):
    ``{version: {ok, failed, p99_ms}}`` — during a canary rollout
    this is the client-side read of how each version actually
    behaved, split exactly the way the promotion gate saw it.
    Returns ``{}`` against a target without version series (a bare
    ModelServer)."""
    text = _fetch_exposition(url, timeout_s)
    req: Dict[str, float] = {}
    err: Dict[str, float] = {}
    buckets: Dict[str, Dict[float, float]] = {}
    counts: Dict[str, float] = {}
    for line in text.splitlines():
        if not line.startswith("router_version_"):
            continue
        ver = _label_value(line, "version")
        if ver is None:
            continue
        try:
            value = float(line.rsplit(" ", 1)[1])
        except (IndexError, ValueError):
            continue
        if line.startswith("router_version_requests_total"):
            req[ver] = req.get(ver, 0.0) + value
        elif line.startswith("router_version_errors_total"):
            err[ver] = err.get(ver, 0.0) + value
        elif line.startswith(
                "router_version_latency_seconds_bucket"):
            le = _label_value(line, "le")
            if le is None:
                continue
            edge = float("inf") if le in ("+Inf", "inf") \
                else float(le)
            vb = buckets.setdefault(ver, {})
            vb[edge] = vb.get(edge, 0.0) + value
        elif line.startswith(
                "router_version_latency_seconds_count"):
            counts[ver] = counts.get(ver, 0.0) + value
    out = {}
    for ver in sorted(req, key=lambda v: (len(v), v)):
        failed = int(err.get(ver, 0.0))
        entry = {"ok": int(req[ver]) - failed, "failed": failed}
        n = counts.get(ver, 0.0)
        entry["p99_ms"] = _histogram_quantiles(
            buckets.get(ver, {}), n)["p99"] if n else 0.0
        out[ver] = entry
    return out


class LoadGen:
    """Open/closed-loop HTTP load generator with registry-backed
    latency percentiles."""

    def __init__(self, url: str, route: str = "/v1/predict",
                 body_fn: Optional[Callable[[int], dict]] = None,
                 concurrency: int = 8,
                 qps: Optional[float] = None,
                 duration_s: Optional[float] = None,
                 total: Optional[int] = None,
                 timeout_s: float = 10.0,
                 max_retries: int = 2,
                 honor_retry_after: bool = True,
                 backlog_limit: Optional[int] = None,
                 profile: Optional[Callable] = None,
                 registry=None,
                 response_cb: Optional[Callable[[int, bytes],
                                               None]] = None):
        if duration_s is None and total is None:
            raise ValueError("give duration_s or total")
        from deeplearning4j_tpu.observability.registry import (
            MetricsRegistry)
        self.url = url.rstrip("/")
        self.route = route
        self.body_fn = body_fn or _default_body
        self.concurrency = max(1, concurrency)
        self.qps = qps
        self.profile = profile
        self.duration_s = duration_s
        self.total = total
        self.timeout_s = timeout_s
        self.max_retries = max_retries
        self.honor_retry_after = honor_retry_after
        self.backlog_limit = (backlog_limit if backlog_limit
                              is not None else 8 * self.concurrency)
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        # optional per-success body hook — the search mode's recall
        # accounting reads the returned neighbor ids through it
        self.response_cb = response_cb
        self.latency = self.registry.histogram(
            "loadgen_latency_seconds",
            help="client-observed request latency (seconds)",
            labels={"route": route})
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {
            "sent": 0, "ok": 0, "failed": 0, "retries": 0,
            "not_sent": 0, "retry_after_honored": 0}
        self._errors: Dict[str, int] = {}
        self._error_classes: Dict[str, int] = {}
        # per-tier outcome + latency accounting (created lazily on
        # the first tiered body; untiered runs pay nothing)
        self._tier_counts: Dict[str, Dict[str, int]] = {}
        self._tier_errors: Dict[str, Dict[str, int]] = {}
        self._tier_latency: Dict[str, object] = {}
        self._stop = threading.Event()

    def _tier_state(self, tier: str):
        with self._lock:
            if tier not in self._tier_counts:
                self._tier_counts[tier] = {
                    "sent": 0, "ok": 0, "failed": 0, "retries": 0,
                    "shed": 0}
                self._tier_errors[tier] = {}
                self._tier_latency[tier] = self.registry.histogram(
                    "loadgen_latency_seconds",
                    help="client-observed request latency (seconds)",
                    labels={"route": self.route, "tier": tier})
            return (self._tier_counts[tier], self._tier_errors[tier],
                    self._tier_latency[tier])

    # ---- one request, with backoff-aware retries ----
    def _once(self, i: int) -> None:
        body_obj = self.body_fn(i)
        tier = body_obj.get("tier")
        tc = te = th = None
        if tier is not None:
            tc, te, th = self._tier_state(str(tier))
        body = json.dumps(body_obj).encode()
        deadline = time.monotonic() + self.timeout_s
        attempts = 0
        with self._lock:
            # one REQUEST sent (retries are counted separately), so
            # sent == ok + failed holds and a drop rate computed
            # from sent vs ok is honest under failover
            self._counts["sent"] += 1
            if tc is not None:
                tc["sent"] += 1
        t0 = time.perf_counter()

        def record():
            # the ONE terminal latency record (success, retries
            # exhausted, deadline): whole-request wall time into the
            # route histogram and, when tiered, the tier's
            dt = time.perf_counter() - t0
            self.latency.record(dt)
            if th is not None:
                th.record(dt)

        while True:
            attempts += 1
            status, retry_after, data, klass = self._fire(body,
                                                          deadline)
            if klass is not None:
                with self._lock:
                    # every error OCCURRENCE by class, retried or
                    # not: a zero-drop soak still asserts which
                    # failure mode its retries absorbed
                    self._error_classes[klass] = \
                        self._error_classes.get(klass, 0) + 1
            if status in (429, 503) and tc is not None:
                with self._lock:
                    # every shed response the tier absorbed, retried
                    # or not — the "best-effort degraded first"
                    # evidence
                    tc["shed"] += 1
            if status == 200:
                record()
                with self._lock:
                    self._counts["ok"] += 1
                    if tc is not None:
                        tc["ok"] += 1
                if self.response_cb is not None:
                    try:
                        self.response_cb(i, data)
                    except Exception:
                        pass        # accounting hook, never fatal
                return
            retryable = status in ("neterr", 429, 503)
            with self._lock:
                if attempts <= self.max_retries and retryable:
                    self._counts["retries"] += 1
                    if tc is not None:
                        tc["retries"] += 1
                else:
                    self._counts["failed"] += 1
                    # terminal network failures keep their CLASS as
                    # the key ("timeout", "reset", ...), not an
                    # opaque "neterr"
                    key = klass if status == "neterr" \
                        else str(status)
                    self._errors[key] = self._errors.get(key, 0) + 1
                    if tc is not None:
                        tc["failed"] += 1
                        te[key] = te.get(key, 0) + 1
            if attempts > self.max_retries or not retryable:
                record()
                return
            if retry_after and self.honor_retry_after:
                wait = min(retry_after,
                           max(0.0, deadline - time.monotonic()))
                if wait > 0:
                    with self._lock:
                        self._counts["retry_after_honored"] += 1
                    time.sleep(wait)
            if time.monotonic() >= deadline:
                with self._lock:
                    self._counts["failed"] += 1
                    self._errors["deadline"] = \
                        self._errors.get("deadline", 0) + 1
                    if tc is not None:
                        tc["failed"] += 1
                        te["deadline"] = te.get("deadline", 0) + 1
                record()
                return

    @staticmethod
    def _classify(e: BaseException) -> str:
        """The error-class taxonomy a chaos soak asserts against.
        Unwraps urllib's URLError so a refused connect classifies
        the same whether the OS error arrived bare or wrapped."""
        if isinstance(e, urllib.error.URLError) \
                and isinstance(e.reason, BaseException):
            e = e.reason
        if isinstance(e, ConnectionRefusedError):
            return "connect_refused"
        if isinstance(e, (ConnectionResetError, BrokenPipeError,
                          http.client.RemoteDisconnected)):
            return "reset"
        if isinstance(e, (TimeoutError, socket.timeout)):
            return "timeout"
        if isinstance(e, http.client.IncompleteRead):
            return "bad_body"
        if isinstance(e, http.client.HTTPException):
            # BadStatusLine & co: the response bytes were mangled
            # mid-stream (a reset or corruption inside the status
            # line) — the body never parsed as HTTP at all
            return "bad_body"
        return "neterr"

    def _fire(self, body: bytes, deadline: float):
        """(status | "neterr", retry_after_seconds or None, body,
        error class or None). A 2xx whose body is not the JSON the
        server framed (truncated / corrupted on the wire) is a
        ``bad_body`` network error, never a success — and never a
        raw exception unwinding a worker thread."""
        timeout = max(0.05, deadline - time.monotonic())
        req = urllib.request.Request(
            self.url + self.route, data=body,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                status, data = r.status, r.read()
        except urllib.error.HTTPError as e:
            e.read()
            ra = e.headers.get("Retry-After")
            try:
                ra = float(ra) if ra is not None else None
            except ValueError:
                ra = None
            klass = ("shed_429_503" if e.code in (429, 503)
                     else "5xx" if e.code >= 500 else "4xx")
            return e.code, ra, None, klass
        except (urllib.error.URLError, OSError, TimeoutError,
                http.client.HTTPException) as e:
            return "neterr", None, None, self._classify(e)
        try:
            json.loads(data.decode())
        except (ValueError, UnicodeDecodeError):
            return "neterr", None, None, "bad_body"
        return status, None, data, None

    # ---- loop disciplines ----
    def _closed_loop(self) -> None:
        seq = threading.Lock()
        counter = [0]
        t_end = (time.monotonic() + self.duration_s
                 if self.duration_s is not None else None)

        def worker():
            while not self._stop.is_set():
                with seq:
                    i = counter[0]
                    counter[0] += 1
                if self.total is not None and i >= self.total:
                    return
                if t_end is not None and time.monotonic() >= t_end:
                    return
                self._once(i)

        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(self.concurrency)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    def _open_loop(self) -> None:
        work: "queue.Queue" = queue.Queue(self.backlog_limit)

        def worker():
            while True:
                try:
                    # heartbeat get (GL008): a wedged arrival loop
                    # must not strand workers in a blocking get
                    # forever — they re-check the stop flag instead
                    i = work.get(timeout=0.5)
                except queue.Empty:
                    if self._stop.is_set():
                        return
                    continue
                if i is None:
                    return
                self._once(i)

        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(self.concurrency)]
        for t in threads:
            t.start()
        interval = (1.0 / float(self.qps)
                    if self.profile is None else None)
        t_start = time.monotonic()
        t_end = (t_start + self.duration_s
                 if self.duration_s is not None else None)
        i = 0
        next_t = t_start
        while not self._stop.is_set():
            if self.total is not None and i >= self.total:
                break
            now = time.monotonic()
            if t_end is not None and now >= t_end:
                break
            if self.profile is not None:
                # time-varying schedule (step / ramp): re-read the
                # target rate every pass so a QPS step lands at its
                # scheduled second, not an arrival later
                rate = float(self.profile(now - t_start,
                                          self.duration_s))
                if rate <= 0:
                    # a zero-rate phase owes no arrivals: idle, and
                    # re-anchor the schedule so the next nonzero
                    # phase starts from NOW instead of replaying a
                    # backlog of arrivals the schedule never asked
                    # for
                    next_t = now + 0.05
                    time.sleep(0.05)
                    continue
                interval = 1.0 / rate
            if now < next_t:
                time.sleep(min(next_t - now, 0.05))
                continue
            # the OPEN-loop contract: this arrival happens NOW
            # whether or not the system kept up; a full backlog is a
            # client that gave up, not a schedule that stretched
            try:
                work.put_nowait(i)
            except queue.Full:
                with self._lock:
                    self._counts["not_sent"] += 1
            i += 1
            next_t += interval
        for _ in threads:
            work.put(None)
        for t in threads:
            t.join()

    # ---- entry ----
    def run(self) -> dict:
        t0 = time.monotonic()
        if self.qps is None and self.profile is None:
            self._closed_loop()
        else:
            self._open_loop()
        wall = time.monotonic() - t0
        with self._lock:
            counts = dict(self._counts)
            errors = dict(self._errors)
            error_classes = dict(self._error_classes)
        snap = self.latency.snapshot()
        report = {
            "route": self.route,
            "mode": ("closed" if self.qps is None
                     and self.profile is None else "open"),
            "target_qps": self.qps,
            "concurrency": self.concurrency,
            "wall_s": round(wall, 3),
            "achieved_qps": round(counts["ok"] / wall, 1)
            if wall > 0 else 0.0,
            "latency_ms": {
                "p50": round(self.latency.quantile(0.50) * 1e3, 3),
                "p95": round(self.latency.quantile(0.95) * 1e3, 3),
                "p99": round(self.latency.quantile(0.99) * 1e3, 3),
                "mean": round(snap["sum"] / snap["count"] * 1e3, 3)
                if snap["count"] else 0.0},
            "errors": errors,
            "error_classes": error_classes,
        }
        report.update(counts)
        with self._lock:
            tier_counts = {t: dict(c)
                           for t, c in self._tier_counts.items()}
            tier_errors = {t: dict(e)
                           for t, e in self._tier_errors.items()}
            tier_hists = dict(self._tier_latency)
        if tier_counts:
            tiers_rep = {}
            for t, c in tier_counts.items():
                h = tier_hists[t]
                entry = dict(c)
                entry["errors"] = tier_errors.get(t, {})
                entry["latency_ms"] = {
                    "p50": round(h.quantile(0.50) * 1e3, 3),
                    "p95": round(h.quantile(0.95) * 1e3, 3),
                    "p99": round(h.quantile(0.99) * 1e3, 3)}
                tiers_rep[t] = entry
            report["tiers"] = tiers_rep
        return report

    def stop(self) -> None:
        self._stop.set()


def main(argv=None):
    p = argparse.ArgumentParser(
        prog="loadgen",
        description="open/closed-loop load generator for the "
                    "serving router / ModelServer")
    p.add_argument("--url", required=True,
                   help="base URL (router or replica)")
    p.add_argument("--route", default=None,
                   help="override the request path (default: by "
                        "--mode)")
    p.add_argument("--mode", choices=("predict", "generate",
                                      "search"),
                   default="predict",
                   help="predict = one-shot /v1/predict bodies; "
                        "generate = streaming /v1/generate bodies "
                        "with a duplicate-prompt mix; search = "
                        "Zipf-skewed /v1/search queries over "
                        "--corpus with a client-side recall@k "
                        "oracle")
    p.add_argument("--model", default="default")
    p.add_argument("--features", type=int, default=4,
                   help="input feature count for the default "
                        "predict body")
    p.add_argument("--prompt-len", type=int, default=16,
                   help="generate mode: prompt tokens per request")
    p.add_argument("--n-tokens", type=int, default=16,
                   help="generate mode: tokens to decode per request")
    p.add_argument("--vocab", type=int, default=64,
                   help="generate mode: prompt ids drawn from "
                        "[1, vocab)")
    p.add_argument("--dup-ratio", type=float, default=0.0,
                   help="generate mode: fraction of requests reusing "
                        "ONE shared prompt (prefix-cache hits after "
                        "the first completes)")
    p.add_argument("--metrics-url", default=None,
                   help="generate mode: scrape TTFT/ITL histogram "
                        "percentiles from this server after the run "
                        "(default: --url; 'off' disables)")
    p.add_argument("--corpus", default=None, metavar="SPEC",
                   help="search mode: the corpus the TARGET serves "
                        "('random:n=..,dim=..,seed=..' or .npz) — "
                        "must match the server's --index so the "
                        "recall oracle is exact")
    p.add_argument("--k", type=int, default=10,
                   help="search mode: neighbors per query")
    p.add_argument("--nprobe", type=int, default=None,
                   help="search mode: IVF cells probed (omit for "
                        "the server default)")
    p.add_argument("--metric", default="cosine",
                   choices=("cosine", "dot", "euclidean"),
                   help="search mode: oracle metric (match the "
                        "server's --index-metric)")
    p.add_argument("--zipf-s", type=float, default=1.1,
                   help="search mode: Zipf skew exponent of the "
                        "query popularity distribution")
    p.add_argument("--query-pool", type=int, default=256,
                   help="search mode: distinct query count")
    p.add_argument("--query-noise", type=float, default=0.05,
                   help="search mode: gaussian noise stddev added "
                        "to each pooled corpus vector")
    p.add_argument("--seed", type=int, default=0,
                   help="search mode: query pool seed")
    p.add_argument("--concurrency", type=int, default=8)
    p.add_argument("--qps", type=float, default=None,
                   help="open-loop target rate; omit for closed "
                        "loop")
    p.add_argument("--profile", default=None, metavar="SPEC",
                   help="open-loop QPS schedule: 'step:LOW:HIGH:AT"
                        "[:UNTIL]' (LOW q/s, stepping to HIGH at AT "
                        "seconds) or 'ramp:LOW:HIGH' (linear over "
                        "the run) — the autoscaler soak's traffic "
                        "shape; overrides --qps")
    p.add_argument("--tier-mix", default=None, metavar="MIX",
                   help="per-tier request mix, e.g. "
                        "'gold=0.2,standard=0.5,best_effort=0.3': "
                        "each request carries a deterministically "
                        "assigned tier and the report adds per-tier "
                        "latency/outcome percentiles")
    p.add_argument("--duration", type=float, default=None,
                   help="seconds to run")
    p.add_argument("--total", type=int, default=None,
                   help="total requests (alternative to --duration)")
    p.add_argument("--timeout", type=float, default=10.0,
                   help="per-request budget incl. retries (seconds)")
    p.add_argument("--retries", type=int, default=2)
    p.add_argument("--out", default=None, metavar="PATH",
                   help="also write the full report as JSON to PATH "
                        "(machine-readable: the fleet collector "
                        "tests read this instead of parsing "
                        "stdout)")
    args = p.parse_args(argv)
    if args.duration is None and args.total is None:
        args.duration = 10.0

    workload = None
    recall_lock = threading.Lock()
    recall_acc: Dict[str, float] = {}
    if args.mode == "generate":
        route = args.route or "/v1/generate"
        body = generate_body_fn(model=args.model,
                                prompt_len=args.prompt_len,
                                n_tokens=args.n_tokens,
                                vocab=args.vocab,
                                dup_ratio=args.dup_ratio)
    elif args.mode == "search":
        if not args.corpus:
            p.error("--mode search needs --corpus (the same spec "
                    "the server's --index loaded)")
        from deeplearning4j_tpu.cli import _load_corpus
        try:
            ids, vectors, _, _ = _load_corpus(args.corpus)
        except SystemExit as e:
            p.error(str(e))
        route = args.route or "/v1/search"
        workload = SearchWorkload(
            vectors, ids=ids, k=args.k, nprobe=args.nprobe,
            metric=args.metric, pool=args.query_pool,
            zipf_s=args.zipf_s, noise=args.query_noise,
            seed=args.seed)
        body = workload.body
    else:
        route = args.route or "/v1/predict"

        def body(i, model=args.model, feat=args.features):
            return {"model": model,
                    "inputs": [[float((i + j) % 7)
                                for j in range(feat)]]}

    try:
        mix = parse_tier_mix(args.tier_mix)
        profile = parse_profile(args.profile)
    except ValueError as e:
        p.error(str(e))
    if mix is not None:
        body = tiered_body_fn(body, mix)
    if profile is not None and args.duration is None:
        p.error("--profile needs --duration (the schedule is "
                "expressed in run seconds)")
    gen = LoadGen(args.url, route=route, body_fn=body,
                  concurrency=args.concurrency, qps=args.qps,
                  profile=profile,
                  duration_s=args.duration, total=args.total,
                  timeout_s=args.timeout, max_retries=args.retries,
                  response_cb=workload.make_response_cb(
                      recall_lock, recall_acc)
                  if workload is not None else None)
    try:
        report = gen.run()
    except KeyboardInterrupt:
        gen.stop()
        report = {"interrupted": True}
    if workload is not None:
        with recall_lock:
            report["search"] = {
                "recall_at_k": workload.recall(recall_acc),
                "k": args.k, "nprobe": args.nprobe,
                "metric": args.metric, "zipf_s": args.zipf_s,
                "query_pool": len(workload.queries),
                "scored": int(recall_acc.get("total", 0)
                              // max(args.k, 1))}
    if args.mode == "generate" and args.metrics_url != "off":
        # the serving stack's OWN streaming histograms: TTFT / ITL
        # percentiles as the server measured them, not a client proxy
        try:
            report["streaming"] = scrape_streaming_latency(
                args.metrics_url or args.url)
            report["dup_ratio"] = args.dup_ratio
        except Exception as e:        # scrape is best-effort
            report["streaming_error"] = str(e)
    if args.metrics_url != "off":
        # per-model-version outcome split (router targets only):
        # during a rollout the report shows ok/failed/p99 for the
        # incumbent AND the candidate separately
        try:
            versions = scrape_version_breakdown(
                args.metrics_url or args.url)
            if versions:
                report["versions"] = versions
        except Exception:
            pass          # not a router, or no metrics: no split
    json.dump(report, sys.stdout, indent=2)
    sys.stdout.write("\n")
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
    return 0 if not report.get("failed") else 1


if __name__ == "__main__":
    sys.exit(main())
