"""Serving metrics: latency histograms, queue depth, batch occupancy.

The observable surface of the serving stack (per-endpoint p50/p95/p99
latency, queue depth, batch occupancy actual/max, shed count),
exported as one JSON snapshot on ``/metrics`` and feedable into the
existing ``ui/stats.py`` storage so the training dashboard's plumbing
(InMemoryStatsStorage / FileStatsStorage, the remote-POST route)
carries serving telemetry too.

Since the observability subsystem landed, every instrument here is
backed by the unified registry
(``deeplearning4j_tpu/observability/registry.py``): the histogram /
quantile code that used to live in this file moved there, counters
and queue-depth gauges register as labeled Prometheus families, and
``prometheus_text()`` renders the standard exposition the
``/metrics`` endpoint now serves to scrapers. Each ``ServingMetrics``
owns its registry by default (parallel test servers must not share
counters); pass ``registry=observability.REGISTRY`` to join the
process-wide pipe with training metrics.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Callable, Dict, List, Optional

from deeplearning4j_tpu.observability.registry import (
    Histogram, MetricsRegistry, default_latency_buckets,
)

__all__ = ["LatencyHistogram", "EndpointMetrics", "BatchOccupancy",
           "BatcherStepMetrics", "ServingMetrics"]


_EDGES = default_latency_buckets()    # seconds; +1 overflow at the end


class LatencyHistogram(Histogram):
    """Log-bucketed latency histogram (seconds in, ms out) — the
    registry Histogram with the serving snapshot shape preserved."""

    def __init__(self, name: str = "serving_latency_seconds",
                 labels: Optional[Dict[str, str]] = None):
        super().__init__(name, help="request latency (seconds)",
                         labels=labels, buckets=_EDGES)

    def snapshot(self) -> dict:
        with self._lock:
            count, total = self.count, self.sum
        return {"count": count,
                "mean_ms": round(total / count * 1e3, 3) if count else 0.0,
                "p50_ms": round(self.quantile(0.50) * 1e3, 3),
                "p95_ms": round(self.quantile(0.95) * 1e3, 3),
                "p99_ms": round(self.quantile(0.99) * 1e3, 3)}


class EndpointMetrics:
    """Counters + latency histogram for one endpoint, registered as
    ``serving_*`` Prometheus families labeled by endpoint."""

    _RATE_WINDOW = 30.0           # seconds of completions behind the
    _RATE_EVENTS = 4096           # current-rate estimate

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 name: str = "endpoint"):
        reg = registry or MetricsRegistry()
        lbl = {"endpoint": name}
        self.name = name
        self._registry = reg
        # per-phase latency histograms (serving_phase_seconds), keyed
        # by phase name; phases form a small fixed set per backend so
        # this cache stays tiny — instruments are created once per
        # (endpoint, phase), never per request
        self._phases: Dict[str, Histogram] = {}
        self._lock = threading.Lock()
        self._requests = reg.counter(
            "serving_requests_total", help="completed requests",
            labels=lbl)
        self._errors = reg.counter(
            "serving_errors_total", help="errored responses",
            labels=lbl)
        self._shed = reg.counter(
            "serving_shed_total", help="load-shed (QueueFullError)",
            labels=lbl)
        self._expired = reg.counter(
            "serving_deadline_expired_total", help="deadline expiry",
            labels=lbl)
        # atomic get-or-adopt, matching the counters' get-or-create:
        # two EndpointMetrics for one endpoint on a SHARED registry
        # (the process-wide pipe) must merge, not raise
        self.latency = reg.adopt(LatencyHistogram(labels=lbl))
        self._recent = collections.deque(maxlen=self._RATE_EVENTS)
        self._t0 = time.monotonic()

    # int views preserving the pre-registry attribute API
    @property
    def requests(self) -> int:
        return int(self._requests.value)

    @property
    def errors(self) -> int:
        return int(self._errors.value)

    @property
    def shed(self) -> int:
        return int(self._shed.value)

    @property
    def expired(self) -> int:
        return int(self._expired.value)

    def observe(self, seconds: float,
                trace_id: Optional[str] = None) -> None:
        self._requests.inc()
        with self._lock:
            self._recent.append(time.monotonic())
        # a sampled request leaves its trace id as the bucket's
        # exemplar: the /metrics p99 spike links to a concrete trace
        self.latency.record(
            seconds,
            exemplar={"trace_id": trace_id} if trace_id else None)

    def phase_histogram(self, phase: str) -> Histogram:
        with self._lock:
            h = self._phases.get(phase)
            if h is None:
                h = self._phases[phase] = self._registry.histogram(
                    "serving_phase_seconds",
                    help="per-phase request latency decomposition "
                         "(seconds)",
                    labels={"endpoint": self.name, "phase": phase},
                    buckets=_EDGES)
            return h

    def record_phases(self, phases: Dict[str, float],
                      trace_id: Optional[str] = None) -> None:
        """Record one completed request's phase ledger. Phases are
        contiguous segments of the request's wall time, so per-phase
        histogram sums reconcile against the whole-request histogram
        (the latency-attribution contract)."""
        ex = {"trace_id": trace_id} if trace_id else None
        for phase, dur in phases.items():
            self.phase_histogram(phase).record(dur, exemplar=ex)

    def count_error(self) -> None:
        # an errored response is still a completed request: folding it
        # into ``requests`` keeps requests_per_sec honest during an
        # outage (error rate can never exceed 100%) — requests FIRST,
        # so a concurrent scrape never reads errors > requests
        self._requests.inc()
        self._errors.inc()
        with self._lock:
            self._recent.append(time.monotonic())

    def count_shed(self) -> None:
        self._shed.inc()

    def count_expired(self) -> None:
        self._expired.inc()

    def snapshot(self) -> dict:
        now = time.monotonic()
        # errors read BEFORE requests: count_error increments requests
        # first, so any error this read observes already has its
        # request counted — a scrape can never see errors > requests
        errors = self.errors
        out = {"requests": self.requests, "errors": errors,
               "shed": self.shed, "deadline_expired": self.expired}
        with self._lock:
            recent = list(self._recent)
        # CURRENT rate over a sliding window, not a lifetime average
        # (a lifetime mean can never show a traffic drop). If the
        # event ring overflowed inside the window, the true rate is
        # higher — use the ring's own span as the denominator then.
        n = sum(1 for t in recent if t >= now - self._RATE_WINDOW)
        if n >= self._RATE_EVENTS:
            span = max(now - recent[0], 1e-9)
        else:
            span = min(self._RATE_WINDOW, max(now - self._t0, 1e-9))
        out["requests_per_sec"] = round(n / span, 2)
        out["latency"] = self.latency.snapshot()
        return out


class BatchOccupancy:
    """How full the coalesced device calls actually are — THE number
    that says whether dynamic/continuous batching is working (avg 1.0
    under load means the batcher degraded to sequential serving)."""

    def __init__(self, max_batch_size: int,
                 registry: Optional[MetricsRegistry] = None,
                 name: str = "batch"):
        reg = registry or MetricsRegistry()
        lbl = {"endpoint": name}
        self._lock = threading.Lock()
        self.max_batch_size = max_batch_size
        self._batches = reg.counter(
            "serving_batches_total", help="coalesced device calls",
            labels=lbl)
        self._items = reg.counter(
            "serving_batch_items_total",
            help="items across coalesced calls", labels=lbl)
        self.max_seen = 0

    @property
    def batches(self) -> int:
        return int(self._batches.value)

    @property
    def items(self) -> int:
        return int(self._items.value)

    def record(self, n_items: int) -> None:
        self._batches.inc()
        self._items.inc(n_items)
        with self._lock:
            self.max_seen = max(self.max_seen, n_items)

    def snapshot(self) -> dict:
        b, i = self.batches, self.items
        with self._lock:
            m = self.max_seen
        return {"batches": b, "items": i,
                "avg_batch_size": round(i / b, 3) if b else 0.0,
                "max_batch_size_seen": m,
                "max_batch_size": self.max_batch_size}


class BatcherStepMetrics:
    """One continuous-batcher step, seen from inside its loop:
    ``serving_step_seconds{part}`` splits a pass's wall time into
    ``admit`` (the scheduling: migration service, queue pump, expiry,
    admission, planning the step and moving the slots past it),
    ``device`` (the step's enqueue and the wait for the ids that are
    due: the previous step's where the loop runs one step ahead, the
    step's own in a synchronous pass;
    ``serving_step_enqueue_seconds`` is the enqueue alone, the host's
    dispatch of the step) and ``sample`` (their
    delivery: host sampling where a request has a temperature,
    bookkeeping, waking waiters);
    ``serving_lookahead_steps_total`` counts the steps enqueued while
    the previous step's ids were not yet on the host;
    ``serving_slot_steps_total{kind}`` counts what each live slot did
    with the step: ``prompt`` (fed prompt tokens only, output
    discarded) or ``decode`` (emitted a token: a chunk that carried
    its prompt's last token counts here);
    ``serving_steps_total{program}`` counts the steps by the program
    they ran, ``single`` (slots, 1) or ``chunk`` (slots, t), and
    ``serving_prompt_tokens_total`` the prompt tokens they fed to the
    device. A pool that holds a second, wider chunk program counts its
    steps under ``chunk`` too, and again in
    ``serving_wide_steps_total``, a series only such a pool has
    (``holds_wide_program``); the gauge ``serving_chunk_rows`` says,
    by ``program`` (``narrow``, ``wide``), how many rows a step of
    each chunk program the pool holds carries (``holds_chunk_rows``).
    ``serving_moe_grouped_steps_total``
    counts the steps whose program runs its expert layers' held
    experts as the grouped pass over the selected pairs, a series
    only a session that holds such a program has
    (``holds_grouped_program``).
    ``serving_kv_positions_read_total`` adds the KV positions a
    step's attention layers read (by table: each slot's pages up to
    its length; by gather: every slot's whole capacity) as the
    session ACCOUNTS them from the lengths it feeds and its layers'
    dispatch, not as the device measured them, and
    ``serving_kv_positions_spanned_total`` the slots x capacity the
    page tables span; both exist only over a paged pool, and count
    the layers whose cache lives in the allocator's pages. Over a
    network with a layer that keeps a ring of pages a slot (a sliding
    window), ``serving_kv_ring_pages_held_total`` adds, a step, the
    ring pages of the slots it fed that hold a position the ring
    still keeps, ``serving_kv_ring_pages_full_total`` the pages the
    same slots would hold had that kind kept every position, and
    ``serving_kv_ring_wraps_total`` the ring pages a write of the
    step began to reuse (0: the traffic never outgrew a ring). Over
    a network with a layer that keeps a fixed-size state in a row a
    slot, ``serving_state_rows_restarted_total`` adds, a step, the
    slots that began a request on a row an earlier request had
    written (the layer starts such a slot from zeros by its position;
    nothing is zeroed), and the gauge ``serving_state_pool_bytes``
    holds the size of those pools. The
    request-phase histograms time a request from outside the steps
    that serve it; these say what a step costs and what it was spent
    on."""

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 name: str = "generate"):
        reg = registry or MetricsRegistry()
        self._reg, self._name, self._experts = reg, name, None
        self._kv = self._pairs = self._ring = self._state = None
        self._wide = None       # ``holds_wide_program``
        self._grouped = None    # ``holds_grouped_program``
        self._parts = {
            part: reg.histogram(
                "serving_step_seconds",
                help="continuous-batcher step wall time by part "
                     "(seconds)",
                labels={"endpoint": name, "part": part},
                buckets=_EDGES)
            for part in ("admit", "device", "sample")}
        self._enqueue = reg.histogram(
            "serving_step_enqueue_seconds",
            help="the host's dispatch of a step, of its device part "
                 "(seconds)",
            labels={"endpoint": name}, buckets=_EDGES)
        self._kinds = {
            kind: reg.counter(
                "serving_slot_steps_total",
                help="slot-steps by what the slot did with the step",
                labels={"endpoint": name, "kind": kind})
            for kind in ("prompt", "decode")}
        self._programs = {
            program: reg.counter(
                "serving_steps_total",
                help="device steps by the step program they ran",
                labels={"endpoint": name, "program": program})
            for program in ("single", "chunk")}
        self._prompt_tokens = reg.counter(
            "serving_prompt_tokens_total",
            help="prompt tokens fed to the device",
            labels={"endpoint": name})
        self._ahead = reg.counter(
            "serving_lookahead_steps_total",
            help="device steps enqueued while the previous step's "
                 "ids were not yet on the host",
            labels={"endpoint": name})

    def record(self, admit_s: float, device_s: float, sample_s: float,
               prompt_slots: int, decode_slots: int,
               program: str = "single", prompt_tokens: int = 0,
               ahead: bool = False, enqueue_s: float = 0.0,
               wide: bool = False, grouped: bool = False) -> None:
        self._parts["admit"].record(admit_s)
        self._parts["device"].record(device_s)
        self._parts["sample"].record(sample_s)
        self._enqueue.record(enqueue_s)
        self._kinds["prompt"].inc(prompt_slots)
        self._kinds["decode"].inc(decode_slots)
        self._programs[program].inc()
        self._prompt_tokens.inc(prompt_tokens)
        if ahead:
            self._ahead.inc()
        if wide:
            self._wide.inc()
        if grouped:
            self._grouped.inc()

    def holds_wide_program(self) -> None:
        """The batcher's pool holds a second, wider chunk program:
        ``serving_wide_steps_total`` counts the steps that ran it."""
        self._wide = self._reg.counter(
            "serving_wide_steps_total",
            help="device steps that ran the wide chunk program",
            labels={"endpoint": self._name})

    def holds_chunk_rows(self, narrow: int, wide: int) -> None:
        """What the pool's chunk programs ARE, in rows a step (slots x
        t): the gauge ``serving_chunk_rows`` by ``program``,
        ``narrow`` and, where the pool holds a second one (``wide``
        not 0), ``wide``."""
        for program, rows in (("narrow", narrow), ("wide", wide)):
            if rows:
                # a set value, no callback: nothing of the batcher is
                # held by the series
                labels = {"endpoint": self._name, "program": program}
                self._reg.gauge(
                    "serving_chunk_rows",
                    help="rows (slots x t) of a step of the pool's "
                         "chunk program", labels=labels).set(rows)

    def holds_grouped_program(self) -> None:
        """Some step program of the batcher's session runs its expert
        layers as the grouped pass
        (``PagedSlotSession.runs_grouped_experts``):
        ``serving_moe_grouped_steps_total`` counts the steps that ran
        such a program."""
        self._grouped = self._reg.counter(
            "serving_moe_grouped_steps_total",
            help="device steps whose program runs the held experts "
                 "over the selected pairs alone",
            labels={"endpoint": self._name})

    def record_kv_positions(self, read: int, spanned: int) -> None:
        """One step's KV positions over a paged pool
        (``PagedSlotSession.step_kv_positions``): read over spanned
        is the share of the pool's span a step's attention touches."""
        if self._kv is None:
            self._kv = tuple(
                self._reg.counter(
                    f"serving_kv_positions_{what}_total", help=text,
                    labels={"endpoint": self._name})
                for what, text in (
                    ("read", "KV positions the steps' attention read"),
                    ("spanned", "slots x capacity per step")))
        self._kv[0].inc(read)
        self._kv[1].inc(spanned)

    def record_kv_ring(self, held: int, full: int, wraps: int) -> None:
        """One step's ring pages of a network with a ring layer
        (``PagedSlotSession.step_ring_pages``)."""
        if self._ring is None:
            self._ring = tuple(
                self._reg.counter(
                    f"serving_kv_ring_{what}_total", help=text,
                    labels={"endpoint": self._name})
                for what, text in (
                    ("pages_held", "ring pages of the fed slots that "
                                   "hold a position the ring keeps"),
                    ("pages_full", "pages the same slots would hold "
                                   "had the rings kept every position"),
                    ("wraps", "ring pages a step began to reuse")))
        for counter, n in zip(self._ring, (held, full, wraps)):
            counter.inc(n)

    def record_state_rows(self, restarted: int, pool_bytes: int) -> None:
        """One step of a network with a layer that keeps a fixed-size
        state in a row a slot (``PagedSlotSession
        .step_state_restarts`` / ``.state_pool_bytes``)."""
        if self._state is None:
            labels = {"endpoint": self._name}
            self._state = self._reg.counter(
                "serving_state_rows_restarted_total",
                help="slots a step that began a request on a state row "
                     "an earlier request had written", labels=labels)
            self._reg.gauge(
                "serving_state_pool_bytes",
                help="bytes of the slot-owned state pools",
                labels=labels).set(pool_bytes)
        self._state.inc(restarted)

    def record_experts(self, counts) -> None:
        """One step's auxiliary counts of a network with expert
        layers: ``counts[l, e]`` tokens served by held expert ``e``
        of expert layer ``l``. ``serving_moe_local_pairs_total``
        adds the (token, held expert) pairs computed here,
        ``serving_moe_expert_hits_total`` the (layer, held expert)
        that served at least one token this step, and
        ``serving_moe_expert_slots_total`` the (layer, held expert)
        there were: hits over slots is the share of the held
        experts' weights a step had to read. The series exist only
        for a backend whose network returns such counts.

        A network with zero-compute experts gives a dict:
        ``counts["held"]`` as above, ``counts["zero"][l]`` the
        (token, zero expert) pairs of layer ``l`` and
        ``counts["selected"][l]`` all its (token, selected expert)
        pairs, which feed ``serving_moe_zero_pairs_total`` and
        ``serving_moe_selected_pairs_total``; the three series above
        keep their meaning (held routed experts only)."""
        if isinstance(counts, dict):
            if self._pairs is None:
                self._pairs = {
                    what: self._reg.counter(
                        f"serving_moe_{what}_pairs_total", help=text,
                        labels={"endpoint": self._name})
                    for what, text in (
                        ("zero", "(token, zero-compute expert) pairs "
                                 "the router selected"),
                        ("selected", "(token, selected expert) pairs "
                                     "over the router's whole width"))}
            for what, counter in self._pairs.items():
                counter.inc(int(counts[what].sum()))
            counts = counts["held"]
        if self._experts is None:
            self._experts = {
                what: self._reg.counter(
                    f"serving_moe_{what}_total", help=text,
                    labels={"endpoint": self._name})
                for what, text in (
                    ("local_pairs", "(token, held expert) pairs "
                                    "computed by this replica"),
                    ("expert_hits", "(layer, held expert) that "
                                    "served a token in a step"),
                    ("expert_slots", "(layer, held expert) per "
                                     "step"))}
        self._experts["local_pairs"].inc(int(counts.sum()))
        self._experts["expert_hits"].inc(int((counts > 0).sum()))
        self._experts["expert_slots"].inc(int(counts.size))


class StreamingMetrics:
    """Token-streaming latency for one generate backend:
    time-to-first-token and inter-token latency, labeled by model
    version (``serving_ttft_seconds`` / ``serving_itl_seconds``) —
    the two numbers a whole-request histogram can never show for a
    stream (a fast total can still mean a terrible first-token
    stall)."""

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 name: str = "generate", version: str = "0"):
        reg = registry or MetricsRegistry()
        lbl = {"endpoint": name, "model_version": str(version)}
        # TTFT is split into COLD and PREFIX-HIT populations (the
        # ``population`` label): the headline of prefix caching /
        # KV-aware routing is the gap between the two, and one
        # blended histogram can never show it — scrapers summing
        # both labels recover the old single-series view exactly
        self.ttft = reg.histogram(
            "serving_ttft_seconds",
            help="time from admission to first generated token "
                 "(seconds), cold prefill",
            labels=dict(lbl, population="cold"), buckets=_EDGES)
        self.ttft_hit = reg.histogram(
            "serving_ttft_seconds",
            help="time from admission to first generated token "
                 "(seconds), prefix-hit / imported-lease resume",
            labels=dict(lbl, population="prefix_hit"),
            buckets=_EDGES)
        self.itl = reg.histogram(
            "serving_itl_seconds",
            help="inter-token latency within one stream (seconds)",
            labels=lbl, buckets=_EDGES)

    def record_ttft(self, seconds: float,
                    trace_id: Optional[str] = None,
                    prefix_hit: bool = False) -> None:
        h = self.ttft_hit if prefix_hit else self.ttft
        h.record(
            seconds,
            exemplar={"trace_id": trace_id} if trace_id else None)

    def record_itl(self, seconds: float,
                   trace_id: Optional[str] = None) -> None:
        self.itl.record(
            seconds,
            exemplar={"trace_id": trace_id} if trace_id else None)


class ServingMetrics:
    """Aggregated registry of endpoint metrics, occupancy trackers and
    queue-depth gauges; one ``snapshot()`` is the /metrics JSON
    payload, ``prometheus_text()`` the scraper exposition."""

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self._lock = threading.Lock()
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self._endpoints: Dict[str, EndpointMetrics] = {}
        self._occupancy: Dict[str, BatchOccupancy] = {}
        self._streaming: Dict[tuple, StreamingMetrics] = {}
        self._steps: Dict[str, BatcherStepMetrics] = {}
        self._gauges: Dict[str, Callable[[], float]] = {}
        self._iteration = 0

    def streaming(self, name: str,
                  version: str = "0") -> StreamingMetrics:
        with self._lock:
            key = (name, str(version))
            if key not in self._streaming:
                self._streaming[key] = StreamingMetrics(
                    registry=self.registry, name=name,
                    version=str(version))
            return self._streaming[key]

    def batcher_steps(self, name: str) -> BatcherStepMetrics:
        with self._lock:
            if name not in self._steps:
                self._steps[name] = BatcherStepMetrics(
                    registry=self.registry, name=name)
            return self._steps[name]

    def latency_attribution(self) -> dict:
        """Tail-latency attribution: per endpoint, the whole-request
        p50/p95/p99 decomposed by phase, the dominant phase at each
        quantile, and the phase-sum/whole reconciliation ratio (means
        are additive, so ``phase_sum_over_total`` ~= 1.0 says the
        decomposition accounts for the request's wall time)."""
        whole: Dict[str, Histogram] = {}
        phases: Dict[str, Dict[str, Histogram]] = {}
        for m in self.registry.collect():
            if not isinstance(m, Histogram) or not m.labels:
                continue
            ep = m.labels.get("endpoint")
            if ep is None:
                continue
            if m.name == "serving_latency_seconds":
                whole[ep] = m
            elif m.name == "serving_phase_seconds":
                phases.setdefault(ep, {})[m.labels["phase"]] = m
        out = {}
        for ep, ph in phases.items():
            w = whole.get(ep)
            rep = {"phases_ms": {}, "count": 0}
            if w is not None:
                rep["count"] = w.count
                rep["whole_ms"] = {
                    q: round(w.quantile(p) * 1e3, 3)
                    for q, p in (("p50", .5), ("p95", .95),
                                 ("p99", .99))}
            phase_sum = 0.0
            for name, h in sorted(ph.items()):
                c = h.count
                rep["phases_ms"][name] = {
                    "p50": round(h.quantile(0.50) * 1e3, 3),
                    "p95": round(h.quantile(0.95) * 1e3, 3),
                    "p99": round(h.quantile(0.99) * 1e3, 3),
                    "mean": round(h.sum / c * 1e3, 3) if c else 0.0}
                phase_sum += h.sum
            if rep["phases_ms"]:
                rep["dominant_phase"] = {
                    q: max(rep["phases_ms"],
                           key=lambda n: rep["phases_ms"][n][q])
                    for q in ("p50", "p99")}
            if w is not None and w.sum > 0:
                rep["phase_sum_over_total"] = round(
                    phase_sum / w.sum, 4)
            out[ep] = rep
        return out

    def endpoint(self, name: str) -> EndpointMetrics:
        with self._lock:
            if name not in self._endpoints:
                self._endpoints[name] = EndpointMetrics(
                    registry=self.registry, name=name)
            return self._endpoints[name]

    def occupancy(self, name: str,
                  max_batch_size: int = 0) -> BatchOccupancy:
        with self._lock:
            if name not in self._occupancy:
                self._occupancy[name] = BatchOccupancy(
                    max_batch_size, registry=self.registry, name=name)
            return self._occupancy[name]

    def register_gauge(self, name: str,
                       fn: Callable[[], float]) -> None:
        """A pull gauge (e.g. current queue depth) sampled at
        snapshot/exposition time."""
        with self._lock:
            self._gauges[name] = fn
        self.registry.gauge("serving_gauge",
                            help="registered serving gauges",
                            labels={"name": name}, fn=fn)

    def unregister_gauge(self, name: str) -> None:
        """Drop a gauge (a shut-down scheduler must unhook its
        queue-depth callback, or the bound method pins the backend —
        and its model — in memory forever)."""
        with self._lock:
            self._gauges.pop(name, None)
        self.registry.unregister("serving_gauge",
                                 labels={"name": name})

    def evict_endpoint(self, name: str) -> int:
        """Unregister every instrument labeled with this endpoint
        (``serving_requests_total{endpoint=...}``, latency and phase
        histograms, batch occupancy, streaming TTFT/ITL). A
        long-running server that hot-swaps model versions would
        otherwise accrete one dead label set per retired version —
        the same leak class as the router's per-replica gauges.
        Returns the number of series dropped."""
        with self._lock:
            self._endpoints.pop(name, None)
            self._occupancy.pop(name, None)
            self._steps.pop(name, None)
            for key in [k for k in self._streaming if k[0] == name]:
                self._streaming.pop(key, None)
        dropped = 0
        for m in self.registry.collect():
            if m.labels and m.labels.get("endpoint") == name:
                self.registry.unregister(m.name, labels=m.labels)
                dropped += 1
        return dropped

    def snapshot(self) -> dict:
        with self._lock:
            endpoints = dict(self._endpoints)
            occupancy = dict(self._occupancy)
            gauges = dict(self._gauges)
        out = {"endpoints": {n: e.snapshot()
                             for n, e in endpoints.items()},
               "batching": {n: o.snapshot()
                            for n, o in occupancy.items()},
               "gauges": {}}
        for name, fn in gauges.items():
            try:
                out["gauges"][name] = fn()
            except Exception:
                out["gauges"][name] = None
        return out

    def prometheus_text(self, openmetrics: bool = False) -> str:
        return self.registry.prometheus_text(openmetrics=openmetrics)

    # ---- bridge into the training-UI stats pipeline ----
    def publish_to(self, storage, session_id: str = "serving",
                   endpoint: Optional[str] = None) -> None:
        """Append one StatsReport snapshot to a ``ui/stats.py``
        storage (InMemory or File): serving throughput rides the
        ``samples_per_sec`` series and p50 latency the
        ``duration_ms`` series, so the existing dashboard and its
        remote-POST route chart serving load with zero new wiring."""
        from deeplearning4j_tpu.ui.stats import StatsReport
        snap = self.snapshot()
        eps = snap["endpoints"]
        if endpoint is not None:
            eps = {endpoint: eps[endpoint]} if endpoint in eps else {}
        requests = sum(e["requests"] for e in eps.values())
        rps = sum(e["requests_per_sec"] for e in eps.values())
        p50 = max((e["latency"]["p50_ms"] for e in eps.values()),
                  default=0.0)
        with self._lock:
            self._iteration += 1
            it = self._iteration
        storage.put_update(StatsReport(
            session_id=session_id, worker_id="serving_0", iteration=it,
            timestamp=time.time(), score=float(requests),
            samples_per_sec=float(rps), duration_ms=float(p50)))
