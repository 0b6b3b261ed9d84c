"""Whole-request latency, client's send to the HTTP answer, 90th
percentile over the requests completed in the window. Per-layer, not
end-to-end: the closed loop keeps the server saturated, and a tail
over the ~125 requests a window completes swings more than any bound
may allow (PERF.md section 2)."""


from benchmark.harness import stats


def read(obs):
    lat = obs.get("latencies_ms")
    return stats.percentile(lat, 90) if lat else None
