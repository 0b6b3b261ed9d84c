"""Percentiles and the arithmetic of a completion window."""

import math


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default), on plain floats."""
    if not values:
        raise ValueError("percentile of nothing")
    v = sorted(values)
    k = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def in_window(records, t_open, t_close):
    """Requests that COMPLETED inside [t_open, t_close): each record
    is (t_send, t_done, ok, n_tokens). A request is counted where it
    ends, so every request is counted in exactly one window."""
    return [r for r in records if t_open <= r[1] < t_close]


def window_summary(records, t_open, t_close):
    """attempted / failed / tokens per second / latencies (ms) of the
    requests completed in the window. A failed request counts as
    attempted and failed, adds no tokens and no latency sample."""
    done = in_window(records, t_open, t_close)
    ok = [r for r in done if r[2]]
    span = t_close - t_open
    return {"attempted": len(done), "failed": len(done) - len(ok),
            "tokens_per_s": sum(r[3] for r in ok) / span,
            "latencies_ms": [(r[1] - r[0]) * 1e3 for r in ok]}
