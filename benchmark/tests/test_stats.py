import pytest

from benchmark.harness import stats


def test_percentile_interpolates():
    v = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(v, 0) == 10.0
    assert stats.percentile(v, 50) == 30.0
    assert stats.percentile(v, 90) == pytest.approx(46.0)
    assert stats.percentile(v, 100) == 50.0
    assert stats.percentile([7.0], 90) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_window_counts_where_a_request_ends():
    # (t_send, t_done, ok, n_tokens)
    recs = [(0.0, 0.9, True, 5),     # ends before the window
            (0.5, 1.5, True, 10),    # sent before, ends inside: counts
            (1.2, 1.8, False, 7),    # fails inside: attempted + failed
            (1.9, 2.0, True, 3),     # ends at the close: next window
            (1.0, 1.0, True, 4)]     # ends at the open: counts
    w = stats.window_summary(recs, 1.0, 2.0)
    assert (w["attempted"], w["failed"]) == (3, 1)
    assert w["tokens_per_s"] == pytest.approx(14.0)
    assert sorted(w["latencies_ms"]) == pytest.approx([0.0, 1000.0])
    # every request lands in exactly one of two adjoining windows
    both = (stats.in_window(recs, 0.0, 1.0)
            + stats.in_window(recs, 1.0, 2.0)
            + stats.in_window(recs, 2.0, 3.0))
    assert sorted(both) == sorted(recs)
