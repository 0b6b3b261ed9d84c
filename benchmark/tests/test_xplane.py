"""The reduction from a trace to numbers, on a small recorded trace
(recorded_trace.json: a real TPU v5e trace, see its ``recorded`` key)
and on a hand-made two-op case for the collective arithmetic (a
single-chip trace holds no collective)."""

import json
import os

import pytest

from benchmark.harness import readers, xplane

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return json.load(f)


def test_interval_arithmetic():
    u = xplane.union([(5, 9), (0, 3), (2, 4), (9, 9), (8, 12)])
    assert u == [[0, 4], [5, 12]]
    assert xplane.length(u) == 11
    assert xplane.subtract([[0, 10]], [[2, 3], [5, 7], [9, 12]]) == [
        [0, 2], [3, 5], [7, 9]]
    assert xplane.subtract([[0, 4], [6, 8]], [[0, 8]]) == []


def test_busy_idle_union_on_the_recorded_trace(recorded):
    busy, span = xplane.busy_and_window(recorded)
    ops = recorded["devices"][0]["ops"]
    # ops of one core never overlap: the union is the plain sum
    assert busy == pytest.approx(sum(d for _, _, d in ops) / 1e9)
    first = min(s for _, s, _ in ops)
    last = max(s + d for _, s, d in ops)
    assert span == pytest.approx((last - first) / 1e9)
    assert 0 < busy < span
    # three steps of ~8 ms each inside ~36 ms: as read by hand
    assert busy == pytest.approx(0.0245, abs=0.0005)
    assert span == pytest.approx(0.0361, abs=0.0005)
    gaps = xplane.idle_gaps(recorded)
    assert sum(e - s for s, e in gaps) / 1e9 == pytest.approx(span - busy)
    assert gaps[0][1] - gaps[0][0] >= gaps[-1][1] - gaps[-1][0]


def test_a_named_kernels_time(recorded):
    sec, names = xplane.op_time(recorded, "pallas_flash_attention")
    # 2 layers x (1 forward + 2 backward kernels... ) x 3 steps
    assert len(names) == 3 * len(set(names))
    by_hand = sum(d for n, _, d in recorded["devices"][0]["ops"]
                  if n.startswith("%pallas_flash_attention"))
    assert sec == pytest.approx(by_hand / 1e9)
    assert xplane.op_time(recorded, "no_such_kernel") == (0.0, [])


def test_flash_roofline_reads_shapes_from_the_op_text(recorded):
    class Dev:
        device_kind = "TPU v5 lite"
    obs = {"trace": recorded, "device": Dev()}
    pct = readers.flash_roofline_pct(obs)
    assert 5.0 < pct < 105.0
    assert obs["flash_bound"] == "memory"      # float32, head size 64


def test_breakdown_names_ops_and_gaps(recorded):
    b = xplane.breakdown(recorded)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    names = [n for n, _ in b["device_ops"]]
    assert "%pallas_flash_attention_bwd" in names
    assert b["device_ops"][0][1] >= b["device_ops"][-1][1]
    assert any("bench/" in n for n, _ in b["idle_gaps"])


def test_gaps_of_a_trace_without_host_events_say_so(recorded):
    # a mix that sets trace_host_level 0 traces the device alone
    b = xplane.breakdown(dict(recorded, host=[]))
    assert [n for n, _ in b["idle_gaps"]] == ["(host not traced)"]
    busy, span = xplane.busy_and_window(recorded)
    assert b["idle_gaps"][0][1] == pytest.approx(span - busy, rel=1e-3)


def test_exposed_collective_by_hand():
    # device 0: compute 0-10, all-reduce in flight 8-20 (async),
    # waited for 18-20 (sync done op), compute 12-15
    tr = {"devices": [{"name": "/device:TPU:0",
                       "ops": [["%fusion.1", 0, 10],
                               ["%fusion.2", 12, 3],
                               ["%all-reduce-done.1", 18, 2]],
                       "async": [["%all-reduce-start.1", 8, 12]]},
                      {"name": "/device:TPU:1",
                       "ops": [["%fusion.1", 0, 20]],
                       "async": [["%all-reduce-start.1", 8, 12]]}],
          "host": [], "text": {}}
    # device 0: in flight 8-20 minus compute (0-10, 12-15) = 10-12 and
    # 15-20 = 7 ns; device 1: fully hidden = 0
    assert xplane.exposed_collective(tr) == pytest.approx(3.5e-9)
    busy, span = xplane.busy_and_window(tr)
    assert span == pytest.approx(20e-9)
    assert busy == pytest.approx((15 + 20) / 2 * 1e-9)
