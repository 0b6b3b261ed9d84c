"""``flash_fwd_reruns_pct.train`` on hand-made lists of op names (a
step of L layers calls L forward kernels, then a layer at a time,
last first, its dq and dk/dv kernels, behind the forward kernel again
where the layer is recomputed whole) and on the recorded trace."""

import json
import os

import pytest

from benchmark.harness import spec

HERE = os.path.dirname(os.path.abspath(__file__))
READER = spec.load_module("layer_metrics", "flash_fwd_reruns_pct.train")


def _step(layers, rerun):
    """One step's flash ops in time order. The second run of a layer's
    forward kernel is another instruction of the program (``.1<i>``)."""
    fwd = lambda i: f"%pallas_flash_attention.{i}"
    names = [fwd(i) for i in range(layers)]
    for i in reversed(range(layers)):
        names += [fwd(10 + i)] * rerun
        names += [f"%pallas_flash_attention_bwd.{2 * i}",
                  f"%pallas_flash_attention_bwd.{2 * i + 1}"]
    return names


@pytest.mark.parametrize("rerun, want", [(True, 50.0), (False, 0.0)])
@pytest.mark.parametrize("cut", [(0, None), (3, -4), (7, -1), (12, -9)])
def test_a_recomputed_forward_is_half_the_forward_calls(rerun, want, cut):
    """Wherever in a step the trace begins and ends: whole steps alone
    are counted."""
    calls = (_step(5, rerun) * 4)[cut[0]:cut[1]]
    assert READER.reruns_pct(calls) == pytest.approx(want)


def test_one_layer_of_three_recomputed():
    step = _step(3, False)
    step.insert(3, "%pallas_flash_attention.12")
    assert READER.reruns_pct(step * 3) == pytest.approx(25.0)


def test_less_than_a_step_is_counted_as_it_stands():
    assert READER.reruns_pct(_step(2, True)) == pytest.approx(50.0)


def test_nothing_to_read_without_a_backward_call():
    assert READER.reruns_pct(["%pallas_flash_attention.1"] * 3) is None
    assert READER.read({"trace": None}) is None
    assert READER.read({"trace": {"devices": [{"ops": [
        ["%fusion.1", 0, 5]]}]}}) is None


def test_the_recorded_trace_recomputes_nothing():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        assert READER.read({"trace": json.load(f)}) == 0.0
