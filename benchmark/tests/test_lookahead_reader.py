"""``lookahead_steps_pct.serve`` on made-up counters: the share of the
window's steps enqueued one ahead; nothing where the program has no
such counter (the parent of PR 32) or the window held no step; and the
key a real ``BatcherStepMetrics`` writes is the key the reader
matches."""

import json
import os

import pytest

from benchmark.harness import spec

EP = 'endpoint="generate/lm/v1"'
AHEAD = "serving_lookahead_steps_total{%s}" % EP
STEPS = 'serving_steps_total{%s,program="%%s"}' % EP


def read(before, after):
    return spec.load_module("layer_metrics", "lookahead_steps_pct.serve"
                            ).read({"counters": {"before": before,
                                                 "after": after}})


def test_share_of_the_windows_steps():
    before = {AHEAD: 90.0, STEPS % "chunk": 60.0, STEPS % "single": 40.0}
    after = {AHEAD: 280.0, STEPS % "chunk": 180.0,
             STEPS % "single": 120.0}
    # 190 of the window's 200 steps
    assert read(before, after) == pytest.approx(95.0)


def test_nothing_to_read_without_the_counter_or_without_steps():
    steps = {STEPS % "chunk": 180.0, STEPS % "single": 120.0}
    assert read({}, steps) is None
    idle = dict(steps, **{AHEAD: 5.0})
    assert read(idle, idle) is None


def test_the_registry_writes_the_key_the_reader_matches():
    from deeplearning4j_tpu.serving.metrics import ServingMetrics
    m = ServingMetrics()
    steps = m.batcher_steps("generate/lm/v1")
    before = m.registry.snapshot()
    steps.record(0.001, 0.002, 0.001, 0, 2, "chunk", ahead=False)
    steps.record(0.001, 0.002, 0.001, 0, 2, "single", ahead=True)
    steps.record(0.001, 0.002, 0.001, 0, 2, "single", ahead=True)
    steps.record(0.001, 0.002, 0.001, 0, 2, "chunk", ahead=True)
    assert read(before, m.registry.snapshot()) == pytest.approx(75.0)


def test_the_metric_has_its_entry():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        entry = {m["name"]: m for m in json.load(f)["per_layer"]}[
            "lookahead_steps_pct.serve"]
    assert entry == {"name": "lookahead_steps_pct.serve", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "Serving", "moves": "serve_tokens_per_s"}
