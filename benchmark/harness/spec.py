"""Reads BENCHMARK.json and the files it names.

A cell (``workloads`` entry) names a configuration and a traffic mix.
The configuration's file is ``configs[].file``; the mix's file is
``<bench>/traffic/<traffic>.json``. Code that belongs to one of them is
found by the names those files give: ``builders/<builder>.py``,
``reference/<reference>.py``, ``drivers/<driver>.py``, and one reader
per per-layer metric, ``layer_metrics/<name>.py``.
"""

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_module(kind, name):
    """``<bench>/<kind>/<name>.py`` as a module, or None if absent."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}".replace("-", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with its files read."""

    def __init__(self, bench, entry):
        self.name = entry["name"]
        self.chips = int(entry["chips"])
        self.config_name = entry["config"]
        self.traffic_name = entry["traffic"]
        conf = next(c for c in bench["configs"]
                    if c["name"] == entry["config"])
        self.config = _json(os.path.join(ROOT, conf["file"]))
        self.traffic = _json(os.path.join(
            BENCH_DIR, "traffic", entry["traffic"] + ".json"))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if self.name in m.get("workloads",
                                                 [self.name])]
        e2e = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if self.name in m.get("workloads", [self.name])
                          and m["moves"] in e2e]


def load(workload):
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    for entry in bench["workloads"]:
        if entry["name"] == workload:
            return Cell(bench, entry)
    raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; it "
                     f"has {[w['name'] for w in bench['workloads']]}")
