"""The host's share of a batcher step over the window: the sums of
``serving_step_seconds`` parts ``admit`` (migration service, queue
pump, expiry, admission, building the fed tokens) and ``sample`` (the
per-slot loop) over the count of device steps."""

from benchmark.harness import readers

_KEY = r'serving_step_seconds\{.*part="%s"'


def read(obs):
    steps = readers.counter_delta(obs, _KEY % "device", "count")
    if not steps:
        return None
    host = sum(readers.counter_delta(obs, _KEY % part, "sum") or 0.0
               for part in ("admit", "sample"))
    return 1e3 * host / steps
