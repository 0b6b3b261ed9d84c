"""Share of the traced window in which a collective is in flight and
no compute runs on that device. Nothing to read on one chip."""

from benchmark.harness import xplane


def read(obs):
    tr = obs.get("trace")
    if tr is None or obs["n_devices"] < 2:
        return None
    _, span = xplane.busy_and_window(tr)
    return 100.0 * xplane.exposed_collective(tr) / span
