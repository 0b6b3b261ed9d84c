"""``BatchOccupancy``: items over batches x slots, over the window."""

from benchmark.harness import readers


def read(obs):
    items = readers.counter_delta(obs, "serving_batch_items_total")
    batches = readers.counter_delta(obs, "serving_batches_total")
    if not batches:
        return None
    slots = obs["cell"].traffic["server"]["slots"]
    return 100.0 * items / (batches * slots)
