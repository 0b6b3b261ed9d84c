"""Share of the (layer, held expert) that served at least one token
in a step (``serving_moe_expert_hits_total`` over
``serving_moe_expert_slots_total``). A property of the traffic and of
the router, not of the program's speed: the expert layer passes every
row through every held expert, so a step reads all their weights
whatever this share is. It says what a grouped kernel that reads hit
experts only would have to read, and only then moves the rate."""

from benchmark.harness import readers


def read(obs):
    hits = readers.counter_delta(obs, "serving_moe_expert_hits_total")
    slots = readers.counter_delta(obs, "serving_moe_expert_slots_total")
    return 100.0 * hits / slots if slots else None
