"""Share of the (token, selected expert) pairs that went to a
zero-compute expert (``serving_moe_zero_pairs_total`` over
``serving_moe_selected_pairs_total``): picks that cost no expert's
weights and no exchange. A property of the router and of the traffic,
as ``moe_experts_hit_pct.serve`` is; with an untrained router it sits
at the zero experts' share of the router's width. Nothing to read
where the program has no such counters."""

from benchmark.harness import readers


def read(obs):
    zero = readers.counter_delta(obs, "serving_moe_zero_pairs_total")
    picks = readers.counter_delta(obs,
                                  "serving_moe_selected_pairs_total")
    return 100.0 * zero / picks if zero is not None and picks else None
