"""Share of a step's (row, held expert) pairs that its busiest held
expert took: ``train_moe_pairs_busiest_expert_total`` over
``train_moe_pairs_total``. 100 / held under even routing (12.5 at 8
held); what the pairs pass's longest group, and in a deployment the
slowest chip of the group, follows."""

from benchmark.harness import spec


def read(obs):
    t = spec.load_module("layer_metrics", "moe_pairs_per_step.train").totals()
    if t is None or not t.get("pairs_total"):
        return None
    return 100.0 * t["pairs_busiest_expert_total"] / t["pairs_total"]
