"""(row, held expert) pairs the expert layers computed, a training
step, summed over the layers: ``train_moe_pairs_total`` over
``train_moe_steps_total``, the program's own registry read at the
run's end (the training driver snapshots no counters; every step of
the process counts alike). Nothing to read where the program has no
such counters or counted no step."""


def totals():
    """``{short name: value}`` of the program's three train_moe
    counters, or None where it has none."""
    try:
        from deeplearning4j_tpu.observability.registry import REGISTRY
        text = REGISTRY.prometheus_text()
    except Exception:
        return None
    out = {}
    for line in text.splitlines():
        name, _, value = line.partition(" ")
        if name.startswith("train_moe_") and value:
            out[name[len("train_moe_"):]] = float(value)
    return out if out.get("steps_total") else None


def read(obs):
    t = totals()
    return None if t is None else t["pairs_total"] / t["steps_total"]
