"""Share of the window's device steps whose program ran its expert
layers' held experts as the GROUPED pass, over the selected (row,
expert) pairs alone (``serving_moe_grouped_steps_total`` over both
programs of ``serving_steps_total``): the layer's predicate turns on
the rows a step carries, so this follows the widths the batcher runs
(the wide chunk program's steps where that alone passes an MXU tile of
rows), and in those steps an expert no row picked is not read. Nothing
to read where no program of the session takes the grouped pass (the
dense pass at every width, a network without expert layers, the CPU)
or the program has no such counter."""

from benchmark.harness import readers

_STEPS = r'serving_steps_total\{.*program="%s"'


def read(obs):
    grouped = readers.counter_delta(
        obs, r"serving_moe_grouped_steps_total\{")
    chunk = readers.counter_delta(obs, _STEPS % "chunk")
    single = readers.counter_delta(obs, _STEPS % "single")
    if grouped is None or chunk is None or single is None \
            or chunk + single <= 0:
        return None
    return 100.0 * grouped / (chunk + single)
