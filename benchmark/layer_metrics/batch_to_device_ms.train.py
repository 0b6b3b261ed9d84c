"""Mean of the program's ``batch_to_device`` span (kstep.py), over
whole traced iterations: the host's coercion and relayout of the batch
and its ``device_put`` calls, the part of ``train_step`` before the
jitted call is enqueued."""

from benchmark.harness import program_spans


def read(obs):
    return program_spans.mean_ms(obs, "batch_to_device")
