"""Mean of the program's ``h2d_wait`` span (kstep.py), over whole
traced iterations: from the step's enqueue to the batch's arrival on
the device, which is when the device can start the step. The span
exists only while the tracer is on (it blocks on the batch, which an
untraced ``fit`` never does); it does not delay the device."""

from benchmark.harness import program_spans


def read(obs):
    return program_spans.mean_ms(obs, "h2d_wait")
