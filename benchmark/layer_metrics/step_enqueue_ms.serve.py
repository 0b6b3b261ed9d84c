"""Mean of ``serving_step_enqueue_seconds`` over the window: the host's
dispatch of one batcher step (``_enqueue_step``: operands, the jitted
call's return), the part of ``serving_step_seconds{part="device"}``
that is not the wait for the ids that are due. Nothing to read where
the program has no such histogram."""

from benchmark.harness import readers


def read(obs):
    return readers.histogram_mean_ms(obs,
                                     r"serving_step_enqueue_seconds\{")
