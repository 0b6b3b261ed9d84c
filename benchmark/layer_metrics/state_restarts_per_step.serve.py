"""Slots that began a request on a state row an earlier request had
written, a batcher step (``serving_state_rows_restarted_total`` over
the steps of the window): how often the restart by position does the
work a zeroing at release would have done. 0 means no freed row was
let again inside the window. Nothing to read where the program has no
such counter."""

from benchmark.harness import readers


def read(obs):
    restarts = readers.counter_delta(
        obs, r"serving_state_rows_restarted_total\{")
    steps = readers.counter_delta(
        obs, r'serving_step_seconds\{.*part="device"', "count")
    return restarts / steps if restarts is not None and steps else None
