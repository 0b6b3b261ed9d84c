"""The k=1 fit loop's one-batch lookahead (ISSUE 25) changes when a
batch crosses to the device and nothing else: the same batches in the
same order through the same program. ``fit_batches`` has no lookahead
and is the reference here. What the spans and the order of pulls,
placements and listeners look like is in test_program_spans.py.
"""

import numpy as np
import pytest

from test_program_spans import _batches, _graph, _leaves, _mlp

from deeplearning4j_tpu.data.iterators import DataSetIterator
from deeplearning4j_tpu.train.listeners import (
    CollectScoresIterationListener, TrainingListener)


def _mesh_mlp():
    return _mlp().use_mesh("dp=4")


EXECUTORS = pytest.mark.parametrize(
    "make", [_mlp, _graph, _mesh_mlp],
    ids=["multilayer", "graph", "multilayer-dp4"])


class Feed(DataSetIterator):
    """Counts what was pulled; ``fail_at`` raises in place of that
    batch."""

    def __init__(self, batches, fail_at=None):
        self.batches, self.fail_at, self.pulled = batches, fail_at, 0

    def reset(self):
        pass

    def _iterate(self):
        for i, ds in enumerate(self.batches):
            if i == self.fail_at:
                raise OSError("the reader broke")
            self.pulled += 1
            yield ds


class RaiseAt(TrainingListener):
    def __init__(self, iteration):
        self.iteration, self.seen = iteration, []

    def iteration_done(self, model, iteration, score, batch_size):
        self.seen.append(iteration)
        if iteration == self.iteration:
            raise FloatingPointError("stop here")


@EXECUTORS
@pytest.mark.parametrize("listener", [True, False],
                         ids=["listener", "no-listener"])
def test_fit_is_bit_identical_to_fit_batches(make, listener):
    batches = _batches(5)
    ahead, plain = make(), make()
    scores = []
    for net in (ahead, plain):
        if listener:
            scores.append(CollectScoresIterationListener())
            net.set_listeners(scores[-1])
    ahead.fit(Feed(batches))
    losses = plain.fit_batches(batches)
    assert ahead.iteration_count == plain.iteration_count == 5
    for a, b in zip(_leaves(ahead), _leaves(plain)):
        assert a.tobytes() == b.tobytes()
    assert float(ahead.score_value) == float(plain.score_value) == losses[-1]
    if listener:
        got, want = scores[0].scores, scores[1].scores
        assert [i for i, _ in got] == [0, 1, 2, 3, 4]
        assert got == want
        assert [v for _, v in got] == list(losses)


@EXECUTORS
def test_listener_that_raises_leaves_the_iterator_one_batch_past(make):
    """The contract in ``_fit_epoch``'s docstring."""
    batches = _batches(5)
    net, ref = make(), make()
    guard = RaiseAt(2)
    net.set_listeners(guard)
    feed = Feed(batches)
    with pytest.raises(FloatingPointError):
        net.fit(feed)
    assert guard.seen == [0, 1, 2]
    assert net.iteration_count == 2
    assert feed.pulled == 4         # batches 0..2 trained, batch 3 pulled
    ref.fit_batches(batches[:3])
    for a, b in zip(_leaves(net), _leaves(ref)):
        assert a.tobytes() == b.tobytes()


@EXECUTORS
def test_iterator_that_raises_ahead_lets_the_enqueued_step_finish(make):
    """Batch 3 fails inside step 2's lookahead: step 2 is already on
    the device, so its listeners run and it is counted, as when the
    batch was pulled after them."""
    batches = _batches(5)
    net, ref = make(), make()
    seen = RaiseAt(None)
    net.set_listeners(seen)
    with pytest.raises(OSError):
        net.fit(Feed(batches, fail_at=3))
    assert seen.seen == [0, 1, 2]
    assert net.iteration_count == 3
    ref.fit_batches(batches[:3])
    for a, b in zip(_leaves(net), _leaves(ref)):
        assert a.tobytes() == b.tobytes()


def test_early_stopping_on_an_iteration_condition_still_stops_there():
    """``EarlyStoppingTrainer`` stops mid-epoch through a listener
    that raises: the model has taken exactly the steps up to the one
    that tripped, whatever the lookahead had pulled."""
    from deeplearning4j_tpu.train.early_stopping import (
        EarlyStoppingConfiguration, EarlyStoppingTrainer,
        InMemoryModelSaver, MaxEpochsTerminationCondition)

    class AfterThree:
        calls = 0

        def initialize(self):
            pass

        def terminate(self, score):
            self.calls += 1
            return self.calls == 3

    feed = Feed(_batches(5))
    cfg = EarlyStoppingConfiguration(
        model_saver=InMemoryModelSaver(),
        epoch_termination_conditions=[MaxEpochsTerminationCondition(4)],
        iteration_termination_conditions=[AfterThree()])
    net, ref = _mlp(), _mlp()
    result = EarlyStoppingTrainer(cfg, net, feed).fit()
    assert result.termination_reason == "iteration"
    assert net.iteration_count == 2 and feed.pulled == 4
    ref.fit_batches(feed.batches[:3])
    for a, b in zip(_leaves(net), _leaves(ref)):
        assert a.tobytes() == b.tobytes()
