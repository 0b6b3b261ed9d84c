"""Preemption-tolerant elastic training (ISSUE 5): async
checkpointing (single background writer, newest-supersedes
coalescing, durability barriers, crash-window safety), checkpointable
iterator state (resume by restore, not replay), SIGTERM delivered by
a seeded chaos plan as a replayable preemption, and the elastic mesh
shrink on device loss — including the two acceptance soaks:

- SIGTERM mid-epoch with an async write in flight → restart resumes
  via iterator ``state_dict`` (batch-fetch count proves no replay) to
  params bit-identical to the uninterrupted run;
- dp=8 with an injected device loss shrinks to dp=4 without raising,
  completes, and matches a from-checkpoint dp=4 restart bit-for-bit.
"""

import os
import threading
import time

import numpy as np
import pytest

import jax

from deeplearning4j_tpu import chaos
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import (
    ArrayDataSetIterator, AsyncDataSetIterator, ListDataSetIterator,
    SamplingDataSetIterator)
from deeplearning4j_tpu.observability.registry import REGISTRY
from deeplearning4j_tpu.parallel.mesh import (MeshSpec, build_mesh,
                                              largest_pow2,
                                              shrink_data_mesh)
from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
from deeplearning4j_tpu.train.fault_tolerance import (
    ElasticTrainer, _CheckpointWriter)
from deeplearning4j_tpu.util.model_serializer import (restore_model,
                                                      verify_checkpoint,
                                                      write_model)
from fixtures import make_batches, tiny_classifier

pytestmark = pytest.mark.preempt


@pytest.fixture(autouse=True)
def _clean_injector():
    yield
    chaos.uninstall()


def _flat_params(net):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(
        (net.params, net.state, net.opt_state))]


def _features(batches):
    return [np.asarray(b.features) for b in batches]


# ---------------------------------------------------------------------------
# iterator state protocol
# ---------------------------------------------------------------------------

class TestIteratorState:
    def test_list_iterator_resumes_at_cursor(self):
        batches = make_batches(6, seed=0)
        it = ListDataSetIterator(batches)
        gen = iter(it)
        for _ in range(3):
            next(gen)
        st = it.state_dict()
        assert st["cursor"] == 3
        it2 = ListDataSetIterator(batches)
        it2.load_state_dict(st)
        got = _features(list(it2))
        want = _features(batches[3:])
        assert len(got) == 3
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)

    def test_resume_skip_is_not_a_replay(self):
        """The consumed prefix must never be re-fetched: data.fetch
        hit count == batches actually delivered after the resume."""
        batches = make_batches(8, seed=1)
        inj = chaos.install({"faults": [
            {"site": "data.fetch", "kind": "error", "at": [10 ** 9]}]},
            seed=0)
        it = ListDataSetIterator(batches)
        it.load_state_dict({"cursor": 5})
        assert len(list(it)) == 3
        assert inj.hits("data.fetch") == 3       # 5 skipped for free

    def test_shuffled_array_iterator_resume_matches_uninterrupted(self):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(40, 4)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 40)]

        full = ArrayDataSetIterator(x, y, batch_size=8, shuffle=True,
                                    seed=7)
        epoch1 = _features(list(full))
        epoch2 = _features(list(full))

        part = ArrayDataSetIterator(x, y, batch_size=8, shuffle=True,
                                    seed=7)
        gen = iter(part)
        for _ in range(2):
            next(gen)
        st = part.state_dict()
        assert st["cursor"] == 2

        resumed = ArrayDataSetIterator(x, y, batch_size=8,
                                       shuffle=True, seed=7)
        resumed.load_state_dict(st)
        rest = _features(list(resumed))
        assert len(rest) == 3
        for a, b in zip(rest, epoch1[2:]):
            np.testing.assert_array_equal(a, b)   # same permutation
        # the NEXT epoch shuffles fresh, matching the uninterrupted
        # iterator's second epoch
        nxt = _features(list(resumed))
        for a, b in zip(nxt, epoch2):
            np.testing.assert_array_equal(a, b)

    def test_sampling_iterator_resume_matches_uninterrupted(self):
        data = make_batches(1, batch=32, seed=2)[0]
        full = SamplingDataSetIterator(data, batch_size=4,
                                       batches_per_epoch=6, seed=3)
        want = _features(list(full))
        part = SamplingDataSetIterator(data, batch_size=4,
                                       batches_per_epoch=6, seed=3)
        gen = iter(part)
        for _ in range(2):
            next(gen)
        resumed = SamplingDataSetIterator(data, batch_size=4,
                                          batches_per_epoch=6, seed=3)
        resumed.load_state_dict(part.state_dict())
        rest = _features(list(resumed))
        for a, b in zip(rest, want[2:]):
            np.testing.assert_array_equal(a, b)   # rng fast-forward

    def test_record_reader_iterator_resume(self, tmp_path):
        from deeplearning4j_tpu.data.records import (
            CSVRecordReader, RecordReaderDataSetIterator)
        csv = tmp_path / "data.csv"
        rows = "\n".join(f"{i}.0,{i + 1}.0,{i % 3}" for i in range(20))
        csv.write_text(rows + "\n")

        def make():
            rr = CSVRecordReader().initialize(str(csv))
            return RecordReaderDataSetIterator(rr, 4, label_index=2,
                                               num_classes=3)

        want = _features(list(make()))
        part = make()
        gen = iter(part)
        for _ in range(2):
            next(gen)
        st = part.state_dict()
        resumed = make()
        resumed.load_state_dict(st)
        rest = _features(list(resumed))
        assert len(rest) == len(want) - 2
        for a, b in zip(rest, want[2:]):
            np.testing.assert_array_equal(a, b)

    def test_wrong_data_source_rejected_on_resume(self):
        """State resume must keep the replay path's wrong-source
        detection: a checkpointed state loaded against a DIFFERENT
        dataset (even one long enough) fails loudly via the source
        signature instead of silently training on wrong data."""
        it = ListDataSetIterator(make_batches(6, seed=0))
        gen = iter(it)
        next(gen)
        st = it.state_dict()
        other = ListDataSetIterator(make_batches(6, batch=4, seed=1))
        with pytest.raises(ValueError, match="does not match"):
            other.load_state_dict(st)
        # the SAME source (fresh object) is accepted
        same = ListDataSetIterator(make_batches(6, seed=0))
        same.load_state_dict(st)
        assert len(list(same)) == 5

    def test_resume_cursor_beyond_source_raises(self, tmp_path):
        """A state cursor past what the source can produce is a
        shrunken data source — loud, never a silently empty epoch
        (the stateful twin of the trainer's replay shortfall error)."""
        it = ListDataSetIterator(make_batches(4, seed=0))
        it.load_state_dict({"cursor": 6})
        with pytest.raises(ValueError, match="beyond"):
            next(iter(it))
        # and end-to-end through ElasticTrainer's stateful resume
        net = tiny_classifier(seed=0)
        tr = ElasticTrainer(net, str(tmp_path), save_every=3,
                            handle_sigterm=False)
        tr.fit(ListDataSetIterator(make_batches(8, seed=0)),
               until_epoch=1)
        net2 = tiny_classifier(seed=0)
        tr2 = ElasticTrainer(net2, str(tmp_path), save_every=3,
                             handle_sigterm=False)
        assert tr2._batch == 6
        # the shrunk list differs in source signature, so the
        # mismatch is caught at load time (the cursor bounds check
        # above remains the guard for signature-less states)
        with pytest.raises(ValueError,
                           match="does not match this data source"):
            tr2.fit(ListDataSetIterator(make_batches(4, seed=0)),
                    until_epoch=1)

    def test_async_iterator_is_stateless(self):
        """Prefetch queues hold batches the consumer never saw — the
        wrapped cursor would overstate the position, so Async opts
        out and the trainer falls back to replay."""
        it = AsyncDataSetIterator(ListDataSetIterator(make_batches(3)))
        assert it.state_dict() is None
        with pytest.raises(NotImplementedError):
            it.load_state_dict({"cursor": 1})


# ---------------------------------------------------------------------------
# the background checkpoint writer (unit)
# ---------------------------------------------------------------------------

class TestCheckpointWriter:
    def test_coalescing_newest_supersedes_queued(self):
        w = _CheckpointWriter()
        started = threading.Event()
        release = threading.Event()
        done = []

        def blocker():
            done.append("job1")
            started.set()
            release.wait(5.0)

        w.submit(blocker)
        assert started.wait(5.0)
        # job1 is IN FLIGHT: job2 queues, job3 supersedes job2
        w.submit(lambda: done.append("job2"))
        replaced = w.submit(lambda: done.append("job3"))
        assert replaced is True
        release.set()
        w.barrier(timeout=5.0)
        assert done == ["job1", "job3"]          # job2 never ran
        assert w.superseded == 1
        w.close(timeout=5.0)

    def test_barrier_reraises_writer_error_once(self):
        w = _CheckpointWriter()

        def boom():
            raise ValueError("disk on fire")

        w.submit(boom)
        with pytest.raises(ValueError, match="disk on fire"):
            w.barrier(timeout=5.0)
        w.barrier(timeout=5.0)                   # error consumed
        w.close(timeout=5.0)

    def test_submit_surfaces_previous_write_error(self):
        w = _CheckpointWriter()
        w.submit(lambda: (_ for _ in ()).throw(IOError("enospc")))
        deadline = time.monotonic() + 5.0
        while not w.idle() and time.monotonic() < deadline:
            time.sleep(0.005)
        with pytest.raises(IOError, match="enospc"):
            w.submit(lambda: None)
        w.close(timeout=5.0)


# ---------------------------------------------------------------------------
# async checkpointing through ElasticTrainer
# ---------------------------------------------------------------------------

class TestAsyncCheckpointing:
    def test_async_save_equals_sync_save(self, tmp_path):
        net = tiny_classifier(seed=0)
        net.iteration_count = 7
        sync = ElasticTrainer(net, str(tmp_path / "sync"),
                              handle_sigterm=False)
        p_sync = sync.save_checkpoint()
        asyn = ElasticTrainer(net, str(tmp_path / "async"),
                              handle_sigterm=False,
                              async_checkpoint=True)
        assert asyn.save_checkpoint() is None    # handed off
        asyn.checkpoint_barrier()
        p_async = asyn.latest_checkpoint()
        assert os.path.basename(p_async) == os.path.basename(p_sync)
        verify_checkpoint(p_async)
        a, b = restore_model(p_sync), restore_model(p_async)
        for x, y in zip(_flat_params(a), _flat_params(b)):
            np.testing.assert_array_equal(x, y)
        asyn.close()

    def test_blocked_and_total_phases_recorded(self, tmp_path):
        for phase in ("blocked", "total"):
            REGISTRY.unregister("checkpoint_write_seconds",
                                {"phase": phase})
        net = tiny_classifier(seed=0)
        tr = ElasticTrainer(net, str(tmp_path), save_every=2,
                            handle_sigterm=False,
                            async_checkpoint=True)
        tr.fit(ListDataSetIterator(make_batches(4)), epochs=1)
        tr.close()
        blocked = REGISTRY.histogram("checkpoint_write_seconds",
                                     labels={"phase": "blocked"})
        total = REGISTRY.histogram("checkpoint_write_seconds",
                                   labels={"phase": "total"})
        assert blocked.snapshot()["count"] >= 2
        assert total.snapshot()["count"] >= 2

    def test_async_save_blocks_the_train_thread_less_than_its_write(
            self, tmp_path):
        """Handing the write off must beat doing it on the train
        thread: over the same saves the program's own histogram holds
        less under ``blocked`` (snapshot + handoff) than under
        ``total`` (serialize + zip + rename, on the writer thread).
        Direction only, at a size where a write takes tens of
        milliseconds; a barrier after each save, so none is coalesced
        away."""
        for phase in ("blocked", "total"):
            REGISTRY.unregister("checkpoint_write_seconds",
                                {"phase": phase})
        net = tiny_classifier(seed=0, n_in=512, hidden=512)
        tr = ElasticTrainer(net, str(tmp_path), keep=2,
                            handle_sigterm=False,
                            async_checkpoint=True)
        for _ in range(4):
            net.iteration_count += 1
            assert tr.save_checkpoint() is None      # handed off
            tr.checkpoint_barrier()
        tr.close()
        blocked, total = (
            REGISTRY.histogram("checkpoint_write_seconds",
                               labels={"phase": phase}).snapshot()
            for phase in ("blocked", "total"))
        assert blocked["count"] == total["count"] == 4
        assert 0 < blocked["sum"] < total["sum"]

    def test_slow_writer_coalesces_and_newest_wins(self, tmp_path):
        """Back-to-back saves against a deliberately slow writer:
        intermediate generations are superseded (never written), the
        newest always lands, everything on disk verifies."""
        chaos.install({"faults": [{"site": "checkpoint.write",
                                   "kind": "slow", "p": 1.0,
                                   "args": {"delay_s": 0.15}}]},
                      seed=0)
        net = tiny_classifier(seed=0)
        tr = ElasticTrainer(net, str(tmp_path), save_every=1, keep=10,
                            handle_sigterm=False,
                            async_checkpoint=True)
        tr.fit(ListDataSetIterator(make_batches(6)), epochs=1)
        tr.checkpoint_barrier()
        assert tr._writer_obj.superseded >= 1    # coalescing engaged
        newest = tr.latest_checkpoint()
        assert os.path.basename(newest) == "ckpt_6.zip"
        for _, path in tr._ckpts():
            verify_checkpoint(path)
        assert not [f for f in os.listdir(tmp_path) if ".tmp" in f]
        tr.close()

    def test_async_crash_window_no_torn_checkpoint(self, tmp_path):
        """The satellite: a crash between zip-write and rename (chaos
        checkpoint.write crash on the WRITER thread) kills the run —
        but no torn checkpoint is ever visible, keep-pruning never
        touched the in-flight tmp, and the restart sweeps the orphan
        tmp, restores the previous generation and converges to
        params bit-identical to the fault-free run."""
        batches = make_batches(8, seed=5)
        ref = tiny_classifier(seed=4)
        ElasticTrainer(ref, str(tmp_path / "free"), save_every=2,
                       handle_sigterm=False,
                       async_checkpoint=True).fit(
            ListDataSetIterator(batches), until_epoch=1)

        # write hits: 1 = iteration-0 save, 2 = it2, 3 = it4 (crash).
        # Steps are slowed past the tiny write time so no save ever
        # coalesces — write-hit ordinals stay 1:1 with saves (the
        # newest-supersedes queue would otherwise make ordinal 3 a
        # timing-dependent generation)
        chaos.install({"faults": [
            {"site": "checkpoint.write", "kind": "crash", "at": [3]},
            {"site": "train.step", "kind": "hang", "p": 1.0,
             "args": {"delay_s": 0.03}}]}, seed=0)
        cdir = str(tmp_path / "chaotic")
        net = tiny_classifier(seed=4)
        with pytest.raises(chaos.SimulatedCrashError):
            ElasticTrainer(net, cdir, save_every=2,
                           handle_sigterm=False,
                           async_checkpoint=True).fit(
                ListDataSetIterator(batches), until_epoch=1)
        chaos.uninstall()

        # the crash landed between zip-write and rename: the tmp is
        # orphaned, the final name never appeared, and every VISIBLE
        # generation still verifies (no torn checkpoint)
        tmps = [f for f in os.listdir(cdir) if ".tmp" in f]
        assert tmps, "crash should orphan the in-flight tmp"
        finals = sorted(f for f in os.listdir(cdir)
                        if f.endswith(".zip"))
        assert "ckpt_4.zip" not in finals
        for f in finals:
            verify_checkpoint(os.path.join(cdir, f))

        # restart: orphan swept, previous generation restores, run
        # completes bit-identical to fault-free
        net2 = tiny_classifier(seed=4)
        tr2 = ElasticTrainer(net2, cdir, save_every=2,
                             handle_sigterm=False,
                             async_checkpoint=True)
        assert not [f for f in os.listdir(cdir) if ".tmp" in f]
        assert net2.iteration_count == 2
        tr2.fit(ListDataSetIterator(batches), until_epoch=1)
        tr2.close()
        assert net2.iteration_count == ref.iteration_count == 8
        for a, b in zip(_flat_params(ref), _flat_params(net2)):
            np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# diagnostics + plan validation satellites
# ---------------------------------------------------------------------------

class TestDiagnostics:
    def test_replay_shortfall_raises_distinct_error(self, tmp_path):
        """An iterator that runs dry before the checkpointed position
        is a SHRUNKEN DATA SOURCE, not a shuffling bug — the error
        must say so instead of blaming determinism."""
        net = tiny_classifier(seed=0)
        tr = ElasticTrainer(net, str(tmp_path), save_every=3,
                            handle_sigterm=False)
        tr.fit(list(make_batches(8, seed=0)), until_epoch=1)

        net2 = tiny_classifier(seed=0)
        tr2 = ElasticTrainer(net2, str(tmp_path), save_every=3,
                             handle_sigterm=False)
        assert tr2._batch == 6
        with pytest.raises(RuntimeError,
                           match="shorter than checkpointed position"):
            tr2.fit(list(make_batches(4, seed=0)), until_epoch=1)

    def test_reordered_replay_still_flagged_nondeterministic(
            self, tmp_path):
        net = tiny_classifier(seed=0)
        tr = ElasticTrainer(net, str(tmp_path), save_every=3,
                            handle_sigterm=False)
        batches = make_batches(8, seed=0)
        tr.fit(list(batches), until_epoch=1)
        net2 = tiny_classifier(seed=0)
        tr2 = ElasticTrainer(net2, str(tmp_path), save_every=3,
                             handle_sigterm=False)
        reordered = list(reversed(batches))
        with pytest.raises(RuntimeError,
                           match="iterator is not deterministic"):
            tr2.fit(reordered, until_epoch=1)

    def test_sigterm_kind_validated_at_parse_time(self):
        chaos.parse_plan({"faults": [
            {"site": "train.step", "kind": "sigterm", "at": [3]}]})
        with pytest.raises(ValueError, match="does not support"):
            chaos.parse_plan({"faults": [
                {"site": "data.fetch", "kind": "sigterm", "p": 1.0}]})
        chaos.parse_plan({"faults": [
            {"site": "parallel.device", "kind": "loss", "at": [2]}]})
        with pytest.raises(ValueError, match="does not support"):
            chaos.parse_plan({"faults": [
                {"site": "train.step", "kind": "loss", "p": 1.0}]})

    def test_cli_exposes_async_checkpoint_flag(self, capsys):
        from deeplearning4j_tpu.cli import main
        with pytest.raises(SystemExit) as ei:
            main(["train", "--help"])
        assert ei.value.code == 0
        assert "--async-checkpoint" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# ACCEPTANCE: the preemption soak
# ---------------------------------------------------------------------------

class TestPreemptionSoak:
    def test_sigterm_mid_epoch_resumes_via_state_bit_identical(
            self, tmp_path):
        """SIGTERM from a seeded plan lands mid-epoch-2 with an async
        write in flight; the grace protocol checkpoints and stops
        cleanly; the restart resumes via the iterator's state_dict —
        the batch-fetch count proves NO replay — and converges to
        params bit-identical to the uninterrupted run."""
        rng = np.random.default_rng(11)
        x = rng.normal(size=(80, 4)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 80)]

        class CountingIterator(ArrayDataSetIterator):
            """Counts batches actually MATERIALIZED by this source —
            the no-replay audit (chaos data.fetch hits also count
            model.fit's internal single-batch wrapper, so they
            overstate source fetches 2x)."""

            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                self.fetched = 0

            def _iterate(self):
                for b in super()._iterate():
                    self.fetched += 1
                    yield b

        def make_it():
            # shuffled: exactly what the replay fast-forward CANNOT
            # resume (epoch-seeded permutation) and state restore can
            return CountingIterator(x, y, batch_size=8,
                                    shuffle=True, seed=5)

        # ---- uninterrupted reference (2 epochs = 20 iterations) ----
        ref = tiny_classifier(seed=2)
        ElasticTrainer(ref, str(tmp_path / "free"), save_every=4,
                       handle_sigterm=False,
                       async_checkpoint=True).fit(
            make_it(), until_epoch=2)

        # ---- preempted run: SIGTERM at step 14 (epoch 1, batch 4),
        # writes slowed so the it-12 write is still in flight -------
        chaos.install({"faults": [
            {"site": "train.step", "kind": "sigterm", "at": [14]},
            {"site": "checkpoint.write", "kind": "slow", "p": 1.0,
             "args": {"delay_s": 0.15}},
        ]}, seed=9)
        cdir = str(tmp_path / "preempted")
        net = tiny_classifier(seed=2)
        tr = ElasticTrainer(net, cdir, save_every=4,
                            handle_sigterm=True,
                            async_checkpoint=True)
        tr.fit(make_it(), until_epoch=2)         # clean grace stop
        tr.close()
        chaos.uninstall()
        assert tr._stop_requested
        assert net.iteration_count == 14
        newest = tr.latest_checkpoint()
        assert os.path.basename(newest) == "ckpt_14.zip"
        verify_checkpoint(newest)                # grace write landed

        # ---- restart: same command, fetch count audited ------------
        net2 = tiny_classifier(seed=2)
        tr2 = ElasticTrainer(net2, cdir, save_every=4,
                             handle_sigterm=True,
                             async_checkpoint=True)
        assert net2.iteration_count == 14
        it2 = make_it()
        tr2.fit(it2, until_epoch=2)
        tr2.close()
        # state restore: only the 6 REMAINING batches were ever
        # materialized by the source — a replay fast-forward would
        # have fetched the 4 consumed ones again
        assert it2.fetched == 20 - 14

        assert net2.iteration_count == ref.iteration_count == 20
        for a, b in zip(_flat_params(ref), _flat_params(net2)):
            np.testing.assert_array_equal(a, b)
        assert float(net2.score_value) == float(ref.score_value)

    def test_epoch_boundary_crash_restart_bit_identical(
            self, tmp_path):
        """A crash right at an epoch boundary (checkpoint holds
        cursor == full epoch) resumes a SHUFFLED iterator into the
        next epoch with the permutation the uninterrupted run would
        have used: the trainer PINS the iterator's epoch to its own
        counter, so the shuffle is a pure function of (seed, epoch)
        across process restarts."""
        rng = np.random.default_rng(31)
        x = rng.normal(size=(40, 4)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 40)]

        def make_it():
            return ArrayDataSetIterator(x, y, batch_size=8,
                                        shuffle=True, seed=17)

        ref = tiny_classifier(seed=8)
        ElasticTrainer(ref, str(tmp_path / "free"), save_every=5,
                       handle_sigterm=False).fit(make_it(),
                                                 until_epoch=2)

        # 5 batches/epoch: the it-5 save IS the epoch boundary
        # (cursor == 5 == the whole epoch); crash on the first batch
        # of epoch 1
        chaos.install({"faults": [{"site": "train.step",
                                   "kind": "crash", "at": [6]}]},
                      seed=0)
        cdir = str(tmp_path / "boundary")
        net = tiny_classifier(seed=8)
        with pytest.raises(chaos.SimulatedCrashError):
            ElasticTrainer(net, cdir, save_every=5,
                           handle_sigterm=False).fit(make_it(),
                                                     until_epoch=2)
        chaos.uninstall()

        net2 = tiny_classifier(seed=8)
        tr2 = ElasticTrainer(net2, cdir, save_every=5,
                             handle_sigterm=False)
        assert (tr2._epoch, tr2._batch) == (0, 5)   # boundary ckpt
        tr2.fit(make_it(), until_epoch=2)
        assert net2.iteration_count == ref.iteration_count == 10
        for a, b in zip(_flat_params(ref), _flat_params(net2)):
            np.testing.assert_array_equal(a, b)

    def test_kill_right_after_rollback_resumes_shuffled_iterator(
            self, tmp_path):
        """A rollback re-checkpoints the RESTORED position; that
        generation must stay state-resumable too — a process killed
        immediately after a rollback resumes a SHUFFLED iterator
        (which the replay fallback cannot) skip-aware and converges
        bit-identical to the crash-free rollback run."""
        rng = np.random.default_rng(21)
        x = rng.normal(size=(80, 4)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 80)]

        def make_it():
            return ArrayDataSetIterator(x, y, batch_size=8,
                                        shuffle=True, seed=13)

        # crash-free reference: nan-poison at step 13 → one rollback,
        # poison batch skipped, run completes (19 effective steps)
        chaos.install({"faults": [
            {"site": "train.step", "kind": "nan", "at": [13]}]},
            seed=1)
        ref = tiny_classifier(seed=6)
        tref = ElasticTrainer(ref, str(tmp_path / "free"),
                              save_every=4, handle_sigterm=False,
                              async_checkpoint=True)
        tref.fit(make_it(), until_epoch=2)
        tref.close()
        chaos.uninstall()
        assert tref.total_rollbacks == 1

        # chaotic run: same nan, plus a crash on the FIRST batch
        # trained after the rollback
        chaos.install({"faults": [
            {"site": "train.step", "kind": "nan", "at": [13]},
            {"site": "train.step", "kind": "crash", "at": [14]}]},
            seed=1)
        cdir = str(tmp_path / "killed")
        net = tiny_classifier(seed=6)
        with pytest.raises(chaos.SimulatedCrashError):
            ElasticTrainer(net, cdir, save_every=4,
                           handle_sigterm=False,
                           async_checkpoint=True).fit(
                make_it(), until_epoch=2)
        chaos.uninstall()

        # restart: resumes the shuffled iterator from the
        # rollback-written generation (state restore — the replay
        # fallback would raise "not deterministic" here)
        net2 = tiny_classifier(seed=6)
        tr2 = ElasticTrainer(net2, cdir, save_every=4,
                             handle_sigterm=False,
                             async_checkpoint=True)
        tr2.fit(make_it(), until_epoch=2)
        tr2.close()
        assert net2.iteration_count == ref.iteration_count
        for a, b in zip(_flat_params(ref), _flat_params(net2)):
            np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# ACCEPTANCE: the elastic mesh-shrink soak
# ---------------------------------------------------------------------------

@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 virtual devices")
class TestElasticShrink:
    def test_shrink_mesh_unit(self):
        devs = jax.devices()[:8]
        mesh = build_mesh(MeshSpec(data=8), devs)
        shrunk = shrink_data_mesh(mesh, {devs[7]})
        assert shrunk.shape["data"] == 4
        assert devs[7] not in set(shrunk.devices.flat)
        assert largest_pow2(7) == 4 and largest_pow2(8) == 8

    def test_sharded_axes_refuse_to_shrink(self):
        # pipe/seq state dies with the device — those meshes still
        # refuse; data x model shrinks the dp axis keeping tp intact
        # (tests/test_mesh_spec.py covers that path e2e)
        mesh = build_mesh(MeshSpec(data=1, seq=8), jax.devices()[:8])
        with pytest.raises(NotImplementedError, match="data"):
            shrink_data_mesh(mesh, {jax.devices()[0]})
        devs = jax.devices()[:8]
        dptp = build_mesh(MeshSpec(data=4, model=2), devs)
        shrunk = shrink_data_mesh(dptp, {devs[5]})    # kills dp row 2
        assert shrunk.shape["data"] == 2
        assert shrunk.shape["model"] == 2
        assert devs[5] not in set(shrunk.devices.flat)
        assert devs[4] not in set(shrunk.devices.flat)   # same row

    def test_device_loss_shrinks_and_matches_checkpoint_restart(
            self, tmp_path):
        """dp=8 run with a device loss injected at step 6 shrinks to
        dp=4 WITHOUT raising, trains to completion, and the final
        params match a from-checkpoint dp=4 restart bit-for-bit."""
        batches = make_batches(12, batch=16, seed=4)
        before = REGISTRY.counter("elastic_mesh_shrinks_total").value

        # ---- run A: loss mid-run, survive-and-shrink ---------------
        netA = tiny_classifier(seed=3)
        pwA = ParallelWrapper(
            netA, build_mesh(MeshSpec(data=8), jax.devices()[:8]),
            prefetch_buffer=0)
        chaos.install({"faults": [{"site": "parallel.device",
                                   "kind": "loss", "at": [6]}]},
                      seed=0)
        pwA.fit(ListDataSetIterator(batches), epochs=1)   # no raise
        chaos.uninstall()
        assert pwA.mesh.shape["data"] == 4
        assert pwA.mesh_shrinks == 1
        assert netA.iteration_count == 12        # ran to completion
        assert REGISTRY.counter(
            "elastic_mesh_shrinks_total").value == before + 1

        # ---- run B: checkpoint at the loss boundary, dp=4 restart --
        netB = tiny_classifier(seed=3)
        pwB = ParallelWrapper(
            netB, build_mesh(MeshSpec(data=8), jax.devices()[:8]),
            prefetch_buffer=0)
        pwB.fit(ListDataSetIterator(batches[:5]), epochs=1)
        ck = str(tmp_path / "at_loss.zip")
        write_model(netB, ck)
        netC = restore_model(ck)
        pwC = ParallelWrapper(
            netC, build_mesh(MeshSpec(data=4), jax.devices()[:4]),
            prefetch_buffer=0)
        pwC.fit(ListDataSetIterator(batches[5:]), epochs=1)

        assert netC.iteration_count == 12
        for a, b in zip(_flat_params(netA), _flat_params(netC)):
            np.testing.assert_array_equal(a, b)
        assert float(netA.score_value) == float(netC.score_value)

    def test_elastic_trainer_wrapper_composition(self, tmp_path):
        """ElasticTrainer + ParallelWrapper: the trainer owns the
        epoch loop — per-batch wrapper steps must not bump
        epoch_count or fire epoch hooks (and must not wrap each
        single batch in a prefetch thread: prefetch_buffer=2 here
        would crash the old fit([ds]) path on list.reset)."""
        from deeplearning4j_tpu.train.listeners import TrainingListener

        class Hooks(TrainingListener):
            epochs = 0
            iters = 0

            def on_epoch_start(self, model):
                Hooks.epochs += 1

            def iteration_done(self, model, iteration, score, bs):
                Hooks.iters += 1

        batches = make_batches(4, batch=16, seed=9)
        net = tiny_classifier(seed=0)
        net.set_listeners(Hooks())
        pw = ParallelWrapper(
            net, build_mesh(MeshSpec(data=8), jax.devices()[:8]),
            prefetch_buffer=2)
        tr = ElasticTrainer(net, str(tmp_path), save_every=2,
                            handle_sigterm=False, wrapper=pw)
        tr.fit(ListDataSetIterator(batches), epochs=1)
        assert net.iteration_count == 4
        assert net.epoch_count == 0          # trainer owns epochs
        assert Hooks.epochs == 0             # no per-batch epoch hooks
        assert Hooks.iters == 4

    def test_lose_device_and_explicit_regrow(self):
        batches = make_batches(4, batch=16, seed=6)
        net = tiny_classifier(seed=1)
        pw = ParallelWrapper(
            net, build_mesh(MeshSpec(data=8), jax.devices()[:8]),
            prefetch_buffer=0)
        pw.fit(ListDataSetIterator(batches[:2]), epochs=1)
        lost = list(pw.mesh.devices.flat)[3]
        pw.lose_device(3)
        assert pw.mesh.shape["data"] == 4
        pw.fit(ListDataSetIterator(batches[2:3]), epochs=1)
        # regrow is EXPLICIT, never automatic — and its default
        # refuses to re-adopt a device still recorded as lost
        assert pw.regrow().shape["data"] == 4
        assert lost not in set(pw.mesh.devices.flat)
        # an explicit device list is the operator vouching for them
        mesh = pw.regrow(jax.devices()[:8])
        assert mesh.shape["data"] == 8
        pw.fit(ListDataSetIterator(batches[3:]), epochs=1)
        assert net.iteration_count == 4
        assert np.isfinite(float(net.score_value))
