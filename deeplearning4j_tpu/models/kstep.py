"""k-step fused on-device training + AOT train-program warmup.

Small models are dispatch-bound: a LeNet step is little device
compute and a full host round-trip. The classic fix is the
in-graph training loop of the TensorFlow papers (arXiv:1605.08695
§3.3, arXiv:1603.04467): keep the device busy across many steps per
host interaction, and pre-compile the executables so the steady state
never traces.

Two pieces, shared by both executors
(``models/multi_layer_network.py``, ``models/computation_graph.py``;
the executor supplies its traced single-step core ``_train_core`` and
this module supplies the window plumbing):

- :func:`make_kstep_fn` fuses k training steps into ONE device
  program — a ``lax.scan`` over a host-stacked ``[k, ...]`` batch
  window with the ``(params, state, opt_state)`` carry donated,
  emitting
  stacked per-step ``loss`` — and, when the health monitor is
  attached, the fused ``[k, 5]`` health block — so the host still
  observes EVERY step from a single device→host fetch per window:
  detection/rollback lag is bounded by k, never lost. k is a
  PYTHON-static loop bound (the scan length is the window's leading
  dim, fixed at trace time), never a traced value — no GL002
  recompile hazard.

- :func:`aot_compile` / :func:`warmup_train_programs` pre-build the
  k-step program AND the k=1 tail-remainder program via
  ``jit(...).lower(shapes).compile()`` at startup — compilation from
  abstract shapes only, no execution (training warmup must not
  advance params) and no real buffers. The executors then dispatch
  the AOT-compiled executable directly whenever the incoming batch
  signature matches, so the steady state neither traces nor compiles
  (``observability.compile_watch.zero_compile_scope`` proves it).
"""

from __future__ import annotations

import contextlib
import copy
import functools
import time
from typing import Any, Dict, Sequence, Tuple

import numpy as np

__all__ = ["signature", "stack_batches", "make_kstep_fn",
           "aot_compile", "warmup_train_programs", "canonical_np",
           "KStepExecutorMixin"]


def signature(tree) -> Tuple:
    """Hashable shape/dtype signature of an argument pytree.

    The treedef is part of the key, so mask-presence (a ``None`` slot
    vs an array) distinguishes signatures. Used both as the AOT
    program-cache key and as the uniformity check that decides
    whether a window of batches may be fused into one scan."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (treedef,
            tuple((tuple(np.shape(l)), np.dtype(_dtype_of(l)).str)
                  for l in leaves))


def _dtype_of(x):
    dt = getattr(x, "dtype", None)
    return dt if dt is not None else np.asarray(x).dtype


def canonical_np(x):
    """Host array in JAX's CANONICAL dtype (f64→f32, i64→i32 unless
    x64 is enabled). The executors' host batch tuples go through
    this so an AOT cache key computed from host arrays matches what
    ``jnp.asarray`` will actually hand the program at dispatch — a
    float64 label array (``np.eye`` defaults to f64) must not make
    the warmed k=1 executable unreachable."""
    import jax
    a = np.asarray(x)
    dt = jax.dtypes.canonicalize_dtype(a.dtype)
    return a if a.dtype == dt else a.astype(dt)


def stack_batches(batch_tuples: Sequence):
    """Host-stack k same-signature batch tuples into one ``[k, ...]``
    window (``np.stack`` per leaf; ``None`` mask slots must be
    ``None`` in every batch — enforced upstream by comparing
    :func:`signature`). Stacking on HOST means the window reaches the
    device as one transfer and the per-batch device arrays of the
    per-step path are never materialized."""
    if len(batch_tuples) < 2:
        raise ValueError("a window needs at least 2 batches")
    import jax
    return jax.tree_util.tree_map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]),
        *batch_tuples)


def _tree_nbytes(tree) -> int:
    """Bytes of a tree's arrays (a batch as it crosses to the device,
    a network's parameters); a leaf that is no array counts nothing."""
    import jax
    return sum(int(getattr(x, "nbytes", 0))
               for x in jax.tree_util.tree_leaves(tree))


# the k=1 lookahead has pulled nothing (None is an exhausted iterator)
_UNPULLED = object()


def _h2d_wait(trace, batch) -> None:
    """While tracing, and with the step already enqueued: wait until
    the batch's copy has landed, which is when the device can start
    the step. The span's end names what the device waited for; it
    does not delay the device, and the host would block in a
    listener's score fetch next anyway. A batch the k=1 lookahead
    placed a step early has landed already, and the span is empty.
    Off, nothing is called."""
    if trace.enabled:
        import jax
        with trace.span("h2d_wait"):
            jax.block_until_ready(batch)


def make_kstep_fn(step_core, k: int, health_enabled: bool,
                  out_shardings=None):
    """Build the fused k-step train program.

    ``step_core(params, state, opt_state, batch, rng)`` is the
    executor's traced single-step math — the SAME function the k=1
    jitted step wraps, so the two programs compute identical updates
    (bit-identical params across k, regression-tested).

    Donation (GL003-audited): the ``(params, state, opt_state)``
    carry is consumed by the scan — argnums 0-2 donate and the caller
    rebinds from the outputs. The stacked window is deliberately NOT
    donated even though its buffer is dead after the call: scan xs
    are consumed by slicing and no output shares their shape, so XLA
    can never alias them — donation would be a no-op that warns
    "donated buffers were not usable" on every trace. ``base_rng`` is
    reused across calls and must not donate either.

    ``out_shardings`` (the mesh-spec fit path,
    ``parallel/mesh_spec.py``) pins the program's output layout to
    the input layout: without the pin GSPMD may emit a different
    sharding for a carry leaf than the one it arrived with, and the
    NEXT window's changed input shardings silently recompile every
    call.
    """
    import jax
    return jax.jit(kstep_fn(step_core, k, health_enabled),
                   **_carry_jit_kwargs(out_shardings))


def _carry_jit_kwargs(out_shardings=None) -> dict:
    """``jax.jit`` options of a train program: the ``(params, state,
    opt_state)`` carry donated, the outputs pinned under a mesh."""
    kw = {"donate_argnums": (0, 1, 2)}
    if out_shardings is not None:
        kw["out_shardings"] = out_shardings
    return kw


def kstep_fn(step_core, k: int, health_enabled: bool):
    """The Python function :func:`make_kstep_fn` jits."""
    if k < 2:
        raise ValueError("k-step fusion needs k >= 2; the k=1 path "
                         "is the executor's single-step program")
    import jax
    import jax.numpy as jnp

    def kstep_train(params, state, opt_state, window, base_rng, step0):
        def body(carry, xs):
            p, s, o = carry
            batch_i, i = xs
            # per-step rng identical to the per-step loop's
            # fold_in(base_rng, iteration_count): step0 + i
            rng = jax.random.fold_in(base_rng, step0 + i)
            out = step_core(p, s, o, batch_i, rng)
            if health_enabled:
                p2, s2, o2, loss, health = out
                return (p2, s2, o2), (loss, health)
            p2, s2, o2, loss = out
            return (p2, s2, o2), loss

        (p, s, o), ys = jax.lax.scan(
            body, (params, state, opt_state),
            (window, jnp.arange(k, dtype=jnp.int32)))
        if health_enabled:
            losses, healths = ys
            return p, s, o, losses, healths
        return p, s, o, ys

    return kstep_train


def aot_compile(jit_fn, example_args) -> Tuple[Any, float]:
    """``jit(...).lower(shapes).compile()``: build the executable from
    abstract shapes WITHOUT executing (a training warmup must not
    advance params) and WITHOUT allocating real buffers. Returns
    ``(compiled, seconds)``; the compiled object is directly callable
    with concrete arguments of exactly this signature (donation
    preserved).

    Example leaves that are mesh-placed ``jax.Array``s (or
    ``ShapeDtypeStruct``s already carrying a sharding — the
    mesh-spec fit path's abstract batches) keep their sharding in
    the lowered signature, so the compiled executable accepts
    exactly the sharded arguments dispatch will feed it; a
    sharding-less lowering would compile an executable the sharded
    steady state can never hit."""
    import jax
    from jax.sharding import NamedSharding

    def _abstract(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        sh = getattr(x, "sharding", None)
        if isinstance(sh, NamedSharding):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)
        return jax.ShapeDtypeStruct(np.shape(x), _dtype_of(x))

    abstract = jax.tree_util.tree_map(_abstract, example_args)
    t0 = time.perf_counter()
    compiled = jit_fn.lower(*abstract).compile()
    return compiled, time.perf_counter() - t0


class KStepExecutorMixin:
    """The executor-side window plumbing both executors share — one
    copy, so a fix to program selection, AOT dispatch, the per-step
    listener fan-out or the window entry point cannot drift between
    them. The host executor supplies ``_train_core``,
    ``_batch_tuple``/``_batch_tuple_np``, the
    ``_jit_train_step``/``_jit_kstep``/``_aot`` caches, and three
    small adapters — ``_coerce_fit_batch`` (DataSet → its native
    batch object), ``_batch_is_tbptt`` and ``_run_tbptt``; batches
    only need ``num_examples()``.

    MESH-SPEC SHARDING (``parallel/mesh_spec.py``): :meth:`use_mesh`
    installs a :class:`~deeplearning4j_tpu.parallel.mesh_spec.MeshContext`
    — params/opt-state placed per the spec (tensor-parallel rules
    over 'model', replication over 'data'), every batch/window
    transfer sharded over 'data', and every train program (k=1 AND
    the fused k-step scan) built with pinned ``out_shardings`` so
    the sharded steady state never recompiles. The k-step window
    machinery below is mesh-agnostic: a fused window over a dp x tp
    mesh is the same ``lax.scan`` program, GSPMD-partitioned —
    fused multichip steps in ONE device program."""

    # the installed MeshContext (None = single-device semantics);
    # a class default so both executors inherit it without touching
    # their __init__s
    _mesh_ctx = None
    # does the k=1 train step return the held experts' counts beside
    # the loss (``MultiLayerNetwork.counts_experts``)? Not the fused
    # window's, whose steps are counted by nobody
    counts_experts = False
    # counts of enqueued steps, still on the device (``_tally_counts``)
    _pending_counts = ()

    def use_mesh(self, mesh_spec, devices=None, *,
                 respect_existing: bool = False):
        """Install a declarative mesh spec (``"dp=4,tp=2"`` | dict |
        JSON | a prebuilt ``MeshContext``) on this executor: place
        the model, and invalidate every compiled train program so
        the next fit builds sharded, output-pinned executables.
        ``respect_existing`` keeps param leaves a caller already
        placed on an equal mesh (the ParallelWrapper contract)."""
        from deeplearning4j_tpu.parallel.mesh_spec import (
            MeshContext, build_mesh_context)
        if mesh_spec is None:
            return self
        tbptt = self.conf.conf.tbptt
        if tbptt is not None:
            raise NotImplementedError(
                "tBPTT does not compose with mesh_spec yet (the "
                "chunked step threads recurrent carries the sharded "
                "program does not pin); drop tbptt or the mesh spec")
        if self.params is None:
            self.init()
        ctx = (mesh_spec if isinstance(mesh_spec, MeshContext)
               else build_mesh_context(mesh_spec, self, devices))
        cur = self._mesh_ctx
        if (cur is not None and cur.plan == ctx.plan
                and tuple(cur.mesh.devices.flat)
                == tuple(ctx.mesh.devices.flat)):
            # same spec over the same devices: keep the installed
            # context AND its compiled programs — warmup(mesh_spec=X)
            # followed by fit(mesh_spec=X) must not flush the
            # AOT-warmed executables and recompile on the first step
            cur.place_model(self, respect_existing=True)
            return self
        self._mesh_ctx = ctx
        ctx.place_model(self, respect_existing=respect_existing)
        # every compiled program pins shardings — rebuild them all
        self._flush_compiled_programs()
        return self

    def _flush_compiled_programs(self) -> None:
        """Drop every compiled/AOT train program — the ONE flush
        both mesh installers use (``use_mesh`` here, the wrapper's
        shrink/regrow rebuild), so a future executor cache cannot be
        missed at one site and serve stale-mesh executables."""
        self._jit_train_step = None
        self._jit_tbptt_step = None
        self._jit_kstep = {}
        self._aot = {}

    def _mesh_scope(self):
        """Trace-time announcement of the installed mesh
        (``parallel/seq_context.gspmd_mesh``), entered by the traced
        train and output bodies: what GSPMD cannot partition (the
        Pallas attention kernels) wraps itself in a shard_map on it.
        A null scope without a mesh."""
        if self._mesh_ctx is None:
            return contextlib.nullcontext()
        from deeplearning4j_tpu.parallel.seq_context import gspmd_mesh
        return gspmd_mesh(self._mesh_ctx.mesh)

    def _mesh_out_shardings(self):
        """Pinned ``out_shardings`` for the train programs under the
        installed mesh context (None otherwise) — the single place
        that knows how many trailing scalar/stacked outputs the step
        tuple carries (loss, plus the health block when enabled)."""
        if self._mesh_ctx is None:
            return None
        n_out = 2 if self._health_enabled else 1
        return self._mesh_ctx.step_out_shardings(self, n_out)

    def _train_step_jit_kwargs(self) -> dict:
        """``jax.jit`` options of the k=1 train program: the fused
        window's, with one more replicated output where the step
        returns the experts' counts."""
        pinned = self._mesh_out_shardings()
        if pinned is not None and self.counts_experts:
            pinned += pinned[-1:]
        return _carry_jit_kwargs(pinned)

    def _train_step_fn(self):
        """The Python function of the k=1 train program: ``(params,
        state, opt_state, loss[, health][, counts])``, the counts
        last and only where the network ``counts_experts``. One
        program and one maker (``_make_train_step``, which the
        benchmark's tools replace to break or to read the step), so a
        caller that wants the carry and the loss takes the first
        four (``ParallelWrapper._train_batch``)."""
        import jax
        core = self._train_core
        if self.counts_experts:
            core = functools.partial(core, extras=True)

        def train_step(params, state, opt_state, batch, base_rng, step):
            # step arrives as a traced scalar; folding inside the jit
            # avoids a host-side dispatch per iteration
            rng = jax.random.fold_in(base_rng, step)
            return core(params, state, opt_state, batch, rng)

        return train_step

    def _make_train_step(self):
        # under a mesh context the program's output layout is pinned
        # to the placed model's: GSPMD must not drift a carry
        # sharding and recompile every step
        import jax
        return jax.jit(self._train_step_fn(),
                       **self._train_step_jit_kwargs())

    def _without_arrays(self):
        """A shallow copy of this executor that can trace its
        programs and keeps nothing else alive: every attribute that
        holds a ``jax.Array`` (parameters, optimizer state, the rng
        key, the latest batch and score), every compiled program and
        the listeners are dropped. What the traced math reads (the
        configuration, the layers, the optimizer's rule, the mesh
        context) is shared with the original."""
        import jax
        shell = copy.copy(self)
        for key, value in vars(self).items():
            if key.startswith("_jit") or key in ("_aot", "_registered"):
                setattr(shell, key, {} if isinstance(value, dict) else None)
            elif key == "listeners" or any(
                    isinstance(leaf, jax.Array)
                    for leaf in jax.tree_util.tree_leaves(value)):
                setattr(shell, key, None)
        return shell

    def _first_call(self, name: str, jitted, fn_of, call, args):
        """The first call of the train program ``jitted`` (built by
        this executor), whole, under one ``setup/program`` span of the
        set-up timeline: ``observability.programs`` is told of it,
        then ``call(*args)`` runs it (trace, lowering, compile or
        load, dispatch). ``fn_of(executor)`` makes the program's
        Python function: it is called on :meth:`_without_arrays`, so
        what the registry keeps holds no parameter."""
        from deeplearning4j_tpu.observability import programs
        from deeplearning4j_tpu.observability.tracing import (
            startup, trace)
        with startup.span("setup/program", {"program": name}):
            self._registered[name] = jitted
            programs.register(
                name, fn_of(self._without_arrays()),
                self._train_step_jit_kwargs() if name == "train_step"
                else _carry_jit_kwargs(self._mesh_out_shardings()),
                args)
            with trace.span("enqueue"):
                return call(*args)

    def _fit_epoch(self, data_iter, k: int, tbptt) -> None:
        """One epoch's batch loop (shared by both executors' ``fit``):
        time the data wait, collect k-batch windows (k > 1), flush on
        tBPTT entries so step order is preserved, and flush the tail
        at exhaustion. Epoch hooks stay with the caller.

        At k=1 the loop looks exactly ONE batch ahead: once step n is
        enqueued, and before its listeners run, batch n+1 is pulled
        and its placement started, so its copy runs while the device
        computes step n and while the host blocks in a listener's
        score fetch; the next pass only enqueues. The lookahead's
        ``data_wait`` and ``batch_to_device`` spans hang under step
        n's ``step``. It never crosses the epoch's end (an exhausted
        iterator is not pulled again), never places a tBPTT batch (the
        next pass gets it unplaced) and does not exist at k > 1.

        Contract: when a listener raises out of ``iteration_done`` at
        iteration n, ``iteration_count`` is n and the parameters are
        those after step n, as before, and the iterator has been
        advanced one batch PAST the step that raised. When the
        iterator or the placement of batch n+1 raises, step n's
        listeners have run and counted it first."""
        from deeplearning4j_tpu.observability.tracing import trace
        pending = []          # k-step window under collection
        nxt = _UNPULLED       # what the k=1 lookahead pulled
        while True:
            # one iteration's spans hang under ``step`` (a group, so
            # not annotated into the profiler's trace); the tracer may
            # be switched inside the iterator, so an iteration can
            # arrive without one
            with trace.span("step", annotate=False) as step:
                if nxt is _UNPULLED:
                    nxt = self._pull_batch(data_iter, tbptt, ahead=False)
                if nxt is None:
                    step.set("exhausted", True)
                    break
                (m, wait, batch), nxt = nxt, _UNPULLED
                # the iteration this batch becomes (a window's batches
                # wait in ``pending`` for its last)
                step.set("iteration", self.iteration_count + len(pending))
                step.set("samples", m.num_examples())
                if self._batch_is_tbptt(m, tbptt):
                    # tBPTT chunks its own loop — flush the window
                    # first so step order is preserved
                    self._flush_window(pending, k)
                    with trace.span("train_step_tbptt"):
                        self._run_tbptt(m, tbptt, data_wait_s=wait)
                    continue
                if k == 1:
                    step.set("prefetched", batch is not None)
                    self._enqueue_step(m, wait, batch)
                    try:
                        nxt = self._pull_batch(data_iter, tbptt,
                                               ahead=True)
                    finally:
                        self._finish_step(m)
                    continue
                pending.append((m, wait))
                if len(pending) == k:
                    self._flush_window(pending, k)
        self._flush_window(pending, k)
        self._tally_counts(wait=True)

    def _pull_batch(self, data_iter, tbptt, ahead: bool):
        """The iterator's next batch as ``(batch object, data wait
        seconds, placed tuple or None)``, or None at exhaustion.
        ``ahead`` (the k=1 lookahead, called while the step just
        enqueued runs) also starts the batch's placement, unless it is
        a tBPTT batch."""
        from deeplearning4j_tpu.observability.tracing import trace
        # data wait timed apart from the step so the profiler/tracer
        # can tell an input-starved chip from a dispatch-bound host
        t0 = time.perf_counter()
        with trace.span("data_wait"):
            ds = next(data_iter, None)
        if ds is None:
            return None
        wait = time.perf_counter() - t0
        m = self._coerce_fit_batch(ds)
        placed = None
        if ahead and not self._batch_is_tbptt(m, tbptt):
            placed = self._place_batch(m, ahead=True)
        return m, wait, placed

    def _place_batch(self, ds, ahead: bool):
        """Start the batch's host→device copy (``device_put`` returns
        before it lands). ``ahead``: started before the previous
        step's listeners ran."""
        from deeplearning4j_tpu.observability.tracing import trace
        with trace.span("batch_to_device") as sp:
            if self._mesh_ctx is not None:
                # shard from HOST arrays: host→mesh device_put is
                # a plain per-shard copy, while resharding an
                # already-committed device array onto a multi-axis
                # mesh compiles a _multi_slice program per shape —
                # a stray compile the warmed zero-compile steady
                # state must not pay
                batch = self._mesh_ctx.shard_batch(
                    self._batch_tuple_np(ds))
            else:
                batch = self._batch_tuple(ds)
            if trace.enabled:
                sp.set("bytes", _tree_nbytes(batch)).set("ahead", ahead)
        return batch

    def _fit_one(self, ds, data_wait_s: float = 0.0):
        """One single-step device call + listener pass (the k=1 path
        of ``fit_batches`` and of a window's tail: no lookahead)."""
        self._enqueue_step(ds, data_wait_s)
        self._finish_step(ds)

    def _enqueue_step(self, ds, data_wait_s: float, batch=None):
        """Enqueue the k=1 program on ``ds``; ``batch`` is its placed
        tuple when the lookahead placed it, else it is placed here."""
        from deeplearning4j_tpu.observability.tracing import trace
        t1 = time.perf_counter()
        with trace.span("train_step"):
            if batch is None:
                batch = self._place_batch(ds, ahead=False)
            args = (self.params, self.state, self.opt_state, batch,
                    self._rng_key, np.int32(self.iteration_count))
            if self._registered.get("train_step") \
                    is not self._jit_train_step:
                out = self._first_call(
                    "train_step", self._jit_train_step,
                    KStepExecutorMixin._train_step_fn,
                    self._step_fn_for(batch), args)
            else:
                with trace.span("enqueue"):
                    out = self._step_fn_for(batch)(*args)
        if self.counts_experts:
            *out, counts = out
            self._pending_counts += (counts,)
        if self._health_enabled:
            (self.params, self.state, self.opt_state,
             loss, self._last_health) = out
        else:
            (self.params, self.state, self.opt_state, loss) = out
        self._last_batch = batch
        self.score_value = loss
        # (data_wait_s, dispatch_s) — ProfilerListener
        self._step_timing = (data_wait_s, time.perf_counter() - t1)
        _h2d_wait(trace, batch)

    def _finish_step(self, ds):
        """The enqueued step's listener pass; counts the iteration."""
        from deeplearning4j_tpu.observability.tracing import trace
        with trace.span("listeners"):
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration_count,
                                   self.score_value, ds.num_examples())
        self.iteration_count += 1
        self._tally_counts()

    def _tally_counts(self, wait: bool = False) -> None:
        """Add the held experts' counts of the steps that have ended
        to ``train_moe_pairs_total`` (the (row, held expert) pairs the
        steps computed), ``train_moe_pairs_busiest_expert_total`` (of
        those, the pairs of each step's busiest held expert) and
        ``train_moe_steps_total`` (the steps counted). A step whose
        score a listener has read has ended, and its counts are read
        without a wait; one still running keeps them until a later
        call, or the epoch's end (``wait``)."""
        if not self._pending_counts:
            return
        from deeplearning4j_tpu.observability.registry import REGISTRY
        pairs = REGISTRY.counter("train_moe_pairs_total")
        busiest = REGISTRY.counter("train_moe_pairs_busiest_expert_total")
        steps = REGISTRY.counter("train_moe_steps_total")
        ended = [wait or c.is_ready() for c in self._pending_counts]
        pending, self._pending_counts = self._pending_counts, tuple(
            c for c, done in zip(self._pending_counts, ended) if not done)
        for counts, done in zip(pending, ended):
            if done:
                counts = np.asarray(counts)
                pairs.inc(int(counts.sum()))
                busiest.inc(int(counts.max()))
                steps.inc()

    def fit_batches(self, batches, *, steps_per_device_call=1):
        """Train on a list of batches in one listener-visible pass
        with NO epoch bookkeeping (ElasticTrainer's window entry
        point, the k-step analog of ``ParallelWrapper.fit_batch``).
        When ``len(batches) == steps_per_device_call > 1`` and all
        batches share one shape signature, the whole window runs as
        a single fused device program; otherwise batches run through
        the (pre-compiled) single-step program. The default is the
        per-step path — fusing is OPT-IN via ``steps_per_device_call``
        because a fused program's compile cost grows with k (a
        convenience caller passing 200 batches must not silently
        compile a 200-step scan). Returns the per-step losses as a
        host numpy array."""
        from deeplearning4j_tpu.observability.tracing import trace
        if self.params is None:
            self.init()
        self._sync_health_mode()
        if self._jit_train_step is None:
            self._jit_train_step = self._make_train_step()
        items = [self._coerce_fit_batch(d) for d in batches]
        k = int(steps_per_device_call)
        tbptt = self.conf.conf.tbptt
        if k > 1 and len(items) == k and not any(
                self._batch_is_tbptt(m, tbptt) for m in items):
            tups = [self._batch_tuple_np(m) for m in items]
            if len({signature(t) for t in tups}) == 1:
                return self._dispatch_window(tups, items, [0.0] * k, k)
        out = []
        for i, m in enumerate(items):
            # which window entry is live (a tBPTT entry spans several
            # iterations — ElasticTrainer must not map a mid-entry
            # rollback to a neighbouring batch's ordinal)
            self._window_batch_index = i
            if self._batch_is_tbptt(m, tbptt):
                with trace.span("train_step_tbptt"):
                    self._run_tbptt(m, tbptt)
                out.append(float(self.score_value))
                continue
            self._fit_one(m)
            out.append(float(self.score_value))
        return np.asarray(out, dtype=np.float64)

    def _step_fn_for(self, batch):
        """The k=1 program for this batch signature: the AOT-compiled
        executable when :meth:`warmup` built one (zero trace, zero
        compile), else the jit wrapper."""
        if self._aot:
            fn = self._aot.get(("train1", signature(batch)))
            if fn is not None:
                return fn
        return self._jit_train_step

    def _kstep_fn_for(self, window, k: int):
        if self._aot:
            fn = self._aot.get(("kstep", k, signature(window)))
            if fn is not None:
                return fn
        fn = self._jit_kstep.get(k)
        if fn is None:
            fn = self._jit_kstep[k] = make_kstep_fn(
                self._train_core, k, self._health_enabled,
                out_shardings=self._mesh_out_shardings())
        return fn

    def _flush_window(self, pending, k: int):
        """Dispatch the collected window: one fused program when the
        window is FULL (len == k) and every batch shares one shape
        signature; anything else (the epoch tail, a shape-churn
        batch) runs per-batch through the pre-compiled k=1 program —
        never a fresh mid-epoch trace of an odd-length scan."""
        if not pending:
            return
        batches = [d for d, _ in pending]
        waits = [w for _, w in pending]
        del pending[:]
        if len(batches) == k and k > 1:
            tups = [self._batch_tuple_np(d) for d in batches]
            if len({signature(t) for t in tups}) == 1:
                self._dispatch_window(tups, batches, waits, k)
                return
        for d, w in zip(batches, waits):
            self._fit_one(d, w)

    def _dispatch_window(self, tups, batches, waits, k: int):
        """One fused k-step device call, then the per-step listener
        pass over the stacked outputs. The loss vector (and, with a
        health listener, the [k, 5] health block) is fetched ONCE per
        window — every step is still observed, detection lag is
        bounded by k."""
        from deeplearning4j_tpu.observability.tracing import trace
        with trace.span("train_step_fused") as fused:
            with trace.span("batch_to_device") as sp:
                window = stack_batches(tups)
                if self._mesh_ctx is not None:
                    window = self._mesh_ctx.shard_window(window)
                if trace.enabled:
                    sp.set("bytes", _tree_nbytes(window))
            fn = self._kstep_fn_for(window, k)
            args = (self.params, self.state, self.opt_state, window,
                    self._rng_key, np.int32(self.iteration_count))
            name = f"train_step_fused/k={k}"
            t1 = time.perf_counter()
            # without a mesh the window is still on the host here: its
            # copy rides the call
            if self._registered.get(name) is not self._jit_kstep.get(k):
                health = self._health_enabled
                out = self._first_call(
                    name, self._jit_kstep[k],
                    lambda net: kstep_fn(net._train_core, k, health),
                    fn, args)
            else:
                with trace.span("enqueue"):
                    out = fn(*args)
            fused.set("steps", k)
        _h2d_wait(trace, window)
        health_host = None
        if self._health_enabled:
            (self.params, self.state, self.opt_state,
             losses, healths) = out
            health_host = np.asarray(healths)     # ONE fetch, [k, 5]
        else:
            (self.params, self.state, self.opt_state, losses) = out
        loss_host = np.asarray(losses)            # ONE fetch, [k]
        dispatch_s = time.perf_counter() - t1
        # the last sub-batch (host arrays — the stacked window's
        # device buffer was consumed by the scan) for the
        # dead-activation checker
        self._last_batch = tups[-1]
        per_step_s = dispatch_s / k
        with trace.span("listeners"):
            for i in range(k):
                # which window entry is live — ElasticTrainer maps a
                # listener-raised rollback back to its batch ordinal
                # through this (robust to multi-iteration tBPTT
                # entries on the non-fused path)
                self._window_batch_index = i
                self._last_health = (None if health_host is None
                                     else health_host[i])
                self.score_value = loss_host[i]
                self._step_timing = (waits[i], per_step_s)
                for lst in self.listeners:
                    lst.iteration_done(self, self.iteration_count,
                                       loss_host[i],
                                       batches[i].num_examples())
                self.iteration_count += 1
        return loss_host


def warmup_train_programs(model, batch_np, k: int) -> Dict[str, float]:
    """AOT-compile a model's train-step programs for one batch
    signature: the k=1 single-step program (also the tail-remainder
    program when ``n_batches % k != 0``) and, for ``k > 1``, the
    fused k-step scan program. Installs the executables in
    ``model._aot`` (keyed by signature, consulted by the fit loop
    before falling back to the jit wrapper) and returns
    ``{program_name: compile_seconds}`` for what was actually built
    (already-warm signatures are skipped).

    Works on both executors — needs ``_train_core`` /
    ``_jit_train_step`` / ``_jit_kstep`` / ``_aot`` /
    ``_health_enabled`` and live ``params/state/opt_state/_rng_key``
    (call after ``init()``; the executor's ``warmup()`` method
    handles that)."""
    from deeplearning4j_tpu.observability.tracing import startup
    out: Dict[str, float] = {}
    # under a mesh context the lowered batch/window signatures carry
    # the data shardings dispatch will use — a sharding-less lowering
    # would build executables the sharded fit loop can never hit
    ctx = getattr(model, "_mesh_ctx", None)
    batch_ex = ctx.abstract_batch(batch_np) if ctx else batch_np
    args1 = (model.params, model.state, model.opt_state, batch_ex,
             model._rng_key, np.int32(0))
    key1 = ("train1", signature(batch_np))
    if key1 not in model._aot:
        with startup.span("setup/program", {"program": "train_step"}):
            compiled, secs = aot_compile(model._jit_train_step, args1)
        model._aot[key1] = compiled
        out["train_step"] = secs
    if k > 1:
        window = stack_batches([batch_np] * k)
        keyk = ("kstep", k, signature(window))
        if keyk not in model._aot:
            # the SAME get-or-create the fit loop uses — warmup and
            # dispatch can never build different programs for one k
            fn = model._kstep_fn_for(window, k)
            window_ex = ctx.abstract_window(window) if ctx else window
            argsk = (model.params, model.state, model.opt_state,
                     window_ex, model._rng_key, np.int32(0))
            with startup.span(
                    "setup/program",
                    {"program": f"train_step_fused/k={k}"}):
                compiled, secs = aot_compile(fn, argsk)
            model._aot[keyk] = compiled
            out[f"kstep_{k}"] = secs
    return out
