"""MultiLayerNetwork: the sequential-stack executor.

TPU rewrite of nn/multilayer/MultiLayerNetwork.java (3186 LoC). The
reference's per-iteration machinery — feedForwardToLayer (:900),
backprop (:1278)/calcBackpropGradients (:1293) with per-layer manual
gradients, Solver/StochasticGradientDescent (:57-100), updater blocks,
workspaces — collapses into ONE jitted ``train_step``:

    loss(params) = output_layer.loss(forward(params, x)) + reg
    grads        = jax.grad(loss)          (replaces calcBackpropGradients)
    updates      = optax update            (replaces UpdaterBlock.update)
    params'      = params + updates        (replaces StepFunction.step)
    constraints  = projection              (replaces applyConstraints :96)

XLA fuses the whole thing into a single TPU program; buffers are
donated so params update in place in HBM (the workspace analog).

Masking, tBPTT (doTruncatedBPTT :1404), stateful streaming inference
(rnnTimeStep :2656), layerwise pretraining (:221-343), and listener
dispatch (:1180, :89) all have direct equivalents below.
"""

from __future__ import annotations

import functools
import logging
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import (
    ArrayDataSetIterator, DataSetIterator, ListDataSetIterator,
)
from deeplearning4j_tpu.nn.conf import updaters as updaters_mod
from deeplearning4j_tpu.nn.conf.layers.base import Layer
from deeplearning4j_tpu.nn.conf.layers.output import (
    CenterLossOutputLayer, OutputLayer,
)
from deeplearning4j_tpu.nn.conf.layers.recurrent import BaseRecurrentLayer
from deeplearning4j_tpu.models.kstep import (KStepExecutorMixin,
                                             _tree_nbytes)
from deeplearning4j_tpu.nn.conf.multi_layer import MultiLayerConfiguration
from deeplearning4j_tpu.ops.attention import FLASH_KEPT
from deeplearning4j_tpu.train.constraints import apply_layer_constraints

logger = logging.getLogger("deeplearning4j_tpu")

__all__ = ["MultiLayerNetwork"]

# what a recomputed layer keeps beside its input (one object: jax keys
# its caches of traced functions by the policy)
_KEEPS_FLASH = jax.checkpoint_policies.save_only_these_names(*FLASH_KEPT)


def _as_iterator(data, labels=None, batch_size=None) -> DataSetIterator:
    if isinstance(data, DataSetIterator):
        return data
    if isinstance(data, DataSet):
        if batch_size is None:
            return ListDataSetIterator([data])
        return ListDataSetIterator(data.batch_by(batch_size))
    if labels is not None:
        return ArrayDataSetIterator(data, labels,
                                    batch_size or data.shape[0])
    raise TypeError(f"Cannot build iterator from {type(data)}")


class MultiLayerNetwork(KStepExecutorMixin):
    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers: List[Layer] = conf.layers
        self.params: Optional[List[Dict[str, jnp.ndarray]]] = None
        self.state: Optional[List[Dict[str, jnp.ndarray]]] = None
        self.opt_state = None
        self.listeners = []
        self.iteration_count = 0
        self.epoch_count = 0
        self.score_value: float = float("nan")
        self._rng_key = None
        self._rnn_state: Optional[List[Any]] = None    # rnnTimeStep stateMap
        self._jit_train_step = None
        self._jit_tbptt_step = None
        # train programs told to observability.programs, by name
        # (kstep._first_call)
        self._registered: Dict[str, Any] = {}
        # k-step fused programs (models/kstep.py): dict k -> jitted
        # scan program, plus AOT-compiled executables keyed by batch
        # signature (warmup() fills; the fit loop dispatches them
        # directly so the steady state never traces or compiles)
        self._jit_kstep: Dict[int, Any] = {}
        self._aot: Dict[tuple, Any] = {}
        self._jit_output = {}
        self._optimizer = None
        # (data_wait_s, dispatch_s) of the latest fit iteration —
        # read by observability.step_profile.ProfilerListener
        self._step_timing = None
        # observability.health wiring: when a listener sets
        # wants_device_health, the train step also returns the fused
        # [finite_bits, loss, |grads|, |updates|, |params|] vector,
        # stashed here UNFETCHED (the monitor does the one transfer)
        self._health_enabled = False
        self._last_health = None
        # device refs of the latest batch tuple (for the monitor's
        # optional dead-activation forward pass) — a reference, not a
        # copy or sync
        self._last_batch = None

    # ------------------------------------------------------------------
    # init (reference MultiLayerNetwork.init :396-554)
    # ------------------------------------------------------------------
    def init(self, seed: Optional[int] = None) -> "MultiLayerNetwork":
        from deeplearning4j_tpu.observability.tracing import startup
        seed = self.conf.conf.seed if seed is None else seed
        with startup.span("setup/init",
                          {"layers": len(self.layers)}) as sp:
            key = jax.random.PRNGKey(seed)
            self._rng_key = jax.random.fold_in(key, 0xD1)
            params, states = [], []
            t = self.conf.input_type
            keys = jax.random.split(key, max(len(self.layers), 1))
            for i, layer in enumerate(self.layers):
                if t is not None and i in self.conf.preprocessors:
                    t = self.conf.preprocessors[i].output_type(t)
                if t is not None:
                    layer.set_n_in(t)
                p, s = layer.initialize(keys[i], t)
                params.append(p)
                states.append(s)
                if t is not None:
                    t = layer.output_type(t)
            self.params = params
            self.state = states
            sp.set("param_bytes", _tree_nbytes(params))
            self._build_optimizer()
        return self

    def _build_optimizer(self):
        from deeplearning4j_tpu.observability.tracing import startup
        global_cfg = self.conf.conf.updater_cfg or updaters_mod.sgd()
        overrides = [getattr(l, "updater", None) for l in self.layers]
        if any(o is not None for o in overrides):
            labels = []
            transforms = {"__global__": updaters_mod.to_optax(global_cfg)}
            for i, (l, o) in enumerate(zip(self.layers, overrides)):
                if o is not None:
                    name = f"layer{i}"
                    transforms[name] = updaters_mod.to_optax(o)
                else:
                    name = "__global__"
                labels.append(jax.tree_util.tree_map(lambda _: name,
                                                     self.params[i]))
            self._optimizer = optax.multi_transform(transforms, labels)
        else:
            self._optimizer = updaters_mod.to_optax(global_cfg)
        clip = self.conf.conf.gradient_clip
        if clip is not None:
            if clip["type"] == "norm":
                pre = optax.clip_by_global_norm(clip["v"])
            elif clip["type"] == "value":
                pre = optax.clip(clip["v"])
            else:
                raise ValueError(clip)
            self._optimizer = optax.chain(pre, self._optimizer)
        with startup.span("setup/init/optimizer") as sp:
            self.opt_state = self._optimizer.init(self.params)
            sp.set("state_bytes", _tree_nbytes(self.opt_state))
        self._jit_train_step = None    # invalidate
        self._jit_tbptt_step = None
        self._jit_kstep = {}
        self._aot = {}

    # ------------------------------------------------------------------
    # forward (reference feedForward :863-975)
    # ------------------------------------------------------------------
    def _forward(self, params, state, x, *, training, rng, fmask=None,
                 upto: Optional[int] = None, collect=False, carries=None,
                 train_step=None):
        """carries: optional per-layer recurrent (h, c) initial states —
        used by tBPTT to carry hidden state across chunks (reference
        rnnActivateUsingStoredState :2219). Returns new carries too.
        ``train_step``: a list the k=1 train step passes; every expert
        layer's held counts are appended to it, and under the
        configuration's ``recompute`` each layer's ``apply`` is wrapped
        in ``jax.checkpoint``."""
        acts = []
        new_states = []
        new_carries = [None] * len(self.layers)
        n = len(self.layers) if upto is None else upto
        for i in range(len(self.layers)):
            layer = self.layers[i]
            if i >= n:
                new_states.append(state[i])
                continue
            from deeplearning4j_tpu.nn.errors import layer_error_context
            if i in self.conf.preprocessors:
                with layer_error_context(f"preprocessor before layer {i}",
                                         self.conf.preprocessors[i], x):
                    x = self.conf.preprocessors[i](x)
            lrng = None
            if rng is not None:
                lrng = jax.random.fold_in(rng, i)
            # the layer's name on its device ops (metadata only): a
            # profiler trace then splits fusion time by layer
            scope = jax.named_scope(f"{i}_{type(layer).__name__}")
            recurrent = carries is not None and isinstance(
                layer, BaseRecurrentLayer)
            with layer_error_context(f"layer {i}", layer, x):
                if train_step is not None and not recurrent:
                    # the scope goes inside what may be recomputed:
                    # its backward ops then carry the layer's name too
                    x, s, counts = self._apply_in_train_step(
                        layer, scope, params[i], state[i], x, lrng,
                        fmask)
                    train_step.extend(counts)
                elif recurrent:
                    with scope:
                        c0 = carries[i]
                        if c0 is None:
                            c0 = layer.zero_state(x.shape[0])
                        xd = layer.apply_input_dropout(
                            x, training=training, rng=lrng)
                        x, c1 = layer.apply_rnn(params[i], xd, c0,
                                                training=training,
                                                rng=lrng, mask=fmask)
                    new_carries[i] = c1
                    s = state[i]
                else:
                    with scope:
                        x, s = layer.apply(params[i], state[i], x,
                                           training=training,
                                           rng=lrng, mask=fmask)
            new_states.append(s)
            if collect:
                acts.append(x)
        return x, new_states, acts, new_carries

    def _apply_in_train_step(self, layer, scope, params, state, x, rng,
                             fmask):
        """``layer.apply`` under its ``scope`` as the train step runs
        it: ``(out, state, [held counts] of a layer with experts)``,
        computed again in the backward pass where the configuration
        says ``recompute``, all but a flash call's output and row
        statistics (``ops.attention.FLASH_KEPT``), which are kept: the
        forward kernel runs once a step. The counts leave through the
        wrapped function's outputs, so they are the step's own values
        and not the recomputation's."""
        counted = getattr(layer, "apply_with_counts", None)

        def run(params, state, x):
            with scope:
                if counted is None:
                    return *layer.apply(params, state, x, training=True,
                                        rng=rng, mask=fmask), []
                y, s, counts = counted(params, state, x, training=True,
                                       rng=rng, mask=fmask)
            return y, s, [] if counts is None else [counts]

        if self.conf.conf.recompute == "layers":
            run = jax.checkpoint(run, policy=_KEEPS_FLASH)
        return run(params, state, x)

    def _loss(self, params, state, batch, rng, *, training=True,
              carries=None, train_step=None):
        x, labels, fmask, lmask = batch
        out_idx = len(self.layers) - 1
        out_layer = self.layers[out_idx]
        if not out_layer.has_loss():
            raise ValueError("Last layer has no loss; use an OutputLayer/"
                             "LossLayer for fit()")
        h, new_states, _, new_carries = self._forward(
            params, state, x, training=training, rng=rng, fmask=fmask,
            upto=out_idx, carries=carries, train_step=train_step)
        if out_idx in self.conf.preprocessors:
            h = self.conf.preprocessors[out_idx](h)
        orng = jax.random.fold_in(rng, out_idx) if rng is not None else None
        with jax.named_scope(
                f"{out_idx}_{type(out_layer).__name__}"):
            loss = out_layer.loss_from_input(
                params[out_idx], h, labels, training=training,
                rng=orng, mask=lmask)
        if isinstance(out_layer, CenterLossOutputLayer):
            loss = loss + out_layer.lambda_ * out_layer.center_loss(
                state[out_idx], h, labels)
            new_states[out_idx] = out_layer.update_centers(
                state[out_idx], h, labels)
        reg = jnp.zeros(())
        for layer, p in zip(self.layers, params):
            reg = reg + layer.regularization_loss(p)
        if carries is not None:
            return loss + reg, (new_states, new_carries)
        return loss + reg, new_states

    # ------------------------------------------------------------------
    # jitted train step (replaces Solver.optimize + SGD.optimize)
    # ------------------------------------------------------------------
    @property
    def counts_experts(self) -> bool:
        """Does the k=1 train step return the held experts' counts
        beside the loss? Where the network has expert layers that all
        hold as many experts (the counts are summed over the
        layers)."""
        return len({getattr(layer, "held_experts", 0)
                    for layer in self.layers} - {0}) == 1

    def _train_core(self, params, state, opt_state, batch, rng,
                    extras=False):
        """Traced single-step training math: loss → grads → updates →
        constraints (+ the fused health vector when a health listener
        is attached). Shared verbatim by the k=1 jitted step and the
        k-step ``lax.scan`` body (models/kstep.py), so the fused and
        per-step programs compute bit-identical updates. A network
        with ``recompute`` or with expert layers runs its layers
        through ``_apply_in_train_step``; any other's step is what it
        was. ``extras`` (the k=1 step of a network that
        ``counts_experts``): the held experts' counts summed over the
        layers, (held,) int32, are the last output."""
        from deeplearning4j_tpu.train.gradnorm import (
            apply_gradient_normalization)
        optimizer = self._optimizer

        def loss_fn(p):
            counts = ([] if self.counts_experts
                      or self.conf.conf.recompute else None)
            loss, new_states = self._loss(p, state, batch, rng,
                                          training=True,
                                          train_step=counts)
            return loss, (new_states, counts)

        with self._mesh_scope():
            (loss, (new_states, counts)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
        with jax.named_scope("updater"):
            grads = apply_gradient_normalization(self.layers, grads)
            updates, new_opt_state = optimizer.update(grads, opt_state,
                                                      params)
            new_params = optax.apply_updates(params, updates)
            new_params = [
                apply_layer_constraints(l, p)
                for l, p in zip(self.layers, new_params)
            ]
        if self._health_enabled:
            # fused finite check + global norms, computed inside
            # this same XLA program (observability/health.py)
            from deeplearning4j_tpu.observability.health import (
                fused_health)
            health = fused_health(loss, grads, updates, new_params)
            out = new_params, new_states, new_opt_state, loss, health
        else:
            out = new_params, new_states, new_opt_state, loss
        if extras:
            out += (sum(counts),)
        return out

    def _sync_health_mode(self) -> None:
        """Compile the fused health check into the train step iff a
        health-monitoring listener is attached (one jit invalidation
        per toggle, not per fit)."""
        want = any(getattr(l, "wants_device_health", False)
                   for l in self.listeners)
        if want != self._health_enabled:
            self._health_enabled = want
            self._jit_train_step = None
            self._jit_tbptt_step = None
            # the k-step programs' output structure includes the
            # stacked health block iff enabled — rebuild them too
            self._jit_kstep = {}
            self._aot = {}
            if not want:
                self._last_health = None

    def _make_tbptt_step(self):
        """Train step that also threads recurrent carries across chunks
        (reference doTruncatedBPTT :1404: state carried, gradient
        truncated at chunk boundaries)."""
        optimizer = self._optimizer
        from deeplearning4j_tpu.train.gradnorm import (
            apply_gradient_normalization)

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def tbptt_step(params, state, opt_state, batch, carries, base_rng,
                       step):
            rng = jax.random.fold_in(base_rng, step)
            carries = jax.lax.stop_gradient(carries)

            def loss_fn(p):
                loss, aux = self._loss(p, state, batch, rng, training=True,
                                       carries=carries)
                return loss, aux

            (loss, (new_states, new_carries)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            grads = apply_gradient_normalization(self.layers, grads)
            updates, new_opt_state = optimizer.update(grads, opt_state,
                                                      params)
            new_params = optax.apply_updates(params, updates)
            new_params = [apply_layer_constraints(l, p)
                          for l, p in zip(self.layers, new_params)]
            return (new_params, new_states, new_opt_state, loss,
                    jax.lax.stop_gradient(new_carries))

        return tbptt_step

    def _batch_tuple(self, ds: DataSet):
        f = jnp.asarray(ds.features)
        l = None if ds.labels is None else jnp.asarray(ds.labels)
        fm = None if ds.features_mask is None else jnp.asarray(
            ds.features_mask)
        lm = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask)
        return (f, l, fm, lm)

    def _batch_tuple_np(self, ds: DataSet):
        """Host-side batch tuple (numpy, no device transfer, dtypes
        JAX-canonicalized): the unit the k-step window stacker works
        on — stacking k batches on host means ONE host→device
        transfer per window instead of k, and canonical dtypes keep
        AOT cache keys consistent with what the program actually
        receives."""
        from deeplearning4j_tpu.models.kstep import canonical_np
        f = canonical_np(ds.features)
        l = None if ds.labels is None else canonical_np(ds.labels)
        fm = None if ds.features_mask is None else canonical_np(
            ds.features_mask)
        lm = (None if ds.labels_mask is None
              else canonical_np(ds.labels_mask))
        return (f, l, fm, lm)

    # ------------------------------------------------------------------
    # fit (reference fit(DataSetIterator) :1167)
    # ------------------------------------------------------------------
    def fit(self, data, labels=None, *, epochs: int = 1,
            batch_size: Optional[int] = None,
            steps_per_device_call: int = 1, mesh_spec=None):
        """``steps_per_device_call=k`` fuses k train steps into ONE
        device program (a ``lax.scan`` over a stacked batch window —
        models/kstep.py): the dispatch-bound regime pays one host
        round-trip per k steps instead of per step. Listeners still
        fire per step (losses and the fused health vector come back
        stacked, one fetch per window); a tail of ``n_batches % k``
        runs through the k=1 program — pre-compile both with
        :meth:`warmup` and the steady state never compiles.

        ``mesh_spec`` ("dp=4,tp=2" | dict | JSON — see
        ``parallel/mesh_spec.py``) trains SHARDED: params placed per
        the spec, batches split over the mesh's data axis, and the
        train programs (fused k-step windows included) run as single
        SPMD device programs with pinned output shardings. Composes
        with ``steps_per_device_call`` — k sharded steps per host
        round-trip."""
        from deeplearning4j_tpu.observability.tracing import trace
        k = int(steps_per_device_call)
        if k < 1:
            raise ValueError("steps_per_device_call must be >= 1")
        if mesh_spec is not None:
            self.use_mesh(mesh_spec)
        if self.params is None:
            self.init()
        it = _as_iterator(data, labels, batch_size)
        self._sync_health_mode()
        if self._jit_train_step is None:
            self._jit_train_step = self._make_train_step()
        tbptt = self.conf.conf.tbptt
        try:
            for _ in range(epochs):
                with trace.span("epoch"):
                    for lst in self.listeners:
                        lst.on_epoch_start(self)
                    self._fit_epoch(iter(it), k, tbptt)
                    for lst in self.listeners:
                        lst.on_epoch_end(self)
                self.epoch_count += 1
        except Exception as e:
            # black box: an escaping exception leaves a post-mortem
            # bundle when a flight recorder is installed (no-op
            # otherwise), then propagates unchanged
            from deeplearning4j_tpu.observability.flight_recorder \
                import on_fit_exception
            on_fit_exception(self, e)
            raise
        return self

    # KStepExecutorMixin adapters (fit_batches/_fit_one live there)
    def _coerce_fit_batch(self, ds: DataSet) -> DataSet:
        return ds

    def _batch_is_tbptt(self, ds: DataSet, tbptt) -> bool:
        return tbptt is not None and ds.features.ndim == 3

    def _run_tbptt(self, ds: DataSet, tbptt,
                   data_wait_s: float = 0.0) -> None:
        self._fit_tbptt(ds, None, tbptt, data_wait_s=data_wait_s)

    def warmup(self, example: DataSet, *,
               steps_per_device_call: int = 1, mesh_spec=None):
        """AOT warmup: ``jit(...).lower(shapes).compile()`` the train
        programs this batch signature will need — the k-step fused
        program (``steps_per_device_call > 1``) and the k=1
        single-step/tail-remainder program — so a subsequent
        ``fit``/``fit_batches`` steady state compiles ZERO times
        (``compile_watch.zero_compile_scope`` can assert it). Attach
        listeners (HealthMonitor in particular) BEFORE warming: the
        health toggle changes the program signature and flushes the
        AOT cache. Only the example's signature is warmed — a shape
        not seen here (e.g. a partial final batch when the dataset
        size isn't divisible by the batch size) still compiles once
        on first use; warm it with a second ``warmup`` call, or rely
        on the persistent cache (``--xla-cache``) to make it
        one-time across runs. Returns
        ``{program: compile_seconds}``."""
        from deeplearning4j_tpu.models import kstep as _kstep
        if mesh_spec is not None:
            self.use_mesh(mesh_spec)
        if self.params is None:
            self.init()
        self._sync_health_mode()
        if self._jit_train_step is None:
            self._jit_train_step = self._make_train_step()
        batch_np = self._batch_tuple_np(example)
        return _kstep.warmup_train_programs(
            self, batch_np, int(steps_per_device_call))

    def _fit_tbptt(self, ds: DataSet, step_fn_unused, tbptt,
                   data_wait_s: float = 0.0):
        """Truncated BPTT (reference doTruncatedBPTT :1404): split the
        sequence into fwd_length chunks; recurrent hidden state carries
        across chunks (stop_gradient at the boundary), exactly the
        reference's carried-state/truncated-gradient semantics.
        ``data_wait_s`` is the batch's input wait, billed to the FIRST
        chunk's ``_step_timing`` (each chunk is one listener
        iteration; later chunks waited on no data)."""
        import time
        fwd = tbptt["fwd_length"]
        T = ds.features.shape[1]
        B = ds.features.shape[0]
        # the tBPTT step has no fused health vector: a stale one from
        # the standard path must not masquerade as this chunk's
        self._last_health = None
        if self._jit_tbptt_step is None:
            self._jit_tbptt_step = self._make_tbptt_step()
        step_fn = self._jit_tbptt_step
        carries = [layer.zero_state(B)
                   if isinstance(layer, BaseRecurrentLayer) else None
                   for layer in self.layers]
        for start in range(0, T, fwd):
            end = min(start + fwd, T)
            sub = DataSet(
                ds.features[:, start:end],
                None if ds.labels is None else ds.labels[:, start:end],
                None if ds.features_mask is None
                else ds.features_mask[:, start:end],
                None if ds.labels_mask is None
                else ds.labels_mask[:, start:end])
            t_chunk = time.perf_counter()
            batch = self._batch_tuple(sub)
            (self.params, self.state, self.opt_state, loss,
             carries) = step_fn(self.params, self.state, self.opt_state,
                                batch, carries, self._rng_key,
                                np.int32(self.iteration_count))
            self.score_value = loss
            self._step_timing = (data_wait_s if start == 0 else 0.0,
                                 time.perf_counter() - t_chunk)
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration_count, loss,
                                   sub.num_examples())
            self.iteration_count += 1

    # ------------------------------------------------------------------
    # inference (reference output :1876-1971)
    # ------------------------------------------------------------------
    def output(self, x, training: bool = False):
        if self.params is None:
            self.init()
        x = jnp.asarray(x)
        if training not in self._jit_output:
            @jax.jit
            def fwd(params, state, x, rng):
                with self._mesh_scope():
                    y, _, _, _ = self._forward(params, state, x,
                                               training=training,
                                               rng=rng)
                return y
            self._jit_output[training] = fwd
        rng = self._rng_key if training else None
        return self._jit_output[training](self.params, self.state, x, rng)

    def feed_forward(self, x, training: bool = False) -> List[jnp.ndarray]:
        """All layer activations (reference feedForward :863)."""
        x = jnp.asarray(x)
        rng = self._rng_key if training else None
        _, _, acts, _ = self._forward(self.params, self.state, x,
                                      training=training, rng=rng,
                                      collect=True)
        return acts

    def score(self, ds: DataSet, training: bool = False) -> float:
        batch = self._batch_tuple(ds)
        loss, _ = self._loss(self.params, self.state, batch,
                             self._rng_key if training else None,
                             training=training)
        return float(loss)

    def evaluate(self, data, labels=None):
        from deeplearning4j_tpu.evaluation.classification import Evaluation
        it = _as_iterator(data, labels)
        ev = Evaluation()
        for ds in it:
            preds = np.asarray(self.output(ds.features))
            ev.eval(ds.labels, preds, mask=ds.labels_mask)
        return ev

    def evaluate_regression(self, data, labels=None):
        from deeplearning4j_tpu.evaluation.regression import (
            RegressionEvaluation)
        it = _as_iterator(data, labels)
        ev = RegressionEvaluation()
        for ds in it:
            preds = np.asarray(self.output(ds.features))
            ev.eval(ds.labels, preds, mask=ds.labels_mask)
        return ev

    def evaluate_roc(self, data, labels=None, threshold_steps: int = 0):
        from deeplearning4j_tpu.evaluation.roc import ROC
        it = _as_iterator(data, labels)
        roc = ROC(threshold_steps)
        for ds in it:
            preds = np.asarray(self.output(ds.features))
            roc.eval(ds.labels, preds)
        return roc

    # ------------------------------------------------------------------
    # layerwise pretraining (reference pretrain :221-343)
    # ------------------------------------------------------------------
    def pretrain(self, data, *, epochs: int = 1, batch_size=None):
        if self.params is None:
            self.init()
        it = _as_iterator(data, None, batch_size)
        for idx, layer in enumerate(self.layers):
            if not hasattr(layer, "pretrain_loss"):
                continue
            self._pretrain_layer(idx, it, epochs)
        return self

    def _pretrain_layer(self, idx: int, it: DataSetIterator, epochs: int):
        layer = self.layers[idx]
        opt = updaters_mod.to_optax(
            getattr(layer, "updater", None) or self.conf.conf.updater_cfg)
        opt_state = opt.init(self.params[idx])

        @jax.jit
        def pre_step(lp, opt_state, x, rng):
            def loss_fn(p):
                return layer.pretrain_loss(p, x, rng)

            loss, grads = jax.value_and_grad(loss_fn)(lp)
            updates, opt_state2 = opt.update(grads, opt_state, lp)
            return optax.apply_updates(lp, updates), opt_state2, loss

        step = 0
        for _ in range(epochs):
            for ds in it:
                x = jnp.asarray(ds.features)
                # feed input forward through the already-pretrained stack
                if idx > 0:
                    x, _, _, _ = self._forward(self.params, self.state, x,
                                               training=False, rng=None,
                                               upto=idx)
                rng = jax.random.fold_in(self._rng_key, step)
                self.params[idx], opt_state, loss = pre_step(
                    self.params[idx], opt_state, x, rng)
                step += 1
        logger.info("pretrained layer %d (%s), final loss %.5f", idx,
                    type(layer).__name__, float(loss))

    # ------------------------------------------------------------------
    # stateful RNN inference (reference rnnTimeStep :2656)
    # ------------------------------------------------------------------
    def rnn_time_step(self, x):
        x = jnp.asarray(x)
        squeeze = x.ndim == 2
        if squeeze:                      # (B,C) -> single timestep
            x = x[:, None, :]
        if self._rnn_state is None:
            self._rnn_state = [None] * len(self.layers)
        h = x
        for i, layer in enumerate(self.layers):
            if i in self.conf.preprocessors:
                h = self.conf.preprocessors[i](h)
            if isinstance(layer, BaseRecurrentLayer):
                carry = self._rnn_state[i]
                if carry is None:
                    carry = layer.zero_state(h.shape[0])
                h, carry = layer.apply_rnn(self.params[i], h, carry,
                                           training=False)
                self._rnn_state[i] = carry
            elif hasattr(layer, "apply_stream"):
                # attention layers: the streaming carry is the KV
                # cache (rnnTimeStep contract extended to
                # transformers)
                h, self._rnn_state[i] = layer.apply_stream(
                    self.params[i], self._rnn_state[i], h)
            else:
                h, _ = layer.apply(self.params[i], self.state[i], h,
                                   training=False)
        if squeeze and h.ndim == 3:
            h = h[:, -1, :]
        return h

    def rnn_clear_previous_state(self):
        self._rnn_state = None

    def streaming_session(self, capacity: int, batch: int,
                          dtype=None):
        """Jitted bounded-cache streaming inference: the TPU-first
        counterpart to the eager ``rnn_time_step`` (same contract,
        one compiled XLA executable per chunk length, fixed-capacity
        KV caches updated in place — see models/streaming.py).
        ``capacity`` is the max total sequence length the session can
        stream before ``reset()``."""
        import jax.numpy as jnp

        from deeplearning4j_tpu.models.streaming import StreamingSession
        return StreamingSession(self, capacity, batch,
                                dtype or jnp.float32)

    def slot_streaming_session(self, capacity: int, slots: int,
                               dtype=None):
        """Per-slot-position streaming session for continuous
        batching: each of the ``slots`` batch rows is an independent
        decode stream that can be reset and re-admitted while its
        neighbours keep generating (see
        ``serving.ContinuousBatcher``)."""
        import jax.numpy as jnp

        from deeplearning4j_tpu.models.streaming import (
            SlotStreamingSession)
        return SlotStreamingSession(self, capacity, slots,
                                    dtype or jnp.float32)

    def paged_slot_streaming_session(self, capacity: int, slots: int,
                                     page_size: int = 16,
                                     n_pages=None, dtype=None):
        """Paged-KV continuous-batching session: per-slot page tables
        into one refcounted page pool, so concurrent slot count is
        bounded by total KV memory (``n_pages * page_size`` tokens)
        instead of ``slots x capacity`` — plus prompt-prefix sharing
        between slots (see ``models/paged_kv.py``). Raises
        ``ValueError`` for models whose layers carry state with no
        paged analog (recurrent carries, running statistics)."""
        from deeplearning4j_tpu.models.paged_kv import PagedSlotSession
        return PagedSlotSession(self, slots=slots, capacity=capacity,
                                page_size=page_size, n_pages=n_pages,
                                dtype=dtype)

    # ------------------------------------------------------------------
    # params plumbing (reference flat params view :542-554)
    # ------------------------------------------------------------------
    def num_params(self) -> int:
        return sum(int(p.size)
                   for p in jax.tree_util.tree_leaves(self.params))

    def params_flat(self) -> np.ndarray:
        from deeplearning4j_tpu.util.tree import tree_flat_vector
        return tree_flat_vector(self.params)

    def set_params_flat(self, flat: np.ndarray):
        from deeplearning4j_tpu.util.tree import tree_from_flat_vector
        self.params = tree_from_flat_vector(self.params, flat)

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def add_listeners(self, *listeners):
        self.listeners.extend(listeners)
        return self

    def clone(self) -> "MultiLayerNetwork":
        m = MultiLayerNetwork(self.conf.clone())
        if self.params is not None:
            m.init()
            from deeplearning4j_tpu.util.tree import tree_copy
            m.params = tree_copy(self.params)
            m.state = tree_copy(self.state)
        return m

    def summary(self) -> str:
        lines = ["idx  type                      params    out_type"]
        t = self.conf.input_type
        for i, layer in enumerate(self.layers):
            if t is not None and i in self.conf.preprocessors:
                t = self.conf.preprocessors[i].output_type(t)
            n = (sum(int(p.size) for p in
                     jax.tree_util.tree_leaves(self.params[i]))
                 if self.params else 0)
            t = layer.output_type(t) if t is not None else None
            lines.append(f"{i:<4} {type(layer).__name__:<25} {n:<9} {t}")
        lines.append(f"total params: {self.num_params() if self.params else 0}")
        return "\n".join(lines)
