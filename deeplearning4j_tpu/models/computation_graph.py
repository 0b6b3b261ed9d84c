"""ComputationGraph: the DAG executor.

TPU rewrite of nn/graph/ComputationGraph.java (3350 LoC): forward walks
the cached topological order (reference :1187, fan-out at :817);
training is one jitted step over the whole DAG — multi-input,
multi-output, summed output losses (reference computeGradientAndScore
:1295 sums output-layer scores).

Params/state are dicts keyed by vertex name (the reference keeps a
params view array per vertex; a name-keyed pytree is the JAX-native
equivalent and checkpoint-stable).
"""

from __future__ import annotations

import functools
import logging
import time
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.nn.conf import updaters as updaters_mod
from deeplearning4j_tpu.models.kstep import (KStepExecutorMixin,
                                             _tree_nbytes)
from deeplearning4j_tpu.nn.conf.graph_conf import (
    ComputationGraphConfiguration,
)
from deeplearning4j_tpu.nn.conf.layers.base import Layer
from deeplearning4j_tpu.nn.conf.layers.recurrent import BaseRecurrentLayer
from deeplearning4j_tpu.train.constraints import apply_layer_constraints

logger = logging.getLogger("deeplearning4j_tpu")

__all__ = ["ComputationGraph"]


class ComputationGraph(KStepExecutorMixin):
    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.params: Optional[Dict[str, dict]] = None
        self.state: Optional[Dict[str, dict]] = None
        self.opt_state = None
        self.listeners = []
        self.iteration_count = 0
        self.epoch_count = 0
        self.score_value = float("nan")
        self._rng_key = None
        self._optimizer = None
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
        self._rnn_state: Optional[Dict[str, object]] = None
        # (data_wait_s, dispatch_s) of the latest fit iteration —
        # read by observability.step_profile.ProfilerListener
        self._step_timing = None
        # observability.health wiring (see MultiLayerNetwork): fused
        # finite-check vector stashed unfetched + latest batch refs
        self._health_enabled = False
        self._last_health = None
        self._last_batch = None

    # ------------------------------------------------------------------
    def init(self, seed: Optional[int] = None) -> "ComputationGraph":
        from deeplearning4j_tpu.observability.tracing import startup
        seed = self.conf.conf.seed if seed is None else seed
        with startup.span("setup/init") as sp:
            key = jax.random.PRNGKey(seed)
            self._rng_key = jax.random.fold_in(key, 0xC6)
            order = self.conf.topological_order()
            params, states = {}, {}
            keys = jax.random.split(key, max(len(order), 1))
            for k, name in zip(keys, order):
                obj, ins = self.conf.vertices[name]
                if isinstance(obj, Layer):
                    it = self.conf.vertex_input_type(name)
                    p, s = obj.initialize(k, it)
                    params[name] = p
                    states[name] = s
            self.params = params
            self.state = states
            sp.set("layers", len(params))
            sp.set("param_bytes", _tree_nbytes(params))
            self._build_optimizer()
        return self

    def _build_optimizer(self):
        from deeplearning4j_tpu.observability.tracing import startup
        global_cfg = self.conf.conf.updater_cfg or updaters_mod.sgd()
        overrides = {name: getattr(obj, "updater", None)
                     for name, (obj, _) in self.conf.vertices.items()
                     if isinstance(obj, Layer)
                     and getattr(obj, "updater", None) is not None}
        if overrides:
            transforms = {"__global__": updaters_mod.to_optax(global_cfg)}
            labels = {}
            for name in self.params:
                if name in overrides:
                    transforms[name] = updaters_mod.to_optax(overrides[name])
                    tag = name
                else:
                    tag = "__global__"
                labels[name] = jax.tree_util.tree_map(lambda _: tag,
                                                      self.params[name])
            self._optimizer = optax.multi_transform(transforms, labels)
        else:
            self._optimizer = updaters_mod.to_optax(global_cfg)
        clip = self.conf.conf.gradient_clip
        if clip is not None:
            pre = (optax.clip_by_global_norm(clip["v"])
                   if clip["type"] == "norm" else optax.clip(clip["v"]))
            self._optimizer = optax.chain(pre, self._optimizer)
        with startup.span("setup/init/optimizer") as sp:
            self.opt_state = self._optimizer.init(self.params)
            sp.set("state_bytes", _tree_nbytes(self.opt_state))
        self._jit_train_step = None
        self._jit_tbptt_step = None
        self._jit_kstep = {}
        self._aot = {}
        self._jit_output = {}

    # ------------------------------------------------------------------
    def _forward(self, params, state, inputs: Sequence, *, training, rng,
                 fmasks=None, exclude_outputs: bool = False, carries=None,
                 only=None):
        """Topo-order interpreter (reference ComputationGraph.java
        :793-817). Masks are routed per vertex via
        ``GraphVertex.propagate_mask`` (reference feedForwardMaskArrays
        per vertex impl), NOT first-non-None-input. ``carries``: dict
        vertex-name -> recurrent (h, c) initial state, used by tBPTT to
        carry hidden state across chunks (reference
        rnnActivateUsingStoredState :2219). Returns (activations dict,
        new state dict, new carries dict)."""
        from deeplearning4j_tpu.nn.conf.graph import (
            LastTimeStepVertex, combine_masks_or)
        acts: Dict[str, jnp.ndarray] = dict(
            zip(self.conf.network_inputs, inputs))
        masks: Dict[str, Optional[jnp.ndarray]] = {
            n: None for n in self.conf.network_inputs}
        if fmasks is not None:
            masks.update(zip(self.conf.network_inputs, fmasks))
        new_state = {}
        new_carries = {} if carries is not None else None
        for vidx, name in enumerate(self.conf.topological_order()):
            if only is not None and name not in only:
                continue        # pretrain: only the ancestor subgraph
            obj, ins = self.conf.vertices[name]
            xs = [acts[i] for i in ins]
            in_masks = [masks.get(i) for i in ins]
            if isinstance(obj, Layer):
                # a layer vertex consumes its (single) wired input's mask
                in_mask = in_masks[0]
                if exclude_outputs and name in self.conf.network_outputs \
                        and obj.has_loss():
                    # leave the loss layer's input available instead
                    acts[name] = xs[0]
                    new_state[name] = state[name]
                    masks[name] = in_mask
                    continue
                # stable per-vertex rng: topo index, NOT hash(name)
                # (python hash is per-process randomized)
                lrng = (jax.random.fold_in(rng, vidx)
                        if rng is not None else None)
                from deeplearning4j_tpu.nn.errors import (
                    layer_error_context)
                # the vertex's name on its device ops (metadata
                # only): a profiler trace then splits fusion time by
                # layer
                with layer_error_context(f"vertex '{name}'", obj,
                                         xs[0]), jax.named_scope(name):
                    if carries is not None and \
                            isinstance(obj, BaseRecurrentLayer):
                        c0 = carries.get(name)
                        if c0 is None:
                            c0 = obj.zero_state(xs[0].shape[0])
                        xd = obj.apply_input_dropout(xs[0],
                                                     training=training,
                                                     rng=lrng)
                        y, c1 = obj.apply_rnn(params[name], xd, c0,
                                              training=training, rng=lrng,
                                              mask=in_mask)
                        new_carries[name] = c1
                        s = state[name]
                    else:
                        y, s = obj.apply(params[name], state[name], xs[0],
                                         training=training, rng=lrng,
                                         mask=in_mask)
                new_state[name] = s
                acts[name] = y
                # a layer that collapses the time dimension (e.g.
                # GlobalPooling) must null the propagated (B, T) mask —
                # mirrors the reference's per-layer feedForwardMaskArray
                # (round-2 advisor): downstream consumers would get a
                # stale wrong-shaped mask otherwise
                if (in_mask is not None and (y.ndim < 3
                        or y.shape[1] != in_mask.shape[1])):
                    masks[name] = None
                else:
                    masks[name] = in_mask
            else:
                from deeplearning4j_tpu.nn.errors import (
                    layer_error_context)
                if isinstance(obj, LastTimeStepVertex) and \
                        obj.mask_input is not None:
                    use_mask = masks.get(obj.mask_input)
                else:
                    use_mask = combine_masks_or(in_masks)
                with layer_error_context(f"vertex '{name}'", obj,
                                         xs[0] if xs else None), \
                        jax.named_scope(name):
                    acts[name] = obj.apply(xs, mask=use_mask)
                masks[name] = obj.propagate_mask(in_masks, xs,
                                                 mask_env=masks)
        return acts, new_state, new_carries

    def _loss(self, params, state, batch, rng, *, training=True,
              carries=None):
        inputs, labels, fmasks, lmasks = batch
        acts, new_state, new_carries = self._forward(
            params, state, inputs, training=training, rng=rng,
            fmasks=fmasks, exclude_outputs=True, carries=carries)
        from deeplearning4j_tpu.nn.conf.layers.output import (
            CenterLossOutputLayer)
        total = jnp.zeros(())
        topo = self.conf.topological_order()
        for i, out_name in enumerate(self.conf.network_outputs):
            obj, ins = self.conf.vertices[out_name]
            if isinstance(obj, Layer) and obj.has_loss():
                lrng = (jax.random.fold_in(rng, 1000 + topo.index(out_name))
                        if rng is not None else None)
                lmask = lmasks[i] if lmasks is not None else None
                with jax.named_scope(out_name):
                    total = total + obj.loss_from_input(
                        params[out_name], acts[out_name], labels[i],
                        training=training, rng=lrng, mask=lmask)
                if isinstance(obj, CenterLossOutputLayer):
                    total = total + obj.lambda_ * obj.center_loss(
                        state[out_name], acts[out_name], labels[i])
                    new_state[out_name] = obj.update_centers(
                        state[out_name], acts[out_name], labels[i])
            else:
                raise ValueError(f"Output vertex '{out_name}' has no loss")
        for name, (obj, _) in self.conf.vertices.items():
            if isinstance(obj, Layer):
                total = total + obj.regularization_loss(params[name])
        if carries is not None:
            return total, (new_state, new_carries)
        return total, new_state

    def _train_core(self, params, state, opt_state, batch, rng):
        """Traced single-step training math over the whole DAG —
        shared verbatim by the k=1 jitted step and the k-step
        ``lax.scan`` body (models/kstep.py), so the fused and
        per-step programs compute bit-identical updates."""
        optimizer = self._optimizer

        def loss_fn(p):
            return self._loss(p, state, batch, rng, training=True)

        with self._mesh_scope():
            (loss, new_state), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
        from deeplearning4j_tpu.train.gradnorm import (
            apply_gradient_normalization)
        layer_cfgs = {n: v[0] for n, v in self.conf.vertices.items()
                      if n in params}
        with jax.named_scope("updater"):
            grads = apply_gradient_normalization(layer_cfgs, grads)
            updates, new_opt = optimizer.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            constrained = {}
            for name, p in new_params.items():
                obj, _ = self.conf.vertices[name]
                constrained[name] = apply_layer_constraints(obj, p)
        if self._health_enabled:
            # fused finite check + global norms, computed inside
            # this same XLA program (observability/health.py)
            from deeplearning4j_tpu.observability.health import (
                fused_health)
            health = fused_health(loss, grads, updates, constrained)
            return constrained, new_state, new_opt, loss, health
        return constrained, new_state, new_opt, loss

    def _sync_health_mode(self) -> None:
        """Compile the fused health check into the train step iff a
        health-monitoring listener is attached."""
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
        """Graph tBPTT step (reference ComputationGraph.doTruncatedBPTT
        :2532, dispatched from fit :928/:1031): recurrent vertex state
        carries across chunks, gradients are truncated at the chunk
        boundary via stop_gradient."""
        optimizer = self._optimizer

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def tbptt_step(params, state, opt_state, batch, carries, base_rng,
                       step):
            rng = jax.random.fold_in(base_rng, step)
            carries = jax.lax.stop_gradient(carries)

            def loss_fn(p):
                return self._loss(p, state, batch, rng, training=True,
                                  carries=carries)

            (loss, (new_state, new_carries)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            from deeplearning4j_tpu.train.gradnorm import (
                apply_gradient_normalization)
            layer_cfgs = {n: v[0] for n, v in self.conf.vertices.items()
                          if n in params}
            grads = apply_gradient_normalization(layer_cfgs, grads)
            updates, new_opt = optimizer.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            constrained = {}
            for name, p in new_params.items():
                obj, _ = self.conf.vertices[name]
                constrained[name] = apply_layer_constraints(obj, p)
            return (constrained, new_state, new_opt, loss,
                    jax.lax.stop_gradient(new_carries))

        return tbptt_step

    # ------------------------------------------------------------------
    def _as_multi(self, ds) -> MultiDataSet:
        if isinstance(ds, MultiDataSet):
            return ds
        if isinstance(ds, DataSet):
            return MultiDataSet(
                [ds.features], [ds.labels],
                [ds.features_mask] if ds.features_mask is not None else None,
                [ds.labels_mask] if ds.labels_mask is not None else None)
        raise TypeError(type(ds))

    def _batch_tuple(self, mds: MultiDataSet):
        inputs = tuple(jnp.asarray(f) for f in mds.features)
        labels = tuple(jnp.asarray(l) for l in mds.labels)
        fm = (tuple(None if m is None else jnp.asarray(m)
                    for m in mds.features_masks)
              if mds.features_masks is not None else None)
        lm = (tuple(None if m is None else jnp.asarray(m)
                    for m in mds.labels_masks)
              if mds.labels_masks is not None else None)
        return (inputs, labels, fm, lm)

    def _batch_tuple_np(self, mds: MultiDataSet):
        """Host-side batch tuple (numpy, no device transfer, dtypes
        JAX-canonicalized so AOT cache keys match what the program
        actually receives): the unit the k-step window stacker works
        on."""
        from deeplearning4j_tpu.models.kstep import canonical_np
        inputs = tuple(canonical_np(f) for f in mds.features)
        labels = tuple(canonical_np(l) for l in mds.labels)
        fm = (tuple(None if m is None else canonical_np(m)
                    for m in mds.features_masks)
              if mds.features_masks is not None else None)
        lm = (tuple(None if m is None else canonical_np(m)
                    for m in mds.labels_masks)
              if mds.labels_masks is not None else None)
        return (inputs, labels, fm, lm)

    def fit(self, data, *, epochs: int = 1,
            steps_per_device_call: int = 1, mesh_spec=None):
        """data: iterable of DataSet/MultiDataSet, or a single one.
        ``steps_per_device_call=k`` fuses k train steps into one
        ``lax.scan`` device program (see
        :meth:`MultiLayerNetwork.fit`); the epoch tail runs through
        the pre-compiled k=1 program. ``mesh_spec`` trains sharded
        over a declarative device mesh and composes with the fused
        windows (see :meth:`MultiLayerNetwork.fit` /
        ``parallel/mesh_spec.py``)."""
        from deeplearning4j_tpu.observability.tracing import trace
        k = int(steps_per_device_call)
        if k < 1:
            raise ValueError("steps_per_device_call must be >= 1")
        if mesh_spec is not None:
            self.use_mesh(mesh_spec)
        if self.params is None:
            self.init()
        if isinstance(data, (DataSet, MultiDataSet)):
            data = [data]
        elif not isinstance(data, (list, tuple)) and \
                not hasattr(data, "reset"):
            # one-shot generators would be exhausted after epoch 1;
            # materialize so every epoch actually trains
            data = list(data)
        self._sync_health_mode()
        if self._jit_train_step is None:
            self._jit_train_step = self._make_train_step()
        tbptt = self.conf.conf.tbptt
        try:
            for _ in range(epochs):
                with trace.span("epoch"):
                    for lst in self.listeners:
                        lst.on_epoch_start(self)
                    self._fit_epoch(iter(data), k, tbptt)
                    for lst in self.listeners:
                        lst.on_epoch_end(self)
                self.epoch_count += 1
        except Exception as e:
            # black box: leave a post-mortem bundle when a flight
            # recorder is installed, then propagate unchanged
            from deeplearning4j_tpu.observability.flight_recorder \
                import on_fit_exception
            on_fit_exception(self, e)
            raise
        return self

    # KStepExecutorMixin adapters (fit_batches/_fit_one live there)
    def _coerce_fit_batch(self, ds) -> MultiDataSet:
        return self._as_multi(ds)

    def _batch_is_tbptt(self, mds: MultiDataSet, tbptt) -> bool:
        return tbptt is not None and any(np.ndim(f) == 3
                                         for f in mds.features)

    def _run_tbptt(self, mds: MultiDataSet, tbptt,
                   data_wait_s: float = 0.0) -> None:
        self._fit_tbptt(mds, tbptt, data_wait_s=data_wait_s)

    def warmup(self, example, *, steps_per_device_call: int = 1,
               mesh_spec=None):
        """AOT warmup: ``jit(...).lower(shapes).compile()`` the
        k-step and k=1 train programs for this batch signature (see
        :meth:`MultiLayerNetwork.warmup`). Attach listeners before
        warming. Returns ``{program: compile_seconds}``."""
        from deeplearning4j_tpu.models import kstep as _kstep
        if mesh_spec is not None:
            self.use_mesh(mesh_spec)
        if self.params is None:
            self.init()
        self._sync_health_mode()
        if self._jit_train_step is None:
            self._jit_train_step = self._make_train_step()
        batch_np = self._batch_tuple_np(self._as_multi(example))
        return _kstep.warmup_train_programs(
            self, batch_np, int(steps_per_device_call))

    def _fit_tbptt(self, mds: MultiDataSet, tbptt,
                   data_wait_s: float = 0.0):
        """Truncated BPTT over a MultiDataSet (reference
        ComputationGraph.doTruncatedBPTT :2532): every time-series
        array (features, labels, masks) is split into fwd_length
        chunks; recurrent vertex hidden state carries across chunks
        with the gradient stopped at the boundary. ``data_wait_s`` is
        billed to the first chunk's ``_step_timing``."""
        import time
        fwd = tbptt["fwd_length"]
        ts = [f for f in mds.features if np.ndim(f) == 3]
        T = ts[0].shape[1]
        B = ts[0].shape[0]
        # the tBPTT step has no fused health vector: a stale one from
        # the standard path must not masquerade as this chunk's
        self._last_health = None
        if self._jit_tbptt_step is None:
            self._jit_tbptt_step = self._make_tbptt_step()
        step_fn = self._jit_tbptt_step
        carries = {name: obj.zero_state(B)
                   for name, (obj, _) in self.conf.vertices.items()
                   if isinstance(obj, BaseRecurrentLayer)}

        for start in range(0, T, fwd):
            end = min(start + fwd, T)
            feats = tuple(f[:, start:end] if np.ndim(f) == 3 else f
                          for f in mds.features)
            labels = tuple(l[:, start:end] if np.ndim(l) == 3 else l
                           for l in mds.labels)
            fm = (tuple(None if m is None
                        else (m[:, start:end]
                              if np.ndim(m) == 2 and m.shape[1] == T
                              else m)
                        for m in mds.features_masks)
                  if mds.features_masks is not None else None)
            lm = (tuple(None if m is None
                        else (m[:, start:end]
                              if np.ndim(m) == 2 and m.shape[1] == T
                              else m)
                        for m in mds.labels_masks)
                  if mds.labels_masks is not None else None)
            sub = MultiDataSet(list(feats), list(labels),
                               None if fm is None else list(fm),
                               None if lm is None else list(lm))
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
    def output(self, *inputs, training: bool = False, input_masks=None):
        if self.params is None:
            self.init()
        xs = tuple(jnp.asarray(x) for x in inputs)
        fmasks = (tuple(None if m is None else jnp.asarray(m)
                        for m in input_masks)
                  if input_masks is not None else None)
        key = (training, fmasks is not None)
        if key not in self._jit_output:
            @jax.jit
            def fwd(params, state, xs, rng, fmasks):
                with self._mesh_scope():
                    acts, _, _ = self._forward(params, state, xs,
                                               training=training,
                                               rng=rng, fmasks=fmasks)
                return tuple(acts[o] for o in self.conf.network_outputs)
            self._jit_output[key] = fwd
        rng = self._next_call_rng() if training else None
        outs = self._jit_output[key](self.params, self.state, xs, rng,
                                     fmasks)
        return outs if len(outs) > 1 else outs[0]

    def _next_call_rng(self):
        # fold a per-call counter into the key: repeated training-mode
        # forward passes (MC-dropout sampling) must draw FRESH dropout
        # masks, not N identical ones (round-2 advisor, medium)
        self._output_calls = getattr(self, "_output_calls", 0) + 1
        return jax.random.fold_in(self._rng_key, self._output_calls)

    def feed_forward(self, *inputs, training: bool = False,
                     input_masks=None):
        xs = tuple(jnp.asarray(x) for x in inputs)
        acts, _, _ = self._forward(self.params, self.state, xs,
                                   training=training,
                                   rng=(self._next_call_rng()
                                        if training else None),
                                   fmasks=input_masks)
        return acts

    def score(self, ds) -> float:
        mds = self._as_multi(ds)
        loss, _ = self._loss(self.params, self.state,
                             self._batch_tuple(mds), None, training=False)
        return float(loss)

    def _iter_pred_batches(self, data):
        """Shared eval iteration: one forward per batch, ALL heads."""
        if isinstance(data, (DataSet, MultiDataSet)):
            data = [data]
        for ds in data:
            mds = self._as_multi(ds)
            preds = self.output(*mds.features,
                                input_masks=mds.features_masks)
            if not isinstance(preds, tuple):
                preds = (preds,)
            yield mds, preds

    @staticmethod
    def _eval_one(ev, labels, preds, mask):
        try:
            ev.eval(labels, preds, mask=mask)
        except TypeError:         # evaluators without mask support (ROC)
            ev.eval(labels, preds)

    def _eval_with(self, data, ev, output_index: int = 0):
        for mds, preds in self._iter_pred_batches(data):
            lmask = (mds.labels_masks[output_index]
                     if mds.labels_masks is not None else None)
            self._eval_one(ev, mds.labels[output_index],
                           np.asarray(preds[output_index]), lmask)
        return ev

    def evaluate(self, data, output_index: int = 0):
        from deeplearning4j_tpu.evaluation.classification import Evaluation
        return self._eval_with(data, Evaluation(), output_index)

    def evaluate_outputs(self, data, eval_factory=None):
        """Evaluate EVERY output head in a single pass over the data
        (fixes the reference-parity gap where only output[0] was
        scored). Returns ``{output_name: Evaluation}``."""
        if eval_factory is None:
            from deeplearning4j_tpu.evaluation.classification import (
                Evaluation)
            eval_factory = Evaluation
        evs = [eval_factory() for _ in self.conf.network_outputs]
        for mds, preds in self._iter_pred_batches(data):
            for i, ev in enumerate(evs):
                lmask = (mds.labels_masks[i]
                         if mds.labels_masks is not None else None)
                self._eval_one(ev, mds.labels[i], np.asarray(preds[i]),
                               lmask)
        return dict(zip(self.conf.network_outputs, evs))

    def evaluate_regression(self, data, output_index: int = 0):
        from deeplearning4j_tpu.evaluation.regression import (
            RegressionEvaluation)
        return self._eval_with(data, RegressionEvaluation(), output_index)

    def evaluate_roc(self, data, threshold_steps: int = 0,
                     output_index: int = 0):
        from deeplearning4j_tpu.evaluation.roc import ROC
        return self._eval_with(data, ROC(threshold_steps), output_index)

    # ------------------------------------------------------------------
    def rnn_time_step(self, *inputs):
        """Stateful streaming inference (reference rnnTimeStep :2358)."""
        xs = [jnp.asarray(x) for x in inputs]
        squeeze = xs[0].ndim == 2
        if squeeze:
            xs = [x[:, None, :] for x in xs]
        if self._rnn_state is None:
            self._rnn_state = {}
        acts = dict(zip(self.conf.network_inputs, xs))
        for name in self.conf.topological_order():
            obj, ins = self.conf.vertices[name]
            xin = [acts[i] for i in ins]
            if isinstance(obj, BaseRecurrentLayer):
                carry = self._rnn_state.get(name)
                if carry is None:
                    carry = obj.zero_state(xin[0].shape[0])
                y, carry = obj.apply_rnn(self.params[name], xin[0], carry,
                                         training=False)
                self._rnn_state[name] = carry
                acts[name] = y
            elif hasattr(obj, "apply_stream"):
                # attention vertices: the streaming carry is the KV
                # cache (rnnTimeStep contract extended to transformers)
                acts[name], self._rnn_state[name] = obj.apply_stream(
                    self.params[name], self._rnn_state.get(name),
                    xin[0])
            elif isinstance(obj, Layer):
                acts[name], _ = obj.apply(self.params[name],
                                          self.state[name], xin[0],
                                          training=False)
            else:
                acts[name] = obj.apply(xin)
        outs = tuple(acts[o] for o in self.conf.network_outputs)
        if squeeze:
            outs = tuple(o[:, -1, :] if o.ndim == 3 else o for o in outs)
        return outs if len(outs) > 1 else outs[0]

    def rnn_clear_previous_state(self):
        self._rnn_state = None

    def streaming_session(self, capacity: int, batch: int,
                          dtype=None):
        """Jitted bounded-cache streaming inference over the graph
        topology — the TPU-first counterpart to the eager
        ``rnn_time_step`` (see models/streaming.py)."""
        import jax.numpy as jnp

        from deeplearning4j_tpu.models.streaming import (
            GraphStreamingSession)
        return GraphStreamingSession(self, capacity, batch,
                                     dtype or jnp.float32)

    # ------------------------------------------------------------------
    # layerwise pretraining (reference ComputationGraph.pretrain
    # :652,664: each pretrainable layer vertex is trained on its own
    # input activations, fed through the already-pretrained stack)
    # ------------------------------------------------------------------
    def pretrain(self, data, *, epochs: int = 1):
        if self.params is None:
            self.init()
        if isinstance(data, (DataSet, MultiDataSet)):
            data = [data]
        elif not isinstance(data, (list, tuple)):
            data = list(data)
        for name in self.conf.topological_order():
            obj, _ = self.conf.vertices[name]
            if isinstance(obj, Layer) and hasattr(obj, "pretrain_loss"):
                self._pretrain_vertex(name, data, epochs)
        return self

    def _pretrain_vertex(self, name: str, data, epochs: int):
        obj, ins = self.conf.vertices[name]
        opt = updaters_mod.to_optax(
            getattr(obj, "updater", None) or self.conf.conf.updater_cfg
            or updaters_mod.sgd())
        opt_state = opt.init(self.params[name])

        @jax.jit
        def pre_step(lp, opt_state, x, rng):
            def loss_fn(p):
                return obj.pretrain_loss(p, x, rng)

            loss, grads = jax.value_and_grad(loss_fn)(lp)
            updates, opt_state2 = opt.update(grads, opt_state, lp)
            return optax.apply_updates(lp, updates), opt_state2, loss

        # only the ancestor subgraph of the vertex's input is needed —
        # running the full DAG per batch would multiply pretraining
        # cost by the network depth
        needed = set()
        stack = [ins[0]]
        while stack:
            cur = stack.pop()
            if cur in needed or cur not in self.conf.vertices:
                continue
            needed.add(cur)
            stack.extend(self.conf.vertices[cur][1])

        @jax.jit
        def vertex_input(params, state, inputs, fmasks):
            acts, _, _ = self._forward(params, state, inputs,
                                       training=False, rng=None,
                                       fmasks=fmasks, only=needed)
            return acts[ins[0]]

        step = 0
        loss = float("nan")
        for _ in range(epochs):
            for ds in data:
                mds = self._as_multi(ds)
                inputs = tuple(jnp.asarray(f) for f in mds.features)
                fmasks = (tuple(None if m is None else jnp.asarray(m)
                                for m in mds.features_masks)
                          if mds.features_masks is not None else None)
                x = vertex_input(self.params, self.state, inputs, fmasks)
                rng = jax.random.fold_in(self._rng_key, step)
                self.params[name], opt_state, loss = pre_step(
                    self.params[name], opt_state, x, rng)
                step += 1
        logger.info("pretrained vertex '%s' (%s), final loss %.5f", name,
                    type(obj).__name__, float(loss))

    # ------------------------------------------------------------------
    # params plumbing (parity with MultiLayerNetwork; reference keeps a
    # flat params view per graph, ComputationGraph.params())
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

    def clone(self) -> "ComputationGraph":
        g = ComputationGraph(self.conf.clone())
        if self.params is not None:
            g.init()
            from deeplearning4j_tpu.util.tree import tree_copy
            g.params = tree_copy(self.params)
            g.state = tree_copy(self.state)
        return g

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def add_listeners(self, *listeners):
        self.listeners.extend(listeners)
        return self

    def summary(self) -> str:
        lines = ["name                 type                      inputs"]
        for name in self.conf.topological_order():
            obj, ins = self.conf.vertices[name]
            lines.append(f"{name:<20} {type(obj).__name__:<25} {ins}")
        if self.params:
            lines.append(f"total params: {self.num_params()}")
        return "\n".join(lines)
