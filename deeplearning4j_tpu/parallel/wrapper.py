"""ParallelWrapper: data-parallel training over a device mesh.

The TPU rewrite of deeplearning4j-scaleout-parallelwrapper's
``ParallelWrapper`` (ParallelWrapper.java:58, 898 LoC of worker
threads, model clones, round-robin queues, averaging): here the model
is **sharded, not cloned** — params replicated, batch split over the
``data`` mesh axis, and the model's OWN jitted train step runs SPMD on
every device with XLA inserting the gradient ``psum`` over ICI (the
shardings of batch vs params force an all-reduce in the backward pass;
no wrapper-specific step code is needed).

Equivalences to the reference:
- AVERAGING mode (params averaged every N iters, :251-257)   →
  synchronous all-reduce EVERY step (strictly stronger consistency,
  and faster on ICI than host-side averaging ever was over PCIe).
- SHARED_GRADIENTS / EncodedGradientsAccumulator 1-bit compression →
  unnecessary on ICI; a compressed path belongs to DCN-spanning
  multi-slice topologies (parallel/compression.py).
- prefetchBuffer / MagicQueue → AsyncDataSetIterator + device put.
- workers(n) → mesh data-axis size.

ELASTIC MESH SHRINK (the preemption PR): losing a device out of a
pure data-parallel mesh mid-``fit`` no longer kills the run. On a
device failure (the ``parallel.device`` chaos site's ``loss`` kind
drills it; :meth:`ParallelWrapper.lose_device` is the programmatic
entry) the wrapper takes a host snapshot at the step boundary (params
are replicated over 'data', so every survivor holds a complete copy),
rebuilds the mesh over the survivors at the largest power-of-two dp
(dp=8 → dp=4), re-places params/opt-state, rescales the per-device
batch split, and continues — counted as
``elastic_mesh_shrinks_total`` and recorded by the flight recorder.
Regrow is explicit (``wrapper.regrow()`` after capacity returns,
counted as ``elastic_mesh_regrows_total``), never automatic: capacity
coming back is an operator decision, not an event the step loop
should react to. What is NOT preserved across a shrink: the
dcn-compression error-feedback residual (per-device state — it is
re-zeroed) and compiled executables (the step retraces for the new
topology). Meshes that also shard 'model'/'pipe'/'seq' do not shrink
— sharded state died with the device; recover via ElasticTrainer's
checkpoint restart.

Works with both executors: MultiLayerNetwork and ComputationGraph
(GraphParallelWrapper alias keeps call sites explicit).
"""

from __future__ import annotations

import logging
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu import chaos
from deeplearning4j_tpu.data.iterators import (AsyncDataSetIterator,
                                               DataSetIterator)
from deeplearning4j_tpu.parallel.mesh import (MeshSpec, build_mesh,
                                              largest_pow2,
                                              shrink_data_mesh)
from deeplearning4j_tpu.parallel.seq_context import gspmd_mesh

logger = logging.getLogger("deeplearning4j_tpu")

__all__ = ["ParallelWrapper", "GraphParallelWrapper"]


def _grad_update(model, is_graph, optimizer, grads, opt_state, params):
    """Gradient normalization → optimizer → per-layer constraints:
    the single update path every wrapper step variant (plain GSPMD
    seq, manual seq, compressed) routes through so a fix here applies
    to all of them."""
    import optax

    from deeplearning4j_tpu.train.constraints import (
        apply_layer_constraints)
    from deeplearning4j_tpu.train.gradnorm import (
        apply_gradient_normalization)

    if is_graph:
        layer_cfgs = {n: v[0] for n, v in model.conf.vertices.items()
                      if n in params}
    else:
        layer_cfgs = model.layers
    grads = apply_gradient_normalization(layer_cfgs, grads)
    updates, new_opt = optimizer.update(grads, opt_state, params)
    new_params = optax.apply_updates(params, updates)
    if is_graph:
        new_params = {
            n: apply_layer_constraints(model.conf.vertices[n][0], p)
            for n, p in new_params.items()}
    else:
        new_params = [apply_layer_constraints(l, p)
                      for l, p in zip(model.layers, new_params)]
    return new_params, new_opt


def _spmd_update_tail(model, is_graph, optimizer, grads, new_state,
                      loss, opt_state, params, axes):
    """Shared per-device tail of the explicit shard_map train steps
    (compressed-DCN and sequence-parallel): the common update path,
    then merge the per-device aux state (BN stats, centers — average
    floats / max ints) and pmean the loss so the replicated
    out-specs hold."""
    new_params, new_opt = _grad_update(model, is_graph, optimizer,
                                       grads, opt_state, params)
    new_state = jax.tree_util.tree_map(
        lambda s: (jax.lax.pmean(s, axes)
                   if jnp.issubdtype(s.dtype, jnp.floating)
                   else jax.lax.pmax(s, axes)), new_state)
    loss = jax.lax.pmean(loss, axes)
    return new_params, new_state, new_opt, loss


class ParallelWrapper:
    def __init__(self, model, mesh: Optional[Mesh] = None,
                 prefetch_buffer: int = 2,
                 dcn_compression: Optional[dict] = None):
        """``dcn_compression``: None for full-precision ICI psum (the
        default; right on a single slice), or
        ``{"threshold": t}`` to train with the int8 + threshold +
        residual-error-feedback gradient reduce — the DCN-spanning
        equivalent of the reference's SharedTrainingMaster /
        EncodingHandler threshold encoding
        (dl4j-spark-parameterserver/.../SharedTrainingMaster.java:55,
        deeplearning4j-nn/.../EncodingHandler.java:116-181)."""
        self.model = model
        self.mesh = mesh if mesh is not None else build_mesh(MeshSpec())
        self.prefetch = prefetch_buffer
        self.dcn_compression = dcn_compression
        self._compressed_step = None
        self._seq_step = None
        self._seq_collapses = False   # set by _validate_seq_model
        self._seq_gspmd = False       # set by _validate_seq_model
        self._residual = None
        # elastic bookkeeping: the dp the wrapper was built with (the
        # regrow target) and the devices declared lost so far
        self._initial_dp = self.mesh.shape.get("data", 1)
        self._lost_devices: set = set()
        self.mesh_shrinks = 0

    # ---- builder parity ----
    class Builder:
        def __init__(self, model):
            self._model = model
            self._workers = None
            self._prefetch = 2
            self._compression = None

        def workers(self, n: int):
            self._workers = n
            return self

        def prefetch_buffer(self, n: int):
            self._prefetch = n
            return self

        def averaging_frequency(self, n: int):
            if n not in (0, 1):
                logger.warning(
                    "averaging_frequency(%d) requested, but the mesh "
                    "trainer synchronizes gradients EVERY step (psum "
                    "over ICI) — strictly stronger consistency than "
                    "periodic parameter averaging; the value is "
                    "ignored", n)
            return self

        def dcn_compression(self, threshold: float = 0.0):
            """Enable int8 + residual-error-feedback gradient reduce
            (see ParallelWrapper dcn_compression)."""
            self._compression = {"threshold": threshold}
            return self

        def build(self) -> "ParallelWrapper":
            if self._workers is not None:
                devs = jax.devices()[:self._workers]
                mesh = build_mesh(MeshSpec(data=self._workers), devs)
            else:
                mesh = build_mesh(MeshSpec())
            return ParallelWrapper(self._model, mesh, self._prefetch,
                                   self._compression)

    @staticmethod
    def builder(model) -> "ParallelWrapper.Builder":
        return ParallelWrapper.Builder(model)

    # ---- compressed DCN train step ----
    def _make_compressed_step(self):
        """Explicit shard_map data-parallel step with int8 + threshold
        + residual-error-feedback gradient reduce — the trainer the
        reference wires EncodingHandler into (SharedTrainingWrapper
        .java:161-195 attaches the encoding accumulator to the local
        wrapper). The residual rides along as per-device state with a
        leading mesh axis."""
        from deeplearning4j_tpu.models.computation_graph import (
            ComputationGraph)
        from deeplearning4j_tpu.parallel.compression import (
            make_compressed_psum_ef)

        model = self.model
        mesh = self.mesh
        is_graph = isinstance(model, ComputationGraph)
        optimizer = model._optimizer
        ndata = mesh.shape["data"]
        psum_ef = make_compressed_psum_ef(
            float(self.dcn_compression.get("threshold", 0.0)))

        def per_device(params, state, opt_state, residual, batch,
                       base_rng, step):
            # fold the device index in: otherwise every shard draws the
            # SAME dropout mask (correlated regularization noise)
            rng = jax.random.fold_in(
                jax.random.fold_in(base_rng, step),
                jax.lax.axis_index("data"))
            residual = jax.tree_util.tree_map(lambda r: r[0], residual)
            # mark params device-varying: otherwise jax's varying-axes
            # AD auto-psums the cotangent (full-precision!) before we
            # get to intercept it with the compressed reduce
            params_v = jax.tree_util.tree_map(
                lambda p: jax.lax.pcast(p, "data", to="varying"),
                params)

            def loss_fn(p):
                return model._loss(p, state, batch, rng, training=True)

            (loss, new_state), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params_v)
            # local grads are means over the LOCAL shard; divide by the
            # device count so the compressed psum yields the global mean
            grads = jax.tree_util.tree_map(lambda g: g / ndata, grads)
            grads, new_residual = psum_ef(grads, residual, "data")
            new_params, new_state, new_opt, loss = _spmd_update_tail(
                model, is_graph, optimizer, grads, new_state, loss,
                opt_state, params, ("data",))
            new_residual = jax.tree_util.tree_map(lambda r: r[None],
                                                  new_residual)
            return new_params, new_state, new_opt, new_residual, loss

        # check_vma=True: the varying-axes types are what make AD
        # psum replicated-param cotangents (and what _varying opts
        # out of) — the step's math depends on them being tracked
        smapped = jax.shard_map(
            per_device, mesh=mesh,
            in_specs=(P(), P(), P(), P("data"), P("data"), P(), P()),
            out_specs=(P(), P(), P(), P("data"), P()),
            check_vma=True)
        return jax.jit(smapped, donate_argnums=(0, 1, 2, 3))

    # ---- sequence-parallel train step ----
    def _seq_axis_size(self) -> int:
        return (self.mesh.shape["seq"]
                if "seq" in self.mesh.axis_names else 1)

    def _validate_seq_model(self):
        """Sequence parallelism shards TIME: every layer/vertex must
        be exact on a local chunk (pointwise in time, or self-routing
        through the ring like attention). Fail loudly otherwise — a
        silently wrong chunked LSTM would be far worse than an
        error. Supports both executors: MultiLayerNetwork stacks and
        ComputationGraphs whose vertices are all time-pointwise."""
        from deeplearning4j_tpu.models.computation_graph import (
            ComputationGraph)
        from deeplearning4j_tpu.models.multi_layer_network import (
            MultiLayerNetwork)
        extra = [a for a in self.mesh.axis_names
                 if a not in ("data", "seq") and self.mesh.shape[a] > 1]
        if self.dcn_compression is not None and extra:
            raise NotImplementedError(
                "dcn_compression composes with 'data' x 'seq' meshes "
                f"(manual step); mesh also carries {extra}")
        # dp x seq runs the manual all-shard_map step; any further
        # axis (tensor-parallel 'model') switches to the GSPMD step:
        # plain jit partitions data/model automatically and the
        # attention layers open ring islands over just 'seq'
        # (seq_context.sequence_parallel_gspmd) — that is how
        # dp x tp x sp composes on one mesh (round-4 verdict next #4)
        self._seq_gspmd = bool(extra)
        self._seq_collapses = False      # recomputed per validation
        if isinstance(self.model, ComputationGraph):
            # layers AND vertices self-declare time-pointwiseness via
            # the seq_parallelizable class attribute (Layer base +
            # GraphVertex base; see nn/conf/graph.py for which
            # vertices opt in and why the rest cannot)
            bad = []
            for name, (obj, _) in self.model.conf.vertices.items():
                if not getattr(obj, "seq_parallelizable", False):
                    bad.append(f"vertex '{name}' "
                               f"({type(obj).__name__})")
            if bad:
                raise ValueError(
                    "these graph vertices cannot train over a 'seq' "
                    "mesh axis (not pointwise in time): "
                    + ", ".join(bad)
                    + " — or drop the seq axis from the mesh")
            # every input must be TEMPORAL: the batch shards axis 1
            # over 'seq', which is only time for recurrent inputs —
            # a (B, F) static input would silently shard features
            in_types = getattr(self.model.conf, "input_types", None)
            if not in_types:
                raise ValueError(
                    "sequence-parallel graphs need set_input_types("
                    "InputType.recurrent(...)) so the wrapper can "
                    "prove every input is temporal before sharding "
                    "axis 1 over 'seq'")
            non_rnn = [f"input {i} ({t.kind})"
                       for i, t in enumerate(in_types)
                       if t.kind != "rnn"]
            if non_rnn:
                raise ValueError(
                    "sequence-parallel graphs need recurrent (B, T, "
                    "...) inputs; got " + ", ".join(non_rnn))
            return
        if not isinstance(self.model, MultiLayerNetwork):
            raise NotImplementedError(
                "sequence-parallel training supports "
                "MultiLayerNetwork and ComputationGraph; got "
                f"{type(self.model).__name__}")
        # the batch shards axis 1 over 'seq' — that must be TIME, so
        # the network input has to be recurrent (mirrors the graph
        # branch; a CNN input would silently shard image height)
        in_t = getattr(self.model.conf, "input_type", None)
        if in_t is None or in_t.kind != "rnn":
            raise ValueError(
                "sequence-parallel training needs set_input_type("
                "InputType.recurrent(...)) — got "
                f"{getattr(in_t, 'kind', None)!r}; the wrapper shards "
                "axis 1 over 'seq', which is only time for recurrent "
                "inputs")
        bad = []
        collapsed = False
        for i, l in enumerate(self.model.layers):
            if collapsed:
                # time axis already pooled away with a collective:
                # downstream activations are REPLICATED over seq, so
                # any deterministic layer is exact — but stochastic
                # layers draw per-shard rng (the step decorrelates
                # dropout by seq index) and would break replication
                if getattr(l, "dropout", 0.0):
                    bad.append(f"layer {i} ({type(l).__name__}: "
                               "dropout after the time collapse)")
                continue
            if getattr(l, "seq_collapses_time", False):
                collapsed = True
            elif not getattr(l, "seq_parallelizable", False):
                bad.append(f"layer {i} ({type(l).__name__})")
        if bad:
            raise ValueError(
                "these layers cannot train over a 'seq' mesh axis (not "
                "pointwise in time): " + ", ".join(bad)
                + " — use attention/dense/time-distributed layers "
                  "(optionally a GlobalPoolingLayer collapse), or "
                  "drop the seq axis from the mesh")
        # time-collapsed nets have NON-temporal labels: (B, K) shards
        # over 'data' only (the batch sharder consults this)
        self._seq_collapses = collapsed
        # input preprocessors reshape with GLOBAL timestep counts
        # (e.g. FeedForwardToRnn) — wrong on a local time chunk
        pps = getattr(self.model.conf, "preprocessors", None) or {}
        if pps:
            names = ", ".join(f"layer {i}: {type(p).__name__}"
                              for i, p in sorted(pps.items()))
            raise ValueError(
                "input preprocessors are not supported under sequence "
                f"parallelism ({names}) — they reshape with global "
                "timestep counts; restructure the net so activations "
                "stay (B, T, ...) end to end, or drop the seq axis")

    def _make_seq_step(self):
        """Explicit shard_map train step over a mesh with a ``seq``
        axis: (B, T, ...) batches sharded B→'data', T→'seq'; the model
        is traced under ``sequence_parallel`` so attention layers ride
        the ring (``parallel/ring_attention.ring_self_attention``)
        while every other layer computes its local time chunk. Params
        stay replicated; AD psums their cotangents over every mesh
        axis, so dividing by the shard count yields the exact global
        mean gradient — sp training matches the single-device step to
        float tolerance (dryrun regime 8 asserts it).

        With ``dcn_compression`` the data-axis reduction is
        intercepted: params are marked device-varying over 'data'
        ONLY, so AD auto-psums the seq cotangent in full precision
        (intra-slice ICI) while the int8 + threshold + residual-error-
        feedback reduce runs over 'data' — the DCN-spanning axis the
        compression exists for."""
        from deeplearning4j_tpu.models.computation_graph import (
            ComputationGraph)
        from deeplearning4j_tpu.parallel.seq_context import (
            sequence_parallel)

        model = self.model
        mesh = self.mesh
        is_graph = isinstance(model, ComputationGraph)
        optimizer = model._optimizer
        axes = tuple(a for a in ("data", "seq") if a in mesh.axis_names)
        nshards = 1
        for a in axes:
            nshards *= mesh.shape[a]
        compressed = self.dcn_compression is not None
        if compressed:
            from deeplearning4j_tpu.parallel.compression import (
                make_compressed_psum_ef)
            psum_ef = make_compressed_psum_ef(
                float(self.dcn_compression.get("threshold", 0.0)))

        def per_device(params, state, opt_state, residual, batch,
                       base_rng, step):
            rng = jax.random.fold_in(base_rng, step)
            # decorrelate dropout across every shard (data AND seq —
            # two time-chunks of one example are distinct positions)
            for ax in axes:
                rng = jax.random.fold_in(rng, jax.lax.axis_index(ax))
            if compressed:
                residual = jax.tree_util.tree_map(lambda r: r[0],
                                                  residual)
                # varying over 'data' only: the seq cotangent still
                # auto-psums (full precision, ICI); the data-axis
                # reduction is ours to compress
                params_in = jax.tree_util.tree_map(
                    lambda p: jax.lax.pcast(p, "data", to="varying"),
                    params)
            else:
                params_in = params
            with sequence_parallel("seq", loss_axes=axes):
                def loss_fn(p):
                    return model._loss(p, state, batch, rng,
                                       training=True)

                (loss, new_state), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params_in)
            # grads on each data shard: Σ over seq shards of ∂(local
            # mean loss); the global loss is the MEAN of the uniform
            # local means — normalize by the full shard count
            grads = jax.tree_util.tree_map(lambda g: g / nshards, grads)
            if compressed:
                grads, new_residual = psum_ef(grads, residual, "data")
            new_params, new_state, new_opt, loss = _spmd_update_tail(
                model, is_graph, optimizer, grads, new_state, loss,
                opt_state, params, axes)
            if compressed:
                new_residual = jax.tree_util.tree_map(
                    lambda r: r[None], new_residual)
                return new_params, new_state, new_opt, new_residual, \
                    loss
            return new_params, new_state, new_opt, loss

        daxis = "data" if "data" in mesh.axis_names else None
        bspec_t = P(daxis, "seq")              # temporal leaves
        # labels of a time-collapsing net are (B, K): batch-axis only
        bspec_l = P(daxis) if self._seq_collapses else bspec_t
        bspec = (bspec_t, bspec_l, bspec_t, bspec_l)
        if compressed:
            smapped = jax.shard_map(
                per_device, mesh=mesh,
                in_specs=(P(), P(), P(), P("data"), bspec, P(), P()),
                out_specs=(P(), P(), P(), P("data"), P()),
                check_vma=True)
            return jax.jit(smapped, donate_argnums=(0, 1, 2, 3))

        def no_residual(params, state, opt_state, batch, base_rng,
                        step):
            return per_device(params, state, opt_state, None, batch,
                              base_rng, step)

        # check_vma=True: AD's psum of the replicated-param
        # cotangents over every mesh axis IS the gradient reduction
        smapped = jax.shard_map(
            no_residual, mesh=mesh,
            in_specs=(P(), P(), P(), bspec, P(), P()),
            out_specs=(P(), P(), P(), P()),
            check_vma=True)
        return jax.jit(smapped, donate_argnums=(0, 1, 2))

    def _make_seq_gspmd_step(self):
        """Sequence-parallel step for meshes that ALSO carry other
        sharded axes (tensor-parallel 'model'): a plain jit — GSPMD
        partitions params (tp shardings preserved), batch (B→'data',
        T→'seq') and every pointwise op automatically, computing
        global-mean losses and auto-psumming replicated-param
        cotangents — traced under ``sequence_parallel_gspmd`` so the
        attention layers open manual ring islands over just 'seq'.
        No manual normalization is needed: the loss IS the global
        mean, so gradients match the single-device step to float
        tolerance (dryrun regime 11 asserts dp=2 x tp=2 x sp=2)."""
        import functools

        from deeplearning4j_tpu.models.computation_graph import (
            ComputationGraph)
        from deeplearning4j_tpu.parallel.seq_context import (
            sequence_parallel_gspmd)

        model = self.model
        mesh = self.mesh
        is_graph = isinstance(model, ComputationGraph)
        optimizer = model._optimizer

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def train_step(params, state, opt_state, batch, base_rng, step):
            # the context is entered INSIDE the jitted body so every
            # (re)trace sees the routing, not just the first call
            with sequence_parallel_gspmd(mesh, "seq"):
                rng = jax.random.fold_in(base_rng, step)

                def loss_fn(p):
                    return model._loss(p, state, batch, rng,
                                       training=True)

                (loss, new_state), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                new_params, new_opt = _grad_update(
                    model, is_graph, optimizer, grads, opt_state,
                    params)
            return new_params, new_state, new_opt, loss

        return train_step

    def _shard_seq_batch(self, batch):
        """Every batch leaf (B, T, ...) → B over 'data', T over 'seq'
        — masks included (the attention layers rotate mask chunks
        around the ring, and time-distributed losses psum the masked
        denominator via seq_context.current_loss_axes). Handles both
        executors' batch tuples: plain arrays (MLN) and per-input /
        per-output lists (ComputationGraph MultiDataSet)."""
        nseq = self._seq_axis_size()
        ndata = self.mesh.shape.get("data", 1)
        daxis = "data" if "data" in self.mesh.axis_names else None
        temporal = NamedSharding(self.mesh, P(daxis, "seq"))
        batch_only = NamedSharding(self.mesh, P(daxis))

        def put_temporal(a):
            if a.ndim < 2:
                raise ValueError(f"seq-parallel batch arrays must be "
                                 f"(B, T, ...); got shape {a.shape}")
            if a.shape[0] % ndata or a.shape[1] % nseq:
                raise ValueError(
                    f"seq-parallel batch shape {a.shape} not divisible "
                    f"by mesh (data={ndata}, seq={nseq})")
            return jax.device_put(a, temporal)

        def put_batch_only(a):
            if a.shape[0] % ndata:
                raise ValueError(
                    f"seq-parallel batch shape {a.shape} not divisible "
                    f"by mesh (data={ndata})")
            return jax.device_put(a, batch_only)

        f, l, fm, lm = batch
        # features/feature-masks are always temporal; labels are
        # temporal only for seq-to-seq nets — a time-collapsing net
        # (GlobalPooling) has (B, K) labels sharded over 'data' alone
        put_label = (put_batch_only if self._seq_collapses
                     else put_temporal)
        t = jax.tree_util.tree_map
        return (t(put_temporal, f), t(put_label, l),
                t(put_temporal, fm), t(put_label, lm))

    def _init_residual(self):
        ndev = self.mesh.shape["data"]
        # float32 regardless of param dtype: the EF residual carries
        # the exact quantization error (compression._ef_carry), and
        # int8_all_reduce_ef returns it as float32 — a narrower init
        # would change the carry aval after the first step
        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros((ndev,) + p.shape, jnp.float32),
            self.model.params)
        return jax.device_put(zeros, NamedSharding(self.mesh, P("data")))

    # ---- sharding helpers ----
    def _replicated(self):
        return NamedSharding(self.mesh, P())

    def _on_mesh(self, tree):
        """Replicate leaves over this mesh — but PRESERVE any existing
        tensor-parallel placement (tensor_parallel.shard_params /
        shard_graph_params) already on the same mesh: dp x tp is the
        wrapper's mesh carrying both axes, with GSPMD inserting the
        collectives."""
        repl = self._replicated()

        def place(a):
            sh = getattr(a, "sharding", None)
            if isinstance(sh, NamedSharding) and (
                    sh.mesh is self.mesh   # fast path: placed by us
                    or (sh.mesh.shape == self.mesh.shape
                        and tuple(sh.mesh.axis_names)
                        == tuple(self.mesh.axis_names))):
                return a                 # already placed on this mesh
            return jax.device_put(a, repl)

        return jax.tree_util.tree_map(place, tree)

    def _shard_leaf(self, a):
        return jax.device_put(
            a, NamedSharding(self.mesh, P("data", *([None] * (a.ndim - 1)))))

    def _shard_batch(self, batch):
        return jax.tree_util.tree_map(self._shard_leaf, batch)

    # ---- elastic mesh shrink / regrow ----
    def lose_device(self, index: int = -1) -> None:
        """Declare the mesh device at ``index`` (into the current
        mesh's flat device list) lost and shrink onto the survivors.
        The programmatic twin of the ``parallel.device`` chaos site's
        ``loss`` kind."""
        devs = list(self.mesh.devices.flat)
        self._shrink({devs[index % len(devs)]})

    def _on_device_loss(self, fault) -> None:
        devs = list(self.mesh.devices.flat)
        idx = int(fault.args.get("device", len(devs) - 1))
        self._shrink({devs[idx % len(devs)]})

    def _rebuild_on(self, new_mesh) -> None:
        """Move the model onto ``new_mesh``: host snapshot from the
        current placement (``device_get`` gathers tensor-parallel
        shards into full arrays), mesh swap, re-place, reset every
        mesh-shaped compiled artifact (steps retrace; the
        compression error-feedback residual is per-device state and
        re-zeroes — the one thing a topology change does NOT
        preserve). A mesh with a 'model' axis re-places params
        through the DEFAULT tensor-parallel rule table
        (``tensor_parallel.default_tp_rules``) — hand-written rules
        do not survive a shrink."""
        from deeplearning4j_tpu.parallel.mesh_spec import MeshContext
        m = self.model
        host = jax.device_get((m.params, m.state, m.opt_state))
        self.mesh = new_mesh
        self._compressed_step = None
        self._seq_step = None
        self._residual = None
        m.params, m.state, m.opt_state = host
        if (new_mesh.shape.get("model", 1) > 1
                or getattr(m, "_mesh_ctx", None) is not None):
            ctx = MeshContext.from_mesh(new_mesh)
            ctx.place_model(m)
            if getattr(m, "_mesh_ctx", None) is not None:
                # the model's own programs pin the OLD mesh's output
                # shardings — swap the context and flush them
                m._mesh_ctx = ctx
                m._flush_compiled_programs()
        else:
            m.params = self._on_mesh(m.params)
            m.state = self._on_mesh(m.state)
            m.opt_state = self._on_mesh(m.opt_state)
        if self.dcn_compression is not None:
            self._residual = self._init_residual()

    def _shrink(self, lost: set) -> None:
        old_dp = self.mesh.shape.get("data", 1)
        # host snapshot at the step boundary: params/opt-state are
        # replicated over 'data', so the survivors hold a complete
        # copy of the last committed step — the lost device
        # contributes nothing unique (shrink_data_mesh refuses
        # meshes where that would not hold)
        new_mesh = shrink_data_mesh(self.mesh, lost)
        self._lost_devices |= set(lost)
        self._rebuild_on(new_mesh)
        self.mesh_shrinks += 1
        new_dp = self.mesh.shape.get("data", 1)
        logger.warning(
            "device loss: mesh shrunk dp=%d -> dp=%d over %d "
            "survivor(s); per-device batch split rescaled, training "
            "continues (regrow is explicit via wrapper.regrow())",
            old_dp, new_dp, new_dp)
        self._account_elastic("elastic_mesh_shrinks_total",
                              "mesh shrinks after a device loss",
                              "mesh_shrink", old_dp, new_dp)

    def regrow(self, devices=None):
        """Explicitly rebuild the mesh after capacity returns:
        ``devices`` (default ``jax.devices()``) at the original dp
        (or the largest power of two that fits), keeping any
        tensor-parallel 'model' axis intact. Params/opt-state are
        re-placed from the current host copy; compiled steps
        retrace. Returns the new mesh."""
        if devices is not None:
            # an explicit device list is the operator vouching for
            # every device in it — including ones previously
            # declared lost
            devices = list(devices)
            self._lost_devices.clear()
        else:
            # default: everything visible EXCEPT devices recorded as
            # lost — a sick device must not silently rejoin just
            # because the runtime still enumerates it
            devices = [d for d in jax.devices()
                       if d not in self._lost_devices]
        old_dp = self.mesh.shape.get("data", 1)
        tp = self.mesh.shape.get("model", 1)
        dp = min(self._initial_dp, largest_pow2(len(devices) // tp))
        self._rebuild_on(build_mesh(MeshSpec(data=dp, model=tp),
                                    devices[:dp * tp]))
        logger.warning("mesh regrown dp=%d -> dp=%d", old_dp, dp)
        self._account_elastic("elastic_mesh_regrows_total",
                              "explicit mesh regrows after a shrink",
                              "mesh_regrow", old_dp, dp)
        return self.mesh

    @staticmethod
    def _account_elastic(counter: str, help: str, event: str,
                         dp_from: int, dp_to: int) -> None:
        try:
            from deeplearning4j_tpu.observability.registry import (
                safe_inc)
            safe_inc(counter, help=help)
        except Exception:
            pass
        try:
            from deeplearning4j_tpu.observability import (
                flight_recorder)
            rec = flight_recorder.get_recorder()
            if rec is not None:
                rec.record(event, dp_from=dp_from, dp_to=dp_to)
        except Exception:
            pass

    def _current_step(self):
        """Resolve the compiled step for the CURRENT mesh/config —
        consulted every batch, so a mid-fit shrink or regrow (which
        nulls the cached step) can never leave a stale executable
        running against a rebuilt mesh/residual. Cache hits are a
        couple of attribute checks."""
        model = self.model
        if self._seq_axis_size() > 1:
            if self._seq_step is None:
                self._validate_seq_model()
                self._seq_step = (self._make_seq_gspmd_step()
                                  if self._seq_gspmd
                                  else self._make_seq_step())
            return self._seq_step
        if self.dcn_compression is not None:
            if self._compressed_step is None:
                self._compressed_step = self._make_compressed_step()
            return self._compressed_step
        if model._jit_train_step is None:
            model._jit_train_step = model._make_train_step()
        return model._jit_train_step

    def _place_model(self):
        """Put params/state/opt-state on this mesh (no-op for leaves
        already placed there) and materialize the compression
        residual."""
        model = self.model
        model.params = self._on_mesh(model.params)
        model.state = self._on_mesh(model.state)
        model.opt_state = self._on_mesh(model.opt_state)
        if self.dcn_compression is not None and self._residual is None:
            self._residual = self._init_residual()

    def _train_batch(self, ds) -> bool:
        """One batch through the mesh step: chaos site, divisibility
        trim, shard, device step, iteration listeners. Returns False
        when the batch was dropped (fewer examples than devices)."""
        from deeplearning4j_tpu.models.computation_graph import (
            ComputationGraph)
        model = self.model
        is_graph = isinstance(model, ComputationGraph)
        # chaos site: 'crash' raises (process death — the
        # ElasticTrainer checkpoint-restart path), 'loss' simulates
        # losing one mesh device — the wrapper shrinks and trains
        # THIS batch on the survivors
        f = chaos.step_fault("parallel.device")
        if f is not None and f.kind == "loss":
            self._on_device_loss(f)
        # step AND ndata resolved after any shrink: the per-device
        # split and the executable both follow the current mesh
        step = self._current_step()
        seq_parallel = self._seq_axis_size() > 1
        compressed = self.dcn_compression is not None
        ndata = self.mesh.shape.get("data", 1)
        n = ds.num_examples()
        if n % ndata:
            if n < ndata:
                logger.debug("dropping final batch of %d (< %d "
                             "devices)", n, ndata)
                return False
            # truncate to a device-divisible count; repeating
            # examples would bias the mean gradient
            ds = _truncate_batch(ds, (n // ndata) * ndata)
            n = ds.num_examples()
        if is_graph:
            batch = model._batch_tuple(model._as_multi(ds))
        else:
            batch = model._batch_tuple(ds)
        batch = (self._shard_seq_batch(batch) if seq_parallel
                 else self._shard_batch(batch))
        if compressed:
            (model.params, model.state, model.opt_state,
             self._residual, loss) = step(
                model.params, model.state, model.opt_state,
                self._residual, batch, model._rng_key,
                np.int32(model.iteration_count))
        else:
            # the plain step is the model's own jit, partitioned by
            # GSPMD over this mesh: announce it for whenever the call
            # traces, so kernel calls wrap themselves for it (the
            # manual seq step sets its own scope inside)
            # (a network with expert layers returns their counts
            # behind the loss: ``_train_step_fn``; the wrapper tallies
            # none)
            with gspmd_mesh(self.mesh):
                model.params, model.state, model.opt_state, loss = \
                    step(model.params, model.state, model.opt_state,
                         batch, model._rng_key,
                         np.int32(model.iteration_count))[:4]
        model.score_value = loss
        for lst in model.listeners:
            lst.iteration_done(model, model.iteration_count, loss, n)
        model.iteration_count += 1
        return True

    def fit_batch(self, ds):
        """Train exactly ONE batch on the mesh with NO epoch
        bookkeeping — no epoch hooks, no ``epoch_count`` bump, no
        prefetch thread. The ElasticTrainer integration point: the
        trainer owns the epoch loop (and so the listeners' epoch
        cadence and the checkpointed epoch counter); the wrapper owns
        the mesh step."""
        if self.model.params is None:
            self.model.init()
        # seq validation happens in _current_step on step-cache miss;
        # repeating it per batch would walk the model every step
        self._place_model()
        self._train_batch(ds)
        return self.model

    # ---- fused k-step windows on the mesh ----
    def supports_fused_windows(self) -> bool:
        """Whether this wrapper's mesh can run k-step fused windows
        as ONE sharded device program: data / data x model meshes
        with full-precision reduce. The seq step is a manual
        shard_map (ring islands don't compose with the scanned
        window) and the compressed reduce threads per-device
        residual state the scan carry does not hold — both stay
        per-batch."""
        return (self._seq_axis_size() == 1
                and self.mesh.shape.get("pipe", 1) == 1
                and self.dcn_compression is None)

    def _ensure_model_ctx(self) -> None:
        """Install (or refresh after a shrink/regrow) a
        ``MeshContext`` over THIS mesh on the model, preserving any
        hand-applied tensor-parallel placement already on it."""
        from deeplearning4j_tpu.parallel.mesh_spec import MeshContext
        ctx = getattr(self.model, "_mesh_ctx", None)
        if ctx is None or ctx.mesh is not self.mesh:
            self.model.use_mesh(MeshContext.from_mesh(self.mesh),
                                respect_existing=True)

    def fit_batches(self, batches, *, steps_per_device_call: int = 1):
        """Train a window of batches with the model's k-step fused
        machinery running ON this wrapper's mesh — window fusion +
        mesh step in ONE device program (the ElasticTrainer k>1
        entry point; the per-batch twin is :meth:`fit_batch`). The
        ``parallel.device`` chaos site is consulted once per window:
        a device loss shrinks the mesh first and the whole window
        trains on the survivors. Returns per-step losses."""
        if not self.supports_fused_windows():
            raise ValueError(
                "fused k-step windows need a data / data x model "
                "mesh with full-precision reduce; this wrapper's "
                "mesh/config (seq/pipe axis or dcn_compression) "
                "trains per-batch — use fit_batch or "
                "steps_per_device_call=1")
        if self.model.params is None:
            self.model.init()
        f = chaos.step_fault("parallel.device")
        if f is not None and f.kind == "loss":
            self._on_device_loss(f)
        self._ensure_model_ctx()
        return self.model.fit_batches(
            batches, steps_per_device_call=steps_per_device_call)

    def fit(self, iterator: DataSetIterator, *, epochs: int = 1):
        model = self.model
        if model.params is None:
            model.init()
        if self._seq_axis_size() > 1:
            self._validate_seq_model()
        self._place_model()
        it = AsyncDataSetIterator(iterator, self.prefetch) \
            if self.prefetch > 0 else iterator
        for _ in range(epochs):
            for lst in model.listeners:
                lst.on_epoch_start(model)
            for ds in it:
                self._train_batch(ds)
            for lst in model.listeners:
                lst.on_epoch_end(model)
            model.epoch_count += 1
        return model


# graph and sequential models share the wrapper; alias for readability
GraphParallelWrapper = ParallelWrapper


def _truncate_batch(ds, target: int):
    """Trim a batch to ``target`` examples (device-divisible static
    shape without the gradient bias padding-by-repeat would cause).
    Handles DataSet and MultiDataSet (lists of per-input arrays)."""
    from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet

    def take(a):
        return None if a is None else a[:target]

    if isinstance(ds, MultiDataSet):
        def take_list(lst):
            return None if lst is None else [take(a) for a in lst]
        return MultiDataSet(take_list(ds.features), take_list(ds.labels),
                            take_list(ds.features_masks),
                            take_list(ds.labels_masks))
    return DataSet(take(ds.features), take(ds.labels),
                   take(ds.features_mask), take(ds.labels_mask))
