#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once — build from the config DSL, ``fit``, save,
``serve``, generate — through the entry points a user calls, on ONE
TPU chip, at the full width of the widest language model this repo has
trained (``EmbeddingSequenceLayer(2048 -> 1024)`` + 8 causal
``TransformerEncoderLayer(n_heads=16)`` + ``RnnOutputLayer(2048)``,
T = 1024, batch 8, built under ``dtypes.tpu_bf16()``), plus three
steps of zoo ResNet50 (batch 128, 224x224, f32) through the second
executor. Weights and data come from ``--seed``;
nothing is read but the repo. (The transformer layers do not read the
policy's compute dtype: this LM's tensors are float32 and its matmuls
run at the backend's default precision — on a TPU, operands rounded to
bf16, f32 accumulation. The phase lines print the dtype the attention
kernels saw.)

    python chip_smoke.py              # one chip: device, train, serve
    python chip_smoke.py --chips 4    # four chips: the sharded phase
                                      # and its one-device comparison
    python chip_smoke.py --rehearse   # any backend, tiny sizes: finds
                                      # wrong paths and arguments; it
                                      # never prints "ok": true

Each phase prints one JSON line as it finishes (name, seconds, compile
seconds and cache traffic, what it checked). A phase that fails raises,
and the script ends non-zero at once — there is no ``except`` on the
path. Timings printed here are smoke timings, not benchmark numbers.
The last line of stdout is the result,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every phase passed on a TPU at full size.

One process holds the chip: the server runs on a thread of THIS
process, through the same function ``python -m deeplearning4j_tpu
serve`` runs (``cli.build_server``), and no child is started.
"""

import argparse
import concurrent.futures
import contextlib
import json
import logging
import os
import sys
import tempfile
import time
import urllib.request

import numpy as np

# the LM the train, serve and sharded phases share: (full, rehearsal)
FULL = dict(vocab=2048, width=1024, layers=8, heads=16, seq=1024,
            batch=8, steps=6,
            resnet=dict(batch=128, img=224, classes=1000, steps=3),
            prompt_lens=(16, 128, 64, 32, 16, 128, 64, 32),
            new_tokens=32, capacity=256, page_size=16, predict_seq=128)
TINY = dict(vocab=64, width=64, layers=2, heads=4, seq=32,
            batch=8, steps=6,
            resnet=dict(batch=4, img=32, classes=10, steps=3),
            prompt_lens=(4, 16, 8, 6, 4, 16, 8, 6),
            new_tokens=4, capacity=32, page_size=4, predict_seq=16)

# dp-vs-one-device loss parity, relative. On the CPU in f32 the bar is
# 1e-5 (tests/test_mesh_spec.py). On a TPU the default matmul
# precision rounds operands to bf16 (one ulp = 3.9e-3); a sharded step
# (2 examples per device, all-reduced gradients) accumulates in
# another order, the f32 differences flip a few of those roundings,
# and the loss — an f32 mean over batch x seq tokens — averages them
# out. Measured on four v5e chips (PR 21): 5.8e-5 over six steps.
# 5e-4 leaves an order of magnitude for another compiler's rounding
# and is still an eighth of ONE operand's ulp — far tighter than "the
# curve also goes down"; the phase also requires it to sit well inside
# the loss drop the run itself shows.
DP_PARITY_RTOL = {"tpu": 5e-4, "cpu": 1e-5}

def emit(obj):
    print(json.dumps(obj), flush=True)


class Phases:
    """Runs phases in order, printing one JSON line for each."""

    def __init__(self):
        from deeplearning4j_tpu.observability.compile_watch import (
            install_global_watch)
        self.stats = install_global_watch()

    def run(self, name, fn):
        mark, t0 = self.stats.mark(), time.perf_counter()
        checked = fn()
        s = self.stats.summary(mark)
        emit({"phase": name,
              "seconds": round(time.perf_counter() - t0, 2),
              "compile_seconds": s["compile_secs"],
              "backend_compiles": s["backend_compiles"],
              "cold_compiles": s["cold_compiles"],
              "cache_requests": s["cache_requests"],
              "persistent_cache_hits": s["persistent_cache_hits"],
              "cache_hit": s["cache_hit"],
              "checked": checked})
        return checked


class DispatchLog(logging.Handler):
    """Collects the trace-time 'flash_attention: <impl> ...' /
    'ring_self_attention: <impl> ...' records, so a phase can assert
    which implementation its step was built from."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.impls, self.dtypes = [], set()

    def emit(self, record):
        msg = record.getMessage()
        for op in ("flash_attention: ", "ring_self_attention: "):
            if msg.startswith(op):
                impl, _, rest = msg[len(op):].partition(" q=")
                self.impls.append(impl)
                # "(B, T, H, D) <dtype> block=..."
                self.dtypes.add(rest.split(") ")[1].split(" ")[0])


@contextlib.contextmanager
def dispatch_log():
    logger = logging.getLogger("deeplearning4j_tpu")
    handler, level = DispatchLog(), logger.level
    logger.addHandler(handler)
    logger.setLevel(logging.DEBUG)
    try:
        yield handler
    finally:
        logger.removeHandler(handler)
        logger.setLevel(level)


def check(cond, what):
    if not cond:
        raise AssertionError(what)


class Stopwatch:
    """Wall seconds of a phase's parts (smoke timings: they say where
    a slow phase spent its time, nothing more)."""

    def __init__(self):
        self.laps, self._t = {}, time.perf_counter()

    def lap(self, name):
        now = time.perf_counter()
        self.laps[name], self._t = round(now - self._t, 2), now


# ---------------------------------------------------------------- model


def lm_conf(size, seed):
    from deeplearning4j_tpu import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (
        EmbeddingSequenceLayer, RnnOutputLayer, TransformerEncoderLayer)
    # adam(1e-4): at this width 1e-3 diverges within three steps (on
    # the CPU in f32 as on the chip)
    b = (NeuralNetConfiguration.builder().set_seed(seed)
         .updater(updaters.adam(1e-4)).list()
         .layer(EmbeddingSequenceLayer(n_in=size["vocab"],
                                       n_out=size["width"])))
    for _ in range(size["layers"]):
        b = b.layer(TransformerEncoderLayer(n_heads=size["heads"],
                                            causal=True))
    return (b.layer(RnnOutputLayer(n_out=size["vocab"], loss="mcxent"))
            .set_input_type(InputType.recurrent(size["vocab"],
                                                size["seq"])).build())


def lm_batch(size, seed):
    """A fixed seeded batch: token ids and one-hot next-token
    targets."""
    from deeplearning4j_tpu.data.dataset import DataSet
    rng = np.random.default_rng(seed)
    shape = (size["batch"], size["seq"])
    ids = rng.integers(0, size["vocab"], shape).astype("float32")
    y = np.eye(size["vocab"], dtype="float32")[
        rng.integers(0, size["vocab"], shape)]
    return DataSet(ids, y)


def loss_recorder(net):
    """Attach the package's score-collecting listener; its ``scores``
    are (iteration, loss) pairs, one per step."""
    from deeplearning4j_tpu.train.listeners import (
        CollectScoresIterationListener)
    rec = CollectScoresIterationListener()
    net.set_listeners(rec)
    return rec


def kernels_in_step(net, batch, expect_kernels):
    """The lowered train step's text must hold the flash kernels,
    forward and backward (on a TPU); a silent drop to the blockwise
    formulation fails here."""
    text = net._jit_train_step.lower(
        net.params, net.state, net.opt_state, batch, net._rng_key,
        np.int32(0)).as_text()
    found = {k: text.count(k) for k in
             ("tpu_custom_call", "_fwd_kernel", "_dq_kernel",
              "_dkv_kernel")}
    check(all(found.values()) or not expect_kernels,
          f"flash kernels missing from the lowered step: {found}")
    return found


# --------------------------------------------------------------- phases


def phase_device(rehearse):
    import jax
    import jaxlib

    from deeplearning4j_tpu.observability.step_profile import (
        peak_flops_for_kind)
    from deeplearning4j_tpu.util.platform import setup_compile_cache
    cache_dir = setup_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    if not rehearse:
        check(dev.platform == "tpu",
              f"no TPU: jax found {dev.platform} ({dev.device_kind})")
        check(peak_flops_for_kind(dev.device_kind) is not None,
              f"device kind {dev.device_kind!r} is not in "
              "observability/step_profile.py's peak table")
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs), "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "libtpu": libtpu_version,
            "compile_cache_dir": cache_dir,
            "cache_dir_from_env":
                "JAX_COMPILATION_CACHE_DIR" in os.environ}


def train_lm(size, seed, ds, stats, on_tpu, **fit_kwargs):
    """conf -> JSON -> conf, then the public fit() on ``ds``: one
    warm-up step, then the rest under a zero-compile scope. Returns
    (net, losses, facts)."""
    from deeplearning4j_tpu import (MultiLayerConfiguration,
                                    MultiLayerNetwork)
    conf = lm_conf(size, seed)
    text = conf.to_json()
    conf = MultiLayerConfiguration.from_json(text)
    check(conf.to_json() == text, "config JSON round trip changed it")
    net = MultiLayerNetwork(conf).init()
    rec = loss_recorder(net)
    with dispatch_log() as log:
        t0 = time.perf_counter()
        net.fit(ds, **fit_kwargs)                  # warm-up: compiles
        first_s = time.perf_counter() - t0
    want = "pallas" if on_tpu else "blockwise"
    check(log.impls and all(i.startswith(want) for i in log.impls),
          f"attention dispatch chose {log.impls}, expected {want}")
    t0 = time.perf_counter()
    with stats.zero_compile_scope("LM steps after the warm-up step"):
        net.fit(ds, epochs=size["steps"] - 1, **fit_kwargs)
    steady_s = (time.perf_counter() - t0) / (size["steps"] - 1)
    v = [loss for _, loss in rec.scores]
    check(len(v) == size["steps"] and all(np.isfinite(v)),
          f"losses not finite: {v}")
    check(v[-1] < v[0], f"loss did not go down: {v}")
    return net, v, {"dispatch": sorted(set(log.impls)),
                    "attention_dtype": sorted(log.dtypes),
                    "first_step_seconds_smoke": round(first_s, 2),
                    "step_seconds_smoke": round(steady_s, 4)}


def phase_train(size, seed, stats, on_tpu, keep):
    from deeplearning4j_tpu import dtypes
    ds = lm_batch(size, seed)
    with dtypes.policy_scope(dtypes.tpu_bf16()):
        net, losses, facts = train_lm(size, seed, ds, stats, on_tpu)
        found = kernels_in_step(net, net._batch_tuple(ds), on_tpu)
    keep["lm"] = net
    lm = dict(facts, losses=[round(x, 5) for x in losses],
              lowered_step=found, zero_compiles_after_warmup=True,
              shape={k: size[k] for k in ("vocab", "width", "layers",
                                          "heads", "seq", "batch")})
    return {"lm": lm, "resnet50": train_resnet50(size["resnet"], seed)}


def train_resnet50(rs, seed):
    """The second executor and the BASELINE headline model: a few
    steps through ComputationGraph.fit, f32."""
    import jax

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.zoo import ResNet50
    net = ResNet50(n_classes=rs["classes"],
                   input_shape=(rs["img"], rs["img"], 3),
                   updater=updaters.nesterovs(0.1, 0.9)).init()
    before = np.asarray(jax.tree_util.tree_leaves(net.params)[0])
    rec = loss_recorder(net)
    rng = np.random.default_rng(seed)
    x = rng.normal(0, 1, (rs["batch"], rs["img"], rs["img"], 3)
                   ).astype("float32")
    y = np.eye(rs["classes"], dtype="float32")[
        rng.integers(0, rs["classes"], rs["batch"])]
    t0 = time.perf_counter()
    net.fit(DataSet(x, y), epochs=rs["steps"])
    v = [loss for _, loss in rec.scores]
    check(len(v) == rs["steps"] and all(np.isfinite(v)),
          f"ResNet50 losses not finite: {v}")
    after = np.asarray(jax.tree_util.tree_leaves(net.params)[0])
    check(np.isfinite(after).all() and not np.array_equal(before, after),
          "ResNet50 parameters did not move")
    return {"losses": [round(x, 5) for x in v], "params_moved": True,
            "seconds_smoke": round(time.perf_counter() - t0, 2),
            "shape": dict(rs)}


def _http(base, path, body=None, timeout=600):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        base + path, data, {"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def phase_serve(size, seed, keep, workdir):
    """Save the trained LM, serve it through what ``python -m
    deeplearning4j_tpu serve --model lm=<zip>`` runs, and hold the
    answers to in-process references."""
    from deeplearning4j_tpu import cli, dtypes
    from deeplearning4j_tpu.serving.continuous import ContinuousBatcher
    from deeplearning4j_tpu.util.model_serializer import (restore_model,
                                                          write_model)
    path = os.path.join(workdir, "lm.zip")
    clock = Stopwatch()
    write_model(keep.pop("lm"), path)
    clock.lap("write_model")
    rng = np.random.default_rng(seed + 1)
    prompts = [rng.integers(0, size["vocab"], n).tolist()
               for n in size["prompt_lens"]]
    n_new = size["new_tokens"]
    args = cli.build_parser().parse_args([
        "serve", "--model", f"lm={path}", "--port", "0",
        "--capacity", str(size["capacity"]),
        "--page-size", str(size["page_size"])])
    with dtypes.policy_scope(dtypes.tpu_bf16()):
        server = cli.build_server(args)
        server.start()
        clock.lap("restore_and_start")
        base = f"http://{args.host}:{server.port}"
        try:
            health = _http(base, "/healthz")
            check(health["status"] == "ok", f"/healthz: {health}")
            models = _http(base, "/v1/models")["models"]
            check([m["name"] for m in models] == ["lm"],
                  f"/v1/models: {models}")
            with concurrent.futures.ThreadPoolExecutor(
                    len(prompts)) as pool:
                futures = [pool.submit(
                    _http, base, "/v1/generate",
                    {"model": "lm", "prompt": p, "n_tokens": n_new})
                    for p in prompts]
                served = [f.result()["ids"] for f in futures]
            clock.lap("http_generate")
            x = np.asarray([prompts[1][:size["predict_seq"]]],
                           "float32")
            predicted = np.asarray(_http(
                base, "/v1/predict",
                {"model": "lm", "inputs": x.tolist()})["outputs"],
                "float32")
            direct = np.asarray(
                server.registry.get("lm").output(x), "float32")
            metrics = _http(base, "/metrics")["endpoints"]
            clock.lap("http_predict")
        finally:
            stopped = server.stop(drain=True, timeout=60.0)
        clock.lap("stop")
        check(stopped, "server.stop(drain=True) did not drain")
        check(all(len(s) == n_new for s in served),
              f"generate lengths: {[len(s) for s in served]}")
        # float32 outputs of the same program at the same (1-row)
        # bucket, through JSON: a float32 ulp, no more
        np.testing.assert_allclose(predicted, direct, rtol=1e-6,
                                   atol=1e-9)
        check(metrics["generate/lm/v1"]["requests"] == len(prompts)
              and metrics["predict/lm/v1"]["requests"] == 1,
              f"/metrics request counts: { {k: v['requests'] for k, v in metrics.items()} }")

        # reference 1: the same batcher, in process, on the restored
        # zip with the server's slot and KV settings — the same
        # compiled program, so the ids must be IDENTICAL
        restored = restore_model(path)
        batcher = ContinuousBatcher(
            restored, slots=args.slots, capacity=args.capacity,
            kv_mode=args.kv_mode, page_size=args.page_size,
            kv_pages=args.kv_pages)
        try:
            with concurrent.futures.ThreadPoolExecutor(
                    len(prompts)) as pool:
                futures = [pool.submit(batcher.generate,
                                       np.asarray(p), n_new)
                           for p in prompts]
                in_process = [np.asarray(f.result()).tolist()
                              for f in futures]
        finally:
            batcher.shutdown(drain=True, timeout=60.0)
        clock.lap("in_process_batcher")
        check(served == in_process,
              "HTTP generate ids differ from the in-process batcher: "
              f"{served} vs {in_process}")

        # reference 2: the single-stream session (the parity
        # tests/test_serving.py pins on the CPU) — reported as a
        # share; below 1.0 is a finding, not a failure
        session = restored.streaming_session(
            capacity=args.capacity, batch=1)
        same, first_diff = 0, []
        for p, ids in zip(prompts, served):
            session.reset()
            ref = np.asarray(session.generate(np.asarray([p]), n_new))[0]
            agree = ref == np.asarray(ids)
            same += int(agree.sum())
            first_diff.append(None if agree.all()
                              else int(np.argmin(agree)))
        clock.lap("single_stream_sessions")
    return {"healthz": health["status"], "models": ["lm"],
            "generate_requests": len(prompts),
            "prompt_lens": list(size["prompt_lens"]),
            "new_tokens": n_new, "slots": args.slots,
            "kv_mode": args.kv_mode, "paged": bool(batcher._paged),
            "ids_identical_to_in_process_batcher": True,
            "single_stream_token_agreement":
                round(same / (n_new * len(prompts)), 4),
            "single_stream_first_mismatch": first_diff,
            "seconds_smoke": clock.laps,
            "predict_matches_output": True,
            "metrics_requests": {k: v["requests"]
                                 for k, v in metrics.items()},
            "stop_drained": True}


def phase_sharded(size, seed, stats, on_tpu):
    """Four chips: the LM trained on one device, then the same seed
    and data through fit(mesh_spec="dp=4") and through
    ParallelWrapper(data=4); then one dp=2,tp=2 forward."""
    import jax

    from deeplearning4j_tpu import MultiLayerNetwork, dtypes
    from deeplearning4j_tpu.data.iterators import ListDataSetIterator
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, build_mesh
    from deeplearning4j_tpu.parallel.seq_context import gspmd_mesh
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
    from deeplearning4j_tpu.serving.tp_backend import (
        TensorParallelModel)
    check(len(jax.devices()) >= 4,
          f"--chips 4 needs four devices; jax found {jax.devices()}")
    devs = jax.devices()[:4]
    out = {}
    with dtypes.policy_scope(dtypes.tpu_bf16()):
        rtol = DP_PARITY_RTOL["tpu" if on_tpu else "cpu"]
        ds = lm_batch(size, seed)
        one, base, facts = train_lm(size, seed, ds, stats, on_tpu)
        out["one_device"] = dict(facts, losses=base)
        del one

        def on_four(net, batch):
            held = {d for leaf in jax.tree_util.tree_leaves(
                (net.params, net.opt_state, batch))
                for d in leaf.devices()}
            check(held == set(devs),
                  f"params/batch live on {held}, not on {devs}")
            if on_tpu:
                in_use = {str(d): d.memory_stats()["bytes_in_use"]
                          for d in devs}
                check(all(in_use.values()),
                      f"a device holds nothing: {in_use}")
                return in_use
            return None

        def parity(losses, what):
            dev = float(np.max(np.abs(np.asarray(losses) - base)
                               / np.abs(base)))
            emit({"note": f"{what} vs one device", "losses": losses,
                  "one_device": base, "max_rel_dev": dev,
                  "rtol": rtol})
            check(dev <= rtol, f"{what} losses left the one-device "
                               f"curve: {dev} > {rtol}")
            check(rtol * base[0] < (base[0] - base[-1]) / 4,
                  "tolerance is not small against the loss drop")
            return dev

        net, losses, facts = train_lm(size, seed, ds, stats, on_tpu,
                                      mesh_spec="dp=4")
        batch = net._mesh_ctx.shard_batch(net._batch_tuple_np(ds))
        out["fit_mesh_spec_dp4"] = dict(
            facts, max_rel_dev=parity(losses, 'fit(mesh_spec="dp=4")'),
            lowered_step=kernels_in_step(net, batch, on_tpu),
            bytes_in_use=on_four(net, batch))
        del net, batch

        # ParallelWrapper(data=4): its own fit loop, so the steps are
        # driven here (warm-up, then zero compiles)
        net = MultiLayerNetwork(lm_conf(size, seed)).init()
        rec = loss_recorder(net)
        mesh = build_mesh(MeshSpec(data=4), devs)
        wrapper = ParallelWrapper(net, mesh, prefetch_buffer=0)
        with dispatch_log() as log:
            wrapper.fit(ListDataSetIterator([ds]))
        with stats.zero_compile_scope("ParallelWrapper steps after "
                                      "the warm-up step"):
            wrapper.fit(ListDataSetIterator([ds]),
                        epochs=size["steps"] - 1)
        losses = [loss for _, loss in rec.scores]
        check(all(np.isfinite(losses)), f"losses: {losses}")
        batch = wrapper._shard_batch(net._batch_tuple(ds))
        with gspmd_mesh(mesh):
            found = kernels_in_step(net, batch, on_tpu)
        out["parallel_wrapper_data4"] = dict(
            dispatch=sorted(set(log.impls)),
            max_rel_dev=parity(losses, "ParallelWrapper(data=4)"),
            lowered_step=found, bytes_in_use=on_four(net, batch))
        del wrapper, batch

        # dp=2 x tp=2 forward through the serving backend, against
        # the same (fresh, seeded) model's own one-device output()
        net = MultiLayerNetwork(lm_conf(size, seed)).init()
        x = np.asarray(ds.features[:, :size["predict_seq"]])
        want = np.asarray(net.output(x), "float32")
        with dispatch_log() as log:
            got = np.asarray(TensorParallelModel(
                net, "dp=2,tp=2", devs).output(x), "float32")
        # Next-token probabilities of an untrained 8-layer model. On a
        # TPU the head- and row-sharded matmuls accumulate in another
        # order, which flips some bf16 operand roundings (see
        # DP_PARITY_RTOL), and eight layers compound the flips:
        # measured 3.2e-3 absolute on four v5e chips (PR 21). A
        # mis-sharded head or a missing all-reduce changes the
        # probabilities by their own size, so the bar is a quarter of
        # the largest one (CPU, f32: 1e-5 of it).
        dev = float(np.max(np.abs(got - want)))
        bar = float(want.max()) * (2 ** -2 if on_tpu else 1e-5)
        check(got.shape == want.shape and np.isfinite(got).all()
              and dev <= bar,
              f"dp=2,tp=2 forward is {dev} from the one-device "
              f"output(); bar {bar}")
        out["tp_backend_dp2_tp2"] = {
            "dispatch": sorted(set(log.impls)),
            "max_abs_dev": dev, "bar": bar,
            "max_probability": float(want.max()),
            "matches_one_device_output": True}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the sharded phase and what it is "
                         "compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="run every phase at a tiny size on whatever "
                         "backend jax has; never prints \"ok\": true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    size = TINY if args.rehearse else FULL

    phases = Phases()
    device = phases.run("device", lambda: phase_device(args.rehearse))
    on_tpu = device["platform"] == "tpu"
    t0 = time.perf_counter()
    if args.chips == 4:
        phases.run("sharded", lambda: phase_sharded(
            size, args.seed, phases.stats, on_tpu))
    else:
        keep = {}
        phases.run("train", lambda: phase_train(
            size, args.seed, phases.stats, on_tpu, keep))
        with tempfile.TemporaryDirectory(
                prefix="chip_smoke_") as workdir:
            phases.run("serve", lambda: phase_serve(
                size, args.seed, keep, workdir))
    s = phases.stats.summary()
    emit({"total_seconds": round(time.perf_counter() - t0, 2),
          "compile_seconds": s["compile_secs"],
          "backend_compiles": s["backend_compiles"],
          "cold_compiles": s["cold_compiles"],
          "cache_requests": s["cache_requests"],
          "persistent_cache_hits": s["persistent_cache_hits"],
          "cache_hit": s["cache_hit"],
          "compile_cache_dir": device["compile_cache_dir"]})
    result = {"ok": on_tpu and not args.rehearse,
              "device": {k: device[k]
                         for k in ("platform", "kind", "count")}}
    if args.rehearse:
        result["rehearsal"] = "every phase passed at the tiny size"
    emit(result)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
