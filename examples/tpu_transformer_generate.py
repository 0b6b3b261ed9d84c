"""Transformer-LM streaming generation, traced, on whatever backend
jax gives this process.

The framework's front door to the accelerator it is named for: no
probe and no fallback — on a machine with a TPU it runs there, with
``JAX_PLATFORMS=cpu`` it runs on the CPU, and the first line it prints
says which (platform, device kind, device count).

It trains a small character LM briefly with the step profiler attached
(data-wait / dispatch / device-fence decomposition,
observability/step_profile.py), counts every XLA compile and
persistent-cache hit via the process-wide compile watch
(observability/compile_watch.py), streams a generation through the
bounded KV-cache session, and writes a Chrome trace (--trace, open
in Perfetto) of the whole run.

Run: python examples/tpu_transformer_generate.py [--trace trace.json]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

TEXT = ("the quick brown fox jumps over the lazy dog and the cat "
        "sat on the mat while the dog ran in the park ") * 40


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--gen-tokens", type=int, default=24)
    ap.add_argument("--trace", default="tpu_generate_trace.json",
                    help="Chrome trace-event output path")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    print(f"running on platform {dev.platform} ({dev.device_kind}, "
          f"{len(jax.devices())} device(s))")

    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (
        EmbeddingSequenceLayer, RnnOutputLayer,
        TransformerEncoderLayer)
    from deeplearning4j_tpu.observability import (
        ProfilerListener, install_global_watch, startup, trace)

    trace.enable()
    compile_stats = install_global_watch()

    chars = sorted(set(TEXT))
    V = len(chars)
    idx = {c: i for i, c in enumerate(chars)}
    ids = np.array([idx[c] for c in TEXT], np.int32)
    T = args.seq_len

    conf = (NeuralNetConfiguration.builder().set_seed(7)
            .updater(updaters.adam(3e-3)).list()
            .layer(EmbeddingSequenceLayer(n_in=V, n_out=32))
            .layer(TransformerEncoderLayer(n_heads=4, causal=True))
            .layer(TransformerEncoderLayer(n_heads=4, causal=True))
            .layer(RnnOutputLayer(n_out=V, loss="mcxent"))
            .set_input_type(InputType.recurrent(V, T)).build())
    net = MultiLayerNetwork(conf).init()
    profiler = ProfilerListener(frequency=8, report=False)
    net.set_listeners(profiler)

    rng = np.random.default_rng(0)
    starts = rng.integers(0, len(ids) - T - 1, 256)
    x = np.stack([ids[s:s + T] for s in starts]).astype(np.float32)
    y = np.eye(V, dtype=np.float32)[
        np.stack([ids[s + 1:s + T + 1] for s in starts])]
    with trace.span("train"):
        for epoch in range(args.epochs):
            for b in range(0, len(x), args.batch):
                net.fit(DataSet(x[b:b + args.batch],
                                y[b:b + args.batch]))
            print(f"epoch {epoch}: loss {float(net.score_value):.4f}")
    if profiler.reports:
        rep = profiler.reports[-1]
        print("step profile: "
              f"{rep['samples_per_sec']:.0f} samples/sec — "
              f"data_wait {rep['data_wait_ms']:.2f} ms, dispatch "
              f"{rep['dispatch_ms']:.2f} ms, device fence "
              f"{rep['device_fence_ms']:.2f} ms per report window")

    # streaming generation through the bounded KV-cache session; the
    # global compile watch counts its executables (a healthy session
    # compiles prefill + decode ONCE — the summary below shows it)
    prompt_txt = "the quick"
    prompt = np.array([[idx[c] for c in prompt_txt]], np.int32)
    n = args.gen_tokens
    sess = net.streaming_session(capacity=prompt.shape[1] + n, batch=1)
    with trace.span("generate"):
        out_ids = np.asarray(sess.generate(prompt, n))[0]
    text = "".join(chars[i] for i in out_ids)
    print(f"prompt: {prompt_txt!r}")
    print(f"generated: {text!r}")
    print(f"decode executables compiled for chunk lengths: "
          f"{sorted(sess._step_cache)}")

    s = compile_stats.summary()
    print(f"compile watch: {s['backend_compiles']} backend compile "
          f"events ({s['cold_compiles']} cold), "
          f"{s['compile_secs']:.1f}s in them, {s['trace_secs']:.1f}s "
          f"tracing, {s['lower_secs']:.1f}s lowering, persistent cache "
          f"hits {s['persistent_cache_hits']}/{s['cache_requests']}")
    n_ev = trace.export_chrome_trace(args.trace, also=(startup,))
    trace.disable()
    print(f"trace written: {args.trace} ({n_ev} events) — open in "
          "Perfetto / chrome://tracing")


if __name__ == "__main__":
    main()
