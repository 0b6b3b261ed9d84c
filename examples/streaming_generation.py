"""Streaming decode + autoregressive generation — round-5 features
end to end.

A small character LM (embedding + causal transformer blocks) trains
briefly, then generates text two ways and checks they agree:

1. the eager ``rnn_time_step`` path (reference rnnTimeStep contract,
   MultiLayerNetwork.java:2656 — concat-grown KV cache, a Python
   dispatch per token);
2. the TPU-first ``streaming_session``: fixed-capacity KV caches
   updated in place, ONE compiled executable per chunk length, and
   ``generate()`` sampling on device arrays with no per-token host
   sync.

Run: python examples/streaming_generation.py [--epochs 3]
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
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--gen-tokens", type=int, default=24)
    args = ap.parse_args()

    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (
        EmbeddingSequenceLayer, RnnOutputLayer,
        TransformerEncoderLayer)

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

    # next-char batches
    rng = np.random.default_rng(0)
    starts = rng.integers(0, len(ids) - T - 1, 256)
    x = np.stack([ids[s:s + T] for s in starts]).astype(np.float32)
    y = np.eye(V, dtype=np.float32)[
        np.stack([ids[s + 1:s + T + 1] for s in starts])]
    for epoch in range(args.epochs):
        for b in range(0, len(x), args.batch):
            net.fit(DataSet(x[b:b + args.batch], y[b:b + args.batch]))
        print(f"epoch {epoch}: loss {float(net.score_value):.4f}")

    prompt_txt = "the quick"
    prompt = np.array([[idx[c] for c in prompt_txt]], np.int32)
    n = args.gen_tokens
    cap = prompt.shape[1] + n

    # 1. TPU-first: bounded session + device-side greedy sampling
    # (step-by-step here so per-step probabilities are observable;
    # sess.generate(prompt, n) / generate(..., fused=True) wrap the
    # same loop in one call / one XLA program)
    sess = net.streaming_session(capacity=cap, batch=1)
    p = np.asarray(sess.step(prompt[:, :, None].astype(np.float32)))
    last = p[:, -1]
    gen, probs_fast = [], []
    for _ in range(n):
        probs_fast.append(last[0])
        nxt = last.argmax(axis=-1)
        gen.append(int(nxt[0]))
        last = np.asarray(sess.step(
            nxt[:, None, None].astype(np.float32)))[:, 0]
    text_fast = "".join(chars[i] for i in gen)

    # fused: the whole decode as ONE XLA program — same computation
    # path as the stepped loop, so ids match exactly
    sess.reset()
    ids_f = np.asarray(sess.generate(prompt, n, fused=True))[0]
    assert list(ids_f) == gen, "fused generate diverged"
    print("fused single-program generate matches stepped loop OK")

    # 2. eager reference: rnn_time_step + host argmax per token
    net.rnn_clear_previous_state()
    probs = np.asarray(net.rnn_time_step(
        prompt[:, :, None].astype(np.float32)))
    last = probs[:, -1]
    out, probs_eager = [], []
    for _ in range(n):
        probs_eager.append(last[0])
        nxt = last.argmax(axis=-1)
        out.append(int(nxt[0]))
        last = np.asarray(net.rnn_time_step(
            nxt[:, None, None].astype(np.float32)))[:, 0]
    text_eager = "".join(chars[i] for i in out)

    print(f"prompt: {prompt_txt!r}")
    print(f"generated (bounded session): {text_fast!r}")
    print(f"generated (eager reference): {text_eager!r}")
    # the two paths reduce attention in different orders; a near-tied
    # argmax may legitimately flip one character and diverge after it,
    # so the asserted contract is the per-step probabilities up to the
    # first divergence, not a 24-token exact id chain
    if text_fast != text_eager:
        k = next(i for i, (a, b) in
                 enumerate(zip(text_fast, text_eager)) if a != b)
        np.testing.assert_allclose(probs_fast[k], probs_eager[k],
                                   atol=1e-4)
        print(f"paths diverged at a float-tied step {k} "
              "(probabilities equal to 1e-4) — OK")
    print("bounded session matches eager decode OK")
    print(f"compiled executables: "
          f"{sorted(sess._step_cache)} (prefill + decode)")


if __name__ == "__main__":
    main()
