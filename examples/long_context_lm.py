"""Sequence-parallel language-model training — round-4 features end
to end.

A causal transformer LM built from the config DSL trains over a mesh
whose `seq` axis shards the TIME dimension across devices: the
standard ``ParallelWrapper`` traces the model under the
sequence-parallel context and ``SelfAttentionLayer`` rides ring flash
attention (exact global attention; Pallas kernels per chunk on TPU).
The batch is VARIABLE-LENGTH: key-padding mask chunks rotate around
the ring with their K/V blocks, and the masked loss denominator psums
globally. Training matches the single-device step to float tolerance
— the same property the dryrun regimes 8a–c assert.

Run: python examples/long_context_lm.py [--epochs 20]
(needs >= 4 devices; tests run it on a virtual 4-device CPU mesh)
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax

import numpy as np


def make_net(seed=3):
    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (
        EmbeddingSequenceLayer, RnnOutputLayer,
        TransformerEncoderLayer)
    conf = (NeuralNetConfiguration.builder().set_seed(seed)
            .updater(updaters.adam(1e-2)).list()
            .layer(EmbeddingSequenceLayer(n_in=VOCAB, n_out=16))
            .layer(TransformerEncoderLayer(n_heads=4, causal=True))
            .layer(TransformerEncoderLayer(n_heads=4, causal=True))
            .layer(RnnOutputLayer(n_out=VOCAB, loss="mcxent"))
            .set_input_type(InputType.recurrent(VOCAB, T)).build())
    return MultiLayerNetwork(conf).init()


VOCAB, T, B = 11, 32, 8


def make_data(seed=0):
    """Cyclic-successor LM: token[t+1] = (token[t] + k) mod V with a
    per-sequence stride k the model must infer from context — causal
    attention's bread and butter. Sequences are RAGGED (variable
    length), exercising the rotating mask chunks."""
    rng = np.random.default_rng(seed)
    toks = np.zeros((B, T), np.int64)
    for b in range(B):
        k = rng.integers(1, 4)
        toks[b, 0] = rng.integers(0, VOCAB)
        for t in range(1, T):
            toks[b, t] = (toks[b, t - 1] + k) % VOCAB
    x = toks.astype("float32")           # int ids -> embedding layer
    y = np.eye(VOCAB, dtype="float32")[np.roll(toks, -1, axis=1)]
    mask = np.ones((B, T), np.float32)
    lengths = rng.integers(T // 2, T, B)   # ragged, < T: the final
    for b in range(B):                     # position never has a
        mask[b, lengths[b]:] = 0.0         # next-token target anyway
    return x, y, mask


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=20)
    args = ap.parse_args()
    epochs = max(2, args.epochs)     # need >=2 to show loss movement

    if jax.device_count() < 4:
        raise SystemExit("needs >= 4 devices (set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=4)")

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import ListDataSetIterator
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, build_mesh
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    x, y, mask = make_data()
    ds = DataSet(x, y, mask, mask)

    mesh = build_mesh(MeshSpec(data=2, seq=2), jax.devices()[:4])
    print(f"mesh: data=2 x seq=2 over {mesh.devices.size} devices — "
          f"T={T} sharded 2-way, ragged lengths "
          f"{[int(mask[b].sum()) for b in range(B)]}")

    net = make_net()
    pw = ParallelWrapper(net, mesh, prefetch_buffer=0)
    pw.fit(ListDataSetIterator([ds]), epochs=1)
    first = float(net.score_value)
    pw.fit(ListDataSetIterator([ds]), epochs=epochs - 1)
    last = float(net.score_value)
    print(f"seq-parallel masked LM loss: {first:.3f} -> {last:.3f}")

    # the headline property: identical to the single-device step
    single = make_net()
    for _ in range(epochs):
        single.fit(ds)
    same = np.allclose(np.asarray(net.params_flat()),
                       np.asarray(single.params_flat()),
                       rtol=2e-4, atol=2e-5)
    print(f"matches single-device params: {same}")
    if not same or not last < first:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
