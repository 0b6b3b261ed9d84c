"""LeNet on MNIST — the framework's hello-world.

Mirrors the reference's canonical LeNet example: config DSL →
MultiLayerNetwork → fit with listeners → evaluate → checkpoint →
reload. Uses real MNIST if cached locally, a deterministic synthetic
surrogate otherwise.

Run: python examples/lenet_mnist.py [--epochs 3] [--batch 128]
"""

import os
import sys

# allow running straight from a repo checkout
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import argparse

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.data.fetchers import MnistDataSetIterator
from deeplearning4j_tpu.data.iterators import AsyncDataSetIterator
from deeplearning4j_tpu.nn.conf import updaters
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (ConvolutionLayer, DenseLayer,
                                               OutputLayer,
                                               SubsamplingLayer)
from deeplearning4j_tpu.train.listeners import (PerformanceListener,
                                                ScoreIterationListener)
from deeplearning4j_tpu.util.model_serializer import (restore_model,
                                                      write_model)


def main(epochs=3, batch=128, n_train=4096, out="/tmp/lenet.zip"):
    conf = (NeuralNetConfiguration.builder()
            .set_seed(12345)
            .updater(updaters.adam(2e-3))
            .list()
            .layer(ConvolutionLayer(n_out=20, kernel=(5, 5),
                                    activation="relu"))
            .layer(SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
            .layer(ConvolutionLayer(n_out=50, kernel=(5, 5),
                                    activation="relu"))
            .layer(SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
            .layer(DenseLayer(n_out=500, activation="relu"))
            .layer(OutputLayer(n_out=10, loss="mcxent"))
            .set_input_type(InputType.convolutional_flat(28, 28, 1))
            .build())

    net = MultiLayerNetwork(conf).init()
    print(net.summary())
    net.set_listeners(ScoreIterationListener(10),
                      PerformanceListener(frequency=10))

    train = AsyncDataSetIterator(
        MnistDataSetIterator(batch, train=True, n=n_train))
    test = MnistDataSetIterator(256, train=False, n=1024, shuffle=False)

    net.fit(train, epochs=epochs)
    ev = net.evaluate(test)
    print(ev.stats())

    write_model(net, out)
    reloaded = restore_model(out)
    print(f"checkpoint round trip OK: "
          f"{reloaded.evaluate(test).accuracy():.4f} accuracy")
    return ev.accuracy()


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--batch", type=int, default=128)
    args = p.parse_args()
    main(args.epochs, args.batch)
