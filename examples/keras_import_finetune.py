"""Import a Keras model and fine-tune it with transfer learning.

Mirrors the reference's modelimport + transfer-learning workflow:
KerasModelImport → freeze feature extractor → replace head → fit.
Builds a small Keras model on the fly (keras must be installed) so the
example is self-contained.

Run: python examples/keras_import_finetune.py
"""

import os
import sys

# allow running straight from a repo checkout
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def main(h5_path="/tmp/keras_base.h5"):
    os.environ.setdefault("KERAS_BACKEND", "tensorflow")
    import keras
    from keras import layers

    # 1. a "pretrained" Keras model
    km = keras.Sequential([
        keras.Input((4,)),
        layers.Dense(16, activation="relu"),
        layers.Dense(8, activation="relu"),
        layers.Dense(3, activation="softmax"),
    ])
    km.save(h5_path)

    # 2. import
    from deeplearning4j_tpu.keras import import_keras_model_and_weights
    net = import_keras_model_and_weights(h5_path)
    print("imported:")
    print(net.summary())

    # 3. verify parity with Keras on the same inputs
    x = np.random.default_rng(0).normal(0, 1, (4, 4)).astype("float32")
    diff = np.abs(km.predict(x, verbose=0)
                  - np.asarray(net.output(x))).max()
    print(f"max |keras - ours| = {diff:.2e}")

    # 4. freeze the feature extractor, new head, fine-tune
    from deeplearning4j_tpu.data.fetchers import iris_data
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.layers import OutputLayer
    from deeplearning4j_tpu.nn.transfer_learning import (
        FineTuneConfiguration, TransferLearning)
    tuned = (TransferLearning.builder(net)
             .fine_tune_configuration(
                 FineTuneConfiguration(updater=updaters.adam(0.02)))
             .set_feature_extractor(1)
             .remove_output_layer()
             .add_layer(OutputLayer(n_out=3))
             .build())
    xs, ys = iris_data()
    tuned.fit(xs[:120], ys[:120], epochs=30, batch_size=32)
    acc = tuned.evaluate(xs[120:], ys[120:]).accuracy()
    print(f"fine-tuned accuracy: {acc:.3f}")
    return acc


if __name__ == "__main__":
    main()
