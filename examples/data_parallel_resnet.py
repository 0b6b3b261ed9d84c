"""Data-parallel ResNet50 over a device mesh.

The BASELINE.json headline workload: zoo ResNet50 trained via the
ParallelWrapper equivalent — batch sharded over the mesh's 'data'
axis, gradient all-reduce inserted by XLA over ICI. Runs on however
many devices are available (single chip included; for a virtual
multi-device run: XLA_FLAGS=--xla_force_host_platform_device_count=8
JAX_PLATFORMS=cpu).

Run: python examples/data_parallel_resnet.py [--img 64] [--steps 10]
"""

import os
import sys

# allow running straight from a repo checkout
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import argparse

import jax

import numpy as np

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import ListDataSetIterator
from deeplearning4j_tpu.nn.conf import updaters
from deeplearning4j_tpu.parallel.mesh import MeshSpec, build_mesh
from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
from deeplearning4j_tpu.train.listeners import PerformanceListener
from deeplearning4j_tpu.zoo import ResNet50


def main(img=64, batch_per_device=8, steps=10, n_classes=100):
    n_dev = jax.device_count()
    mesh = build_mesh(MeshSpec(data=n_dev))
    print(f"{n_dev} devices, mesh {dict(mesh.shape)}")

    net = ResNet50(n_classes=n_classes, input_shape=(img, img, 3),
                   updater=updaters.nesterovs(0.1, 0.9)).init()
    rng = np.random.default_rng(0)
    batch = batch_per_device * n_dev
    x = rng.normal(0, 1, (batch, img, img, 3)).astype("float32")
    y = np.eye(n_classes, dtype="float32")[
        rng.integers(0, n_classes, batch)]

    net.set_listeners(PerformanceListener(frequency=2))
    pw = ParallelWrapper(net, mesh, prefetch_buffer=2)
    pw.fit(ListDataSetIterator([DataSet(x, y)] * steps), epochs=1)
    print(f"final loss {float(net.score_value):.4f} after "
          f"{net.iteration_count} steps")


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--img", type=int, default=64)
    p.add_argument("--steps", type=int, default=10)
    args = p.parse_args()
    main(img=args.img, steps=args.steps)
