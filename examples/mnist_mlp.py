"""MNIST MLP — BASELINE config #1, the PR1 regression anchor.

Reference: ``python/flexflow/examples/native/mnist_mlp.py`` — 784 -> 512 relu
-> 512 relu -> 10 softmax, SGD, sparse categorical crossentropy.

Runs on whatever devices JAX finds (the TPU chip by default on a machine
that has one; the CPU with ``JAX_PLATFORMS=cpu``, or ``--cpu N`` for N
virtual CPU devices).  Uses the real MNIST arrays if an ``mnist.npz`` is
found (no network in this environment), else a deterministic synthetic
stand-in with learnable structure so loss/accuracy trends are meaningful.
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

if "--cpu" in sys.argv:  # e.g. "--cpu 8": run on N virtual CPU devices
    i = sys.argv.index("--cpu")
    n = int(sys.argv[i + 1]) if i + 1 < len(sys.argv) else 8
    from flexflow_tpu.utils.platform import force_cpu

    force_cpu(n)
else:  # persistent compile cache (not for virtual-device collectives)
    from flexflow_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer, losses


def load_mnist():
    for path in ("mnist.npz", "/root/data/mnist.npz"):
        if os.path.exists(path):
            d = np.load(path)
            x = d["x_train"].reshape(-1, 784).astype(np.float32) / 255.0
            y = d["y_train"].astype(np.int32)
            return x, y, "mnist"
    # synthetic fallback: 10 gaussian clusters in 784-d
    rng = np.random.RandomState(42)
    n = 8192
    centers = rng.randn(10, 784).astype(np.float32) * 2.0
    y = rng.randint(0, 10, size=n).astype(np.int32)
    x = centers[y] + rng.randn(n, 784).astype(np.float32)
    return x, y, "synthetic"


def top_level_task():
    cfg = FFConfig.parse_args()
    x_train, y_train, source = load_mnist()
    print(f"dataset: {source}, {len(x_train)} samples")

    model = FFModel(cfg)
    x = model.create_tensor((cfg.batch_size, 784))
    h = model.dense(x, 512, activation="relu")
    h = model.dense(h, 512, activation="relu")
    out = model.softmax(model.dense(h, 10))

    model.compile(
        optimizer=SGDOptimizer(lr=cfg.learning_rate, momentum=0.9),
        loss_type=losses.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=["accuracy", "sparse_categorical_crossentropy"],
    )
    model.fit(x_train, y_train, epochs=cfg.epochs)
    final = model.evaluate(x_train, y_train)
    print(f"final: {final}")
    return final


if __name__ == "__main__":
    top_level_task()
