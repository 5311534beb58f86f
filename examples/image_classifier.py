"""MNIST-scale CNN, data-parallel (≙ reference ``examples/image_classifier.py``).

Runs on synthetic MNIST-shaped data (no dataset downloads in this image)::

    python examples/image_classifier.py --steps 30
"""
import argparse

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import jax
import numpy as np
import optax

from autodist_tpu import AutoDist
from autodist_tpu.models.cnn import make_cnn_trainable


def main():
    from autodist_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--strategy", default="AllReduce")
    args = ap.parse_args()

    trainable = make_cnn_trainable(optax.adam(1e-3), jax.random.PRNGKey(0))
    runner = AutoDist({}, args.strategy).build(trainable)

    rng = np.random.RandomState(0)
    for step in range(args.steps):
        x = rng.rand(args.batch_size, 28, 28, 1).astype(np.float32)
        y = rng.randint(0, 10, (args.batch_size,)).astype(np.int32)
        metrics = runner.step({"x": x, "y": y})
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step}: loss={float(np.asarray(metrics['loss'])):.4f}")


if __name__ == "__main__":
    main()
