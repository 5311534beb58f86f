"""Minimal end-to-end example (≙ reference ``examples/linear_regression.py``).

Train a linear model with the default strategy on whatever devices are
visible::

    python examples/linear_regression.py --steps 50
"""
import argparse

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import numpy as np
import optax

from autodist_tpu import AutoDist
from autodist_tpu.models.cnn import make_linear_regression_trainable


def main():
    from autodist_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--strategy", default="AllReduce")
    args = ap.parse_args()

    trainable = make_linear_regression_trainable(optax.sgd(0.1), dim=13)
    ad = AutoDist({}, args.strategy)
    runner = ad.build(trainable)

    rng = np.random.RandomState(0)
    true_w = rng.randn(13, 1)
    for step in range(args.steps):
        x = rng.randn(args.batch_size, 13).astype(np.float32)
        y = (x @ true_w + 0.01 * rng.randn(args.batch_size, 1)).astype(np.float32)
        metrics = runner.step({"x": x, "y": y})
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step}: loss={float(np.asarray(metrics['loss'])):.5f}")


if __name__ == "__main__":
    main()
