"""lm1b-style word language model with sampled softmax
(≙ reference ``examples/lm1b/lm1b_train.py``), Parallax hybrid strategy:
dense LSTM weights go over allreduce, the embedding and softmax tables
take the sharded sparse path.

    python examples/lm1b_train.py --steps 20
"""
import argparse

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import jax
import numpy as np
import optax

from autodist_tpu import AutoDist
from autodist_tpu.models.lm1b import make_lm1b_trainable


def main():
    from autodist_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--seq-len", type=int, default=20)
    ap.add_argument("--vocab-size", type=int, default=10_000)
    ap.add_argument("--strategy", default="Parallax")
    ap.add_argument("--data", default=None,
                    help="flat binary int32 token file (native mmap "
                         "reader); default: synthetic tokens")
    args = ap.parse_args()

    trainable = make_lm1b_trainable(
        optax.adagrad(0.2), jax.random.PRNGKey(0),
        vocab_size=args.vocab_size, seq_len=args.seq_len,
        batch_size=args.batch_size)
    runner = AutoDist({}, args.strategy).build(trainable)

    if args.data:
        from autodist_tpu.data import lm_window_loader
        # The embedding gather clamps out-of-range ids silently; scan the
        # whole file's max once up front (a streaming pass over the mmap)
        # so a bad id in ANY window fails loudly, not just step 0's.
        mm = np.memmap(args.data, dtype=np.int32, mode="r")
        hi = int(mm.max()) if len(mm) else 0
        del mm
        if hi >= args.vocab_size:
            raise SystemExit(
                f"--data contains token id {hi} >= --vocab-size "
                f"{args.vocab_size}; pass the tokenizer's size")
        source = lm_window_loader(args.data, batch_size=args.batch_size,
                                  seq_len=args.seq_len, seed=0)
    else:
        rng = np.random.RandomState(0)

        def source(step):
            x = rng.randint(0, args.vocab_size,
                            (args.batch_size, args.seq_len)).astype(np.int32)
            return {"x": x, "y": np.roll(x, -1, axis=1)}

    for step in range(args.steps):
        metrics = runner.step(source(step))
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step}: loss={float(np.asarray(metrics['loss'])):.4f}")


if __name__ == "__main__":
    main()
