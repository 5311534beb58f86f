"""Sentiment classifier with a sharded embedding table
(≙ reference ``examples/sentiment_classifier.py``, which used
PartitionedPS to shard its embedding).

The embedding is the sparse/sharded path: under ``PartitionedPS`` or
``Parallax`` its rows are split across the data axis and synchronized
with the sparse gather/scatter lowering; the dense classifier head is
replicated.

    python examples/sentiment_classifier.py --steps 30
"""
import argparse

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import jax
import jax.numpy as jnp
import numpy as np
import optax

from autodist_tpu import AutoDist, Trainable


def make_trainable(vocab_size=20_000, embed_dim=64, hidden=64, seq_len=64):
    rng = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(rng, 3)
    params = {
        "embedding": jax.random.normal(k1, (vocab_size, embed_dim)) * 0.05,
        "dense": {"w": jax.random.normal(k2, (embed_dim, hidden)) * 0.1,
                  "b": jnp.zeros((hidden,))},
        "head": {"w": jax.random.normal(k3, (hidden, 2)) * 0.1,
                 "b": jnp.zeros((2,))},
    }

    def loss_fn(p, batch):
        emb = p["embedding"][batch["tokens"]]          # [B, L, E] gather
        pooled = emb.mean(axis=1)                      # mean-pool
        h = jax.nn.relu(pooled @ p["dense"]["w"] + p["dense"]["b"])
        logits = h @ p["head"]["w"] + p["head"]["b"]
        labels = batch["labels"]
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
        acc = (logits.argmax(-1) == labels).mean()
        return loss, {"accuracy": acc}

    return Trainable.from_loss_fn(loss_fn, params, optax.adagrad(0.1),
                                  sparse_params=("embedding",),
                                  name="sentiment")


def main():
    from autodist_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--strategy", default="PartitionedPS")
    ap.add_argument("--vocab-size", type=int, default=20_000)
    ap.add_argument("--seq-len", type=int, default=64)
    args = ap.parse_args()

    trainable = make_trainable(vocab_size=args.vocab_size,
                               seq_len=args.seq_len)
    runner = AutoDist({}, args.strategy).build(trainable)

    rng = np.random.RandomState(0)
    for step in range(args.steps):
        tokens = rng.randint(0, args.vocab_size,
                             (args.batch_size, args.seq_len)).astype(np.int32)
        # Synthetic rule: label = parity of the first token.
        labels = (tokens[:, 0] % 2).astype(np.int32)
        metrics = runner.step({"tokens": tokens, "labels": labels})
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step}: loss={float(np.asarray(metrics['loss'])):.4f} "
                  f"acc={float(np.asarray(metrics['accuracy'])):.3f}")


if __name__ == "__main__":
    main()
