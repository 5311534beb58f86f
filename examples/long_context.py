"""Long-context training with sequence parallelism (beyond reference
parity — the reference never sharded the sequence dimension, SURVEY.md
§5.7).

Shards the token dimension over a ``seq`` mesh axis: ring attention
rotates k/v blocks so every token attends globally while activation
memory per device scales as O(L/seq).  Positions come from
``sequence.global_positions`` so shards embed their true offsets::

    python examples/long_context.py --seq-len 2048 --seq-parallel 4
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    from autodist_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--seq-parallel", type=int, default=None,
                    help="seq-axis size (default: all devices)")
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--flash", action="store_true",
                    help="Pallas flash kernel per ring chunk")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import flax.linen as nn
    import optax
    from jax.sharding import Mesh

    from autodist_tpu.capture import Trainable
    from autodist_tpu.parallel.ring_attention import ring_self_attention
    from autodist_tpu.parallel.sequence import (global_positions,
                                                lower_sequence_parallel)

    n = len(jax.devices())
    sp = args.seq_parallel or n
    dp = n // sp
    if dp * sp != n:
        raise SystemExit(f"{n} devices != data {dp} x seq {sp}")
    axes = ("data", "seq") if dp > 1 else ("seq",)
    shape = (dp, sp) if dp > 1 else (sp,)
    mesh = Mesh(np.array(jax.devices()).reshape(shape), axes)
    H, L, V = args.hidden, args.seq_len, 1024
    heads = 4

    class Block(nn.Module):
        sharded: bool = True

        @nn.compact
        def __call__(self, x):
            B, Ll, _ = x.shape
            qkv = nn.Dense(3 * H, name="qkv")(x).reshape(
                B, Ll, 3, heads, H // heads)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            if self.sharded:
                if args.flash:
                    from autodist_tpu.parallel.ring_attention import (
                        ring_flash_attention)
                    o = ring_flash_attention(q, k, v, axis_name="seq",
                                             causal=True)
                else:
                    o = ring_self_attention(q, k, v, axis_name="seq",
                                            causal=True)
            else:  # init-time trace outside the mesh
                s = jnp.einsum("blhd,bmhd->bhlm", q, k) / np.sqrt(
                    H // heads)
                mask = jnp.tril(jnp.ones((Ll, Ll), bool))
                s = jnp.where(mask[None, None], s, -1e30)
                o = jnp.einsum("bhlm,bmhd->blhd",
                               jax.nn.softmax(s, axis=-1), v)
            o = o.reshape(B, Ll, H)
            x = nn.LayerNorm()(x + nn.Dense(H, name="out")(o))
            h = nn.gelu(nn.Dense(4 * H, name="wi")(x))
            return nn.LayerNorm()(x + nn.Dense(H, name="wo")(h))

    class LM(nn.Module):
        # Positions are pluggable: plain arange at init time (outside the
        # mesh), shard-aware global_positions inside the sharded step.
        sharded: bool = True

        @nn.compact
        def __call__(self, tokens):
            B, Ll = tokens.shape
            embed = nn.Embed(V, H, name="embed")
            pos = self.param("pos", nn.initializers.normal(0.02), (L, H))
            ids = global_positions(Ll) if self.sharded else jnp.arange(Ll)
            x = embed(tokens) + pos[ids]
            for i in range(args.layers):
                x = Block(sharded=self.sharded, name=f"layer_{i}")(x)
            return embed.attend(x)

    model = LM(sharded=True)

    def loss_fn(p, batch):
        logits = model.apply({"params": p}, batch["x"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, batch["y"][..., None], axis=-1)
        return -jnp.mean(ll)

    # Init outside the mesh with the unsharded variant (same params).
    params = LM(sharded=False).init(
        jax.random.PRNGKey(0), jnp.zeros((2, L), jnp.int32))["params"]
    trainable = Trainable.from_loss_fn(loss_fn, params, optax.adamw(3e-4))

    init_fn, step_fn, _ = lower_sequence_parallel(trainable, mesh)
    state = init_fn(params, None)
    rng = np.random.RandomState(0)

    def batch(_):
        x = rng.randint(0, V, (args.batch_size, L)).astype(np.int32)
        return {"x": x, "y": np.roll(x, -1, axis=1)}

    state, m = step_fn(state, batch(0), jax.random.PRNGKey(0))  # compile
    float(np.asarray(m["loss"]))
    t0 = time.perf_counter()
    for i in range(args.steps):
        state, m = step_fn(state, batch(i), jax.random.PRNGKey(i))
    loss = float(np.asarray(m["loss"]))
    dt = time.perf_counter() - t0
    tokens_per_sec = args.batch_size * L * args.steps / dt
    print(f"long-context: seq={L} dp={dp} sp={sp} "
          f"attn={'flash' if args.flash else 'einsum'} "
          f"loss={loss:.4f} tokens/s={tokens_per_sec:,.0f}")


if __name__ == "__main__":
    main()
