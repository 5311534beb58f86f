"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py

drives the main path once, through the entry points a user would call,
in ONE process (a chip belongs to one process at a time), at the full
width of the models the benchmark modes use — depth as published,
weights random from a seed:

1. **training** — ``bert.bert_base`` (12 layers, hidden 768, vocab
   30522), seq 512, 16 examples per chip, 76 masked positions, adamw
   with a bf16 first moment: ``make_mlm_trainable`` →
   ``AutoDist(ResourceSpec({}), AllReduce(chunk_size=256)).build`` →
   three ``runner.step`` calls and a k=4 ``runner.run_steps`` window,
   over every visible chip (``ResourceSpec({})`` resolves to
   ``{"data": n}``);
2. **serving** — 8 layers, hidden 1024, 16 heads, vocab 32768, bf16:
   ``ServingEngine`` → ``ContinuousBatcher`` → 8 requests of mixed
   prompt length, dense and paged KV, composed attention (the dense
   engine's default election, the fused decode kernel, runs in 4);
3. **parity** — the step-0 training loss and the first decoded token's
   logits against a float32 evaluation of the same functions on the
   host CPU;
4. **kernels** — every Pallas kernel compiled through Mosaic
   (``interpret=False``) against the composed path it replaces, both on
   the chip, then once through the engine; and what a mixed stack's
   decode step runs beside attention, at the widths of the benchmark's
   ``qwen3-next-80b-a3b`` cell, against its composed form on the chip:
   the recurrent state's update (one position; a window through the
   chunked form) and the routed layer (sorted pairs through the grouped
   matmul, 64 of 512 experts held, 10 a token: two ``ragged_dot`` and,
   at a decode step's rows, this repo's grouped-matmul kernel); and a
   latent-attention layer's absorbed decode step over cached rows
   against its expanded form over the same window, at the widths of the
   benchmark's ``deepseek-v2-lite`` cell (16 heads, a row of 512 + 64,
   bf16), with that cell's routed layer (8 of 64 experts held, 6 a
   token) the same two ways;
5. **multi-chip** (when ``jax.device_count() > 1``) — the six lowering
   programs of ``__graft_entry__``, the ring kernels over real ICI,
   tensor-parallel serving, and two one-chip engines on two chips.

It exits non-zero when jax's backend is not a TPU, when any phase
raises, or when any check fails; nothing below catches a phase's
failure.  On success the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Compile
seconds (the first call) and run seconds (later calls) are printed
apart, for the record only — they are not metrics.

The phases are functions of their sizes so that tier-1
(``tests/unit/test_chip_smoke.py``) drives the same code at toy width on
the simulated CPU mesh; only ``__main__`` needs the chip.

Tolerances (bf16 on the MXU against float32 on the host; chosen for the
TPU's default matmul precision, not copied from the CPU goldens, which
run at ``highest``):

* step-0 loss: ``5e-3`` absolute on a loss of ~10.8 (2^-11 relative).
  The logits are bf16 products with float32 accumulation and the loss
  is a float32 mean over 16 x 76 log-softmax terms, so bf16's 2^-8 unit
  roundoff averages down; a path that rounds the loss or the softmax
  itself to bf16 lands at 2^-8 x 10.8 = 4e-2 and fails.
* first-token logits: ``2^-5`` of the largest host logit, absolute —
  one bf16 roundoff (2^-8) per layer of the 8-layer stack; the token
  the engine emitted must be within twice that of the host's best.
* attention kernels against the composed bf16 path: ``2^-5`` of the
  reference's largest magnitude forward, ``2^-4`` backward (both sides
  round to bf16 between their matmuls, in different places).
* the recurrent state's update against the recurrence in einsums at
  ``highest``: ``1e-5`` of the largest magnitude for one position (both
  float32, sums in another order), ``1e-4`` through the chunked form
  (its matmuls run at ``highest``; the solved chunk adds a few float32
  roundings a position).  The routed layer against every held expert
  over every row: ``2e-2`` (bf16 products on both sides, summed in
  another order over 10 experts).
* ring hop kernels: the scale bit-equal, levels within one (a value on
  a rounding boundary may land on either side); ring collectives
  against the exact float32 collective: ``2^-5`` of its largest
  magnitude (at most four int8 roundings of 1/254 each).
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import sys
import time

LOSS_TOL = 5e-3
LOGIT_RTOL = 2.0 ** -5
ATTN_FWD_RTOL = 2.0 ** -5
ATTN_BWD_RTOL = 2.0 ** -4
RING_RTOL = 2.0 ** -5


class SmokeFailure(RuntimeError):
    """A check's observed value is outside what the smoke accepts."""


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def require(cond, phase: str, what: str, observed) -> None:
    """Print one check with its observed value; raise if it failed."""
    say(phase, f"check {what}: {observed} -> {'ok' if cond else 'FAILED'}")
    if not cond:
        raise SmokeFailure(f"{phase}: {what}: {observed}")


def timed(fn):
    """``(result, seconds)`` of ``fn()``; the caller's ``fn`` ends in a
    host fetch, so the seconds include the device's work."""
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def devices_of(tree) -> set:
    import jax

    return {s.device for leaf in jax.tree.leaves(tree)
            for s in leaf.addressable_shards}


def max_err(got, ref):
    """``(max |got - ref|, max |ref|)`` in float32 on the host."""
    import numpy as np

    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref))), float(np.max(np.abs(ref)))


def require_close(phase, what, got, ref, rtol) -> None:
    err, scale = max_err(got, ref)
    require(err <= rtol * scale, phase, what,
            f"max|err|={err:.3g} vs tol={rtol * scale:.3g} "
            f"(max|ref|={scale:.3g})")


# --------------------------------------------------------------------------- #
# 1. training
# --------------------------------------------------------------------------- #
def training_phase(cfg, *, resource_spec, batch_per_device: int,
                   seq_len: int, num_masked: int, window: int = 4,
                   platform: str = "tpu", seed: int = 0) -> dict:
    """BERT MLM through ``AutoDist(...).build`` → ``step`` x3 →
    ``run_steps`` (k=``window``) on a repeated batch, then the step-0
    loss against float32 on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from autodist_tpu import AllReduce, AutoDist, stack_steps
    from autodist_tpu.models import bert
    from autodist_tpu.resource import ResourceSpec

    ph = "train"
    rs = ResourceSpec(resource_spec)
    n = rs.num_devices()
    say(ph, f"layers={cfg.num_layers} hidden={cfg.hidden_size} "
            f"heads={cfg.num_heads} vocab={cfg.vocab_size} seq={seq_len} "
            f"batch={batch_per_device}x{n} masked={num_masked} "
            f"AllReduce(chunk_size=256) mesh={rs.resolved_mesh_shape()}")
    trainable, init_s = timed(lambda: bert.make_mlm_trainable(
        cfg, optax.adamw(1e-4, weight_decay=0.01, mu_dtype=jnp.bfloat16),
        jax.random.PRNGKey(seed), batch_size=2, seq_len=seq_len,
        num_masked=num_masked, with_input_mask=False))
    params0 = jax.tree.map(np.asarray, trainable.params)   # host copy
    runner, build_s = timed(
        lambda: AutoDist(rs, AllReduce(chunk_size=256)).build(trainable))
    say(ph, f"init_s={init_s:.1f} build_s={build_s:.1f}")
    batch = bert.synthetic_mlm_batch(seed, batch_per_device * n, seq_len,
                                     num_masked, cfg.vocab_size)
    batch.pop("input_mask", None)      # unpadded: no mask pass on scores

    def loss_of(metrics):
        return np.asarray(metrics["loss"], np.float32)

    step_losses, step_s = [], []
    for _ in range(3):
        loss, dt = timed(lambda: float(loss_of(runner.step(batch))))
        step_losses.append(loss)
        step_s.append(dt)
    say(ph, f"step: compile_s={step_s[0]:.2f} (first call) "
            f"run_s={[round(t, 3) for t in step_s[1:]]}")
    stacked = runner.place_steps(stack_steps([batch] * window))
    w1, w1_s = timed(lambda: loss_of(runner.run_steps(stacked)))
    w2, w2_s = timed(lambda: loss_of(runner.run_steps(stacked)))
    say(ph, f"run_steps(k={window}): compile_s={w1_s:.2f} (first call) "
            f"run_s={w2_s:.3f}")
    losses = np.concatenate([step_losses, w1, w2])
    require(bool(np.isfinite(losses).all()), ph, "losses finite",
            [round(float(x), 4) for x in losses])
    require(w1.shape == (window,), ph, f"run_steps returns k={window} losses",
            w1.shape)
    require(float(w1[-1]) < step_losses[0], ph,
            "loss after the window below step 0 (repeated batch)",
            f"{float(w1[-1]):.4f} < {step_losses[0]:.4f}")
    pdevs = devices_of(runner.state["params"])
    require({d.platform for d in pdevs} == {platform}, ph,
            f"params live on {platform} devices", sorted(map(str, pdevs)))
    if n > 1:
        require(len(devices_of(stacked)) == n, ph,
                f"batch spread over {n} distinct devices",
                len(devices_of(stacked)))
        odevs = devices_of(runner.state["opt_state"])
        require(len(odevs) == n and len(pdevs) == n, ph,
                f"optimizer state and params on {n} distinct devices",
                (len(odevs), len(pdevs)))
    runner.close()

    # ---- not silently wrong: the same loss in float32 on the host ------
    cpu = jax.devices("cpu")[0]
    model32 = bert.BertModel(dataclasses.replace(cfg, dtype=jnp.float32))

    def host_loss():
        with jax.default_device(cpu):
            def loss32(p, b):
                logits, bias = model32.apply({"params": p}, b,
                                             deterministic=True)
                return bert.mlm_loss_head(logits, b, bias)[0]

            f = jax.jit(loss32)
            return float(f(jax.device_put(params0, cpu),
                           jax.device_put(batch, cpu)))

    ref, host_s = timed(host_loss)
    require(abs(step_losses[0] - ref) <= LOSS_TOL, ph,
            "step-0 loss vs host float32",
            f"|{step_losses[0]:.5f} - {ref:.5f}| = "
            f"{abs(step_losses[0] - ref):.2g} <= {LOSS_TOL} "
            f"(host_s={host_s:.1f})")
    return {"devices": n, "losses": [float(x) for x in losses]}


# --------------------------------------------------------------------------- #
# 2. serving
# --------------------------------------------------------------------------- #
def make_prompts(vocab_size: int, prefill_len: int, count: int = 8,
                 seed: int = 1) -> list:
    """``count`` prompts of mixed length in ``[1, prefill_len]``; the
    first is the full bucket (the parity check reads it)."""
    import numpy as np

    r = np.random.RandomState(seed)
    lens = [prefill_len] + [int(r.randint(1, prefill_len + 1))
                            for _ in range(count - 1)]
    return [r.randint(0, vocab_size, (n,)).tolist() for n in lens]


def serving_phase(cfg, params, prompts, *, label: str, num_slots: int,
                  prefill_len: int, decode_steps: int, max_new_tokens: int,
                  marker_of=None, **engine_kwargs) -> dict:
    """``ServingEngine`` → ``ContinuousBatcher`` → submit every prompt →
    ``run()``, twice (the first call compiles).  Every request must
    complete with ``max_new_tokens`` tokens, and the second run must
    repeat the first token for token.  ``marker_of`` names a kernel
    whose marker must be in the compiled decode or prefill program."""
    from autodist_tpu import serving

    ph = f"serve:{label}"
    engine, build_s = timed(lambda: serving.ServingEngine(
        cfg, params, num_slots=num_slots, max_len=cfg.max_len,
        prefill_len=prefill_len, decode_steps=decode_steps,
        **engine_kwargs))
    batcher = serving.ContinuousBatcher(engine)

    def serve():
        rids = [batcher.submit(p, max_new_tokens=max_new_tokens)
                for p in prompts]
        done = batcher.run()
        return [done[r] for r in rids]

    first, first_s = timed(serve)
    again, again_s = timed(serve)
    say(ph, f"layers={cfg.num_layers} hidden={cfg.hidden_size} "
            f"vocab={cfg.vocab_size} max_len={cfg.max_len} "
            f"slots={num_slots} K={decode_steps} requests={len(prompts)} "
            f"prompt_lens={[len(p) for p in prompts]} {engine_kwargs}")
    say(ph, f"build_s={build_s:.2f} compile+run_s={first_s:.2f} "
            f"(first call) run_s={again_s:.3f}")
    require(all(len(c.tokens) == max_new_tokens
                and c.finish_reason == "max_tokens" for c in first), ph,
            f"all {len(first)} requests complete with {max_new_tokens} "
            "tokens",
            sorted({(len(c.tokens), c.finish_reason) for c in first}))
    tokens = [c.tokens for c in first]
    require([c.tokens for c in again] == tokens, ph,
            "a second run repeats the first token for token",
            f"{sum(a.tokens == t for a, t in zip(again, tokens))}"
            f"/{len(tokens)} identical")
    if marker_of is not None:
        from autodist_tpu.kernel.pallas import kernel_marker

        text = (engine.compiled_prefill_text()
                if marker_of == "flash_prefill"
                else engine.compiled_decode_text())
        require(kernel_marker(marker_of) in text, ph,
                f"{marker_of} is in the compiled program",
                f"marker {kernel_marker(marker_of)!r} found")
    return {"engine": engine, "tokens": tokens}


def require_mostly_identical(phase, tokens, ref_tokens) -> None:
    """A kernel-elected engine against the composed engine: greedy
    decode in bf16 may part ways at a near-tie, after which a stream
    differs to its end — but a kernel wired to the wrong cache rows
    agrees on none.  At least half the requests must match in full."""
    same = sum(a == b for a, b in zip(tokens, ref_tokens))
    require(2 * same >= len(tokens), phase,
            "token streams identical to the composed engine's",
            f"{same}/{len(tokens)}")


def serving_parity(cfg, params, prompt, first_token: int) -> None:
    """The first decoded token's logits: ``sequential_logits`` (the
    layer function the engine's prefill runs) on the default backend at
    ``cfg.dtype`` against float32 on the host CPU."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from autodist_tpu.models.pipeline_lm import sequential_logits

    ph = "serve:parity"
    tokens = np.asarray([prompt], np.int32)
    got = np.asarray(jax.jit(
        lambda p, t: sequential_logits(cfg, p, t))(params, tokens))[0, -1]
    cpu = jax.devices("cpu")[0]
    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32)
    with jax.default_device(cpu):
        host = jax.device_put(jax.tree.map(np.asarray, params), cpu)
        ref = np.asarray(jax.jit(
            lambda p, t: sequential_logits(cfg32, p, t))(
                host, jax.device_put(tokens, cpu)))[0, -1]
    require_close(ph, f"first-token logits ({jnp.dtype(cfg.dtype).name} "
                      "vs host float32)", got, ref, LOGIT_RTOL)
    tol = LOGIT_RTOL * float(np.max(np.abs(ref)))
    gap = float(ref.max() - ref[first_token])
    require(gap <= 2 * tol, ph,
            "the engine's first token is the host's best within tolerance",
            f"token {first_token} (host argmax {int(ref.argmax())}), "
            f"host logit gap {gap:.3g} <= {2 * tol:.3g}")


# --------------------------------------------------------------------------- #
# 4. kernels
# --------------------------------------------------------------------------- #
def kernels_phase(*, interpret: bool, seq_len: int = 512, heads: int = 12,
                  head_dim: int = 64, cache_len: int = 1024,
                  block_len: int = 16, chunk: int = 64, slots: int = 8,
                  hop_elems: int = 1 << 20,
                  matmul_shape=(8192, 2048, 512),
                  flash_max_len: int = 16384, seed: int = 0) -> list:
    """Each Pallas kernel against the composed path it replaces, both
    on the default backend.  The chip passes ``interpret=False``: a
    kernel can never pass here by being interpreted."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from autodist_tpu.kernel import quantize as qz
    from autodist_tpu.kernel.pallas import (a2a_ring, collective_matmul,
                                            flash_decode, flash_prefill,
                                            quant_ring)
    from autodist_tpu.models.transformer import dot_product_attention
    from autodist_tpu.ops.flash_attention import flash_attention
    from autodist_tpu.serving import kv_cache

    ph = "kernel"
    r = np.random.RandomState(seed)
    bf16 = jnp.bfloat16
    done = []

    def rand(*shape, scale=1.0, dtype=bf16):
        return jnp.asarray(r.randn(*shape) * scale, dtype)

    # ---- ops/flash_attention.py: forward and backward (custom VJP) -----
    q, k, v = (rand(4, seq_len, heads, head_dim, scale=0.5)
               for _ in range(3))
    for causal in (False, True):
        mask = (jnp.tril(jnp.ones((seq_len, seq_len), bool))[None, None]
                if causal else None)

        def composed(q, k, v, mask=mask):
            return dot_product_attention(q, k, v, mask, dtype=bf16)

        def fused(q, k, v, causal=causal):
            return flash_attention(q, k, v, causal=causal,
                                   interpret=interpret)

        def grads(f):
            return jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) ** 2),
                argnums=(0, 1, 2)))

        name = f"flash_attention(causal={causal}, seq={seq_len})"
        (got, ref), fwd_s = timed(lambda: jax.block_until_ready(
            (jax.jit(fused)(q, k, v), jax.jit(composed)(q, k, v))))
        require_close(ph, f"{name} forward", got, ref, ATTN_FWD_RTOL)
        (gg, gr), bwd_s = timed(lambda: jax.block_until_ready(
            (grads(fused)(q, k, v), grads(composed)(q, k, v))))
        for what, a, b in zip(("dq", "dk", "dv"), gg, gr):
            require_close(ph, f"{name} backward {what}", a, b,
                          ATTN_BWD_RTOL)
        say(ph, f"{name}: fwd compile+run_s={fwd_s:.2f} "
                f"bwd compile+run_s={bwd_s:.2f}")
        done.append(name)
    if not interpret:
        # The longest sequence the kernels hold in VMEM (see
        # ops/flash_attention.py MAX_SEQ_BYTES): it must still compile.
        shape = jax.ShapeDtypeStruct((1, flash_max_len, 2, head_dim), bf16)
        _, s = timed(lambda: jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(flash_attention(
                q, k, v, causal=True, interpret=False).astype(jnp.float32)),
            argnums=(0, 1, 2))).lower(shape, shape, shape).compile())
        say(ph, f"flash_attention fwd+bwd compiles at the documented "
                f"longest seq_len {flash_max_len}: compile_s={s:.2f}")

    # ---- decode / prefill attention over the KV cache -------------------
    H, d, T, bl = heads, head_dim, cache_len, block_len
    lens = jnp.asarray(r.randint(0, T - 1, (slots,)), jnp.int32) \
        .at[0].set(0).at[1].set(T - 1)
    q1 = rand(slots, 1, H, d)
    kd, vd = rand(slots, H, T, d), rand(slots, H, T, d)
    got, ref = jax.block_until_ready((
        jax.jit(lambda q, k, v, lens:
                flash_decode.flash_decode_attention_dense(
                    q, k[None], v[None], 0, lens, dtype=bf16,
                    interpret=interpret))(q1, kd, vd, lens),
        jax.jit(lambda *a: kv_cache.cached_attention(
            *a, dtype=bf16))(q1, kd, vd, lens)))
    require_close(ph, f"flash_decode_attention_dense(T={T}, reading)", got,
                  ref, ATTN_FWD_RTOL)
    # the same kernel as the engine calls it: the whole cache, a layer
    # index (a traced operand, as a looped stack passes it), only the
    # live blocks read (the other layer holds 1e4), the step's rows
    # written on the way (slot 2 is not decoding) — over the [d, block]
    # tiles of narrow heads and the [block, d] tiles of heads of 128
    def dense_writing(H, d):
        q1, kn, vn = (rand(slots, 1, H, d) for _ in range(3))
        kd, vd = rand(slots, H, T, d), rand(slots, H, T, d)
        k5, v5 = (jnp.stack([jnp.full_like(a, 1e4), a]) for a in (kd, vd))
        act = jnp.ones((slots,), bool).at[2].set(False)
        put = jnp.where(act, lens, T - 1)  # slot 2's row: put back below
        kw, vw = (kv_cache.write_token(c, 1, n, put).at[1, 2].set(c[1, 2])
                  for c, n in ((k5, kn), (v5, vn)))
        ref = jax.jit(lambda *a: kv_cache.cached_attention(*a, dtype=bf16))(
            q1, kw[1], vw[1], lens)
        got, kg, vg = jax.block_until_ready(jax.jit(
            lambda q, k, v, layer, l, kn, vn, a:
            flash_decode.flash_decode_attention_dense(
                q, k, v, layer, l, new_kv=(kn, vn), active=a, dtype=bf16,
                interpret=interpret))(q1, k5, v5, jnp.int32(1), lens, kn,
                                      vn, act))
        keep = np.asarray(act)
        what = f"flash_decode_attention_dense(T={T}, heads of {d}, " \
            f"layer 1 of 2, writing)"
        require_close(ph, what, np.asarray(got, np.float32)[keep],
                      np.asarray(ref, np.float32)[keep], ATTN_FWD_RTOL)
        require(bool(jnp.array_equal(kg, kw))
                and bool(jnp.array_equal(vg, vw)), ph,
                f"heads of {d}: the kernel leaves the caches as "
                "write_token does", "bit-identical")

    dense_writing(H, d)
    if d < 128:
        dense_writing(max(1, H * d // 128), 128)
    done.append("flash_decode_attention_dense")

    mb = T // bl
    pool_k, pool_v = (rand(slots * mb, H, bl, d) for _ in range(2))
    table = jnp.asarray(r.permutation(slots * mb).reshape(slots, mb),
                        jnp.int32)
    got, ref = jax.block_until_ready((
        jax.jit(lambda *a: flash_decode.flash_decode_attention_paged(
            *a, block_len=bl, dtype=bf16, interpret=interpret))(
                q1, pool_k, pool_v, lens, table),
        jax.jit(lambda *a: kv_cache.paged_cached_attention(
            *a, block_len=bl, dtype=bf16))(q1, pool_k, pool_v, lens, table)))
    require_close(ph, f"flash_decode_attention_paged(block_len={bl})",
                  got, ref, ATTN_FWD_RTOL)
    done.append("flash_decode_attention_paged")

    qc = rand(slots, chunk, H, d)
    starts = jnp.asarray(r.randint(0, (T - chunk) // bl + 1, (slots,)) * bl,
                         jnp.int32)
    got, ref = jax.block_until_ready((
        jax.jit(lambda *a: flash_prefill.flash_prefill_attention_paged(
            *a, block_len=bl, dtype=bf16, interpret=interpret))(
                qc, pool_k, pool_v, starts, table),
        jax.jit(lambda *a: kv_cache.paged_chunk_attention(
            *a, block_len=bl, dtype=bf16))(
                qc, pool_k, pool_v, starts, table)))
    require_close(ph, f"flash_prefill_attention_paged(chunk={chunk})",
                  got, ref, ATTN_FWD_RTOL)
    done.append("flash_prefill_attention_paged")

    # ---- the ring kernels' per-hop bodies (one chip suffices) -----------
    local = quant_ring.to_tiles(rand(1, hop_elems, dtype=jnp.float32))[0]
    q_in = quant_ring.to_tiles(jnp.asarray(
        r.randint(-127, 128, (1, hop_elems)), jnp.int8))[0]
    s_in = jnp.float32(0.01)

    def require_levels(name, q_got, s_got, acc):
        scale = qz.abs_max_scale(acc)
        want = qz.quantize_levels(acc, scale).astype(jnp.int32)
        off = jnp.abs(q_got.astype(jnp.int32) - want)
        require(float(s_got) == float(scale) and int(off.max()) <= 1, ph,
                f"{name} scale bit-equal, levels within one",
                f"scale {float(s_got):.6g} vs {float(scale):.6g}, "
                f"max level diff {int(off.max())}, "
                f"{int((off > 0).sum())}/{off.size} differ")

    q_got, s_got = jax.jit(lambda a, b, c: quant_ring._fused_hop(
        a, b, c, interpret=interpret))(q_in, s_in, local)
    require_levels(f"quant_ring._fused_hop({hop_elems} elements)", q_got,
                   s_got, q_in.astype(jnp.float32) * s_in + local)
    done.append("quant_ring._fused_hop")

    arrived, q_got, s_got = jax.jit(lambda a, b, c: a2a_ring._fused_hop(
        a, b, c, interpret=interpret))(q_in, s_in, local)
    require_levels(f"a2a_ring._fused_hop({hop_elems} elements)", q_got,
                   s_got, local)
    require_close(ph, "a2a_ring._fused_hop dequantized arrival", arrived,
                  q_in.astype(jnp.float32) * s_in, 0.0)
    done.append("a2a_ring._fused_hop")

    M, K, C = matmul_shape
    carry, x2d, kc = rand(M, C), rand(M, K, scale=0.1), rand(K, C, scale=0.1)
    got = jax.jit(lambda a, b, c: collective_matmul._fused_matmul_add(
        a, b, c, interpret=interpret))(carry, x2d, kc)
    ref = jax.jit(lambda a, b, c: (a.astype(jnp.float32) + jnp.dot(
        b, c, preferred_element_type=jnp.float32)).astype(bf16))(
            carry, x2d, kc)
    require_close(ph, f"collective_matmul._fused_matmul_add{matmul_shape}",
                  got, ref, ATTN_FWD_RTOL)
    done.append("collective_matmul._fused_matmul_add")
    return done


def mixed_block_phase(*, slots: int = 32, value_heads: int = 32,
                      key_dim: int = 128, value_dim: int = 128,
                      window: int = 256, hidden: int = 2048,
                      experts: int = 512, held: int = 64, top_k: int = 10,
                      width: int = 512, linear_layers: int = 12,
                      kv_heads: int = 2, group: int = 8,
                      head_dim: int = 256, lane_len: int = 2560,
                      full_layers: int = 4, blocks=(128, 256),
                      dtype=None, seed: int = 0) -> list:
    """What a mixed stack's decode step runs, at the widths of the
    benchmark's ``qwen3-next-80b-a3b`` cell, against its
    composed form on the same backend: the recurrent state's update (one
    position; a window through the chunked form) against the recurrence
    written with einsums at ``highest``; where its tiles fit, the fused
    delta-step kernel over the stacked state of ``linear_layers`` layers
    against the composed step on a layer's slice, with the seconds of
    each alone, at every count of heads a grid step can take; the full
    layers' decode attention, ``group`` query heads on each of
    ``kv_heads`` key/value heads of ``head_dim`` — the dense decode
    kernel over the cache itself against ``write_token`` then
    ``cached_attention`` through the cache manager's seam, alone, over
    ``slots`` lanes of ``lane_len`` positions as full as the cell's
    traffic leaves them, and full: the output, the cache after the step
    bit for bit, and the us a call of each at every block in ``blocks``
    (:func:`grouped_decode_checks`); and the routed layer (sorted pairs
    through the grouped matmul) against every held expert over every row
    (:func:`routed_layer_checks`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from autodist_tpu.models import pipeline_lm as lm

    ph = "mixed"
    r = np.random.RandomState(seed)
    rand = lambda *shape, scale=1.0, dtype=jnp.float32: jnp.asarray(
        r.randn(*shape) * scale, dtype)
    hi = jax.lax.Precision.HIGHEST
    done = []

    # ---- the recurrent state: one position, then a window ---------------
    B, Hh, dk, dv, T = slots, value_heads, key_dim, value_dim, window
    q = lm._l2_normalise(rand(B, T, Hh, dk)) * dk ** -0.5
    k = lm._l2_normalise(rand(B, T, Hh, dk))
    v, state = rand(B, T, Hh, dv), rand(B, Hh, dk, dv)
    g = -jax.nn.softplus(rand(B, T, Hh))
    beta = jax.nn.sigmoid(rand(B, T, Hh))

    def composed_step(S, at):
        q_t, k_t, v_t, g_t, b_t = at
        S = S * jnp.exp(g_t)[..., None, None]
        d = (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t, precision=hi)) \
            * b_t[..., None]
        S = S + jnp.einsum("bhk,bhv->bhkv", k_t, d, precision=hi)
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=hi)

    first = tuple(t[:, 0] for t in (q, k, v, g, beta))
    (o, new), s = timed(lambda: jax.block_until_ready(
        jax.jit(lm.gated_delta_step)(*first, state)))
    ref_new, ref_o = jax.jit(composed_step)(state, first)
    require_close(ph, f"gated_delta_step output ({B} slots x {Hh} heads of "
                      f"{dk} x {dv})", o, ref_o, 1e-5)
    require_close(ph, "gated_delta_step state", new, ref_new, 1e-5)
    say(ph, f"state update, one position: first call {s:.2f}s")
    done.append("gated_delta_step")

    # ---- the fused kernel, in place in the cache manager's array --------
    from autodist_tpu.kernel.pallas import delta_step as ds
    from autodist_tpu.serving import kv_cache

    if ds.delta_step_fits(state.shape, state.dtype):
        L, layer, reps = linear_layers, linear_layers // 2, 47
        stack = lambda: jnp.stack([state] * L)

        def composed(ssm):
            o, new = lm.gated_delta_step(*first, ssm[layer])
            return o, kv_cache.write_state((ssm,), layer, (new,))[0]

        def per_call(fn):
            """Seconds a call of ``fn(ssm) -> (o, ssm)``: ``reps`` calls
            in one program, the array donated and carried, so that the
            host's dispatch (longer than the call) is paid once."""
            many = jax.jit(lambda ssm: jax.lax.fori_loop(
                0, reps, lambda _, c: fn(c[1]), fn(ssm))[1], donate_argnums=0)
            ssm = jax.block_until_ready(many(stack()))
            return timed(lambda: jax.block_until_ready(many(ssm)))[1] \
                / (reps + 1)

        ref_o, ref_ssm = jax.jit(composed)(stack())
        took = {"composed": per_call(composed)}
        for hb in (h for h in (8, 16, 32) if Hh % h == 0):
            fused = lambda ssm, hb=hb: ds.gated_delta_step_fused(
                *first, ssm, jnp.int32(layer), heads_per_step=hb)
            o, ssm = jax.jit(fused)(stack())
            require_close(ph, f"delta_step kernel output ({hb} heads a "
                              f"grid step)", o, ref_o, 1e-5)
            require(bool((ssm[:layer] == state).all()
                         and (ssm[layer + 1:] == state).all()), ph,
                    "delta_step kernel leaves the other layers",
                    "bit for bit")
            err, _ = max_err(ssm[layer], ref_ssm[layer])
            require_close(ph, "delta_step kernel state", ssm[layer],
                          ref_ssm[layer], 1e-5)
            took[hb] = per_call(fused)
            say(ph, f"delta_step kernel, {hb} heads a grid step: largest "
                    f"absolute difference of the state {err:.3g}")
        moved = 2 * state.size * 4
        say(ph, f"state update of one of {L} layers, {moved / 1e6:.1f} MB "
                f"there and back, seconds a call alone: " + ", ".join(
                    f"{name} {t:.6f} ({moved / t / 1e9:.0f} GB/s)"
                    for name, t in took.items()))
        done.append("gated_delta_step_fused")

    steps = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    ref_S, ref_o = jax.jit(lambda S, xs: jax.lax.scan(composed_step, S, xs))(
        state, steps)
    (o, S), s = timed(lambda: jax.block_until_ready(
        jax.jit(lm.gated_delta_chunked)(q, k, v, g, beta, state)))
    require_close(ph, f"gated_delta_chunked output (window of {T})", o,
                  jnp.moveaxis(ref_o, 0, 1), 1e-4)
    require_close(ph, "gated_delta_chunked state", S, ref_S, 1e-4)
    say(ph, f"state update, chunked over {T} positions: first call "
            f"{s:.2f}s")
    done.append("gated_delta_chunked")

    # ---- the full layers' decode attention, a group a key/value head ----
    done += grouped_decode_checks(
        ph, r, slots=slots, kv_heads=kv_heads, group=group,
        head_dim=head_dim, lane_len=lane_len, layers=full_layers,
        blocks=blocks, dtype=jnp.bfloat16 if dtype is None else dtype)

    # ---- the routed layer: a decode step's rows, a prefill row's --------
    done += routed_layer_checks(
        ph, r, rows=(slots, 8 * slots), hidden=hidden, experts=experts,
        held=held, top_k=top_k, width=width)
    return done


def seconds_a_call(fn, first, carried, *, reps: int) -> float:
    """Seconds a call of ``fn(first, carried) -> (o, carried)``: ``reps``
    calls in one program, the arrays ``carried()`` makes donated and
    carried and each call's ``first`` made from ALL of the last one's
    output (of ``first``'s shape), so that nothing is lifted out or cut
    down to the part that is used, and the host's dispatch (longer than
    the call) is paid once."""
    import jax

    def chained(_, c):
        o, arr = fn(*c)
        return c[0] + 1e-3 * o.astype(c[0].dtype), arr

    # both carried values come back: a chain nothing reads is dead
    # code, and a composed step's would be removed
    many = jax.jit(lambda arr: jax.lax.fori_loop(
        0, reps, chained, (first, arr)), donate_argnums=0)
    arr = jax.block_until_ready(many(carried()))[1]
    return timed(lambda: jax.block_until_ready(many(arr)))[1] / reps


def grouped_decode_checks(ph, r, *, slots: int, kv_heads: int, group: int,
                          head_dim: int, lane_len: int, layers: int,
                          blocks, dtype, reps: int = 47) -> list:
    """One layer's decode step through ``kv_cache.DenseLayout
    .decode_attend`` over a ``[layers, slots, kv_heads, lane_len,
    head_dim]`` cache, ``group`` query heads a key/value head: the dense
    decode kernel at each block of ``blocks`` that divides the lane
    against ``write_token`` then ``cached_attention`` (output; the cache
    after the step bit for bit), and the seconds a call of each alone —
    with the lanes as full as the ``qwen3-next-80b-a3b`` cell's traffic
    leaves a decoding slot's (a prompt of lognormal median 128, sigma
    0.8, and a part of an output of median 384, sigma 0.6, met in
    proportion to its length), and with every lane full."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from autodist_tpu.serving import kv_cache

    B, H, G, d, T, L = slots, kv_heads, group, head_dim, lane_len, layers
    layer = L // 2
    tol = 3e-2 if jnp.dtype(dtype).itemsize < 4 else 1e-4
    rand = lambda *shape: jnp.asarray(r.randn(*shape), dtype)
    q, k_new, v_new = rand(B, 1, H * G, d), rand(B, 1, H, d), rand(B, 1, H, d)
    kc0, vc0 = rand(L, B, H, T, d), rand(L, B, H, T, d)
    stack = lambda: (kc0 + 0, vc0 + 0)      # fresh arrays to donate
    outs = r.lognormal(np.log(384), 0.6, 16 * B)
    outs = r.choice(outs, B, p=outs / outs.sum())
    live = np.minimum(r.lognormal(np.log(128), 0.8, B) + r.rand(B) * outs,
                      T - 2).astype(np.int32)
    active = jnp.ones((B,), bool)

    def step(block, lengths):
        """``(q, (kc, vc)) -> (out, (kc, vc))``: the kernel with
        ``block``, or the composed step."""
        lay = kv_cache.DenseLayout((L, B, H, d, T), {}, fused_block=block)

        def attend(q, caches):
            out, *caches = lay.decode_attend(
                q, k_new, v_new, *caches, layer, lengths, None, active,
                dtype=dtype)
            return out, tuple(caches)

        return attend

    per_call = lambda fn: seconds_a_call(fn, q, stack, reps=reps)
    for fill, lengths in (("the traffic's", jnp.asarray(live)),
                          ("full", jnp.full((B,), T - 1, jnp.int32))):
        ref, ref_caches = jax.jit(step(None, lengths))(q, stack())
        took = {"composed": per_call(step(None, lengths))}
        for bk in (b for b in blocks if T % b == 0):
            got, caches = jax.jit(step(bk, lengths))(q, stack())
            require_close(ph, f"grouped decode kernel output (blocks of "
                              f"{bk}, {B} lanes of {T}, {H} x {G} heads of "
                              f"{d}, {fill})", got, ref, tol)
            require(all(bool((a == b).all())
                        for a, b in zip(caches, ref_caches)), ph,
                    "grouped decode kernel leaves the caches as "
                    "write_token does", "bit for bit")
            took[bk] = per_call(step(bk, lengths))
        moved = int(jnp.sum(lengths + 1)) * 2 * H * d \
            * jnp.dtype(dtype).itemsize
        say(ph, f"decode attention over one of {L} layers, {fill} lanes "
                f"(mean {float(jnp.mean(lengths)):.0f} of {T}), "
                f"{moved / 1e6:.1f} MB of live keys and values, us a call "
                f"alone: " + ", ".join(
                    f"{name} {t * 1e6:.1f} ({moved / t / 1e9:.0f} GB/s)"
                    for name, t in took.items()))
    return ["grouped_decode_kernel"]


def routed_layer_checks(ph, r, *, rows, hidden: int, experts: int,
                        held: int, top_k: int, width: int,
                        renormalise: bool = True, rtol: float = 2e-2) -> list:
    """``moe.routed_experts`` over bf16 experts of these widths against
    every held expert over every row, for each count of ``rows``: with
    the grouped-matmul kernel forbidden (two ``ragged_dot``) and, where
    the kernel takes the pairs (a decode step's; compiled on a TPU, the
    interpreter elsewhere), with it forced."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from autodist_tpu.kernel.pallas.grouped_matmul import \
        grouped_matmul_elected
    from autodist_tpu.parallel import moe

    bf16 = jnp.bfloat16
    rand = lambda *shape, dtype=jnp.float32: jnp.asarray(
        r.randn(*shape) * 0.02, dtype)
    router = rand(hidden, experts)
    wi = rand(held, hidden, 2 * width, dtype=bf16)
    wo = rand(held, width, hidden, dtype=bf16)

    def every_expert(x):
        exps, w = moe.route_top_k(x, router, top_k, renormalise)
        full = jnp.zeros((x.shape[0], experts), jnp.float32).at[
            jnp.arange(x.shape[0])[:, None], exps].set(w)[:, :held]
        h = jnp.einsum("rh,ehm->erm", x, wi,
                       preferred_element_type=jnp.float32)
        h = (jax.nn.silu(h[..., :width]) * h[..., width:]).astype(bf16)
        y = jnp.einsum("erm,emh->erh", h, wo,
                       preferred_element_type=jnp.float32)
        return jnp.einsum("re,erh->rh", full, y,
                          precision=jax.lax.Precision.HIGHEST)

    done = ["routed_experts"]
    for n in rows:
        x = jnp.asarray(r.randn(n, hidden), bf16)
        want = jax.jit(every_expert)(x)
        exps, _ = moe.route_top_k(x, router, top_k, renormalise)
        landed = int((np.asarray(exps) < held).sum())
        words = {"ragged_dot": False}
        if grouped_matmul_elected(True, n * top_k, hidden, width, bf16):
            words["grouped_matmul"] = True
        for name, word in words.items():
            (got, stats), s = timed(lambda: jax.block_until_ready(jax.jit(
                lambda x: moe.routed_experts(
                    x, router, wi, wo, top_k=top_k, renormalise=renormalise,
                    kernel=word))(x)))
            require_close(ph, f"routed_experts({n} rows x {top_k} of "
                              f"{experts}, {held} held) through {name}",
                          got, want, rtol)
            require(int(stats[0]) == landed, ph, "pairs on held experts",
                    f"{int(stats[0])} counted, {landed} routed there, "
                    f"{int(stats[1])} experts hit; first call {s:.2f}s")
            if word and name not in done:
                done.append(name)
    return done


def latent_block_phase(*, slots: int = 8, heads: int = 16,
                       hidden: int = 2048, kv_rank: int = 512,
                       nope_dim: int = 128, rope_dim: int = 64,
                       value_dim: int = 128, window: int = 384,
                       max_len: int = 512, dtype=None, rtol: float = 3e-2,
                       lane_slots: int = 64, lane_len: int = 3072,
                       blocks=(128, 256, 512, 1024), experts: int = 64,
                       held: int = 8, top_k: int = 6, width: int = 1408,
                       seed: int = 0) -> list:
    """A latent-attention layer's two forms over one set of weights, at
    the widths of the benchmark's ``deepseek-v2-lite`` cell, on the same
    backend: the last position of a ``window`` attended in the EXPANDED
    form (every row projected up to its heads' keys and values) against
    that position attended in the ABSORBED form over the rows the window
    cached, through the cache manager's latent layout — the row written,
    then every query head on the one lane (``LatentLayout
    .decode_attend``).  The two round differently (the absorbed form
    makes ``q_lat`` and ``o_lat`` in the activations' type, the expanded
    form the keys and values), hence ``rtol``.  Then the latent decode
    kernel over the cache itself against the composed step (``write_token``
    and ``cached_attention``) through the same seam, alone, at the cell's
    ``lane_slots`` lanes of ``lane_len`` positions ~43% full: the output,
    the cache after the step bit for bit, and the seconds of each for
    every candidate block in ``blocks`` that divides the lane.  Last the
    cell's routed layer (``top_k`` of ``experts``, not renormalised,
    ``held`` of them here) at a decode step's ``lane_slots`` rows and a
    prefill row's, as :func:`routed_layer_checks` holds it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from autodist_tpu.models import pipeline_lm as lm
    from autodist_tpu.models.transformer import (BlockSpec,
                                                 LatentAttentionSpec,
                                                 RopeScaling,
                                                 TransformerConfig)
    from autodist_tpu.serving import kv_cache

    ph = "latent"
    dtype = jnp.bfloat16 if dtype is None else dtype
    yarn = RopeScaling(factor=40.0, original_max_len=4096, mscale=0.707,
                       mscale_all_dim=0.707)
    lat = LatentAttentionSpec(kv_rank, nope_dim, rope_dim, value_dim)
    cfg = TransformerConfig(
        vocab_size=8, hidden_size=hidden, num_layers=1, num_heads=heads,
        mlp_dim=8, max_len=max_len, dtype=dtype, dropout_rate=0.0,
        attention_dropout_rate=0.0,
        block=BlockSpec(norm="rmsnorm", norm_placement="pre",
                        positions="rope", rope_scaling=yarn, ffn="swiglu",
                        bias=False, tied_head=False, latent=lat))
    r = np.random.RandomState(seed)
    shapes = lm.param_shapes(cfg)["stages"]
    chunk = jax.tree.map(
        lambda shape: jnp.asarray(r.randn(*shape[1:]) * 0.02, dtype),
        {k: shapes[k] for k in ("latent_attention", "ln_attention_in")},
        is_leaf=lambda x: isinstance(x, tuple))
    for norm in (chunk["ln_attention_in"], chunk["latent_attention"]
                 ["kv_norm"]):
        norm["scale"] = jnp.ones_like(norm["scale"])
    B, T = slots, window
    # a stream as small as an embedding's rows: the mixer's output, which
    # the norm makes independent of the stream's size, is then most of
    # what the residual sum holds, and its rounding does not hide it
    x = jnp.asarray(r.randn(B, T, hidden) * 0.02, dtype)
    mask = jnp.tril(jnp.ones((T, T), bool))[None, None]
    (want, rows), s = timed(lambda: jax.block_until_ready(jax.jit(
        lambda x: lm.latent_expanded(cfg, chunk, x, jnp.arange(T), mask))(x)))
    say(ph, f"expanded over {T} positions ({B} rows, {heads} heads of "
            f"{nope_dim + rope_dim} / {value_dim}): first call {s:.2f}s")

    dims = (1, B, 1, lat.row, max_len)
    layout = kv_cache.LatentLayout(dims, {}, kv_rank=kv_rank,
                                   scale=cfg.block.latent_softmax_scale)
    cache = layout.init_cache(dims, dtype)
    # the window's rows but the last, as a prefill would have left them
    kc = cache.k.at[0, :, 0, :T - 1].set(rows[:, :T - 1])
    lengths = jnp.full((B,), T - 1, jnp.int32)

    def absorbed(x_last, kc, vc):
        def attend(q, row):
            out, *caches = layout.decode_attend(
                q, row, None, kc, vc, 0, lengths, None, None, dtype=dtype)
            return out, caches

        return lm.latent_absorbed(cfg, chunk, x_last, lengths[:, None],
                                  attend)

    (got, (kc, _)), s = timed(lambda: jax.block_until_ready(
        jax.jit(absorbed)(x[:, -1:], kc, cache.v)))
    require_close(ph, f"absorbed step over {T} cached rows of {lat.row} "
                      f"against the expanded form", got[:, 0], want[:, -1],
                  rtol)
    require_close(ph, "the step's row, written where it attends",
                  kc[0, :, 0, T - 1], rows[:, -1], 1e-6)
    say(ph, f"absorbed step: first call {s:.2f}s")

    # ---- the decode kernel, in place in the cache manager's array -------
    B, T, L, layer, reps = lane_slots, lane_len, 2, 1, 48
    dims = (L, B, 1, lat.row, T)
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (B, 1, heads, lat.row), dtype)
    new = jax.random.normal(keys[1], (B, 1, 1, lat.row), dtype)
    stack = lambda: jax.random.normal(keys[2], (L, B, 1, T, lat.row), dtype)
    lengths = jnp.asarray(np.linspace(T / 16, 0.8 * T, B), jnp.int32)
    vc = jnp.zeros((L, B, 1, T, 0), dtype)

    def step(block):
        """``(q, kc) -> (o_lat, kc)``: one layer's decode step through
        the layout's seam, the kernel with ``block`` or the composed
        step."""
        lay = kv_cache.LatentLayout(
            dims, {}, kv_rank=kv_rank, scale=cfg.block.latent_softmax_scale,
            fused_block=block)
        return lambda q, kc: lay.decode_attend(
            q, new, None, kc, vc, layer, lengths, None, None,
            dtype=dtype)[:2]

    def per_call(fn):
        """Seconds a call of ``fn``: ``reps`` calls in one program, the
        cache donated and carried and each call's queries made from the
        last one's output, so that nothing runs side by side or is
        lifted out, and the host's dispatch is paid once."""
        def chained(_, c):
            o, kc = fn(*c)
            return c[0].at[..., :kv_rank].add(o * 1e-3), kc

        many = jax.jit(lambda kc: jax.lax.fori_loop(
            0, reps, chained, (q, kc))[1], donate_argnums=0)
        kc = jax.block_until_ready(many(stack()))
        return timed(lambda: jax.block_until_ready(many(kc)))[1] / reps

    ref_o, ref_kc = jax.jit(step(None))(q, stack())
    took = {"composed": per_call(step(None))}
    for bk in (b for b in blocks if T % b == 0):
        o, kc = jax.jit(step(bk))(q, stack())
        require_close(ph, f"latent decode kernel output (blocks of {bk}, "
                          f"{B} lanes of {T})", o, ref_o, rtol)
        require(bool((kc == ref_kc).all()), ph,
                "latent decode kernel leaves the cache as write_token does",
                "bit for bit")
        took[bk] = per_call(step(bk))
    live = int(jnp.sum(lengths + 1)) * lat.row * jnp.dtype(dtype).itemsize
    say(ph, f"decode attention over one of {L} layers, {live / 1e6:.1f} MB "
            f"of live rows, us a call alone: " + ", ".join(
                f"{name} {t * 1e6:.1f} ({live / t / 1e9:.0f} GB/s)"
                for name, t in took.items()))
    return ["latent_expanded", "latent_absorbed", "latent_decode_kernel"] \
        + routed_layer_checks(
            ph, r, rows=(lane_slots, 16 * lane_slots), hidden=hidden,
            experts=experts, held=held, top_k=top_k, width=width,
            renormalise=False)


def hybrid_latent_phase(*, states=((32, 32, 12, "head"),
                                  (96, 32, 10, "channel")),
                        key_dim: int = 128, value_dim: int = 128,
                        latent_heads=(16, 32), kv_rank: int = 512,
                        rope_dim: int = 64, lane_slots: int = 96,
                        lane_len: int = 3072, block: int = 256,
                        dtype=None, rtol: float = 2e-2, reps: int = 47,
                        seed: int = 0) -> list:
    """The two kernels a mixed latent / delta-rule stack's decode step
    elects, each alone against its composed form through the cache
    manager's seam, with the us a call of each.  The delta-step kernel
    over the stacked state at each of ``states`` ``(slots, heads, linear
    layers, gate)`` — the benchmark's ``qwen3-next-80b-a3b`` cell (a
    decay a head) and its ``ling-3.0-flash`` cell (a decay a row of each
    ``[key_dim, value_dim]`` tile): one kernel, the head's decay the same
    column broadcast.  The latent decode kernel over ``lane_slots`` lanes
    of ``lane_len`` rows of ``kv_rank + rope_dim``, ~43% live, at each
    count of absorbed query heads in ``latent_heads`` (16:
    ``deepseek-v2-lite``; 32: ``ling-3.0-flash``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from autodist_tpu.kernel.pallas import delta_step as ds
    from autodist_tpu.models import pipeline_lm as lm
    from autodist_tpu.serving import kv_cache

    ph = "hybrid"
    dtype = jnp.bfloat16 if dtype is None else dtype
    r = np.random.RandomState(seed)
    rand = lambda *shape: jnp.asarray(r.randn(*shape), jnp.float32)
    done = []

    per_call = functools.partial(seconds_a_call, reps=reps)

    # ---- the state kernel: a decay a head, a decay a row ---------------
    dk, dv = key_dim, value_dim
    for B, Hh, L, gate in states:
        if not ds.delta_step_fits((L, B, Hh, dk, dv), jnp.float32):
            continue
        layer = L // 2
        q = lm._l2_normalise(rand(B, Hh, dk)) * dk ** -0.5
        k, v = lm._l2_normalise(rand(B, Hh, dk)), rand(B, Hh, dv)
        g = -5.0 * jax.nn.sigmoid(
            rand(*((B, Hh, dk) if gate == "channel" else (B, Hh))))
        beta, state = jax.nn.sigmoid(rand(B, Hh)), rand(B, Hh, dk, dv)
        stack = lambda: jnp.stack([state] * L)
        took = {}
        for name, word in (("composed", False), ("kernel", True)):
            lay = kv_cache.DenseLayout((1, B, 1, 8, 8), {"delta_step": word})
            step = lambda v_, ssm, lay=lay: lay.advance_state(
                q, k, v_, g, beta, ssm, jnp.int32(layer))
            o, ssm = jax.jit(step)(v, stack())
            if word:
                require_close(ph, f"delta_step kernel output, a decay a "
                                  f"{gate} ({B} slots x {Hh} heads, {L} "
                                  f"layers)", o, ref_o, 1e-5)
                require_close(ph, "delta_step kernel state", ssm[layer],
                              ref_ssm[layer], 1e-5)
                require(bool((ssm[:layer] == state).all()
                             and (ssm[layer + 1:] == state).all()), ph,
                        "delta_step kernel leaves the other layers",
                        "bit for bit")
            ref_o, ref_ssm = o, ssm
            took[name] = per_call(step, v, stack)
        moved = 2 * state.size * 4
        say(ph, f"state update, a decay a {gate}, one of {L} layers of "
                f"{B} slots x {Hh} heads, {moved / 1e6:.1f} MB there and "
                f"back, us a call alone: " + ", ".join(
                    f"{name} {t * 1e6:.1f} ({moved / t / 1e9:.0f} GB/s)"
                    for name, t in took.items()))
        done.append(f"delta_step:{gate}")

    # ---- the latent decode kernel: 16 and 32 absorbed heads ------------
    B, T, L, layer, row = lane_slots, lane_len, 2, 1, kv_rank + rope_dim
    dims = (L, B, 1, row, T)
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    new = jax.random.normal(keys[1], (B, 1, 1, row), dtype)
    stack = lambda: jax.random.normal(keys[2], (L, B, 1, T, row), dtype)
    lengths = jnp.asarray(np.linspace(T / 16, 0.8 * T, B), jnp.int32)
    vc = jnp.zeros((L, B, 1, T, 0), dtype)
    live = int(jnp.sum(lengths + 1)) * row * jnp.dtype(dtype).itemsize
    for heads in latent_heads:
        q = jax.random.normal(keys[0], (B, 1, heads, row), dtype)
        took = {}
        for name, blk in (("composed", None), ("kernel", block)):
            lay = kv_cache.LatentLayout(dims, {}, kv_rank=kv_rank,
                                        scale=row ** -0.5, fused_block=blk)
            def step(q_, kc, lay=lay):
                # the weighted sum at the query's width, for per_call
                o, kc, _ = lay.decode_attend(q_, new, None, kc, vc, layer,
                                             lengths, None, None,
                                             dtype=dtype)
                return jnp.pad(o, [(0, 0)] * 3 + [(0, rope_dim)]), kc

            o, kc = jax.jit(step)(q, stack())
            if blk:
                require_close(ph, f"latent decode kernel output, {heads} "
                                  f"heads ({B} lanes of {T}, blocks of "
                                  f"{blk})", o, ref_o, rtol)
                require(bool((kc == ref_kc).all()), ph,
                        "latent decode kernel leaves the cache as "
                        "write_token does", "bit for bit")
            ref_o, ref_kc = o, kc
            took[name] = per_call(step, q, stack)
        say(ph, f"decode attention, {heads} absorbed heads over one of "
                f"{L} layers, {live / 1e6:.1f} MB of live rows, us a call "
                f"alone: " + ", ".join(
                    f"{name} {t * 1e6:.1f} ({live / t / 1e9:.0f} GB/s)"
                    for name, t in took.items()))
        done.append(f"latent_decode:{heads}")
    return done


def retention_block_phase(*, slots: int = 16, kv_heads: int = 8,
                          group: int = 5, head_dim: int = 128,
                          layers: int = 8, check_slots: int = 2,
                          window: int = 160, offsets=(5, 13, 65),
                          reps: int = 11, seed: int = 0) -> list:
    """A power-retention stack's state step at the widths of the
    benchmark's ``brumby-14b-base`` cell, against its composed forms on
    the same backend: the symmetric square's identity; one position and a
    window through the chunked form against the attention form written
    out (``check_slots`` rows); where its tiles fit, the retention-step
    kernel over the stacked state of ``layers`` layers through the cache
    manager's seam against the composed step on a layer's slice
    (``check_slots`` slots: the composed step's temporaries at the cell's
    16 do not fit beside the state), the other layers bit for bit; then
    on a TPU, the kernel alone at the cell's ``slots`` x ``kv_heads``
    tiles, the us a call at each count of offsets a grid step takes,
    beside a plain pass over the same bytes (the layer's tiles read,
    scaled and written back in place)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from autodist_tpu.kernel.pallas import retention_step as rs
    from autodist_tpu.models import pipeline_lm as lm
    from autodist_tpu.models.transformer import LinearMixerSpec
    from autodist_tpu.serving import kv_cache

    ph = "retention"
    r = np.random.RandomState(seed)
    rand = lambda *shape: jnp.asarray(r.randn(*shape), jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    kv, d, n = kv_heads, head_dim, kv_heads * group
    mixer = LinearMixerSpec.retention(kv, d)
    done = []

    # ---- phi, one position, a window: against the attention form -------
    B, T = check_slots, window
    q, k, v = rand(B, T, n, d) * d ** -0.5, rand(B, T, kv, d), \
        rand(B, T, kv, d)
    # jitted: alone, a rotation by 0 or by half the lanes trips a check of
    # the TPU compiler's fusion emitter (seen on the chip, PR 43)
    pq, pk = jax.jit(lambda a, b: (lm.symmetric_square(a),
                                   lm.symmetric_square(b)))(
        q[:, 0, :kv], k[:, 0])
    require_close(ph, "phi(q) . phi(k) against (q . k)^2",
                  (pq * pk).sum((-1, -2)),
                  jnp.einsum("bhd,bhd->bh", q[:, 0, :kv], k[:, 0],
                             precision=hi) ** 2, 1e-5)
    g = -jax.nn.softplus(rand(B, T, kv))

    def attention_form(q, k, v, g):
        G = jnp.repeat(jnp.cumsum(g, 1), group, 2)          # [B, T, n]
        kk, vv = (jnp.repeat(t, group, 2) for t in (k, v))
        seen = jnp.tril(jnp.ones((T, T), bool))
        w = jnp.where(seen, jnp.exp(jnp.where(
            seen, G.transpose(0, 2, 1)[..., :, None]
            - G.transpose(0, 2, 1)[..., None, :], 0.0)), 0.0)
        a = jnp.einsum("bthd,bshd->bhts", q, kk, precision=hi) ** 2 * w
        return jnp.einsum("bhts,bshd->bthd", a, vv, precision=hi) \
            / (a.sum(-1).transpose(0, 2, 1)[..., None] + lm.RETENTION_EPS)

    ref = jax.jit(attention_form)(q, k, v, g)
    # a prompt's pass: the window from no state, then a window ON a state
    (y, after), s = timed(lambda: jax.block_until_ready(
        jax.jit(lm.retention_chunked)(q, k, v, g, None)))
    require_close(ph, f"retention_chunked output (window of {T} from no "
                      f"state, {n} heads on {kv} of {d})", y, ref, 1e-4)
    say(ph, f"state built once over {T} positions: first call {s:.2f}s")
    half = T // 2
    head = jax.jit(lm.retention_chunked)(
        *(t[:, :half] for t in (q, k, v, g)), None)[1]
    y2, carried = jax.jit(lm.retention_chunked)(
        *(t[:, half:] for t in (q, k, v, g)), head)
    require_close(ph, f"retention_chunked output of {T - half} positions on "
                      f"the state of {half}", y2, ref[:, half:], 1e-4)
    require_close(ph, "its state against the whole window's", carried[0],
                  after[0], 1e-4)
    before = jax.jit(lm.retention_chunked)(q[:, :-1], k[:, :-1], v[:, :-1],
                                           g[:, :-1], None)[1]
    last = tuple(t[:, -1] for t in (q, k, v, g))
    y1, stepped = jax.jit(lm.retention_step)(*last, before)
    require_close(ph, "retention_step output after the window", y1,
                  ref[:, -1], 1e-4)
    require_close(ph, "retention_step state against the chunked form's",
                  stepped[0], after[0], 1e-4)
    done += ["retention_chunked", "retention_step"]

    # ---- the fused kernel, in place in the cache manager's arrays ------
    shape = (layers, slots, *mixer.state_shape)
    if not rs.retention_step_fits(shape, jnp.float32, group):
        return done
    layer = layers // 2
    lay = lambda word: kv_cache.DenseLayout(
        (0, 1, kv, d, 8), {"retention_step": word}, recurrent=(layers, mixer))
    small = lambda: (jnp.stack([before[0]] * layers),
                     jnp.stack([before[1]] * layers))
    step = lambda word: jax.jit(
        lambda ssm, nrm: lay(word).advance_retention(
            *last, (ssm, nrm), jnp.int32(layer)))
    ref_y, (ref_ssm, ref_nrm) = step(False)(*small())
    y, (ssm, nrm) = step(True)(*small())
    require_close(ph, f"retention_step kernel output ({B} slots x {kv} "
                      f"heads, {layers} layers)", y, ref_y, 1e-4)
    require_close(ph, "retention_step kernel state", ssm[layer],
                  ref_ssm[layer], 1e-5)
    require_close(ph, "retention_step kernel normaliser", nrm[layer],
                  ref_nrm[layer], 1e-5)
    require(bool((ssm[:layer] == before[0]).all()
                 and (ssm[layer + 1:] == before[0]).all()
                 and (nrm[:layer] == before[1]).all()
                 and (nrm[layer + 1:] == before[1]).all()), ph,
            "retention_step kernel leaves the other layers", "bit for bit")
    done.append("retention_step_fused")
    if jax.default_backend() != "tpu":     # the interpreter: no times
        return done

    # ---- alone, at the cell's tiles: us a call, beside a plain pass ----
    B = slots
    q1, k1, v1 = rand(B, n, d) * d ** -0.5, rand(B, kv, d), rand(B, kv, d)
    g1 = -jax.nn.softplus(rand(B, kv))
    tile = rand(1, 1, kv, *mixer.state_shape[1:])
    full = lambda: (jnp.broadcast_to(tile, shape) + 0.0,
                    jnp.zeros((layers, B, *mixer.normaliser_shape),
                              jnp.float32))

    def per_call(fn):
        """Seconds a call of ``fn(q, state) -> (y, state)``: ``reps``
        calls in one program, the arrays donated and carried and each
        call's query made from the last one's output."""
        def chained(_, c):
            y, state = fn(*c)
            return c[0] + 1e-3 * y, state

        many = jax.jit(lambda state: jax.lax.fori_loop(
            0, reps, chained, (q1, state)), donate_argnums=0)
        state = jax.block_until_ready(many(full()))[1]
        return timed(lambda: jax.block_until_ready(many(state)))[1] / reps

    def plain(q_, state):
        ssm, nrm = state
        at = (layer,) + (0,) * (ssm.ndim - 1)
        rows = jax.lax.dynamic_slice(ssm, at, (1, *ssm.shape[1:]))
        return q_, (jax.lax.dynamic_update_slice(ssm, rows * 0.999, at), nrm)

    took = {"plain pass": per_call(plain)}
    for ob in offsets:
        took[f"kernel, {ob} offsets a step"] = per_call(
            lambda q_, state, ob=ob: rs.retention_step_fused(
                q_, k1, v1, g1, state, jnp.int32(layer),
                eps=lm.RETENTION_EPS, offsets_per_step=ob))
    moved = 2 * 4 * B * int(np.prod(mixer.state_shape))
    say(ph, f"state step of one of {layers} layers, {B} slots x {kv} heads "
            f"of [{mixer.state_offsets}, {d}, {d}], {moved / 1e6:.1f} MB "
            f"there and back, us a call alone: " + ", ".join(
                f"{name} {t * 1e6:.1f} ({moved / t / 1e9:.0f} GB/s)"
                for name, t in took.items()))
    done.append("retention_step_alone")
    return done


def ssd_block_phase(*, slots: int = 64, heads: int = 64, head_dim: int = 64,
                    state: int = 128, groups: int = 1, layers: int = 36,
                    check_slots: int = 2, window: int = 300,
                    reps: int = 11, seed: int = 0) -> list:
    """A Mamba-2 state-space stack's state step at the widths of the
    benchmark's ``granite-4.0-h-micro`` cell, against its composed forms
    on the same backend: a window through the chunked form (from no
    state, as a prompt's pass runs it, and on the state of the half
    before it) and one position after it against the recurrence run
    position by position (``check_slots`` rows); where its matrices fit,
    the ssd-step kernel over the stacked state of ``layers`` layers
    through the cache manager's seam against the composed step on a
    layer's slice, output and state, the other layers bit for bit; then
    on a TPU, the kernel alone at the cell's ``slots`` matrices of
    ``[state, heads * head_dim]``, the us a call beside a plain copy of
    the same bytes (the layer's matrices read, scaled and written back in
    place) and beside the composed step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from autodist_tpu.kernel.pallas import ssd_step as ss
    from autodist_tpu.models import pipeline_lm as lm
    from autodist_tpu.models.transformer import LinearMixerSpec
    from autodist_tpu.serving import kv_cache

    ph = "ssd"
    r = np.random.RandomState(seed)
    rand = lambda *shape: jnp.asarray(r.randn(*shape), jnp.float32)
    P, N, G = head_dim, state, groups
    mixer = LinearMixerSpec.ssd(heads, P, N, G)
    done = []

    # ---- a window, then one position: against position by position -----
    B, T = check_slots, window
    x, Bm, Cm = rand(B, T, heads, P), rand(B, T, G, N), rand(B, T, G, N)
    dt = jax.nn.softplus(rand(B, T, heads))
    g = -dt * jnp.exp(0.02 * rand(heads))

    def by_position(x, Bm, Cm, g, dt):
        def one(S, at):
            y, S = lm.ssd_step(*at, S)
            return S, y
        first = lambda t: jnp.moveaxis(t, 1, 0)
        S, y = jax.lax.scan(
            one, jnp.zeros((B, *mixer.state_shape), jnp.float32),
            tuple(map(first, (x, Bm, Cm, g, dt))))
        return jnp.moveaxis(y, 0, 1), S

    ref_y, ref_S = jax.jit(by_position)(x, Bm, Cm, g, dt)
    chunked = jax.jit(lm.ssd_chunked)
    (y, after), s = timed(lambda: jax.block_until_ready(
        chunked(x, Bm, Cm, g, dt, None)))
    require_close(ph, f"ssd_chunked output (window of {T} from no state, "
                      f"{heads} heads of {P} on {G} group(s) of {N})", y,
                  ref_y, 1e-4)
    require_close(ph, "its closing state", after, ref_S, 1e-4)
    say(ph, f"a window of {T} in chunks of {lm.SSD_CHUNK}: first call "
            f"{s:.2f}s")
    half = T // 2
    ops = (x, Bm, Cm, g, dt)
    head = chunked(*(t[:, :half] for t in ops), None)[1]
    y2, carried = chunked(*(t[:, half:] for t in ops), head)
    require_close(ph, f"ssd_chunked output of {T - half} positions on the "
                      f"state of {half}", y2, ref_y[:, half:], 1e-4)
    require_close(ph, "its state against the whole window's", carried,
                  after, 1e-4)
    before = chunked(*(t[:, :-1] for t in ops), None)[1]
    last = tuple(t[:, -1] for t in ops)
    y1, stepped = jax.jit(lm.ssd_step)(*last, before)
    require_close(ph, "ssd_step output after the window", y1, ref_y[:, -1],
                  1e-4)
    require_close(ph, "ssd_step state against the chunked form's", stepped,
                  after, 1e-4)
    done += ["ssd_chunked", "ssd_step"]

    # ---- the fused kernel, in place in the cache manager's array -------
    shape = (layers, slots, *mixer.state_shape)
    if not ss.ssd_step_fits(shape, jnp.float32):
        return done
    layer = layers // 2
    lay = lambda word: kv_cache.DenseLayout(
        (0, 1, 1, P, 8), {"ssd_step": word}, recurrent=(layers, mixer))
    few = min(layers, 3)
    at = few // 2
    small = lambda: jnp.stack([before] * few)
    step = lambda word: jax.jit(lambda ssm: lay(word).advance_ssd(
        *last, ssm, jnp.int32(at)))
    want_y, want_ssm = step(False)(small())
    y, ssm = step(True)(small())
    require_close(ph, f"ssd_step kernel output ({B} slots x {G} matrices "
                      f"of [{N}, {mixer.group_width}], {few} layers)", y,
                  want_y, 1e-4)
    require_close(ph, "ssd_step kernel state", ssm[at], want_ssm[at], 1e-5)
    require(bool((ssm[:at] == before).all()
                 and (ssm[at + 1:] == before).all()), ph,
            "ssd_step kernel leaves the other layers", "bit for bit")
    done.append("ssd_step_fused")
    if jax.default_backend() != "tpu":     # the interpreter: no times
        return done

    # ---- alone, at the cell's matrices: us a call ----------------------
    B = slots
    x1, B1, C1 = rand(B, heads, P), rand(B, G, N), rand(B, G, N)
    dt1 = jax.nn.softplus(rand(B, heads))
    g1 = -dt1
    tile = rand(1, 1, *mixer.state_shape)
    full = lambda: jnp.broadcast_to(tile, shape) + 0.0

    def per_call(fn):
        """Seconds a call of ``fn(x, ssm) -> (y, ssm)``: ``reps`` calls in
        one program, the array donated and carried and each call's input
        made from the last one's output."""
        def chained(_, c):
            y, ssm = fn(*c)
            return c[0] + 1e-3 * y, ssm

        many = jax.jit(lambda ssm: jax.lax.fori_loop(
            0, reps, chained, (x1, ssm)), donate_argnums=0)
        ssm = jax.block_until_ready(many(full()))[1]
        return timed(lambda: jax.block_until_ready(many(ssm)))[1] / reps

    def plain(x_, ssm):
        where = (layer,) + (0,) * (ssm.ndim - 1)
        rows = jax.lax.dynamic_slice(ssm, where, (1, *ssm.shape[1:]))
        return x_, jax.lax.dynamic_update_slice(ssm, rows * 0.999, where)

    took = {
        "plain copy": per_call(plain),
        "kernel": per_call(lambda x_, ssm: ss.ssd_step_fused(
            x_, B1, C1, g1, dt1, ssm, jnp.int32(layer))),
        "composed step": per_call(lambda x_, ssm: lay(False).advance_ssd(
            x_, B1, C1, g1, dt1, ssm, jnp.int32(layer)))}
    moved = 2 * 4 * B * int(np.prod(mixer.state_shape))
    say(ph, f"state step of one of {layers} layers, {B} slots x {G} "
            f"matrices of [{N}, {mixer.group_width}], {moved / 1e6:.1f} MB "
            f"there and back, us a call alone: " + ", ".join(
                f"{name} {t * 1e6:.1f} ({moved / t / 1e9:.0f} GB/s)"
                for name, t in took.items()))
    done.append("ssd_step_alone")
    return done


def ring_kernels_phase(devices, *, interpret: bool, elems: int = 1 << 18,
                       matmul_shape=(1024, 1024, 1024), seed: int = 0) -> list:
    """The three ring kernels whole, inside ``shard_map`` over
    ``devices`` — their ``ppermute`` hops cross real links — against the
    exact float32 collective each replaces."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from autodist_tpu.kernel.pallas.a2a_ring import quantized_ring_all_to_all
    from autodist_tpu.kernel.pallas.collective_matmul import \
        collective_matmul_row_fused
    from autodist_tpu.kernel.pallas.quant_ring import \
        quantized_ring_all_reduce

    ph = "kernel:ring"
    n = len(devices)
    mesh = Mesh(np.array(devices), ("model",))
    r = np.random.RandomState(seed)

    def on_mesh(fn, *specs):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=specs,
                                     out_specs=P("model"), check_vma=False))

    x = jnp.asarray(r.randn(n, elems), jnp.float32)
    got = on_mesh(lambda a: quantized_ring_all_reduce(
        a, "model", interpret=interpret), P("model"))(x)
    ref = np.broadcast_to(np.asarray(x).sum(0), (n, elems))
    require_close(ph, f"quantized_ring_all_reduce over {n} devices", got,
                  ref, RING_RTOL)

    M, K, C = matmul_shape
    xs = jnp.asarray(r.randn(M, K) * 0.1, jnp.float32)
    ks = jnp.asarray(r.randn(K, C) * 0.1, jnp.float32)
    got = on_mesh(lambda a, b: collective_matmul_row_fused(
        a, b, "model", 1, interpret), P(None, "model"), P("model"))(xs, ks)
    ref = np.tile(np.asarray(xs) @ np.asarray(ks), (n, 1))
    require_close(ph, f"collective_matmul_row_fused over {n} devices", got,
                  ref, ATTN_FWD_RTOL)

    rows = 8 * n
    y = jnp.asarray(r.randn(n * rows, elems // rows), jnp.float32)
    got = on_mesh(lambda a: quantized_ring_all_to_all(
        a, "model", split_axis=0, concat_axis=0, interpret=interpret),
        P("model"))(y)
    ref = on_mesh(lambda a: lax.all_to_all(
        a, "model", split_axis=0, concat_axis=0, tiled=True),
        P("model"))(y)
    require_close(ph, f"quantized_ring_all_to_all over {n} devices", got,
                  ref, RING_RTOL)
    return ["quantized_ring_all_reduce", "collective_matmul_row_fused",
            "quantized_ring_all_to_all"]


# --------------------------------------------------------------------------- #
# 5. multi-chip
# --------------------------------------------------------------------------- #
def multichip_phase(cfg, params, prompts, ref_tokens, *, serve_sizes: dict,
                    interpret: bool) -> None:
    """What needs more than one device: the six lowering programs, the
    ring kernels over real links, tp=2 serving with the cache on two
    devices, and two tp=1 engines on two different devices.  (The
    data-parallel BERT run is :func:`training_phase` itself —
    ``ResourceSpec({})`` takes every visible device.)"""
    import jax

    import __graft_entry__

    ph = "multichip"
    devices = sorted(jax.devices(), key=lambda d: d.id)
    n = len(devices)
    say(ph, f"using {n} devices: {[str(d) for d in devices]}")
    ran, s = timed(lambda: __graft_entry__.run_lowering_programs(n))
    want = 6 if n % 4 == 0 else 4 if n % 2 == 0 else 2
    require(len(ran) == want, ph,
            f"the {want} lowering programs a {n}-device host admits "
            "completed", f"{ran} in {s:.1f}s")

    ring_kernels_phase(devices, interpret=interpret)
    multichip_serving(cfg, params, prompts, ref_tokens, devices,
                      serve_sizes=serve_sizes)


def multichip_serving(cfg, params, prompts, ref_tokens, devices, *,
                      serve_sizes: dict) -> None:
    """tp=2 serving with the cache on two devices and two tp=1 engines
    on two different devices, each with the engine's own decode election
    (on the chip: the fused kernel, on ``heads / tp`` heads inside
    ``shard_map``), against the composed one-device engine's tokens."""
    import jax

    ph = "multichip"
    elected = "flash_decode" if jax.default_backend() == "tpu" else None
    tp2 = serving_phase(cfg, params, prompts, label="tp2+vocab_parallel",
                        tensor_parallel=2, vocab_parallel=True,
                        devices=devices[:2], marker_of=elected,
                        **serve_sizes)
    cache = tp2["engine"].cache.k
    require(devices_of(cache) == set(devices[:2])
            and cache.sharding.spec[2] == "model", ph,
            "tp=2 KV cache's model axis is on two devices",
            f"{sorted(map(str, devices_of(cache)))} spec="
            f"{cache.sharding.spec}")
    require_mostly_identical(ph + ":tp2", tp2["tokens"], ref_tokens)

    pair = []
    for dev in devices[:2]:
        out = serving_phase(cfg, params, prompts, label=f"tp1@{dev}",
                            devices=[dev], marker_of=elected,
                            **serve_sizes)
        eng = out["engine"]
        where = devices_of((eng.params, eng.cache.k, eng.cache.v, eng._tok))
        require(where == {dev}, ph,
                f"a tp=1 engine given devices=[{dev}] lives there",
                sorted(map(str, where)))
        pair.append(out["tokens"])
    require(pair[0] == pair[1], ph,
            "two engines on two devices decode the same tokens",
            f"{sum(a == b for a, b in zip(*pair))}/{len(pair[0])} identical")


# --------------------------------------------------------------------------- #
def main() -> int:
    # The strategy dump at INFO is ~200 lines for BERT-base.
    os.environ.setdefault("AUTODIST_TPU_MIN_LOG_LEVEL", "WARNING")
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke.py needs a TPU: jax.default_backend() is "
              f"{backend!r} (devices: {jax.devices()}); nothing was run",
              file=sys.stderr)
        return 1

    import jax.numpy as jnp
    import jaxlib
    import optax

    from autodist_tpu.models import bert
    from autodist_tpu.models.pipeline_lm import make_pipeline_lm_trainable
    from autodist_tpu.models.transformer import TransformerConfig
    from autodist_tpu.utils.compile_cache import enable_compile_cache

    t_start = time.perf_counter()
    cache_dir = enable_compile_cache()
    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not importable"
    dev0 = jax.devices()[0]
    n = jax.device_count()
    say("chip_smoke", f"platform={dev0.platform} device_kind="
                      f"{dev0.device_kind!r} devices={n} jax={jax.__version__} "
                      f"jaxlib={jaxlib.__version__} libtpu={libtpu_version}")
    def cache_entries():
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    say("chip_smoke", f"compile cache at {cache_dir}: {cache_entries()} "
                      "entries at start (a warm cache shows as collapsed "
                      "compile_s below)")

    training_phase(
        bert.bert_base(dropout_rate=0.0, attention_dropout_rate=0.0),
        resource_spec={}, batch_per_device=16, seq_len=512, num_masked=76)

    cfg = TransformerConfig(vocab_size=32768, hidden_size=1024, num_layers=8,
                            num_heads=16, mlp_dim=4096, max_len=1024,
                            dtype=jnp.bfloat16, dropout_rate=0.0,
                            attention_dropout_rate=0.0)
    sizes = dict(num_slots=8, prefill_len=64, decode_steps=16,
                 max_new_tokens=32)
    params, s = timed(lambda: make_pipeline_lm_trainable(
        cfg, optax.adam(1e-3), jax.random.PRNGKey(0)).params)
    say("serve", f"params init_s={s:.1f}")
    prompts = make_prompts(cfg.vocab_size, sizes["prefill_len"])
    paged = dict(kv_layout="paged", kv_block_len=16)
    dense = serving_phase(cfg, params, prompts, label="dense",
                          kernel={"flash_decode": False}, **sizes)
    serving_phase(cfg, params, prompts, label="paged", **paged, **sizes)
    serving_parity(cfg, params, prompts[0], dense["tokens"][0][0])

    kernels = kernels_phase(interpret=False)
    for label, marker, kw in (
            # no kernel= at all: on the chip the dense decode elects
            ("dense, the default election", "flash_decode", None),
            ("paged+flash_decode", "flash_decode", paged),
            ("paged+chunked+flash_prefill", "flash_prefill",
             dict(paged, prefill_chunk=32))):
        elect = {} if kw is None else dict(kw, kernel={marker: True})
        out = serving_phase(cfg, params, prompts, label=label,
                            marker_of=marker, **elect, **sizes)
        require_mostly_identical(f"serve:{label}", out["tokens"],
                                 dense["tokens"])
    say("kernel", f"compiled (interpret=False) and agreed: {kernels}")
    say("mixed", f"agreed with their composed forms: {mixed_block_phase()}")
    say("latent", f"agreed with each other: {latent_block_phase()}")
    say("hybrid", f"agreed with their composed forms: "
                  f"{hybrid_latent_phase()}")
    say("retention", f"agreed with their composed forms: "
                     f"{retention_block_phase()}")
    say("ssd", f"agreed with their composed forms: {ssd_block_phase()}")

    if n > 1:
        multichip_phase(cfg, params, prompts, dense["tokens"],
                        serve_sizes=sizes, interpret=False)

    say("chip_smoke", f"all phases passed in "
                      f"{time.perf_counter() - t_start:.0f}s; compile cache "
                      f"now holds {cache_entries()} entries")
    print(json.dumps({"ok": True, "device": {
        "platform": dev0.platform, "kind": dev0.device_kind, "count": n}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
