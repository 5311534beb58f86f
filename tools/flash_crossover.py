"""Measure the einsum-vs-Pallas-flash attention crossover on real hardware.

Round-3 verdict Weak #2: at seq 512 plain einsum beats this repo's flash
kernel and the long-context win was only a projection.  This driver
measures fwd+bwd wall-clock of both attention implementations across
sequence lengths and block sizes, printing one JSON line per point —
the curve that goes into PERF.md and justifies (or bounds) when the
bench self-tuner should pick the kernel.

Usage: ``python tools/flash_crossover.py [--seqs 512,1024,2048,4096]``

``--decode`` switches to the serving-side crossover: single-query-per-
slot shapes (one token attending over a KV cache of each ``--seqs``
length) at ``--fill`` slot-length fractions, comparing the composed
einsum cache attention (``serving/kv_cache.cached_attention``) against
the Pallas flash-decode kernel.  Each point prints one provenance-
stamped record in the bench schema, and ``--write-calibration`` merges
the measured crossover into calibration.json's ``"kernel"`` section
(``flash_decode_crossover_len`` / ``flash_decode_speedup``) — the
constants ``CostModel.decode_cost`` elects the kernel by.
"""
import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from autodist_tpu.ops.flash_attention import flash_attention


def attention_flops(b, l, h, d):
    """fwd matmul FLOPs: scores (2*b*h*l*l*d) + values (same); x3 fwd+bwd
    (bwd recompute excluded — both impls pay their own)."""
    return 3.0 * 2.0 * 2.0 * b * h * l * l * d


def einsum_attention(q, k, v, causal):
    depth = q.shape[-1]
    s = jnp.einsum("blhd,bmhd->bhlm", q, k) / np.sqrt(depth)
    s = s.astype(jnp.float32)
    if causal:
        L = q.shape[1]
        mask = jnp.tril(jnp.ones((L, L), bool))
        s = jnp.where(mask[None, None], s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhlm,bmhd->blhd", p, v)


def fence(out):
    """Host round-trip on one scalar that depends on the computation —
    honest timing on proxied backends (see bench.py)."""
    return float(np.asarray(jax.tree.leaves(out)[0]).ravel()[0])


def timed(fn, args, steps):
    fence(fn(*args))  # compile
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    fence(out)
    return (time.perf_counter() - t0) / steps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="512,1024,2048,4096")
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--tokens", type=int, default=8192,
                    help="per-step token budget: batch = tokens // seq")
    ap.add_argument("--causal", action="store_true")
    ap.add_argument("--blocks", default="128,256,512",
                    help="flash block sizes to try (best reported)")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--write", default="",
                    help="merge results into this flash_tuning.json "
                         "(per-length best blocks + crossover_len; the "
                         "kernel's default blocks and the flash_wins() "
                         "helper read it — commit it at the repo root)")
    ap.add_argument("--decode", action="store_true",
                    help="measure the serving-side crossover instead: "
                         "single-query flash-decode vs the composed "
                         "einsum cache attention over --seqs cache "
                         "lengths")
    ap.add_argument("--prefill", action="store_true",
                    help="measure the chunked-prefill crossover: the "
                         "paged flash-prefill kernel vs the composed "
                         "gather path over --chunks chunk sizes at "
                         "each --seqs cache length; "
                         "--write-calibration merges "
                         "flash_prefill_crossover_chunk / "
                         "flash_prefill_speedup into the 'kernel' "
                         "section")
    ap.add_argument("--chunks", default="64,128,256,512",
                    help="--prefill: prefill chunk sizes to sweep")
    ap.add_argument("--slots", type=int, default=8,
                    help="--decode: batch slots per step")
    ap.add_argument("--fill", default="1.0,0.5",
                    help="--decode: slot-length fractions of the cache "
                         "length (the occupancy distribution decode "
                         "actually sees)")
    ap.add_argument("--write-calibration", default="",
                    metavar="PATH",
                    help="--decode: merge the measured crossover into "
                         "this calibration.json's 'kernel' section "
                         "(flash_decode_crossover_len / "
                         "flash_decode_speedup)")
    args = ap.parse_args()
    if args.decode:
        return _main_decode(args)
    if args.prefill:
        return _main_prefill(args)

    H, D = args.heads, args.head_dim
    causal = bool(args.causal)
    records = []
    wrote = False
    for L in [int(s) for s in args.seqs.split(",")]:
        B = max(args.tokens // L, 1)
        r = np.random.RandomState(0)
        q, k, v = (jnp.asarray(r.randn(B, L, H, D), jnp.bfloat16)
                   for _ in range(3))

        def make_grad(attn):
            def loss(q, k, v):
                return jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

        t_einsum = timed(make_grad(
            lambda q, k, v: einsum_attention(q, k, v, causal)),
            (q, k, v), args.steps)

        best = None
        for blk in [int(b) for b in args.blocks.split(",")]:
            if blk > L:
                continue
            try:
                t = timed(make_grad(
                    lambda q, k, v, blk=blk: flash_attention(
                        q, k, v, causal=causal, block_q=blk, block_k=blk)),
                    (q, k, v), args.steps)
                if best is None or t < best[0]:
                    best = (t, blk)
            except Exception as e:
                print(f"# flash L={L} block={blk} failed: {e}",
                      file=sys.stderr)
        t_flash, blk = best if best else (float("nan"), 0)
        rec = {
            "seq": L, "batch": B, "heads": H, "head_dim": D,
            "causal": causal,
            "einsum_ms": round(t_einsum * 1e3, 3),
            "flash_ms": round(t_flash * 1e3, 3),
            "flash_block": blk,
            "flash_speedup": round(t_einsum / t_flash, 3)
            if t_flash == t_flash else None,
            "attn_tflops_einsum": round(
                attention_flops(B, L, H, D) / t_einsum / 1e12, 2),
        }
        records.append(rec)
        print(json.dumps(rec), flush=True)
        if args.write:
            # Merge-write after EVERY length, not once at the end: a
            # timeout mid-run must not lose the points already measured.
            wrote = _merge_write(records, args.write, causal) or wrote
    wins = [r for r in records if (r["flash_speedup"] or 0) > 1.0]
    print(json.dumps({
        "summary": "flash wins from seq "
                   f"{min((r['seq'] for r in wins), default=None)}"
                   if wins else "einsum wins at every measured length",
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
    }))
    if args.write and not wrote:
        print("# no successful flash timing; tuning table unchanged",
              file=sys.stderr)


def _main_decode(args):
    """The ``--decode`` mode: one record per (cache length, fill)
    point, bench-schema-shaped and provenance-stamped; the summary line
    derives the crossover, and ``--write-calibration`` commits it."""
    from autodist_tpu.serving.kv_cache import cached_attention
    from autodist_tpu.kernel.pallas.flash_decode import \
        flash_decode_attention
    from autodist_tpu.telemetry.records import provenance

    H, D, B = args.heads, args.head_dim, args.slots
    fills = [float(f) for f in args.fill.split(",")]
    records = []
    for T in [int(s) for s in args.seqs.split(",")]:
        r = np.random.RandomState(0)
        q = jnp.asarray(r.randn(B, 1, H, D), jnp.bfloat16)
        k = jnp.asarray(r.randn(B, H, T, D), jnp.bfloat16)
        v = jnp.asarray(r.randn(B, H, T, D), jnp.bfloat16)
        for fill in fills:
            lengths = jnp.full((B,), max(int(T * fill) - 1, 0),
                               jnp.int32)
            t_einsum = timed(jax.jit(
                lambda q, k, v, l: cached_attention(
                    q, k, v, l, dtype=jnp.bfloat16)),
                (q, k, v, lengths), args.steps)
            try:
                t_flash = timed(jax.jit(
                    lambda q, k, v, l: flash_decode_attention(
                        q, k, v, l, dtype=jnp.bfloat16)),
                    (q, k, v, lengths), args.steps)
            except Exception as e:
                print(f"# flash decode T={T} fill={fill} failed: {e}",
                      file=sys.stderr)
                continue
            rec = {
                "metric": "flash_decode_crossover",
                "kv_len": T, "fill": fill, "slots": B, "heads": H,
                "head_dim": D,
                "einsum_ms": round(t_einsum * 1e3, 4),
                "flash_ms": round(t_flash * 1e3, 4),
                "value": round(t_einsum / t_flash, 4),
                "unit": "ratio", "scored": True,
                "provenance": provenance(),
            }
            records.append(rec)
            print(json.dumps(rec), flush=True)
    wins = sorted({r["kv_len"] for r in records if r["value"] > 1.0})
    crossover = wins[0] if wins else None
    speedups = [r["value"] for r in records
                if crossover is not None and r["kv_len"] >= crossover]
    summary = {
        "summary": (f"flash decode wins from kv_len {crossover}"
                    if crossover is not None
                    else "einsum wins at every measured cache length"),
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
    }
    print(json.dumps(summary))
    if args.write_calibration and records:
        if jax.default_backend() == "cpu":
            # Interpreter timings say nothing about the TPU kernel and
            # would mislead every chip's planning (load_calibration has
            # no per-section provenance to filter them back out).
            print("# refusing to write CPU-measured kernel constants "
                  f"into {args.write_calibration}", file=sys.stderr)
            return
        table = {}
        if os.path.exists(args.write_calibration):
            try:
                with open(args.write_calibration) as f:
                    table = json.load(f)
            except (OSError, ValueError):
                table = {}
        kern = dict(table.get("kernel", {}))
        if crossover is not None:
            kern["flash_decode_crossover_len"] = crossover
            kern["flash_decode_speedup"] = round(
                sum(speedups) / len(speedups), 3)
        else:
            # Flash never won: push the crossover past every measured
            # length so the cost model stops electing it in this range.
            kern["flash_decode_crossover_len"] = 2 * max(
                r["kv_len"] for r in records)
        table["kernel"] = kern
        meta = dict(table.get("meta", {}))
        meta["kernel_source"] = (
            f"tools/flash_crossover.py --decode on "
            f"{jax.devices()[0].device_kind} "
            f"({provenance().get('git_sha', '')[:12]})")
        table["meta"] = meta
        tmp = args.write_calibration + ".tmp"
        with open(tmp, "w") as f:
            json.dump(table, f, indent=1)
        os.replace(tmp, args.write_calibration)
        print(f"# wrote kernel section to {args.write_calibration}",
              file=sys.stderr)


def _main_prefill(args):
    """The ``--prefill`` mode: one record per (cache length, chunk
    size) point — the paged flash-prefill kernel against its composed
    gather golden on identical block tables — and the summary derives
    the chunk-size crossover.  ``--write-calibration`` merges
    ``flash_prefill_crossover_chunk`` / ``flash_prefill_speedup`` into
    the ``"kernel"`` section ``CostModel`` loads, closing the loop:
    ``default_serving_candidates(ladder=True)`` seeds its chunked
    candidate at exactly this measured chunk."""
    from autodist_tpu.kernel.pallas.flash_prefill import \
        flash_prefill_attention_paged
    from autodist_tpu.serving.kv_cache import paged_chunk_attention
    from autodist_tpu.telemetry.records import provenance

    H, D, B = args.heads, args.head_dim, args.slots
    records = []
    chunks = [int(c) for c in args.chunks.split(",")]
    for T in [int(s) for s in args.seqs.split(",")]:
        bl = 16
        max_blocks = -(-T // bl)
        r = np.random.RandomState(0)
        k_pool = jnp.asarray(
            r.randn(B * max_blocks, H, bl, D), jnp.bfloat16)
        v_pool = jnp.asarray(
            r.randn(B * max_blocks, H, bl, D), jnp.bfloat16)
        table = jnp.asarray(
            r.permutation(B * max_blocks).reshape(B, max_blocks),
            jnp.int32)
        for C in chunks:
            if C > T:
                continue
            q = jnp.asarray(r.randn(B, C, H, D), jnp.bfloat16)
            # every slot's chunk starts mid-prompt: rows attend through
            # earlier blocks via the table, the shape the chunked
            # prefill loop dispatches
            starts = jnp.full((B,), T - C, jnp.int32)
            t_gather = timed(jax.jit(
                lambda q, s, t: paged_chunk_attention(
                    q, k_pool, v_pool, s, t, block_len=bl,
                    dtype=jnp.bfloat16)),
                (q, starts, table), args.steps)
            try:
                t_flash = timed(jax.jit(
                    lambda q, s, t: flash_prefill_attention_paged(
                        q, k_pool, v_pool, s, t, block_len=bl,
                        dtype=jnp.bfloat16)),
                    (q, starts, table), args.steps)
            except Exception as e:
                print(f"# flash prefill T={T} chunk={C} failed: {e}",
                      file=sys.stderr)
                continue
            rec = {
                "metric": "flash_prefill_crossover",
                "kv_len": T, "chunk": C, "slots": B, "heads": H,
                "head_dim": D, "block_len": bl,
                "gather_ms": round(t_gather * 1e3, 4),
                "flash_ms": round(t_flash * 1e3, 4),
                "value": round(t_gather / t_flash, 4),
                "unit": "ratio", "scored": True,
                "provenance": provenance(),
            }
            records.append(rec)
            print(json.dumps(rec), flush=True)
    wins = sorted({r["chunk"] for r in records if r["value"] > 1.0})
    crossover = wins[0] if wins else None
    speedups = [r["value"] for r in records
                if crossover is not None and r["chunk"] >= crossover]
    print(json.dumps({
        "summary": (f"flash prefill wins from chunk {crossover}"
                    if crossover is not None
                    else "the composed gather wins at every measured "
                         "chunk size"),
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
    }))
    if args.write_calibration and records:
        if jax.default_backend() == "cpu":
            print("# refusing to write CPU-measured kernel constants "
                  f"into {args.write_calibration}", file=sys.stderr)
            return
        table = {}
        if os.path.exists(args.write_calibration):
            try:
                with open(args.write_calibration) as f:
                    table = json.load(f)
            except (OSError, ValueError):
                table = {}
        kern = dict(table.get("kernel", {}))
        if crossover is not None:
            kern["flash_prefill_crossover_chunk"] = crossover
            kern["flash_prefill_speedup"] = round(
                sum(speedups) / len(speedups), 3)
        else:
            kern["flash_prefill_crossover_chunk"] = 2 * max(
                r["chunk"] for r in records)
        table["kernel"] = kern
        meta = dict(table.get("meta", {}))
        meta["kernel_prefill_source"] = (
            f"tools/flash_crossover.py --prefill on "
            f"{jax.devices()[0].device_kind} "
            f"({provenance().get('git_sha', '')[:12]})")
        table["meta"] = meta
        tmp = args.write_calibration + ".tmp"
        with open(tmp, "w") as f:
            json.dump(table, f, indent=1)
        os.replace(tmp, args.write_calibration)
        print(f"# wrote kernel section to {args.write_calibration}",
              file=sys.stderr)


def _merge_write(records, path, causal) -> bool:
    """Merge measured points into the tuning table the kernel reads, PER
    LENGTH: previously measured lengths (and the other causal-ness
    branch) are preserved; lengths where flash failed to run write
    nothing — a measurement failure must stay distinguishable from
    "flash measured and lost" (flash_wins derives the verdict from the
    per-length speedup records at read time)."""
    ok = [r for r in records
          if r["flash_block"] and r["flash_speedup"] is not None]
    if not ok:
        return False
    table = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                loaded = json.load(f)
            table = loaded if isinstance(loaded, dict) else {}
        except (OSError, ValueError):
            table = {}
    if table and table.get("backend") != jax.default_backend():
        # Cross-backend merge would mislabel stale entries under this
        # run's provenance stamp (or discard this run's via the old
        # stamp) — measurements from different backends don't compose;
        # start a fresh table.  Unstamped legacy tables have unknown
        # provenance: same treatment.
        print(f"# discarding {path} measured on "
              f"{table.get('backend')!r} (this run: "
              f"{jax.default_backend()!r})", file=sys.stderr)
        table = {}
    key = "causal" if causal else "noncausal"
    branch = table.get(key)
    branch = dict(branch) if isinstance(branch, dict) else {}
    blocks = branch.get("blocks")
    blocks = dict(blocks) if isinstance(blocks, dict) else {}
    speedup = branch.get("speedup")
    speedup = dict(speedup) if isinstance(speedup, dict) else {}
    for r in ok:
        blocks[str(r["seq"])] = r["flash_block"]
        speedup[str(r["seq"])] = r["flash_speedup"]
    branch["blocks"] = blocks
    branch["speedup"] = speedup
    measured_wins = sorted(int(k) for k, v in speedup.items() if v > 1.0)
    branch["crossover_len"] = measured_wins[0] if measured_wins else None
    table[key] = branch
    table["device_kind"] = jax.devices()[0].device_kind
    # Provenance: load_tuning refuses to auto-load CPU-measured tables
    # (interpret-mode timings would mislead TPU defaults).
    table["backend"] = jax.default_backend()
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(table, f, indent=1)
    os.replace(tmp, path)   # a mid-write kill must not corrupt the table
    print(f"# wrote {path}", file=sys.stderr)
    return True


if __name__ == "__main__":
    main()
