"""Time a decode step's routed experts alone, on real hardware.

The sibling of ``tools/flash_crossover.py`` for
``parallel.moe.routed_experts``' product stage: sorted (row, expert)
pairs through the held experts, at the shapes of the benchmark's two
routed cells (``--cell deepseek-v2-lite``: 64 rows x 6 of 64, 8 held,
``wi [8, 2048, 2816]``, ``wo [8, 1408, 2048]``, 26 layers;
``--cell qwen3-next``: 32 rows x 10 of 512, 64 held, ``wi [64, 2048,
1024]``, ``wo [64, 512, 2048]``, 16 layers), bf16, routing uniform from
``--seed``, every layer's arrays its own and the layers chained in one
program so that nothing stays in a cache.  Four readings, one JSON line
each, microseconds a layer and GB/s of the hit experts' bytes:

* ``ragged_bound``: today's two ``jax.lax.ragged_dot`` over the pairs
  ``_pairs_bound`` gives (192, 320);
* ``ragged_live``: the same over the live pairs rounded up to 64;
* ``dma_walk``: a bare walk of the hit experts' slabs through VMEM with
  2, 3 and 4 slabs requested ahead and no product: the roof this chip
  gives the walk;
* ``kernel``: ``kernel.pallas.grouped_matmul`` at each ``--slab-kib`` x
  ``--in-flight``, and its widest difference from ``ragged_bound``.

``--pairs`` adds the kernel and ``ragged_dot`` at other row counts (rows
x top_k pairs): where the election's ``MAX_GROUPED_PAIRS`` is read
from.  ``--held-only`` routes every choice onto a held expert: what the
most skewed step costs each of them.  Nothing is written: the kernel's
constants are set by hand from a run of this on the chip.
"""
import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from autodist_tpu.kernel.pallas import grouped_matmul as gm
from autodist_tpu.parallel.moe import _pairs_bound, ragged_products

# rows, top_k, experts, held, hidden, expert width, routed layers
CELLS = {
    "deepseek-v2-lite": (64, 6, 64, 8, 2048, 1408, 26),
    "qwen3-next": (32, 10, 512, 64, 2048, 512, 16),
}


def routing(rng, rows, top_k, experts, held, pairs):
    """One layer's ``sizes [held]``: ``rows`` rows choose ``top_k``
    distinct experts of ``experts`` uniformly, the held ones are the
    first ``held``; at most ``pairs`` land."""
    chosen = np.stack([rng.permutation(experts)[:top_k]
                       for _ in range(rows)]).reshape(-1)
    sizes = np.bincount(chosen[chosen < held], minlength=held)
    assert sizes.sum() <= pairs, (sizes.sum(), pairs)
    return sizes.astype(np.int32)


def _walk_kernel(visit_ref, count_ref, wi_hbm, wo_hbm, o_ref, wi_buf,
                 wo_buf, sem, *, tk, tm, ahead):
    """The kernel's walk of the hit experts' slabs, and nothing else."""
    count = count_ref[0]
    slab, request, _, slabs = gm.slab_walk(
        visit_ref, count, wi_hbm, wo_hbm, wi_buf, wo_buf, sem, tk=tk, tm=tm)
    for r in range(ahead):
        request(0, r)

    def expert(idx, carry):
        for r in range(slabs):
            request(idx, r + ahead)
            slab(idx, r)[0].wait()
        return carry

    jax.lax.fori_loop(0, count, expert, 0)
    o_ref[...] = wi_buf[0, :8, :128].astype(jnp.float32) \
        + wo_buf[0, :8, :128].astype(jnp.float32)


def dma_walk(x, wi, wo, sizes, *, slab_bytes, in_flight):
    """``grouped_matmul``'s operands; reads what it reads, computes
    nothing, and returns zeros of ``x``'s shape in float32."""
    H, M = wi.shape[1], wo.shape[1]
    tk = gm.slab_rows(H, wi.shape[2] * 2, slab_bytes)
    tm = gm.slab_rows(M, H * 2, slab_bytes)
    depth = in_flight + 1
    corner = pl.pallas_call(
        functools.partial(_walk_kernel, tk=tk, tm=tm, ahead=in_flight),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=pl.BlockSpec((8, 128), lambda i, *_: (0, 0)),
            scratch_shapes=[pltpu.VMEM((depth, tk, wi.shape[2]), wi.dtype),
                            pltpu.VMEM((depth, tm, H), wo.dtype),
                            pltpu.SemaphoreType.DMA((2, depth))]),
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=gm.VMEM_LIMIT_BYTES),
        interpret=gm.default_interpret(),
    )(*gm.visited(sizes), wi, wo)
    return jnp.zeros(x.shape, jnp.float32) + corner[0, 0] * 0.0


def chained(products, pairs):
    """One program: every layer's ``products`` over the first ``pairs``
    sorted rows, each layer's rows the last one's plus 0 x its result (so
    that no layer can be dropped or reordered, and the rows stay what
    they were: ``silu(gate) * up`` squares what it is fed)."""
    def run(x, wis, wos, sizes):
        for wi, wo, s in zip(wis, wos, sizes):
            y = products(x[:pairs], wi, wo, s)
            # rows past the groups are nobody's, whatever was left there
            live = (jnp.arange(pairs) < s.sum())[:, None]
            x = x.at[:pairs].add(
                (jnp.where(live, y, 0.0) * 0.0).astype(x.dtype))
        return x
    return jax.jit(run)


def timed(fn, args, reps):
    out = fn(*args)
    out.block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        out.block_until_ready()
        best = min(best, (time.perf_counter() - t0) / reps)
    return best, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", choices=sorted(CELLS), required=True)
    ap.add_argument("--layers", type=int, default=0,
                    help="distinct layers chained (0: the cell's own)")
    ap.add_argument("--hidden", type=int, default=0,
                    help="another hidden size (a CPU rehearsal)")
    ap.add_argument("--width", type=int, default=0,
                    help="another expert width (a CPU rehearsal)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--slab-kib", default="768,1536,3072")
    ap.add_argument("--in-flight", default="2,3,4")
    ap.add_argument("--held-only", action="store_true",
                    help="every choice lands on a held expert: the most "
                    "skewed step there is")
    ap.add_argument("--pairs", default="",
                    help="other row counts for the election's bound, "
                    "comma-separated")
    args = ap.parse_args()
    rows, top_k, experts, held, H, M, L = CELLS[args.cell]
    H, M, L = args.hidden or H, args.width or M, args.layers or L
    slabs = [int(s) << 10 for s in args.slab_kib.split(",")]
    ahead = [int(s) for s in args.in_flight.split(",")]
    dev = jax.devices()[0]
    stamp = {"cell": args.cell, "platform": dev.platform,
             "device_kind": dev.device_kind, "layers": L,
             "hidden": H, "width": M, "held": held, "seed": args.seed}
    rng = np.random.default_rng(args.seed)
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 2 * L + 1)
    bf16 = jnp.bfloat16
    wis = [(jax.random.normal(keys[2 * l], (held, H, 2 * M), jnp.float32)
            * 0.02).astype(bf16) for l in range(L)]
    wos = [(jax.random.normal(keys[2 * l + 1], (held, M, H), jnp.float32)
            * 0.02).astype(bf16) for l in range(L)]

    def first_layer(products, x, sizes, pairs):
        live = int(sizes[0].sum())
        return jax.jit(products)(x[:pairs], wis[0], wos[0], sizes[0])[:live]

    def point(name, products, pairs, x, sizes, want=None, **extra):
        sec, _ = timed(chained(products, pairs), (x, wis, wos, sizes),
                       args.reps)
        out = first_layer(products, x, sizes, pairs)
        hit = sum(int((s > 0).sum()) for s in sizes) / L
        moved = hit * 3 * H * M * 2                     # bytes a layer
        rec = dict(stamp, reading=name, us_per_layer=sec / L * 1e6,
                   gb_per_s=moved / (sec / L) / 1e9, experts_hit=hit,
                   pairs=int(x.shape[0]), **extra)
        if want is not None:
            rec["max_abs_diff"] = float(jnp.abs(out - want).max())
            rec["max_abs"] = float(jnp.abs(want).max())
        print(json.dumps(rec), flush=True)
        return out

    def readings(rows):
        total = rows * top_k
        sizes = [jnp.asarray(routing(
            rng, rows, top_k, held if args.held_only else experts, held,
            total)) for _ in range(L)]
        x = jax.random.normal(keys[-1], (total, H), jnp.float32).astype(bf16)
        return total, sizes, x

    def bounds(total, sizes):
        """Today's bound (all the pairs where the held ones exceed it, as
        the layer's ``lax.cond`` goes) and the live pairs in 64s."""
        most = max(int(s.sum()) for s in sizes)
        bound = _pairs_bound(total, held, experts)
        return bound if most <= bound else total, -(-most // 64) * 64

    total, sizes, x = readings(rows)
    bound, live = bounds(total, sizes)
    want = point("ragged_bound", ragged_products, bound, x, sizes,
                 bound=bound)
    point("ragged_live", ragged_products, live, x, sizes, want, bound=live)
    for kib, n in ((s, a) for s in slabs for a in ahead):
        point("dma_walk", functools.partial(
            dma_walk, slab_bytes=kib, in_flight=n), total, x, sizes,
            slab_kib=kib >> 10, in_flight=n)
    for kib, n in ((s, a) for s in slabs for a in ahead):
        point("kernel", functools.partial(
            gm.grouped_matmul, slab_bytes=kib, in_flight=n), total, x,
            sizes, want, slab_kib=kib >> 10, in_flight=n)
    for more in (int(r) for r in args.pairs.split(",") if r):
        gm.MAX_GROUPED_PAIRS = 1024     # the reading is what sets it
        total, sizes, x = readings(more)
        bound, _ = bounds(total, sizes)
        want = point("ragged_bound", ragged_products, bound, x, sizes,
                     rows=more, bound=bound)
        if total <= gm.MAX_GROUPED_PAIRS:
            point("kernel", gm.grouped_matmul, total, x, sizes, want,
                  rows=more)


if __name__ == "__main__":
    main()
