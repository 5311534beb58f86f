"""Measure compressor allreduce cost ratios on the current backend.

The analytic cost model prices compressors by wire-byte counts
(``simulator/cost_model.py COMPRESSOR_FACTOR``), which ignores compute:
int8_ring pays p-1 *sequential* ppermute hops with per-hop requantization
and PowerSGD pays a per-step Gram-Schmidt.  This driver measures each
compressor's end-to-end allreduce wall-clock against the uncompressed
baseline on the live devices and writes ``calibration.json`` at the repo
root — loaded automatically by the cost model (``load_calibration``) so
AutoStrategy ranks with measured ratios instead of guesses.

On a single chip the collective itself is a no-op, so the measured ratio
captures the *compute* overhead (quantize/dequantize passes, power
iteration) — exactly the part the byte count misses; on a multi-device
mesh it also captures the wire.  The JSON records the topology so the
provenance is auditable.

Usage: ``python tools/calibrate_compressors.py [--size 26214400]``
"""
import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from autodist_tpu import const
from autodist_tpu.kernel.compressor import Compressor


def time_compressor(name: str, mesh, x, steps: int = 10) -> float:
    comp = Compressor.create(name)
    state0 = None
    if comp.stateful:
        state0 = jnp.asarray(np.asarray(comp.init_state_flat(x.size),
                                        np.float32))

    def local(x, state):
        out, new_state = comp.allreduce(x, state, const.DATA_AXIS)
        return out, (new_state if comp.stateful else jnp.zeros((1,)))

    fn = jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P() if comp.stateful else P()),
        out_specs=(P(), P()), check_vma=False))
    dummy = state0 if comp.stateful else jnp.zeros((1,))
    out, st = fn(x, dummy)          # compile
    float(np.asarray(out[0]))       # fence
    t0 = time.perf_counter()
    for _ in range(steps):
        out, st = fn(x, st if comp.stateful else dummy)
    float(np.asarray(out[0]))
    return (time.perf_counter() - t0) / steps


def time_quantize(precision: str, x, steps: int = 10) -> float:
    """Wall-clock of one quantize -> dequantize roundtrip (no
    collective): exactly the compute term the per-boundary precision
    policy's cost model charges against its byte savings
    (``simulator/cost_model.py QUANT_PROFILE``)."""
    from autodist_tpu.kernel import quantize as qz

    if precision == "bf16":
        def roundtrip(v):
            return v.astype(jnp.bfloat16).astype(jnp.float32)
    else:
        def roundtrip(v):
            q, scale = qz.quantize_int8(v)
            return qz.dequantize_int8(q, scale)

    fn = jax.jit(roundtrip)
    out = fn(x)                      # compile
    float(np.asarray(out[0]))        # fence
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(out)
    float(np.asarray(out[0]))
    return (time.perf_counter() - t0) / steps


def measure_quant(size: int, steps: int) -> dict:
    """The ``"quant"`` calibration section: measured quantize/dequantize
    seconds per element, per precision, timed at two boundary shapes (a
    TP-activation-sized payload and the full grad-bucket payload) with
    the larger shape setting the per-element constant — fixed overheads
    amortize there, which is the regime the cost model prices."""
    shapes = sorted({max(size // 64, 1), size})
    section: dict = {}
    shape_ms: dict = {}
    for prec in ("bf16", "int8"):
        per_elem = None
        for n in shapes:
            x = jnp.asarray(np.random.RandomState(1).randn(n)
                            .astype(np.float32))
            dt = time_quantize(prec, x, steps)
            shape_ms[f"{prec}_{n}"] = round(dt * 1e3, 4)
            per_elem = dt / n
        section[f"{prec}_s_per_elem"] = float(f"{per_elem:.4g}")
    return section, shape_ms


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", type=int, default=26_214_400,
                    help="flat fp32 buffer elements (default ~100MB, "
                         "BERT-bucket scale)")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "calibration.json"))
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()

    devs = np.array(jax.devices())
    mesh = Mesh(devs, (const.DATA_AXIS,))
    x = jnp.asarray(np.random.RandomState(0).randn(args.size)
                    .astype(np.float32))

    # q/dq compute per boundary shape FIRST (seconds of work, and the
    # term the per-boundary precision policy's pricing needs even if a
    # later compressor compile dies mid-run).
    quant, quant_shape_ms = measure_quant(args.size, args.steps)
    for k, v in quant.items():
        print(f"quant {k:18s} {v:.3e} s/elem", flush=True)

    names = ["none", "bf16", "bf16_ef", "int8_ef", "int8_ring",
             "powersgd:4"]
    times = {}

    def write_out():
        # Incremental, atomic: factors measured so far must survive a
        # mid-run kill (the caller may run this under a timeout).
        base = times["none"]
        factors = {n.partition(":")[0]: round(t / base, 4)
                   for n, t in times.items() if n != "none"}
        record = {
            "compressor_factor": factors,
            # q/dq compute per element (the precision-policy pricing
            # term, simulator/cost_model.py QUANT_PROFILE) — loaded by
            # load_calibration like the "link" constants.
            "quant": quant,
            "meta": {
                "backend": jax.default_backend(),
                "device_kind": devs.flat[0].device_kind,
                "num_devices": int(devs.size),
                "buffer_elements": args.size,
                "baseline_ms": round(base * 1e3, 3),
                "quant_shape_ms": quant_shape_ms,
                "note": "wall-clock ratio vs uncompressed allreduce; on "
                        "one device this is compute overhead only (no "
                        "wire)",
            },
        }
        tmp = args.out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(record, f, indent=1)
        os.replace(tmp, args.out)
        return factors

    for name in names:
        try:
            times[name] = time_compressor(name, mesh, x, args.steps)
            print(f"{name:12s} {times[name]*1e3:8.3f} ms", flush=True)
            if "none" in times and len(times) > 1:
                factors = write_out()
        except Exception as e:  # a compressor that cannot run gets no entry
            print(f"{name:12s} FAILED: {e}", flush=True)
    if "none" not in times:
        raise SystemExit("baseline (none) failed; no calibration written")
    if len(times) == 1:
        raise SystemExit("only the baseline ran; no calibration written")
    print(f"wrote {args.out}: {factors}")


if __name__ == "__main__":
    main()
