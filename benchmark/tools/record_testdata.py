"""Record, on the chip, the small trace that ``tests/test_trace_reduce.py``
reduces: a few runs of a scanned step (two matmuls and a psum over every
chip the machine has) under the same profiler options and annotations a
traced cell uses.  Writes ``<out>.trace.json`` and, beside it, the numbers
the reduction gave when it was cut (``<out>.expected.json``).

    python benchmark/tools/record_testdata.py chiprun_out/psum4
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from harness import trace_reduce as tr, window

    n = jax.device_count()
    mesh = Mesh(np.array(jax.devices()), ("data",))

    def step(w, x):
        g = jnp.tanh(x @ w).T @ x                     # [512, 512] per chip
        g = jax.lax.psum(g, "data") / n
        return w - 1e-3 * g.T, jnp.sum(g)

    def record_step(w, xs):
        return jax.lax.scan(step, w, xs)

    fn = jax.jit(jax.shard_map(record_step, mesh=mesh,
                               in_specs=(P(), P(None, "data")),
                               out_specs=(P(), P())))
    w = jax.device_put(jnp.eye(512, dtype=jnp.float32),
                       NamedSharding(mesh, P()))
    xs = jax.device_put(jnp.ones((4, 256 * n, 512), jnp.float32),
                        NamedSharding(mesh, P(None, "data")))
    w, s = fn(w, xs)
    jax.block_until_ready(s)
    log_dir = out + ".profile"
    with window.profiled(os.path.dirname(log_dir) or ".", True) as d:
        with window.annotate("window"):
            for _ in range(3):
                with window.annotate("run_steps"):
                    w, s = fn(w, xs)
                with window.annotate("wait"):
                    np.asarray(s)
    rec = window.reduce_profile(d)
    trace, lo, hi = rec["trace"], rec["lo"], rec["hi"]
    with open(out + ".trace.json", "w") as f:
        json.dump(trace.to_json(), f)
    expected = {
        "program": "^jit_record_step", "busy_s": rec["busy_s"],
        "window_s": rec["window_s"],
        "exposed_collective_s": tr.exposed_collective_seconds(trace, lo, hi),
        "runs": tr.runs_window(trace, "^jit_record_step", lo, hi)[2],
        "top3": [n for n, _ in tr.top(
            tr.op_seconds_by_name(trace, lo, hi), 3)],
        "devices": len(trace.devices),
    }
    with open(out + ".expected.json", "w") as f:
        json.dump(expected, f, indent=1)
    print(json.dumps(expected, indent=1))
    print("ops by name:", tr.top(tr.op_seconds_by_name(trace, lo, hi), 12))
    print("idle by host span:", tr.idle_seconds_by_host_span(trace, lo, hi))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
