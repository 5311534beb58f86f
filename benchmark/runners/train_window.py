"""A training job measured in windows of fused steps.

Set-up builds ONE runner (the compiled step with its state) from the
seed and drives it through its first window by the window's own call and
program: ``runner.run_steps`` on ``steps_per_window`` host batches, the
one compiled program the measured window drives.  What that first
window left (its losses, Adam's second moment and the parameters) is
kept on the host for the check, a second window warms the steady state,
and that same runner is handed to the measured window.  The window keeps
one ``run_steps`` dispatch in flight behind the one it waits for, as
``train.fit(steps_per_loop=k)`` does, and counts the tokens of the steps
that completed.  After it closes the runner is freed and the plain
reference follows the first window's steps from the same seed.
"""
from __future__ import annotations

import time

import numpy as np

from harness import device, loader, weights, window


def _adam_state(opt_state):
    import jax

    found = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "nu")) if hasattr(s, "nu")]
    if len(found) != 1:
        raise RuntimeError("expected one Adam state in the optimizer state, "
                           f"found {len(found)}")
    return found[0]


def _leaf_norms_fn():
    """``tree -> {"a/b/c": norm of that leaf}`` as one jitted call."""
    import jax
    import jax.numpy as jnp

    def norms(tree):
        flat = jax.tree_util.tree_flatten_with_path(tree)[0]
        return {"/".join(str(getattr(k, "key", k)) for k in path):
                jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                for path, x in flat}
    return jax.jit(norms)


def program_readings(program: dict, params0) -> dict:
    """What ``compare`` takes, from what set-up kept on the host: the
    first window's losses, the root of Adam's second moment after it
    with its leaf norms, and the leaf norms of the parameters' change
    from ``params0``."""
    import jax
    import jax.numpy as jnp

    grad_abs = jax.jit(lambda t: jax.tree.map(jnp.sqrt, t))(
        program["nu_after_window"])
    norms = _leaf_norms_fn()
    delta = norms(jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(
        program["params_after_window"], params0))
    return {"loss": program["loss"], "grad_abs": grad_abs,
            "grad_norm": {n: float(v) for n, v in norms(grad_abs).items()},
            "delta_norm": {n: float(v) for n, v in delta.items()}}


def _replicas_identical(params, say) -> bool:
    """Every chip holds the same parameters, bit for bit: per chip one
    jitted pass gives each leaf's sum and sum of squares, compared
    exactly across the chips."""
    import jax
    import jax.numpy as jnp

    leaves = jax.tree.leaves(params)
    by_device: dict = {}
    for leaf in leaves:
        for s in leaf.addressable_shards:
            by_device.setdefault(s.device, []).append(s.data)
    sums = jax.jit(lambda xs: jnp.stack(
        [jnp.stack([x.sum(), jnp.square(x).sum()]) for x in xs]))
    got = [np.asarray(sums(xs)) for xs in by_device.values()]
    same = all(np.array_equal(got[0], g) for g in got[1:])
    say(f"[check] parameters identical on {len(got)} chips: {same}")
    return same


def first_window(ctx, seed: int, say):
    """Set-up: ONE runner built from the seed and driven through its
    first window by ``run_steps`` on ``steps_per_window`` batches: the
    call, the feed and the compiled program of the measured window.
    Returns the runner, what that window left (each step's loss and, on
    the host so that the device's peak stays the program's, Adam's
    second moment and the parameters after it), the batches it used, the
    measured window's pool and the builder's seconds."""
    import jax

    from autodist_tpu import stack_steps

    cfg, traffic, chips = ctx.config, ctx.traffic, ctx.chips
    ref = loader.load_module("reference", ctx.cell["config"])
    builder = loader.load_module("builders", cfg["builder"])
    k = traffic["steps_per_window"]
    rows = traffic["sequences_per_chip"] * chips
    params = weights.seeded_fill(ref.param_shapes(cfg), seed,
                                 cfg["initializer_range"])
    runner, built = builder.build_training(cfg, traffic, params, chips)
    del params
    say(f"[setup] init_s={built['init_s']:.2f} build_s={built['build_s']:.2f}")
    rng = weights.host_rng(seed, "batches")
    pool = [builder.make_batch(rng, cfg, traffic, rows)
            for _ in range((1 + traffic["pool_windows"]) * k)]
    check_batches = pool[:k]
    windows = [stack_steps(pool[i * k:(i + 1) * k])
               for i in range(1, 1 + traffic["pool_windows"])]
    m = runner.run_steps(stack_steps(check_batches))
    program = {
        "loss": np.asarray(m["loss"]).astype(float).tolist(),
        "nu_after_window": jax.device_get(
            _adam_state(runner.state["opt_state"]).nu),
        "params_after_window": jax.device_get(runner.state["params"])}
    return runner, program, check_batches, windows, built


def run(ctx, say) -> dict:
    cfg, traffic, chips = ctx.config, ctx.traffic, ctx.chips
    ref = loader.load_module("reference", ctx.cell["config"])
    k = traffic["steps_per_window"]
    rows = traffic["sequences_per_chip"] * chips
    tokens_per_step = rows * traffic["seq_len"]
    compiles = window.CompileCounter()
    gc_timer = window.GcTimer()

    # ---- set-up: the runner and its first window, then a second one
    # from the state a window leaves, so that the measured window meets
    # nothing new ----------------------------------------------------
    runner, program, check_batches, windows, built = first_window(
        ctx, ctx.seed, say)
    warm = np.asarray(runner.run_steps(windows[0])["loss"])
    say(f"[setup] first window's losses {program['loss']}; second "
        f"window's {warm.tolist()}")

    # ---- the window --------------------------------------------------
    seconds = traffic["trace_seconds"] if ctx.trace else ctx.seconds
    losses, steps_done, i = [], 0, 0
    window.settle_heap()
    with window.profiled(ctx.out_dir, ctx.trace) as log_dir, \
            compiles.counting(), gc_timer.timing():
        setup_s = time.perf_counter() - ctx.t_start
        with window.annotate("window"):
            t0 = time.perf_counter()
            pending = None
            while True:
                with window.annotate("run_steps"):
                    m = runner.run_steps(windows[(1 + i) % len(windows)])
                i += 1
                if pending is not None:
                    with window.annotate("wait"):
                        losses.extend(np.asarray(pending["loss"]).tolist())
                    steps_done += k
                pending = m
                if time.perf_counter() - t0 >= seconds:
                    break
            with window.annotate("wait"):
                losses.extend(np.asarray(pending["loss"]).tolist())
            steps_done += k
            elapsed = time.perf_counter() - t0
    memory = device.memory_held(ctx.devices, say)
    tokens = steps_done * tokens_per_step
    counts = {"steps": steps_done, "windows": i, "tokens": tokens,
              "sequences_per_step": rows,
              "compilations_in_window": compiles.count}
    say(f"[window] {steps_done} steps in {i} windows; compile events "
        f"inside: {compiles.count} {sorted(set(compiles.events))}; {gc_timer}")

    # ---- what the window left, then the reference --------------------
    checks = []
    finite = bool(np.isfinite(losses).all())
    checks.append(("losses_not_finite", 0 if finite else 1, 0, finite,
                   f"{len(losses)} losses, last {losses[-1]:.4f}"))
    checks.append(("compilations_in_window", compiles.count, 0,
                   compiles.count == 0, ""))
    if chips > 1:
        same = _replicas_identical(runner.state["params"], say)
        checks.append(("replicas_differ", 0 if same else 1, 0, same, ""))
    runner.close()
    del runner, m, pending
    t_ref = time.perf_counter()
    fill = lambda: weights.seeded_fill(
        ref.param_shapes(cfg), ctx.seed, cfg["initializer_range"])
    reference = ref.first_steps(fill(), check_batches, cfg,
                                traffic["optimizer"])
    checks.extend(ref.compare(program_readings(program, fill()), reference))
    say(f"[check] reference followed the first window's {k} steps in "
        f"{time.perf_counter() - t_ref:.1f} s")
    for name, value, limit, ok, note in checks:
        say(f"[check] {name}: {value:.6g} (limit {limit:.6g}) -> "
            f"{'ok' if ok else 'FAILED'} {note}")
    correct = all(c[3] for c in checks)

    result = {"correct": correct, "attempted": steps_done, "failed": 0,
              "memory": memory, "counts": counts, "checks": checks}
    if ctx.rehearse:
        return result
    rate = tokens / elapsed
    flops = loader.load_module("flops", ctx.cell["config"])
    step_flops = flops.train_flops_per_step(cfg, traffic, chips)
    peak = ctx.peaks["bf16_flops_per_s"] * chips
    say(f"[window] {elapsed:.3f} s, {rate:.1f} tokens/s, "
        f"{elapsed / steps_done * 1e3:.3f} ms/step, end-to-end MFU "
        f"{rate / tokens_per_step * step_flops / peak:.4f} "
        f"(model FLOPs over {chips} x peak)")
    result["end_to_end"] = {"train_tokens_per_s": rate, "setup_s": setup_s}
    if ctx.trace:
        record = window.reduce_profile(log_dir)
        record.update(cfg=cfg, traffic=traffic, chips=chips, peaks=ctx.peaks,
                      flops=flops, host=built, steps_per_run=k,
                      program=cfg["trace_programs"]["window"])
        result["record"] = record
    return result


def readings(ctx, seed: int, control: str, say) -> dict:
    """What the limits are set from (``tools/readings.py``): the numbers
    ``compare`` gives for the sound program on ``seed`` and, with
    ``control`` (a lower precision), for the reference computed in it and
    put in the program's place.  No window: training's readings need
    none."""
    ref = loader.load_module("reference", ctx.cell["config"])
    runner, program, batches, _, _ = first_window(ctx, seed, say)
    runner.close()
    del runner
    fill = lambda: weights.seeded_fill(
        ref.param_shapes(ctx.config), seed, ctx.config["initializer_range"])
    opt = ctx.traffic["optimizer"]
    reference = ref.first_steps(fill(), batches, ctx.config, opt)
    out = {"sound": ref.compare(program_readings(program, fill()),
                                reference)}
    if control:
        low = ref.first_steps(fill(), batches, ctx.config, opt,
                              precision=control)
        out["control"] = ref.compare(low, reference)
    return out
