"""Mixture-of-Experts LM training through the ExpertParallel strategy.

Beyond reference parity (SURVEY.md §2.10 lists expert parallelism as
absent): the bundled MoE transformer LM with GShard top-2 routing,
experts sharded over the ``expert`` mesh axis, tokens traveling by
``all_to_all`` — with the dispatch/combine wire joining the
per-collective precision policy (``--collective-precision int8``) and
the fused quantized ring kernel (``--a2a-ring``) on top.

    python examples/moe_train.py --steps 20
    python examples/moe_train.py --num-experts 8 --capacity-factor 1.5
    python examples/moe_train.py --collective-precision int8 --a2a-ring
    python examples/moe_train.py --auto-search --num-slices 2

``--auto-search`` hands the factorization to the topology-aware
search: the MoE trainable declares its expert count and capacity
factor, so the candidate family sweeps the expert-axis degree (1 = the
dense point), its placement (within a slice vs deliberately across
DCN), the dispatch/combine wire precision, and the a2a_ring kernel —
and trains the frontier winner.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    from autodist_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--num-experts", "--experts", type=int, default=8,
                    dest="num_experts",
                    help="expert tables in every MoE block (the model "
                         "shape; the expert-axis degree that shards "
                         "them is the topology's largest compatible "
                         "divisor, or the search's election under "
                         "--auto-search)")
    ap.add_argument("--capacity-factor", type=float, default=2.0,
                    help="per-expert slot headroom: each expert keeps "
                         "capacity_factor x (tokens/experts) slots per "
                         "routing pass; overflow tokens drop (GShard "
                         "semantics) and the dispatch/combine payload "
                         "the cost model prices scales with it")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--collective-precision", default="off",
                    choices=["off", "bf16", "int8"],
                    help="moe_a2a wire precision: quantize the "
                         "dispatch/combine all_to_all payload to this "
                         "width (permute-shaped, so int8 is TRUE s8 on "
                         "the wire); the drift report breaks out the "
                         "predicted a2a bytes/time")
    ap.add_argument("--a2a-ring", action="store_true",
                    help="fuse the q/dq into the dispatch/combine ring "
                         "kernel (EQuARX-style per-hop VMEM passes; "
                         "needs --collective-precision int8)")
    ap.add_argument("--zero-stage", type=int, default=0,
                    choices=[0, 1, 2, 3],
                    help="ZeRO stage over the replicated (dense) "
                         "parameters' sync axes")
    ap.add_argument("--auto-search", action="store_true",
                    help="replace the explicit flags with the "
                         "topology-aware strategy search (the expert "
                         "family: expert-axis degree x placement x "
                         "wire precision x kernel), print the search "
                         "report, and train the winner")
    ap.add_argument("--num-slices", type=int, default=1,
                    help="declare a multi-slice topology (with "
                         "--auto-search): the search keeps the expert "
                         "axis within a slice unless this topology's "
                         "link constants invert the trade")
    ap.add_argument("--telemetry-dir", default=None,
                    help="flush telemetry here: metrics.jsonl, "
                         "manifest.json (run.moe annotation), "
                         "drift.json (predicted-vs-measured with the "
                         "comm/a2a_bytes breakout)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from autodist_tpu import AutoDist, analysis, telemetry
    from autodist_tpu.models.moe_transformer import (MoeConfig,
                                                     make_moe_lm_trainable)
    from autodist_tpu.simulator.cost_model import CostModel
    from autodist_tpu.strategy.parallel_builders import ExpertParallel

    n = jax.device_count()
    # Largest expert-axis degree this topology supports: divides both
    # the device count and the expert count (1 = dense fallback).
    expert_axis = max((d for d in range(1, n + 1)
                       if n % d == 0 and args.num_experts % d == 0),
                      default=1)
    dp = n // expert_axis
    if args.batch % n:
        raise SystemExit(f"--batch {args.batch} must divide over the "
                         f"{n} visible devices (batch shards over "
                         "data x expert)")
    precision = None if args.collective_precision == "off" \
        else args.collective_precision
    if args.a2a_ring and precision != "int8":
        raise SystemExit("--a2a-ring fuses the int8 q/dq into the ring "
                         "hops; pass --collective-precision int8")

    cfg = MoeConfig(vocab_size=args.vocab, hidden_size=args.hidden,
                    num_layers=args.layers, num_heads=4,
                    expert_hidden=2 * args.hidden,
                    num_experts=args.num_experts,
                    capacity_factor=args.capacity_factor,
                    max_len=args.seq_len, dtype=jnp.float32)
    trainable = make_moe_lm_trainable(cfg, optax.adam(1e-3),
                                      jax.random.PRNGKey(0),
                                      batch_size=args.batch,
                                      seq_len=args.seq_len)
    builder = ExpertParallel(
        num_experts=args.num_experts,
        capacity_factor=args.capacity_factor,
        zero_stage=args.zero_stage,
        collective_precision=({"moe_a2a": precision} if precision
                              else None),
        kernel=(("a2a_ring",) if args.a2a_ring else None))

    if args.telemetry_dir:
        telemetry.configure(out_dir=args.telemetry_dir)
    if args.auto_search:
        # The search owns the factorization (expert degree, placement,
        # wire, kernel); the spec declares only the topology.
        topo = {"num_devices": n}
        if args.num_slices > 1:
            topo["num_slices"] = args.num_slices
        ad = AutoDist({"topology": topo}, builder)
        from autodist_tpu.simulator.search import search_strategies

        result = search_strategies(trainable, ad.resource_spec,
                                   global_batch=args.batch)
        print(result.report())
        if result.winner is None:
            raise SystemExit("auto-search: no candidate priced — "
                             "widen the SearchSpace or check the "
                             "topology")
        strategy = result.winner.strategy
        cost_spec = result.winner.spec
        runner = ad.build(trainable, strategy)
    else:
        mesh = {"expert": expert_axis} if dp == 1 \
            else {"data": dp, "expert": expert_axis}
        ad = AutoDist({"topology": {"num_devices": n}, "mesh": mesh},
                      builder)
        # The strategy stays in hand so the drift report below joins
        # the cost model's prediction for exactly the program that ran.
        strategy = ad.build_or_load_strategy(trainable)
        cost_spec = ad.resource_spec
        runner = ad.build(trainable, strategy)

    plan_report = analysis.lint_plan(
        strategy, resource_spec=cost_spec, trainable=trainable,
        lowered=getattr(runner, "lowered", None))
    if plan_report.diagnostics:
        print(f"plan lint ({len(plan_report.errors)} error(s), "
              f"{len(plan_report.warnings)} warning(s)):")
        for diag in plan_report.sorted():
            print(f"  {diag}")
    else:
        print("plan lint: clean")

    gc = strategy.graph_config
    run_expert_axis = int((gc.mesh_axes or {}).get("expert", 1) or 1)
    run_over_dcn = bool((gc.parallel or {}).get("expert_over_dcn",
                                                False))
    if args.auto_search:
        print(f"auto-search winner: {result.winner.name} "
              f"(mesh {gc.mesh_axes})")
    else:
        print(f"MoE LM: {args.num_experts} experts over the "
              f"{run_expert_axis}-way expert axis (dp={dp}), "
              f"capacity_factor={args.capacity_factor}, "
              f"moe_a2a={precision or 'fp32'}"
              f"{' + a2a_ring' if args.a2a_ring else ''}, "
              f"zero_stage={args.zero_stage}")

    cost = CostModel(cost_spec).strategy_cost(trainable, strategy)

    from autodist_tpu.utils import profiling

    timer = profiling.StepTimer(args.batch,
                                warmup=min(2, max(args.steps - 1, 0)))
    import time

    r = np.random.RandomState(0)
    for step in range(args.steps):
        x = r.randint(0, args.vocab,
                      (args.batch, args.seq_len)).astype(np.int32)
        batch = {"x": x, "y": np.roll(x, -1, axis=1)}
        t_step = time.perf_counter()
        with timer:
            metrics = runner.step(batch)
            if args.telemetry_dir:
                jax.block_until_ready(metrics)
        telemetry.record_step(step=step,
                              duration_s=time.perf_counter() - t_step,
                              examples=args.batch)
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step}: "
                  f"loss={float(np.asarray(metrics['loss'])):.4f} "
                  f"nll={float(np.asarray(metrics['nll'])):.4f} "
                  f"aux={float(np.asarray(metrics['aux'])):.4f}")

    summary = timer.summary()
    if args.telemetry_dir:
        from autodist_tpu.utils.profiling import memory_summary

        # The manifest describes the program that RAN: under
        # --auto-search the winner's expert degree/placement, not the
        # CLI flags (which only sized the model there).  The run.moe
        # annotation is what `tools/telemetry_report.py --check` joins
        # the comm/a2a_bytes gauge and drift breakout against.
        telemetry.annotate(
            mesh=dict(gc.mesh_axes or {}),
            auto_search=args.auto_search, batch=args.batch,
            moe=dict(num_experts=args.num_experts,
                     capacity_factor=args.capacity_factor,
                     expert_axis=run_expert_axis,
                     expert_over_dcn=run_over_dcn),
            collective_precision=dict(gc.precision),
            kernel=sorted(gc.kernel or ()),
            zero_stage=args.zero_stage,
            step_summary=summary)
        report = telemetry.drift_report(
            strategy, CostModel(cost_spec),
            {"step": summary, "memory": memory_summary(),
             "examples_per_sec": summary.get("examples_per_sec")},
            trainable=trainable)
        paths = telemetry.flush()
        print(f"telemetry artifacts in {args.telemetry_dir}: "
              f"{sorted(os.path.basename(p) for p in paths.values())}")
        ratios = {k: round(v, 3) for k, v in report["ratios"].items()}
        print(f"drift (measured/predicted): {ratios}")
        if cost.a2a_bytes:
            print(f"dispatch/combine: predicted "
                  f"{cost.a2a_bytes / 1e6:.3f} MB/step on the a2a wire "
                  f"({cost.a2a_time_s * 1e6:.1f} us/step"
                  f"{', over DCN' if run_over_dcn else ''})")


if __name__ == "__main__":
    main()
