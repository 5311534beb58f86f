"""Pipeline-parallel training through the Strategy IR.

Beyond reference parity (the reference declared pipeline parallelism
future work, ``docs/design/architecture.rst:49-51``): a stage-stacked
Megatron MLP trained over the ``pipe`` mesh axis, GPipe or interleaved
(``--virtual-stages 2``), with gradient accumulation composing on top —
and tensor parallelism *inside* each stage (``--tensor-parallel 2``):
the mesh factors as dp×pp×tp and each stage's wi/wo matmuls run
column/row-parallel over the ``model`` axis with one activation
all-reduce per stage.

    python examples/pipeline_train.py --steps 20
    python examples/pipeline_train.py --virtual-stages 2 --microbatches 4
    python examples/pipeline_train.py --tensor-parallel 2 --stages 2
    python examples/pipeline_train.py --tensor-parallel 2 --stages 2 \
        --comm-overlap matmul --profile-dir /tmp/pp_trace
    python examples/pipeline_train.py --tensor-parallel 2 --stages 2 \
        --vocab-parallel --vocab 512

``--vocab-parallel`` switches the workload to the pipelined
transformer LM (the MLP has no embedding to shard) and shards its tied
embedding/unembedding over the ``model`` axis: the prologue runs the
masked-lookup psum and the loss head the streaming fused cross-entropy
epilogue, so embedding state and peak logits memory drop by 1/tp.
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
    ap.add_argument("--stages", type=int, default=4,
                    help="pipe-axis devices")
    ap.add_argument("--virtual-stages", type=int, default=1)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--accum-steps", type=int, default=1)
    ap.add_argument("--tensor-parallel", type=int, default=1,
                    help="model-axis devices per stage (Megatron TP "
                         "inside the pipeline: dp x pp x tp)")
    ap.add_argument("--comm-overlap", choices=["off", "rsag", "matmul"],
                    default="off",
                    help="latency-hiding decomposition of the model-axis "
                         "activation collectives (with --tensor-parallel "
                         "> 1): rsag = reduce-scatter + all-gather pairs, "
                         "matmul = chunked collective-matmul ppermute ring")
    ap.add_argument("--vocab-parallel", action="store_true",
                    help="shard the tied embedding/unembedding's vocab "
                         "dim over the model axis (with --tensor-parallel "
                         "> 1) and run the streaming fused cross-entropy "
                         "epilogue; switches the workload to the "
                         "pipelined transformer LM")
    ap.add_argument("--vocab", type=int, default=256,
                    help="LM vocab size (with --vocab-parallel; odd "
                         "values exercise the zero-pad path)")
    ap.add_argument("--seq", type=int, default=16,
                    help="LM sequence length (with --vocab-parallel)")
    ap.add_argument("--collective-precision", default="off",
                    choices=["off", "bf16", "int8"],
                    help="per-collective precision policy: narrow every "
                         "policied boundary (TP activation psums, "
                         "decomposed rs/ag halves, vocab-epilogue "
                         "stats, ZeRO-3 gathers, dp grad sync via the "
                         "EF compressors) to this wire precision; the "
                         "drift report breaks out the predicted "
                         "bytes-on-wire delta")
    ap.add_argument("--zero-stage", type=int, default=0,
                    choices=[0, 1, 2, 3],
                    help="ZeRO stage over the data axes (stage vars) / "
                         "pipe x data (shared vars): 1 shards optimizer "
                         "state, 2 accounts gradients sharded (same "
                         "reduce-scatter program), 3 stores parameters "
                         "sharded with per-layer on-demand all-gathers")
    ap.add_argument("--zero1", action="store_true",
                    help="deprecated alias for --zero-stage 1")
    ap.add_argument("--remat", action="store_true",
                    help="jax.checkpoint each chunk (memory for compute)")
    ap.add_argument("--auto-search", action="store_true",
                    help="replace the explicit knob flags with the "
                         "topology-aware strategy search: enumerate "
                         "the (dp, pp, tp, vocab, zero, overlap, "
                         "precision, microbatch, compressor) "
                         "cross-product for the visible topology, "
                         "print the search report (configs enumerated/"
                         "pruned/priced, frontier top-10 with "
                         "per-level comm breakdown, winner knob "
                         "string), and train the winner")
    ap.add_argument("--preempt-demo", action="store_true",
                    help="simulate a mid-run preemption: at the halfway "
                         "step a SIGTERM triggers a blocking elastic "
                         "checkpoint, the run shrinks to half the "
                         "devices, the topology-aware search re-elects "
                         "a winner on the survivors, the checkpoint is "
                         "resharded onto it, and training resumes "
                         "(docs/usage/elasticity.md)")
    ap.add_argument("--preempt-ckpt-dir", default=None,
                    help="checkpoint directory for --preempt-demo "
                         "(default: a temp dir)")
    ap.add_argument("--chaos", default=None, metavar="PLAN_JSON",
                    help="run under a fault plan (runtime/faults.py "
                         "JSON: ckpt_write_fail retries/degrades on the "
                         "Saver, preempt_signal takes the elastic "
                         "shrink-resume path, slow_host stalls the "
                         "chief) — the single-process demo of what "
                         "tools/chaos_run.py sweeps against a "
                         "LocalCluster; docs/usage/robustness.md")
    ap.add_argument("--num-slices", type=int, default=1,
                    help="declare a multi-slice topology (with "
                         "--auto-search): the outer dp axis rides DCN "
                         "and the search keeps tp/pp within a slice; "
                         "simulated CPU meshes lower it too")
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--profile-dir", default=None,
                    help="capture an xplane trace of the step loop here; "
                         "also implies --telemetry-dir here, so a "
                         "hardware window yields the trace plus step "
                         "records/manifest/drift report with zero extra "
                         "typing")
    ap.add_argument("--telemetry-dir", default=None,
                    help="flush telemetry here: trace.json (chrome "
                         "trace of build/compile/step spans), "
                         "metrics.jsonl (per-step records + counters), "
                         "manifest.json (git SHA/jax versions/run "
                         "config), drift.json (cost-model predicted vs "
                         "measured step time + memory)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from autodist_tpu import AutoDist, PipelineTrainable
    from autodist_tpu.parallel.pipeline import bubble_fraction
    from autodist_tpu.parallel.tensor import column_parallel, row_parallel
    from autodist_tpu.resource import factor_3d
    from autodist_tpu.strategy.builders import GradAccumulation
    from autodist_tpu.strategy.parallel_builders import Pipeline

    n = jax.device_count()
    tp = args.tensor_parallel
    if tp < 1 or n % tp or n // tp < 1:
        raise SystemExit(
            f"--tensor-parallel {tp} must divide the {n} visible devices")
    pp = min(args.stages, n // tp)
    if (n // tp) % pp:
        raise SystemExit(
            f"--stages resolves to pipe={pp}, which must divide the "
            f"{n // tp} devices left after tp={tp}")
    dp = n // (pp * tp)
    mesh = factor_3d(dp * pp * tp, pipe=pp, model=tp, data=dp)
    C = pp * args.virtual_stages
    HID, FF = args.hidden, 2 * args.hidden
    r = np.random.RandomState(0)
    # Megatron block per stage: wi column-parallel, wo row-parallel —
    # the same variable naming the Pipeline builder's tp rule table keys
    # on (qkv/out/wi/wo).
    stacked = {
        "wi": {"kernel": jnp.asarray(
                   r.randn(C, HID, FF) * (2.0 / HID) ** 0.5, jnp.float32),
               "bias": jnp.zeros((C, FF), jnp.float32)},
        "wo": {"kernel": jnp.asarray(
                   r.randn(C, FF, HID) * (2.0 / FF) ** 0.5, jnp.float32),
               "bias": jnp.zeros((C, HID), jnp.float32)},
    }

    def stage(p, x, model_axis=None, comm_overlap=None):
        h = jax.nn.relu(column_parallel(x, p["wi"]["kernel"],
                                        p["wi"]["bias"],
                                        model_axis=model_axis,
                                        comm_overlap=comm_overlap))
        return row_parallel(h, p["wo"]["kernel"], p["wo"]["bias"],
                            model_axis=model_axis,
                            comm_overlap=comm_overlap)

    def head(outputs, batch):
        loss = jnp.mean((outputs - batch["y"]) ** 2)
        return loss, {}

    if args.vocab_parallel:
        # Vocab parallelism shards the shared embedding/unembedding —
        # the MLP has neither, so this mode trains the pipelined
        # transformer LM (one encoder layer per chunk, tied table).
        from autodist_tpu.models.pipeline_lm import make_pipeline_lm_trainable
        from autodist_tpu.models.transformer import TransformerConfig

        cfg = TransformerConfig(
            vocab_size=args.vocab, hidden_size=HID, num_layers=C,
            num_heads=2, mlp_dim=FF, max_len=args.seq,
            dtype=jnp.float32, dropout_rate=0.0,
            attention_dropout_rate=0.0)
        trainable = make_pipeline_lm_trainable(
            cfg, optax.adam(1e-3), jax.random.PRNGKey(0))
        # activation hints so the cost model prices the epilogue
        # (peak-logits memory, psums) for the drift report below
        trainable.tokens_per_step = args.batch * args.seq
        trainable.act_bytes_per_token = float(4 * HID)

        def make_batch():
            x = r.randint(0, args.vocab,
                          (args.batch, args.seq)).astype(np.int32)
            y = np.concatenate([x[:, 1:], x[:, :1]], axis=1)
            return {"x": x, "y": y}
    else:
        trainable = PipelineTrainable(stage, stacked, head,
                                      optax.adam(1e-3), num_stages=C)
        target = r.randn(HID, HID).astype(np.float32) * 0.1

        def make_batch():
            x = r.randn(args.batch, HID).astype(np.float32)
            return {"x": x, "y": x @ target}
    overlap = None if args.comm_overlap == "off" else args.comm_overlap
    precision = None if args.collective_precision == "off" \
        else args.collective_precision
    zero_stage = max(args.zero_stage, 1 if args.zero1 else 0)
    builder = Pipeline(num_microbatches=args.microbatches,
                       virtual_stages=args.virtual_stages,
                       tensor_parallel=tp, comm_overlap=overlap,
                       vocab_parallel=args.vocab_parallel,
                       zero_stage=zero_stage, remat=args.remat,
                       collective_precision=precision)
    if args.accum_steps > 1:
        builder = GradAccumulation(builder, steps=args.accum_steps)

    from autodist_tpu import telemetry

    tel_dir = args.telemetry_dir or args.profile_dir
    if tel_dir:
        telemetry.configure(out_dir=tel_dir)
    if args.auto_search:
        # The search owns the factorization: the spec declares only the
        # topology (device count, slice count); every (dcn, data, pipe,
        # model) mesh the search elects carries in the winner
        # strategy's mesh_axes, which AutoDist honors at lowering.
        topo = {"num_devices": dp * pp * tp}
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
        if not result.winner.cost.feasible:
            raise SystemExit(
                f"auto-search: best candidate {result.winner.name} "
                f"needs {result.winner.cost.mem_bytes_per_device / 1e9:.2f}"
                " GB/device — nothing fits in memory")
        strategy = result.winner.strategy
        # Lint/price against the winner's own factorization below.
        cost_spec = result.winner.spec
        runner = ad.build(trainable, strategy)
    else:
        ad = AutoDist({"topology": {"num_devices": dp * pp * tp},
                       "mesh": mesh}, builder)
        # The strategy is kept in hand (instead of letting build()
        # resolve it internally) so the drift report below can join the
        # cost model's prediction for exactly the program that ran.
        strategy = ad.build_or_load_strategy(trainable)
        cost_spec = ad.resource_spec
        runner = ad.build(trainable, strategy)

    # Plan lint at build: every silent degrade (ZeRO on a tp shard,
    # vocab no-op at tp=1, orphan precision slot, ...) surfaces as a
    # coded ADT diagnostic instead of a buried log line (the same rules
    # `tools/lint_strategy.py --zoo` gates CI on).
    from autodist_tpu import analysis

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

    if args.auto_search:
        print(f"auto-search winner: {result.winner.name} "
              f"(mesh {strategy.graph_config.mesh_axes})")
    else:
        print(f"pipe={pp} x virtual={args.virtual_stages} "
              f"(C={C} chunks), dp={dp}, tp={tp}, M={args.microbatches}, "
              f"comm_overlap={overlap}, "
              f"vocab_parallel={args.vocab_parallel}, "
              f"zero_stage={zero_stage}, "
              f"collective_precision={precision or 'fp32'}; "
              f"schedule bubble = "
              f"{bubble_fraction(args.microbatches, pp, args.virtual_stages):.3f}")

    from autodist_tpu.simulator.cost_model import CostModel

    # Predicted peak-logits buffer (the memory term vocab parallelism
    # divides by tp) rides every step record + a gauge, so a hardware
    # window's metrics.jsonl can join it against measured HBM.
    cost = CostModel(cost_spec).strategy_cost(trainable, strategy)
    peak_logits = cost.peak_logits_bytes or None
    if peak_logits:
        telemetry.get().gauge("memory/peak_logits_bytes").set(peak_logits)
    # The terms the ZeRO stages divide (stage 2: grads /n, stage 3:
    # params /n too) ride the run as gauges so a hardware window can
    # attribute the measured HBM delta between --zero-stage settings.
    if cost.param_shard_bytes:
        telemetry.get().gauge("memory/param_shard_bytes").set(
            cost.param_shard_bytes)
    if cost.grad_shard_bytes:
        telemetry.get().gauge("memory/grad_shard_bytes").set(
            cost.grad_shard_bytes)

    from contextlib import nullcontext

    from autodist_tpu.utils import profiling

    # warmup must leave at least one recorded step or the summary is all
    # None (short smoke runs with --profile-dir).
    timer = profiling.StepTimer(args.batch,
                                warmup=min(2, max(args.steps - 1, 0)))
    trace_cm = (profiling.trace(args.profile_dir) if args.profile_dir
                else nullcontext())
    import time

    controller = None
    injector = None
    if args.preempt_demo or args.chaos:
        import tempfile

        from autodist_tpu.checkpoint.saver import Saver
        from autodist_tpu.elastic import ElasticController
        from autodist_tpu.runtime.retry import RetryPolicy

        ckpt_dir = args.preempt_ckpt_dir or tempfile.mkdtemp(
            prefix="elastic_ckpt_")
        saver = Saver(ckpt_dir,
                      retry=RetryPolicy(max_attempts=2, base_delay_s=0.1,
                                        cap_delay_s=1.0),
                      degrade_on_failure=bool(args.chaos))
        controller = ElasticController(trainable, saver,
                                       global_batch=args.batch)
        controller.install(runner)
    if args.chaos:
        from autodist_tpu.runtime.faults import FaultInjector, load_fault_plan

        plan = load_fault_plan("@" + args.chaos)
        # Baseline checkpoint BEFORE any fault can fire: every degrade/
        # recovery path falls back to "the last good checkpoint", so a
        # chaos-armed run must have one from step 0.
        saver.save(runner)
        injector = FaultInjector(plan, self_target="chief", saver=saver)
        print(f"chaos plan armed: {[f.kind for f in plan.faults]} "
              f"(seed {plan.seed})")

    with trace_cm:
        for step in range(args.steps):
            if injector is not None:
                injector.maybe_fire(step)
                if controller.preempted:
                    survivors = max(jax.device_count() // 2, 1)
                    runner = controller.resume({"num_devices": survivors})
                    print(f"chaos preemption at step {step}: resumed on "
                          f"{survivors} device(s)")
                if step % 5 == 2:
                    # Periodic checkpoints give the armed
                    # ckpt_write_fail something to hit (and every later
                    # fault a fresher "last good" to fall back to); the
                    # cadence avoids the mid-run preemption step so the
                    # two saves never collide on one step number.
                    saver.save(runner)
            if args.preempt_demo and step == max(args.steps // 2, 1):
                # Simulated preemption: the SIGTERM handler writes a
                # blocking elastic checkpoint; the survivors (here:
                # half the devices) re-elect via the topology-aware
                # search and resume from the resharded checkpoint.
                import signal as _signal

                os.kill(os.getpid(), _signal.SIGTERM)
                assert controller.preempted
                survivors = max(jax.device_count() // 2, 1)
                runner = controller.resume({"num_devices": survivors})
                print(f"preemption at step {step}: resumed on "
                      f"{survivors} device(s), mesh "
                      f"{dict(runner.lowered.mesh.shape)}, strategy "
                      f"{controller.last_result.winner.name}")
            batch = make_batch()
            t_step = time.perf_counter()
            with timer:
                metrics = runner.step(batch)
                if tel_dir:
                    # Honest per-step timing needs the device work done;
                    # without a telemetry/profile sink, keep the
                    # dispatch async.
                    jax.block_until_ready(metrics)
            extra = {"peak_logits_bytes": peak_logits} if peak_logits \
                else {}
            if zero_stage:
                extra["zero_stage"] = zero_stage
                extra["param_shard_bytes"] = cost.param_shard_bytes
                extra["grad_shard_bytes"] = cost.grad_shard_bytes
            telemetry.record_step(step=step,
                                  duration_s=time.perf_counter() - t_step,
                                  examples=args.batch, **extra)
            if step % 5 == 0 or step == args.steps - 1:
                print(f"step {step}: "
                      f"loss={float(np.asarray(metrics['loss'])):.5f}")

    summary = timer.summary()
    if tel_dir:
        from autodist_tpu.utils.profiling import memory_summary

        # The manifest must describe the program that RAN: under
        # --auto-search the winner's Strategy-IR knobs, not the CLI
        # flags (which only sized the topology there).
        if args.auto_search:
            par = strategy.graph_config.parallel or {}
            knobs = dict(
                microbatches=int(par.get("num_microbatches", 1) or 1),
                virtual_stages=int(par.get("virtual_stages", 1) or 1),
                comm_overlap=par.get("comm_overlap") or None,
                tensor_parallel=int(par.get("tensor_parallel", 1) or 1),
                zero_stage=int(par.get("zero_stage", 0) or 0),
                vocab_parallel=bool(par.get("vocab_parallel", False)),
                remat=bool(par.get("remat", False)))
        else:
            knobs = dict(microbatches=args.microbatches,
                         virtual_stages=args.virtual_stages,
                         comm_overlap=overlap, tensor_parallel=tp,
                         zero_stage=zero_stage,
                         vocab_parallel=args.vocab_parallel,
                         remat=args.remat)
        telemetry.annotate(mesh=dict(strategy.graph_config.mesh_axes),
                           auto_search=args.auto_search,
                           batch=args.batch, **knobs,
                           # The normalized per-boundary dict, so
                           # `tools/telemetry_report.py --check` can
                           # gate the precision/<boundary>_bits gauges
                           # the lowering emitted against it.
                           collective_precision=dict(
                               strategy.graph_config.precision),
                           peak_logits_bytes=peak_logits,
                           param_shard_bytes=cost.param_shard_bytes,
                           grad_shard_bytes=cost.grad_shard_bytes,
                           step_summary=summary)
        report = telemetry.drift_report(
            strategy, CostModel(cost_spec),
            {"step": summary, "memory": memory_summary(),
             "examples_per_sec": summary.get("examples_per_sec")},
            trainable=trainable)
        paths = telemetry.flush()
        print(f"telemetry artifacts in {tel_dir}: "
              f"{sorted(os.path.basename(p) for p in paths.values())}")
        ratios = {k: round(v, 3) for k, v in report["ratios"].items()}
        print(f"drift (measured/predicted): {ratios}")
        if cost.wire_bytes_saved:
            print(f"precision policy: predicted "
                  f"{cost.wire_bytes_saved / 1e6:.3f} MB/step saved on "
                  f"the wire vs fp32 (q/dq compute charged: "
                  f"{cost.quant_dq_time_s * 1e6:.1f} us/step)")
    mean = summary["mean_ms"]
    if args.profile_dir and mean is not None:
        print(f"xplane trace in {args.profile_dir} "
              f"(mean {mean:.2f} ms/step)")


if __name__ == "__main__":
    main()
