"""Batched inference through the serving engine (ROADMAP: the serving
path on the same Strategy IR).

Drives the pipelined transformer LM family through
``autodist_tpu/serving/``: a continuous batcher admits synthetic
requests into TP-sharded KV-cache slots, prefill emits each request's
first token, and fused multi-token decode windows stream the rest —
with TTFT / inter-token / tokens-per-sec telemetry through the
``telemetry/`` sink.

    python examples/serve.py --requests 8 --max-new 32
    python examples/serve.py --tensor-parallel 2 --vocab-parallel \
        --vocab 513                       # odd vocab: the zero-pad path
    python examples/serve.py --train-steps 4 --tensor-parallel 2 \
        --telemetry-dir /tmp/serve_run    # serve a freshly trained runner
    python examples/serve.py --smoke      # tier-1 CI subprocess

``--train-steps > 0`` first trains the LM through the ``Pipeline``
strategy on the visible mesh and serves ``runner.get_params()`` —
the live-runner path; otherwise the engine serves the freshly
initialized parameters directly.  ``--artifact DIR`` round-trips
through ``checkpoint/export.py`` instead (export, reload, serve).
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _serve_fleet(args, cfg, trainable):
    """The ``--replicas N`` path: serve the request mix through a
    fleet behind the router, then kill one replica mid-run to show the
    failover path re-homing its in-flight requests (the fleet lint is
    printed first, the launch-gate habit).

    ``--processes`` runs the same mix against REAL replica processes
    (:class:`ProcessFleet` over the tiny shared worker engine — the
    model-size flags don't ship to workers) and stitches every
    process's telemetry shard into ONE ``trace.json``: open it in
    Perfetto and each request's distributed trace reads across the
    chief's dispatch instants and both workers' prefill/decode spans."""
    import time

    import numpy as np

    from autodist_tpu import serving, telemetry
    from autodist_tpu.resource import ResourceSpec

    if args.processes:
        # The tiny worker engine's admission budget, not the CLI's.
        args.vocab, args.max_new = 33, min(args.max_new, 6)
        prompt_cap = 16 - args.max_new
        fleet = serving.ProcessFleet(
            {"factory": "autodist_tpu.serving.remote:"
                        "tiny_engine_factory"},
            config=serving.FleetConfig(replicas=args.replicas),
            telemetry_dir=args.telemetry_dir)
        print(f"replica worker platforms: {fleet.platforms()}")
    else:
        prompt_cap = max(args.prefill_len - args.max_new, 1)

        def factory():
            return serving.ServingEngine(
                cfg, trainable.params,
                tensor_parallel=args.tensor_parallel,
                vocab_parallel=args.vocab_parallel,
                num_slots=args.slots,
                max_len=args.max_len, prefill_len=args.prefill_len,
                decode_steps=args.decode_steps)

        fleet = serving.ServingFleet(factory, replicas=args.replicas)
    report = fleet.lint(resource_spec=ResourceSpec(
        {"topology": {"num_devices":
                      max(args.replicas * args.tensor_parallel, 1)}}))
    print(report.render("fleet lint") if not report.ok
          else "fleet lint: clean")
    router = serving.Router(fleet)
    r = np.random.RandomState(7)
    t0 = time.perf_counter()
    rids = []
    for _ in range(args.requests):
        plen = int(r.randint(1, max(prompt_cap, 1) + 1))
        prompt = r.randint(0, args.vocab, (plen,)).tolist()
        rids.append(router.submit(prompt, max_new_tokens=args.max_new))
    router.step()
    if fleet.has_replica("replica-0"):
        fleet.inject("replica-0", "crash")   # the failover demo
    done = router.run()
    wall = time.perf_counter() - t0
    tokens = sum(len(c.tokens) for c in done.values())
    failovers = sum(c.failovers for c in done.values())
    print(f"fleet served {len(done)} requests / {tokens} tokens in "
          f"{wall:.2f}s across {args.replicas} replicas "
          f"({failovers} failover(s) after the mid-run replica kill); "
          f"replicas: "
          f"{[(x.name, x.incarnation, x.state) for x in fleet.replicas]}")
    if args.telemetry_dir:
        telemetry.annotate(serve=True, replicas=args.replicas,
                           requests=len(done), tokens=tokens)
        telemetry.flush()
    if args.processes:
        # Workers flush their telemetry shards on the stop op: close
        # first and wait for the processes to exit, so the stitch
        # below sees every shard.
        fleet.close()
        deadline = time.perf_counter() + 30.0
        while any(x.handle.running for x in fleet.replicas) \
                and time.perf_counter() < deadline:
            time.sleep(0.05)
    stitched = None
    if args.telemetry_dir:
        stitched = telemetry.stitch_trace(args.telemetry_dir)
        traced = {t for ev in stitched["traceEvents"]
                  for t in telemetry.tracing.event_trace_ids(ev)}
        print(f"stitched trace.json: "
              f"{len(stitched['traceEvents'])} events from "
              f"{stitched['stitched']['shards']} process shard(s) "
              f"(pids {stitched['stitched']['pids']}), "
              f"{len(traced)} traced request(s)")
    if args.smoke:
        assert len(done) == args.requests
        assert all(c.finish_reason in ("eos", "max_tokens", "max_len")
                   for c in done.values())
        assert all(c.trace_id for c in done.values())
        if not args.processes:
            acc = fleet.block_accounting()
            assert all(u == 0 for _, u, _ in acc.values()), acc
        if stitched is not None:
            traced = {t for ev in stitched["traceEvents"]
                      for t in telemetry.tracing.event_trace_ids(ev)}
            assert all(c.trace_id in traced for c in done.values()), \
                "a completion's trace id resolves to no stitched event"
            if args.processes:
                assert len(stitched["stitched"]["pids"]) >= 2, \
                    stitched["stitched"]
        print("fleet serve smoke ok")


def main():
    from autodist_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4,
                    help="continuous-batching slots (the decode batch)")
    ap.add_argument("--decode-steps", type=int, default=8,
                    help="tokens per fused decode dispatch (K)")
    ap.add_argument("--prefill-len", type=int, default=16,
                    help="prompt bucket (prompts pad up to it)")
    ap.add_argument("--max-len", type=int, default=64,
                    help="KV-cache capacity per slot")
    ap.add_argument("--tensor-parallel", type=int, default=1)
    ap.add_argument("--vocab-parallel", action="store_true",
                    help="shard the tied unembedding's vocab dim over "
                         "the model axis (with --tensor-parallel > 1); "
                         "decode never materializes full-vocab logits")
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=2)
    ap.add_argument("--train-steps", type=int, default=0,
                    help="train the LM this many steps first and serve "
                         "the live runner's parameters")
    ap.add_argument("--artifact", default=None,
                    help="export to this directory and serve the "
                         "reloaded artifact (the checkpoint/export.py "
                         "round trip)")
    ap.add_argument("--telemetry-dir", default=None,
                    help="flush serving telemetry here (per-request "
                         "serve records, TTFT/inter-token histograms, "
                         "manifest)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="> 1 serves through a ServingFleet + Router "
                         "(N replica engine+batcher groups, queue-"
                         "depth-aware dispatch, failover/hedging) and "
                         "prints the fleet-objective ranking + a "
                         "mid-run replica-kill failover demo")
    ap.add_argument("--processes", action="store_true",
                    help="with --replicas > 1: real replica worker "
                         "processes (ProcessFleet over the tiny shared "
                         "worker engine) — with --telemetry-dir the "
                         "per-process telemetry shards are stitched "
                         "into ONE distributed trace.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI preset: shrink everything and assert "
                         "the serve loop end to end")
    args = ap.parse_args()
    if args.smoke:
        args.requests = min(args.requests, 4)
        args.max_new = 6
        args.slots = 2
        args.decode_steps = 3
        args.prefill_len = 8
        args.max_len = 24
        args.vocab = 33 if args.vocab_parallel else 32
        args.hidden = 16
        args.layers = 2

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from autodist_tpu import serving, telemetry
    from autodist_tpu.models.pipeline_lm import make_pipeline_lm_trainable
    from autodist_tpu.models.transformer import TransformerConfig
    from autodist_tpu.resource import ResourceSpec
    from autodist_tpu.simulator import rank_serving

    if args.telemetry_dir:
        telemetry.configure(out_dir=args.telemetry_dir)

    cfg = TransformerConfig(
        vocab_size=args.vocab, hidden_size=args.hidden,
        num_layers=args.layers, num_heads=args.heads,
        mlp_dim=2 * args.hidden, max_len=args.max_len,
        dtype=jnp.float32, dropout_rate=0.0, attention_dropout_rate=0.0)
    trainable = make_pipeline_lm_trainable(
        cfg, optax.adam(1e-3), jax.random.PRNGKey(0))

    # AutoStrategy's serving objective: rank the (tp, vocab_parallel)
    # zoo by predicted per-token latency before committing devices.
    rs = ResourceSpec({"topology": {"num_devices": jax.device_count()}})
    ranked = rank_serving(trainable, rs, batch_slots=args.slots,
                          max_len=args.max_len)
    print("serving configs by predicted token latency:")
    for cand, cost in ranked[:4]:
        print(f"  tp={cand['tensor_parallel']} "
              f"vocab_parallel={cand['vocab_parallel']} "
              f"kv={cand.get('kv_layout', 'dense')}: "
              f"{cost.token_time_s * 1e6:.2f} us/token "
              f"(comm {cost.comm_time_s * 1e6:.2f})")

    if args.replicas > 1:
        # The fleet objective: rank (replicas x tp x kv_layout) by
        # aggregate throughput for a short-request mix before
        # committing devices (replicas priced across DCN, tp held
        # within a slice's ICI).
        fleet_ranked = rank_serving(
            trainable, rs, objective="fleet", batch_slots=args.slots,
            max_len=args.max_len, mean_request_len=args.max_new * 2)
        print("fleet shapes by predicted aggregate throughput:")
        for cand, cost in fleet_ranked[:4]:
            print(f"  replicas={cand.get('replicas', 1)} "
                  f"tp={cand['tensor_parallel']} "
                  f"kv={cand.get('kv_layout', 'dense')}: "
                  f"fleet_score={cost.fleet_score:.3e}")
        return _serve_fleet(args, cfg, trainable)

    strategy = None
    if args.train_steps > 0:
        from autodist_tpu import AutoDist
        from autodist_tpu.resource import factor_3d

        n = jax.device_count()
        tp = args.tensor_parallel
        pp = cfg.num_layers
        dp = n // (pp * tp)
        if dp < 1:
            raise SystemExit(
                f"--train-steps needs layers x tp <= devices "
                f"({pp} x {tp} > {n})")
        ad = AutoDist({"topology": {"num_devices": dp * pp * tp},
                       "mesh": factor_3d(dp * pp * tp, pipe=pp, model=tp,
                                         data=dp)},
                      "Pipeline", num_microbatches=2, tensor_parallel=tp,
                      vocab_parallel=args.vocab_parallel)
        strategy = ad.build_or_load_strategy(trainable)
        runner = ad.build(trainable, strategy)
        r = np.random.RandomState(0)
        for _ in range(args.train_steps):
            x = r.randint(0, args.vocab, (8, 8)).astype(np.int32)
            runner.step({"x": x,
                         "y": np.concatenate([x[:, 1:], x[:, :1]], 1)})
        source = {"runner": runner}
    else:
        source = {"params": trainable.params}

    engine_kw = dict(tensor_parallel=args.tensor_parallel,
                     vocab_parallel=args.vocab_parallel,
                     num_slots=args.slots, max_len=args.max_len,
                     prefill_len=args.prefill_len,
                     decode_steps=args.decode_steps)
    if args.artifact:
        # Round-trip through the export artifact: params at logical
        # names/unpadded shapes + a real full-recompute apply program
        # (the artifact stays servable WITHOUT this framework, the
        # export_model contract), then serve the reloaded params.
        from autodist_tpu.checkpoint import export_model
        from autodist_tpu.models.pipeline_lm import sequential_logits

        params = source["runner"].get_params() if "runner" in source \
            else source["params"]

        def apply_fn(p, tokens):
            return sequential_logits(cfg, p, tokens)

        sample = np.zeros((1, args.prefill_len), np.int32)
        export_model(args.artifact, apply_fn, params, [sample],
                     platforms=None)
        engine = serving.serve(cfg, artifact=args.artifact,
                               strategy=strategy, **engine_kw)
    else:
        engine = serving.serve(cfg, strategy=strategy, **source,
                               **engine_kw)

    batcher = serving.ContinuousBatcher(engine)
    r = np.random.RandomState(7)
    t0 = time.perf_counter()
    rids = []
    for i in range(args.requests):
        plen = int(r.randint(1, args.prefill_len + 1))
        prompt = r.randint(0, args.vocab, (plen,)).tolist()
        rids.append(batcher.submit(prompt, max_new_tokens=args.max_new))
    done = batcher.run()
    wall = time.perf_counter() - t0

    total_tokens = sum(len(c.tokens) for c in done.values())
    ttfts = sorted(c.ttft_s for c in done.values())
    print(f"served {len(done)} requests / {total_tokens} tokens in "
          f"{wall:.2f}s ({total_tokens / wall:.1f} tokens/s aggregate), "
          f"ttft p50 {ttfts[len(ttfts) // 2] * 1e3:.1f} ms "
          f"[tp={args.tensor_parallel}, "
          f"vocab_parallel={args.vocab_parallel}, slots={args.slots}, "
          f"K={args.decode_steps}]")

    if args.telemetry_dir:
        telemetry.annotate(serve=True, slots=args.slots,
                           decode_steps=args.decode_steps,
                           tensor_parallel=args.tensor_parallel,
                           vocab_parallel=args.vocab_parallel,
                           requests=len(done), tokens=total_tokens)
        paths = telemetry.flush()
        print(f"telemetry artifacts in {args.telemetry_dir}: "
              f"{sorted(os.path.basename(p) for p in paths.values())}")

    if args.smoke:
        assert len(done) == args.requests, (len(done), args.requests)
        assert all(1 <= len(c.tokens) <= args.max_new
                   for c in done.values())
        assert all(0 <= t < args.vocab for c in done.values()
                   for t in c.tokens), "sampled a padded vocab row"
        print("serve smoke ok")


if __name__ == "__main__":
    main()
