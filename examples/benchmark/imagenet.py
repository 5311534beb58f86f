"""ImageNet CNN benchmark (≙ reference ``examples/benchmark/imagenet.py``):
ResNet50/ResNet101/VGG16/InceptionV3/DenseNet121 with a strategy flag and
the reference's per-model allreduce chunk-size tuning
(``imagenet.py:151-158``: vgg16=25, resnet101=200, inceptionv3=30,
default=512).  Synthetic ImageNet-shaped data.

    python examples/benchmark/imagenet.py --model resnet50 --train-steps 50
    python examples/benchmark/imagenet.py --model resnet18 --preset tiny
"""
import numpy as np

from common import BenchmarkLogger, base_parser, run_benchmark

# Reference-tuned collective bucketing per model (imagenet.py:151-158).
CHUNK_SIZES = {"vgg16": 25, "resnet101": 200, "inceptionv3": 30}
DEFAULT_CHUNK = 512

# Textbook forward-pass GFLOPs per image at the canonical input size
# (224px; inception 299px), for MFU estimation (training ~ 3x fwd).
FWD_GFLOPS = {"resnet50": 4.1, "resnet101": 7.8, "vgg16": 15.5,
              "densenet121": 2.9, "inceptionv3": 5.7}


def build_model(name: str):
    from autodist_tpu.models import densenet, inception, resnet, vgg
    zoo = {
        "resnet18": resnet.ResNet18, "resnet50": resnet.ResNet50,
        "resnet101": resnet.ResNet101, "vgg16": vgg.VGG16,
        "densenet121": densenet.DenseNet121,
        "inceptionv3": inception.InceptionV3,
    }
    return zoo[name](num_classes=1000)


def main():
    from autodist_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = base_parser("ImageNet CNN benchmark")
    ap.add_argument("--model", default="resnet50",
                    choices=["resnet18", "resnet50", "resnet101", "vgg16",
                             "densenet121", "inceptionv3"])
    ap.add_argument("--json", action="store_true",
                    help="also print one machine-readable headline line")
    args = ap.parse_args()

    import jax
    import optax

    from autodist_tpu import AutoDist
    from autodist_tpu.models.resnet import make_image_trainable
    from autodist_tpu.resource import ResourceSpec, on_accelerator
    from autodist_tpu.strategy import builders

    rs = ResourceSpec({})
    n = rs.num_devices()
    on_accel = on_accelerator()
    if args.preset == "tiny":
        image_size, candidates = 32, [8 * n]
    else:
        image_size = 299 if args.model == "inceptionv3" else 224
        if args.batch_size:
            candidates = [args.batch_size]
        elif on_accel:
            # Self-tune the per-chip batch: conv utilization keeps
            # climbing until HBM runs out, and the knee is
            # hardware/model dependent — measure a few steps of each
            # size and score the examples/sec winner (an OOM just
            # loses its probe).  Ascending order so the riskiest
            # allocation comes last.
            candidates = [32 * n, 128 * n, 256 * n]
        else:
            candidates = [32 * n]
    import os
    env_cands = os.environ.get("AUTODIST_TPU_BATCH_CANDIDATES")
    if env_cands and not args.batch_size:
        # Per-chip candidate list override: lets a hardware session
        # re-scope the probe (and CPU tests exercise the probe path)
        # without editing code.
        try:
            candidates = [int(s) * n for s in env_cands.split(",")]
        except ValueError:
            raise SystemExit(
                f"AUTODIST_TPU_BATCH_CANDIDATES={env_cands!r} is not a "
                f"comma-separated list of per-chip batch sizes")
    # Ascending: the probe loop stops at the first failure on the grounds
    # that every LARGER size shares its fate.
    candidates = sorted(candidates)
    if len(candidates) > 1 and jax.process_count() > 1:
        # Each process would pick from its own wall-clock timings; within
        # noise two hosts could choose different global batches and issue
        # shape-mismatched collectives.  Self-tuning is a single-host
        # convenience — multi-host runs state their batch explicitly.
        print("# multi-host run: skipping batch self-tune "
              f"(using {candidates[0] // n}/chip; set --batch-size to override)")
        candidates = candidates[:1]
    chunk = args.chunk_size or CHUNK_SIZES.get(args.model, DEFAULT_CHUNK)

    def build_runner():
        trainable = make_image_trainable(
            build_model(args.model), optax.sgd(0.1, momentum=0.9),
            jax.random.PRNGKey(0), image_size=image_size, batch_size=2,
            name=args.model)
        builder = builders.create(args.strategy, **(
            {"chunk_size": chunk} if args.strategy == "AllReduce" else {}))
        return AutoDist(rs, builder).build(trainable)

    runner = build_runner()
    rng = np.random.RandomState(0)

    def make_data(b):
        return {"x": rng.rand(b, image_size, image_size, 3).astype(np.float32),
                "y": rng.randint(0, 1000, (b,)).astype(np.int32)}

    batch = candidates[0]
    if len(candidates) > 1:
        import time
        rates, failed = {}, False
        for b in candidates:
            try:
                data = make_data(b)
                m = runner.step(data)                      # compile
                float(np.asarray(m["loss"]))
                t0 = time.perf_counter()
                for _ in range(3):
                    m = runner.step(data)
                float(np.asarray(m["loss"]))
                rates[b] = 3 * b / (time.perf_counter() - t0)
                print(f"# probe batch {b // n}/chip: {rates[b]:.1f} ex/s")
            except Exception as e:
                print(f"# probe batch {b // n}/chip failed: {e}")
                failed = True
                break  # larger sizes can only fail the same way
        if not rates:
            raise SystemExit("every batch-size probe failed")
        batch = max(rates, key=rates.get)
        if failed:
            # An OOM'd step may have consumed donated state buffers;
            # rebuild from the deterministic seed for the scored run.
            runner.close()
            runner = build_runner()

    data = make_data(batch)

    logger = BenchmarkLogger(args.benchmark_log_dir)
    flops_per_example = peak_flops = None
    if args.model in FWD_GFLOPS and args.preset != "tiny":
        flops_per_example = 3.0 * FWD_GFLOPS[args.model] * 1e9
        peak_flops = rs.chip.peak_bf16_tflops * 1e12 * n
    summary = run_benchmark(
        runner, lambda step: data, batch_size=batch,
        train_steps=args.train_steps, warmup_steps=args.warmup_steps,
        log_steps=args.log_steps, logger=logger,
        steps_per_loop=args.steps_per_loop, static_data=True,
        flops_per_example=flops_per_example, peak_flops=peak_flops)
    print(f"{args.model}/{args.strategy}: "
          f"{summary['examples_per_sec']:.1f} examples/s "
          f"({summary['step_ms_mean']:.1f} ms/step, {n} devices)")
    if args.json:
        import json
        record = {
            "metric": f"{args.model}_images_per_sec_per_chip",
            "value": round(summary["examples_per_sec"] / n, 2),
            "unit": "examples/sec/chip", "strategy": args.strategy,
            "devices": n, "chip": rs.chip.name, "image_size": image_size,
            "batch_per_chip": batch // n}
        if summary.get("mfu") is not None:
            record["mfu_est"] = round(summary["mfu"], 4)
        print(json.dumps(record))
    logger.close()


if __name__ == "__main__":
    main()
