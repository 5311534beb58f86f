"""NCF (NeuMF) recommendation benchmark
(≙ reference ``examples/benchmark/ncf.py``: NeuMF on MovieLens with
LazyAdam).  Synthetic MovieLens-1M-shaped interactions; the embedding
tables take the sparse/sharded path under PS-family strategies.

    python examples/benchmark/ncf.py --train-steps 50
    python examples/benchmark/ncf.py --preset tiny
"""
import numpy as np

from common import BenchmarkLogger, base_parser, run_benchmark


def main():
    from autodist_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = base_parser("NCF recommendation benchmark")
    ap.add_argument("--num-users", type=int, default=None)
    ap.add_argument("--num-items", type=int, default=None)
    args = ap.parse_args()

    import jax
    import optax

    from autodist_tpu import AutoDist
    from autodist_tpu.models.ncf import make_ncf_trainable
    from autodist_tpu.resource import ResourceSpec

    rs = ResourceSpec({})
    n = rs.num_devices()
    if args.preset == "tiny":
        num_users, num_items, mf_dim, mlp_dims = 500, 200, 8, (32, 16, 8)
        batch = args.batch_size or 64 * n
    else:  # MovieLens-1M scale (reference ncf defaults)
        num_users = args.num_users or 6040
        num_items = args.num_items or 3706
        mf_dim, mlp_dims = 64, (256, 128, 64)
        batch = args.batch_size or 1024 * n

    trainable = make_ncf_trainable(
        # adam stands in for LazyAdam: with the sparse/sharded embedding
        # path only touched rows move, which is what LazyAdam bought on TF
        optax.adam(1e-3), jax.random.PRNGKey(0),
        num_users=num_users, num_items=num_items, mf_dim=mf_dim,
        mlp_dims=mlp_dims)
    runner = AutoDist(rs, args.strategy).build(trainable)

    rng = np.random.RandomState(0)

    def make_batch(step):
        return {
            "users": rng.randint(0, num_users, (batch,)).astype(np.int32),
            "items": rng.randint(0, num_items, (batch,)).astype(np.int32),
            "labels": rng.randint(0, 2, (batch,)).astype(np.int32),
        }

    logger = BenchmarkLogger(args.benchmark_log_dir)
    summary = run_benchmark(
        runner, make_batch, batch_size=batch,
        train_steps=args.train_steps, warmup_steps=args.warmup_steps,
        log_steps=args.log_steps, logger=logger,
        steps_per_loop=args.steps_per_loop)
    print(f"ncf/{args.strategy}: {summary['examples_per_sec']:.0f} "
          f"examples/s ({summary['step_ms_mean']:.2f} ms/step, {n} devices)")
    logger.close()


if __name__ == "__main__":
    main()
