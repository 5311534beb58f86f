"""BERT MLM pretraining benchmark (≙ reference ``examples/benchmark/bert.py``:
BERT-large MLM with chunk-size 256).  Reports examples/sec and MFU.

    python examples/benchmark/bert.py --bert-config base --train-steps 30
    python examples/benchmark/bert.py --bert-config tiny --preset tiny
    python examples/benchmark/bert.py --flash-attention   # causal-free fused path
"""
from common import BenchmarkLogger, base_parser, run_benchmark


def mlm_model_flops_per_example(cfg, seq_len: int, num_masked: int) -> float:
    """Analytic matmul FLOPs for one BERT MLM training example (fwd x3 for
    fwd+bwd).  Counts encoder matmuls (qkv 6H^2 + out-proj 2H^2 + mlp
    4*H*mlp_dim per token), attention score+value einsums (4*L*H per
    token), and the MLM head (2*H^2 transform + 2*H*V tied decode per
    masked position)."""
    H, L, V, P = cfg.hidden_size, seq_len, cfg.vocab_size, num_masked
    per_token_layer = 8.0 * H * H + 4.0 * H * cfg.mlp_dim + 4.0 * L * H
    encoder_fwd = L * cfg.num_layers * per_token_layer
    head_fwd = P * (2.0 * H * H + 2.0 * H * V)
    return 3.0 * (encoder_fwd + head_fwd)


def main():
    from autodist_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = base_parser("BERT MLM pretraining benchmark")
    ap.add_argument("--bert-config", default="base",
                    choices=["tiny", "base", "large"])
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--num-masked", type=int, default=None)
    ap.add_argument("--flash-attention", action="store_true",
                    help="use the Pallas flash-attention kernel (no padding "
                         "mask: synthetic batches are unpadded)")
    args = ap.parse_args()

    import jax
    import optax

    from autodist_tpu import AutoDist
    from autodist_tpu.models import bert
    from autodist_tpu.models.transformer import TransformerConfig
    from autodist_tpu.resource import ResourceSpec
    from autodist_tpu.strategy import builders

    rs = ResourceSpec({})
    n = rs.num_devices()

    attention_fn = None
    if args.flash_attention:
        from autodist_tpu.ops import make_attention_fn
        attention_fn = make_attention_fn(causal=False)

    kw = dict(dropout_rate=0.0, attention_dropout_rate=0.0,
              attention_fn=attention_fn)
    if args.bert_config == "tiny" or args.preset == "tiny":
        cfg = TransformerConfig(vocab_size=1024, hidden_size=64, num_layers=2,
                                num_heads=2, mlp_dim=128, max_len=128, **kw)
        seq_len, num_masked, batch = 64, 8, 4 * n
    else:
        cfg = (bert.bert_base if args.bert_config == "base"
               else bert.bert_large)(**kw)
        seq_len = args.seq_len or 512
        num_masked = args.num_masked or int(seq_len * 0.15)
        batch = args.batch_size or 16 * n
    chunk = args.chunk_size or 256  # reference bert.py:62

    trainable = bert.make_mlm_trainable(
        cfg, optax.adamw(1e-4, weight_decay=0.01), jax.random.PRNGKey(0),
        batch_size=2, seq_len=seq_len, num_masked=num_masked,
        with_input_mask=not args.flash_attention)
    builder = builders.create(args.strategy, **(
        {"chunk_size": chunk} if args.strategy == "AllReduce" else {}))
    runner = AutoDist(rs, builder).build(trainable)

    # Flash attention cannot honor the padding mask; synthetic batches are
    # unpadded (input_mask all ones) so drop it entirely on that path.
    data = bert.synthetic_mlm_batch(0, batch, seq_len, num_masked,
                                    cfg.vocab_size)
    if args.flash_attention:
        data = {k: v for k, v in data.items() if k != "input_mask"}

    flops_per_example = mlm_model_flops_per_example(cfg, seq_len,
                                                    num_masked)
    peak = rs.chip.peak_bf16_tflops * 1e12 * n

    logger = BenchmarkLogger(args.benchmark_log_dir)
    summary = run_benchmark(
        runner, lambda step: data, batch_size=batch,
        train_steps=args.train_steps, warmup_steps=args.warmup_steps,
        log_steps=args.log_steps, logger=logger,
        steps_per_loop=args.steps_per_loop, static_data=True,
        flops_per_example=flops_per_example, peak_flops=peak)
    mfu = summary.get("mfu")
    print(f"bert-{args.bert_config}/{args.strategy}: "
          f"{summary['examples_per_sec']:.1f} examples/s"
          + (f", MFU={mfu:.3f}" if mfu is not None else "")
          + f" ({n}x {rs.chip.name})")
    logger.close()


if __name__ == "__main__":
    main()
