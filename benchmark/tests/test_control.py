"""The control of each configuration — its plain reference computed in
fp8, the precision below the bf16 the configurations state, and put in
the program's place — must come out NOT correct under the limits the
benchmark runs with, and the same reference rounded to bf16 must pass.

At a size a test run holds.  The width is far below the published one,
so ``initializer_range`` is scaled up by the root of the ratio of widths:
each layer then moves the residual stream as much as it does at full
width (at 0.02 a narrow toy only copies its input token, and no precision
moves its choice).  On the chip, at each cell's own size,
``tools/readings.py`` made the same comparison (``PERF.md`` section 2).
"""
import numpy as np

from harness import loader, weights


def test_bert_control_fails_and_bf16_passes():
    ref = loader.load_module("reference", "bert-base-mlm")
    builder = loader.load_module("builders", "bert_mlm")
    cfg = {"hidden_size": 128, "num_hidden_layers": 4,
           "num_attention_heads": 2, "intermediate_size": 512,
           "vocab_size": 2048, "max_position_embeddings": 64,
           "type_vocab_size": 2, "layer_norm_eps": 1e-6,
           "initializer_range": 0.02 * (768 / 128) ** 0.5}
    traffic = {"seq_len": 64, "num_masked": 10}
    opt = {"learning_rate": 1e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
           "weight_decay": 0.01}
    for seed in (1, 2, 3):
        fill = lambda: weights.seeded_fill(ref.param_shapes(cfg), seed,
                                           cfg["initializer_range"])
        rng = weights.host_rng(seed, "batches")
        batches = [builder.make_batch(rng, cfg, traffic, 8) for _ in range(3)]
        reference = ref.first_steps(fill(), batches, cfg, opt)
        rows = {p: {r[0]: r for r in ref.compare(
            ref.first_steps(fill(), batches, cfg, opt, precision=p),
            reference)} for p in ("bfloat16", "fp8")}
        assert all(r[3] for r in rows["bfloat16"].values()), rows["bfloat16"]
        failed = [n for n, r in rows["fp8"].items() if not r[3]]
        assert "grad_abs_gap" in failed, rows["fp8"]
        # and by a margin: three times what bf16 reads
        assert rows["fp8"]["grad_abs_gap"][1] \
            > 3 * rows["bfloat16"]["grad_abs_gap"][1]


def test_gpt2_control_fails_and_bf16_passes():
    ref = loader.load_module("reference", "gpt2-large-postln")
    H = 128
    cfg = {"n_embd": H, "n_layer": 12, "n_head": 2, "n_inner": None,
           "vocab_size": 8192, "n_positions": 64,
           "layer_norm_epsilon": 1e-6,
           "initializer_range": 0.02 * (1280 / H) ** 0.5,
           "serving": {"weights_dtype": "bfloat16", "dtype": "bfloat16"}}
    for seed in (1, 2, 3):
        params = weights.seeded_fill(ref.param_shapes(cfg), seed,
                                     cfg["initializer_range"])
        r = np.random.default_rng(seed)
        served = [(r.integers(0, 8192, 24).tolist(),
                   r.integers(0, 8192, 40).tolist()) for _ in range(3)]
        # at each position, the token each precision puts first
        sound = ref.compare(ref.served_gaps(params, served, cfg,
                                            control="bfloat16"))[0]
        control = ref.compare(ref.served_gaps(params, served, cfg,
                                              control="fp8"))[0]
        assert sound[3], sound
        assert not control[3], control
        assert control[1] > 3 * max(sound[1], 0.01)
