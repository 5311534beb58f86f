"""``qwen3-next-80b-a3b``'s part of the yardstick: its flops module
against a hand count from the published sizes, its controls at a size a
test run holds, and a whole rehearsed run of its cell — sound, and with
the state, the convolution tail or the share broken underneath."""
import json

import numpy as np
import pytest

import run as bench
from harness import loader, weights

NAME = "qwen3-next-80b-a3b"
CELL = NAME + ".closed-loop-32-decode-heavy"
RUN = ["--workload", CELL, "--seed", "2147483659", "--seconds", "1",
       "--rehearse"]


def _cfg(rehearse=False):
    spec = loader.benchmark_spec()
    return loader.sized(loader.config_of(spec, {"name": NAME,
                                                "config": NAME}), rehearse)


def test_published_widths_and_the_cut():
    cfg = _cfg()
    published = {
        "hidden_size": 2048, "num_attention_heads": 16,
        "num_key_value_heads": 2, "head_dim": 256,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_key_head_dim": 128, "linear_value_head_dim": 128,
        "linear_conv_kernel_dim": 4, "moe_intermediate_size": 512,
        "num_experts_per_tok": 10, "num_experts_published": 512,
        "router_width": 512, "shared_expert_intermediate_size": 512,
        "full_attention_interval": 4, "partial_rotary_factor": 0.25,
        "rope_theta": 10000000, "max_position_embeddings": 262144}
    assert {k: cfg[k] for k in published} == published
    assert sorted(cfg["reduced"]) == sorted(
        ["num_hidden_layers", "num_experts", "vocab_size",
         "serving.max_len"])
    # 8 chips share a layer: 64 of 512 experts, 1/8 of the vocabulary;
    # four whole periods of 48 layers' twelve
    assert cfg["num_experts"] * 8 == cfg["num_experts_published"]
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"]
    assert cfg["num_hidden_layers"] % cfg["full_attention_interval"] == 0
    assert cfg["num_hidden_layers"] >= 4


def test_params_and_bytes():
    flops = loader.load_module("flops", NAME)
    cfg = _cfg()
    # an expert: gate, up, down 3 * 2048 * 512
    assert flops.expert_param_count(cfg) == 3_145_728
    # the mixers: qkvz 2048 * 12288 = 25,165,824; ba 2048 * 64 =
    # 131,072; taps 4 * 8192 = 32,768; A_log + dt_bias 64; the gated
    # norm 128; out 4096 * 2048 = 8,388,608
    assert flops.linear_mixer_param_count(cfg) == 33_718_464
    # q with its gate, k, v 2048 * (8192 + 1024) = 18,874,368; q/k
    # norms 512; out 4096 * 2048 = 8,388,608
    assert flops.full_mixer_param_count(cfg) == 27_263_488
    # router 2048 * 512 = 1,048,576; shared expert 3,145,728; its gate
    # 2,048
    assert flops.ffn_shared_param_count(cfg) == 4_196_352
    dense = 4 * 27_263_488 + 12 * 33_718_464 \
        + 16 * (4_196_352 + 4_096) + 18_992 * 2048 + 2048
    assert flops.dense_param_count(cfg) == dense
    # with the embedding and 16 * 64 experts: 3.88 G parameters, 7.76 GB
    assert flops.param_count(cfg) == dense + 18_992 * 2048 \
        + 16 * 64 * 3_145_728
    assert 7.7e9 < 2 * flops.param_count(cfg) < 7.8e9
    # keys and values of a position: 4 layers x 2 x 2 heads x 256 x 2 B
    assert flops.kv_bytes_per_token(cfg) == 8_192
    # a slot's state in one linear layer: 32 x 128 x 128 x 4 B
    assert flops.state_bytes_per_layer(cfg) == 2_097_152
    assert flops.experts_bytes(cfg, 30) == 30 * 6_291_456
    assert flops.state_update_bytes(cfg, 12) == 2 * 12 * 2_097_152
    # a step at 32 live slots, ~30 experts hit a layer, 32 x 500 live
    # positions: dense 1.11 GB, experts 3.02 GB, state 1.61 GB, KV 0.13
    step = flops.decode_step_bytes(cfg, 32 * 500, 32, 16 * 30)
    assert step == 2 * dense + 480 * 6_291_456 \
        + 2 * 32 * 12 * 2_097_152 + 16_000 * 8_192
    assert 5.5e9 < step < 6.5e9
    assert flops.logits_flops(cfg, 8) == 2 * 8 * 2048 * 18_992


def test_reference_tree_is_the_programs():
    import jax

    from autodist_tpu.models import pipeline_lm as lm

    ref = loader.load_module("reference", NAME)
    builder = loader.load_module("builders", "hybrid_moe_lm_serving")
    for rehearse in (False, True):
        cfg = _cfg(rehearse)
        want = lm.param_shapes(builder.transformer_config(cfg))
        got = jax.tree.map(lambda s: s[0], ref.param_shapes(cfg),
                           is_leaf=lambda x: isinstance(x, tuple)
                           and isinstance(x[1], str))
        assert got == want


def test_controls_fail_and_bf16_passes():
    """At the rehearsal's size with bf16 weights: the reference in fp8
    put in the program's place is NOT correct under the cell's limits;
    rounded to bf16, as the program computes, it passes."""
    ref = loader.load_module("reference", NAME)
    cfg = _cfg(True)
    cfg["serving"] = dict(cfg["serving"], weights_dtype="bfloat16",
                          max_len=64)
    for seed in (1, 2):
        params = weights.seeded_fill(ref.param_shapes(cfg), seed,
                                     cfg["initializer_range"])
        r = np.random.default_rng(seed)
        served = [(r.integers(0, 509, 20).tolist(),
                   r.integers(0, 509, 40).tolist()) for _ in range(3)]
        sound = ref.compare(ref.served_gaps(params, served, cfg,
                                            control="bfloat16"))
        control = ref.compare(ref.served_gaps(params, served, cfg,
                                              control="fp8"))
        assert all(row[3] for row in sound), sound
        assert not all(row[3] for row in control), control


def _last_line(capsys, argv):
    rc = bench.main(argv)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


def test_serving_sound(capsys):
    rc, line, out = _last_line(capsys, RUN)
    assert rc == 0 and line["correct"] is True
    assert line["counts"]["requests_completed"] > 0
    assert line["counts"]["compilations_in_window"] == 0


def _broken(capsys):
    rc, line, out = _last_line(capsys, RUN)
    assert rc == 1 and line["correct"] is False
    assert any("logit_gap" in l and "FAILED" in l for l in out)


@pytest.mark.parametrize("plant", ["stale_state", "no_conv_tail",
                                   "share_offset"])
def test_serving_with_a_planted_fault(capsys, plant):
    """A state not overwritten at admission, a dropped convolution tail
    and a share offset by one (``tools/planted.py``) each fail the
    rehearsal."""
    with loader.load_module("tools", "planted").PLANTS[plant]():
        _broken(capsys)


def test_a_fault_of_the_first_tokens_alone_fails():
    """Four requests of 750 tokens whose first four are each 10 below
    the reference's best: the percentile and the mean over all 3,000
    pass, the mean over the first tokens does not."""
    ref = loader.load_module("reference", NAME)
    gaps = [np.r_[np.full(4, 10.0), np.zeros(746)] for _ in range(4)]
    ok = {row[0]: row[3] for row in ref.compare(gaps)}
    assert ok == {"logit_gap_p99": True, "logit_gap_mean": True,
                  "logit_gap_first8_mean": False}
