"""``deepseek-v2-lite``'s part of the yardstick: its flops module against
a hand count from the published sizes, its controls at a size a test run
holds, and a whole rehearsed run of its cell — sound, and with the cached
row, the softmax scale, the top-k weights or the share broken
underneath."""
import json

import numpy as np
import pytest

import run as bench
from harness import loader, weights

NAME = "deepseek-v2-lite"
CELL = NAME + ".closed-loop-64-long-decode"
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
        "num_key_value_heads": 16, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "q_lora_rank": None, "intermediate_size": 10944,
        "moe_intermediate_size": 1408, "num_experts_per_tok": 6,
        "n_routed_experts_published": 64, "router_width": 64,
        "n_shared_experts": 2, "first_k_dense_replace": 1,
        "num_hidden_layers": 27, "norm_topk_prob": False,
        "routed_scaling_factor": 1, "rope_theta": 10000,
        "max_position_embeddings": 163840,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 0.707, "mscale_all_dim": 0.707,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"}}
    assert {k: cfg[k] for k in published} == published
    assert sorted(cfg["reduced"]) == sorted(
        ["n_routed_experts", "vocab_size", "serving.max_len"])
    # 8 chips share a layer: 8 of 64 experts, 1/8 of the vocabulary; no
    # layer is left out
    assert cfg["n_routed_experts"] * 8 == cfg["n_routed_experts_published"]
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"]
    assert cfg["n_routed_experts"] >= 8


def test_every_catalog_key_is_as_published():
    """Every number of the catalog row's ``config`` under the same key,
    but for the keys ``reduced`` lists (the guide's catalog, where it is
    installed)."""
    import os

    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "DeepSeek-V2-Lite")
    cfg = _cfg()
    assert cfg["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differ == {"n_routed_experts", "vocab_size"}


def test_params_and_bytes():
    flops = loader.load_module("flops", NAME)
    cfg = _cfg()
    # an expert: gate, up, down 3 * 2048 * 1408
    assert flops.expert_param_count(cfg) == 8_650_752
    # q_proj 2048 * 3072 = 6,291,456; kv_a_proj_with_mqa 2048 * 576 =
    # 1,179,648; kv_a_layernorm 512; kv_b_proj 512 * 4096 = 2,097,152;
    # o_proj 2048 * 2048 = 4,194,304
    assert flops.attention_param_count(cfg) == 13_763_072
    # router 2048 * 64 = 131,072; the two shared experts 17,301,504
    assert flops.ffn_shared_param_count(cfg) == 17_432_576
    # a routed layer: attention, 8 experts 69,206,016, router and shared
    # experts, two norms 4,096
    assert flops.routed_layer_param_count(cfg) == 100_405_760
    # the leading layer: attention, 3 * 2048 * 10,944, two norms
    dense_layer = 13_763_072 + 67_239_936 + 4_096
    assert dense_layer == 81_007_104
    tables = 2 * 12_800 * 2048
    assert flops.param_count(cfg) == 26 * 100_405_760 + dense_layer \
        + tables + 2048 == 2_743_987_712
    assert 5.48e9 < 2 * flops.param_count(cfg) < 5.50e9
    assert flops.dense_param_count(cfg) == flops.param_count(cfg) \
        - 12_800 * 2048 - 26 * 8 * 8_650_752
    # a position: 27 layers x (512 + 64) x 2 B, where expanded keys and
    # values would be 27 x 16 x (192 + 128) x 2 B
    assert flops.kv_bytes_per_token(cfg) == 31_104
    assert flops.latent_attend_bytes(cfg, 27 * 1000) == 27_000 * 1_152
    assert flops.experts_bytes(cfg, 30) == 30 * 17_301_504
    # a step at 64 live slots of ~1,260 positions, 99% of 8 x 26 experts
    # hit: rows 2.51 GB, dense 1.84 GB, experts 3.56 GB
    step = flops.decode_step_bytes(cfg, 64 * 1260, 64, 206)
    assert step == 2 * flops.dense_param_count(cfg) + 206 * 17_301_504 \
        + 80_640 * 31_104
    assert 7.5e9 < step < 8.3e9
    # a [1, 1024] prompt row in the expanded form: ~2.3 TFLOP
    assert 2.0e12 < flops.forward_flops(cfg, 1024, 512) < 2.6e12
    assert flops.logits_flops(cfg, 8) == 2 * 8 * 2048 * 12_800


def test_reference_tree_is_the_programs():
    import jax

    from autodist_tpu.models import pipeline_lm as lm

    ref = loader.load_module("reference", NAME)
    builder = loader.load_module("builders", "latent_moe_lm_serving")
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


@pytest.mark.parametrize("plant", ["unrotated_key", "latent_unnormed",
                                   "no_mscale", "renormalised",
                                   "share_offset"])
def test_serving_with_a_planted_fault(capsys, plant):
    """A cached row whose positional key lost its rotation or whose
    latent its norm, a softmax scale without ``m ** 2``, top-6 weights
    renormalised and a share offset by one (``tools/planted_latent.py``)
    each fail the rehearsal."""
    with loader.load_module("tools", "planted_latent").PLANTS[plant]():
        rc, line, out = _last_line(capsys, RUN)
    assert rc == 1 and line["correct"] is False
    assert any("logit_gap" in l and "FAILED" in l for l in out)


def test_a_fault_of_the_first_tokens_alone_fails():
    """Four requests of 750 tokens whose first four are each 0.5 below
    the reference's best: the percentile and the mean over all 3,000
    pass, the mean over the first tokens does not."""
    ref = loader.load_module("reference", NAME)
    gaps = [np.r_[np.full(4, 0.5), np.zeros(746)] for _ in range(4)]
    ok = {row[0]: row[3] for row in ref.compare(gaps)}
    assert ok == {"logit_gap_p99": True, "logit_gap_mean": True,
                  "logit_gap_first8_mean": False}
