"""``ling-3.0-flash``'s part of the yardstick: its flops module against a
hand count from the published sizes, its controls at a size a test run
holds, and a whole rehearsed run of its cell — sound, and with the decay,
the state, the router's groups or correction, the heads' gate, the cached
row or the share broken underneath."""
import json

import numpy as np
import pytest

import run as bench
from harness import loader, weights

NAME = "ling-3.0-flash"
CELL = NAME + ".closed-loop-96-long-decode"
RUN = ["--workload", CELL, "--seed", "2147483659", "--seconds", "1",
       "--rehearse"]


def _cfg(rehearse=False):
    spec = loader.benchmark_spec()
    return loader.sized(loader.config_of(spec, {"name": NAME,
                                                "config": NAME}), rehearse)


def test_published_widths_and_the_cut():
    cfg = _cfg()
    published = {
        "hidden_size": 2560, "num_attention_heads": 32,
        "num_key_value_heads": 32, "head_dim": 128, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "q_lora_rank": None, "intermediate_size": 6144,
        "moe_intermediate_size": 768,
        "moe_shared_expert_intermediate_size": 768, "num_shared_experts": 1,
        "num_experts_per_tok": 8, "num_experts_published": 512,
        "router_width": 512, "n_group": 8, "topk_group": 4,
        "routed_scaling_factor": 2.5, "score_function": "sigmoid",
        "norm_topk_prob": True, "first_k_dense_replace": 2,
        "layer_group_size": 6, "short_conv_kernel_size": 4,
        "kda_lower_bound": -5, "rope_theta": 6000000,
        "max_position_embeddings": 262144, "num_hidden_layers_published": 42}
    assert {k: cfg[k] for k in published} == published
    assert sorted(cfg["reduced"]) == sorted(
        ["num_hidden_layers", "num_experts", "vocab_size", "serving.max_len"])
    # 8 chips share a layer, as many as the router has groups: one group
    # of 64 experts, 1/8 of the vocabulary; two whole periods, the two
    # dense layers and the 10 that follow
    assert cfg["num_experts"] * 8 == cfg["num_experts_published"]
    assert cfg["num_experts"] == cfg["num_experts_published"] // cfg["n_group"]
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"]
    assert cfg["num_hidden_layers"] == 2 * cfg["layer_group_size"]
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    # no clamp is built: the limits of the kept layers are 0
    assert not any(cfg["expert_swiglu_limit_list"][:12]
                   + cfg["share_expert_swiglu_limit_list"][:12])
    for key in ("deployment", "assumed", "departures"):
        assert cfg[key]


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
                   if r["name"] == "Ling-3.0-flash")
    cfg = _cfg()
    assert cfg["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differ == {"num_hidden_layers", "num_experts", "vocab_size"}


def test_the_builder_refuses_what_the_block_does_not_implement():
    builder = loader.load_module("builders", "hybrid_latent_moe_lm_serving")
    cfg = _cfg()
    builder.transformer_config(cfg)
    limits = list(cfg["expert_swiglu_limit_list"])
    limits[7] = 4
    for change, says in [
            ({"expert_swiglu_limit_list": limits}, "swiglu limit"),
            ({"q_lora_rank": 1536}, "q_lora_rank"),
            ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
            ({"use_nGPT": True}, "use_nGPT"),
            ({"up_proj_norm": True}, "up_proj_norm"),
            ({"value_norm": True}, "value_norm"),
            ({"scale_router_input": True}, "scale_router_input"),
            ({"mtp_use_kda": True}, "mtp_use_kda")]:
        with pytest.raises(ValueError, match=says):
            builder.transformer_config(dict(cfg, **change))


def test_params_and_bytes():
    flops = loader.load_module("flops", NAME)
    cfg = _cfg()
    # an expert: gate, up, down 3 * 2560 * 768
    assert flops.expert_param_count(cfg) == 5_898_240
    # a KDA mixer: q, k, v, decay, gate, out 6 * 2560 * 4096 =
    # 62,914,560; beta 2560 * 32 = 81,920; taps 4 * 12,288 = 49,152;
    # A_log 32, dt_bias 4,096, the norm's scale 128
    assert flops.linear_mixer_param_count(cfg) == 63_049_888
    # an MLA mixer: q 2560 * 6144 = 15,728,640; kv_a 2560 * 576 =
    # 1,474,560; its norm 512; kv_b 512 * 8192 = 4,194,304; the heads'
    # gate 2560 * 32 = 81,920; out 4096 * 2560 = 10,485,760
    assert flops.latent_mixer_param_count(cfg) == 31_965_696
    # router 2560 * 512 = 1,310,720 and its correction 512; the shared
    # expert 5,898,240
    assert flops.ffn_shared_param_count(cfg) == 7_209_472
    assert flops.dense_ffn_param_count(cfg) == 47_185_920
    dense = 10 * 63_049_888 + 2 * 31_965_696 + 12 * 5_120 \
        + 2 * 47_185_920 + 10 * 7_209_472 + 19_648 * 2560 + 2560
    assert flops.dense_param_count(cfg) == dense
    # with the embedding and 10 * 64 experts: 4.73 G parameters, 9.47 GB
    assert flops.param_count(cfg) == dense + 19_648 * 2560 \
        + 10 * 64 * 5_898_240
    assert 9.45e9 < 2 * flops.param_count(cfg) < 9.49e9
    # a position: 2 latent layers x (512 + 64) x 2 B
    assert flops.kv_bytes_per_token(cfg) == 2_304
    assert flops.latent_attend_bytes(cfg, 2 * 1000) == 2_000 * 1_152
    # a slot's state in one KDA layer: 32 x 128 x 128 x 4 B, and the
    # decay of each of its 32 x 128 rows
    assert flops.state_bytes_per_layer(cfg) == 2_097_152
    assert flops.state_update_bytes(cfg, 12) == 12 * (2 * 2_097_152 + 16_384)
    assert flops.experts_bytes(cfg, 30) == 30 * 11_796_480
    # a step at 96 live slots of ~1,260 positions, 50 of 64 experts hit a
    # layer: dense 2.08 GB, experts 5.90 GB, state 4.04 GB, rows 0.28 GB
    step = flops.decode_step_bytes(cfg, 96 * 1260, 96, 500)
    assert step == 2 * dense + 500 * 11_796_480 \
        + 960 * (2 * 2_097_152 + 16_384) + 120_960 * 2_304
    assert 12.0e9 < step < 12.6e9
    assert flops.logits_flops(cfg, 8) == 2 * 8 * 2560 * 19_648


def test_reference_tree_is_the_programs():
    import jax

    from autodist_tpu.models import pipeline_lm as lm

    ref = loader.load_module("reference", NAME)
    builder = loader.load_module("builders", "hybrid_latent_moe_lm_serving")
    for rehearse in (False, True):
        cfg = _cfg(rehearse)
        want = lm.param_shapes(builder.transformer_config(cfg))
        got = jax.tree.map(lambda s: s[0], ref.param_shapes(cfg),
                           is_leaf=lambda x: isinstance(x, tuple)
                           and isinstance(x[1], str))
        assert got == want


def test_the_reference_imports_nothing_of_the_program():
    import os

    with open(os.path.join(loader.BENCH_DIR, "reference", NAME + ".py")) as f:
        text = f.read()
    assert "autodist_tpu" not in text
    assert 'default_matmul_precision("highest")' in text


def test_controls_fail_and_bf16_passes():
    """At the rehearsal's size with bf16 weights: the reference in fp8
    put in the program's place is NOT correct under the cell's limits;
    rounded to bf16, as the program computes, it passes.  The weights'
    scale is 0.05 here, where the toy's logits and its fp8 control read
    what the cell's do on the chip (p99 0.77-1.07 and mean 0.18 against
    0.86-0.88 and 0.15 there); at the rehearsal's 0.113 bf16 alone moves
    the toy's logits by 0.8."""
    ref = loader.load_module("reference", NAME)
    cfg = _cfg(True)
    cfg["initializer_range"] = 0.05
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
    # float32 on both sides: the served tokens are the reference's
    assert line["checks"]["logit_gap_mean"]["value"] < 1e-4


# what each fault reads in the rehearsal (float32, logits of size ~3):
# the mean gap over the sampled tokens at least this, where a sound run
# reads under 1e-4 (read here: 1.6, 1.4, 0.30, 0.054, 0.075, 0.023, 0.69)
@pytest.mark.parametrize("plant,at_least", [
    ("scalar_gate", 0.5), ("stale_state", 0.5), ("groups_unlimited", 0.1),
    ("correction_weighs", 0.02), ("no_head_gate", 0.03), ("row_short", 0.003),
    ("share_offset", 0.2)])
def test_serving_with_a_planted_fault(capsys, plant, at_least):
    """One gate a head in place of one a channel, a state not overwritten
    at admission, no group left out, the correction used as a weight, the
    heads' gates dropped, a row read one position short and a share
    offset by one (``tools/planted_hybrid_latent.py``) each read far above
    a sound rehearsal."""
    with loader.load_module("tools", "planted_hybrid_latent").PLANTS[plant]():
        rc, line, out = _last_line(capsys, RUN)
    assert line["checks"]["logit_gap_mean"]["value"] > at_least
    if plant != "row_short":    # the mildest: 2 layers of 12, one row
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
