"""``granite-4.0-h-micro``'s part of the yardstick: its configuration
against the catalog, its flops module against a hand count from the
published sizes and against the program's own tree, its controls at a
size a test run holds, and a whole rehearsed run of its cell — sound, and
with each planted fault underneath."""
import json

import numpy as np
import pytest

import run as bench
from harness import loader, weights

NAME = "granite-4.0-h-micro"
BUILDER = "hybrid_ssd_lm_serving"
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
        "hidden_size": 2048, "num_hidden_layers": 40,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "intermediate_size": 8192, "shared_intermediate_size": 8192,
        "vocab_size": 100352, "mamba_n_heads": 64, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_n_groups": 1, "mamba_d_conv": 4,
        "mamba_expand": 2, "mamba_chunk_size": 256, "mamba_conv_bias": True,
        "mamba_proj_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "residual_multiplier": 0.22,
        "logits_scaling": 8, "rms_norm_eps": 1e-5,
        "position_embedding_type": "nope", "tie_word_embeddings": True,
        "max_position_embeddings": 131072}
    assert {k: cfg[k] for k in published} == published
    kinds = cfg["layer_types"]
    assert len(kinds) == 40 and kinds.count("attention") == 4
    assert [l for l, k in enumerate(kinds) if k == "attention"] \
        == [5, 15, 25, 35]
    # nothing of the model is cut
    assert cfg["reduced"] == ["serving.max_len"]
    s = cfg["serving"]
    assert (s["state_dtype"], s["num_slots"], s["max_len"],
            s["prefill_len"], s["decode_steps"], s["kv_layout"],
            s["temperature"]) == ("float32", 64, 3072, 1024, 8, "dense", 0.0)
    for key in ("deployment", "assumed", "departures", "why"):
        assert cfg[key]
    for reading in ("[z | xBC | dt]", "[x | B | C]", "the gate before the "
                    "norm", "time_step_limit", "FIRST half", "A_log, D and "
                    "dt_bias", "NO positions"):
        assert reading in cfg["assumed"]["recalled"], reading
    for reading in ("dt_bias", "ZERO", "A_log and D", "normal x 0.05"):
        assert reading in cfg["assumed"]["weights"], reading
    spec = loader.benchmark_spec()
    entry = next(c for c in spec["configs"] if c["name"] == NAME)
    assert entry["source"] == cfg["source"] \
        and entry["reduced"] == cfg["reduced"]
    cell = loader.find_cell(spec, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "closed-loop-64-long-decode", 1)


def test_every_catalog_key_is_as_published():
    """Every key of the catalog row's ``config`` under the same key (the
    guide's catalog, where it is installed): nothing differs."""
    import os

    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == NAME)
    cfg = _cfg()
    assert cfg["source"] == row["source_url"]
    assert {k for k, v in row["config"].items() if cfg.get(k) != v} == set()


def test_the_builder_refuses_what_the_block_does_not_implement():
    builder = loader.load_module("builders", BUILDER)
    cfg = _cfg()
    tc = builder.transformer_config(cfg)
    assert tc.block.layer_period == ("linear",) * 5 + ("full",) \
        + ("linear",) * 4
    for change, says in [
            ({"attention_bias": True}, "attention_bias"),
            ({"mamba_proj_bias": True}, "mamba_proj_bias"),
            ({"num_local_experts": 8}, "routed experts"),
            ({"shared_intermediate_size": 4096}, "shared_intermediate_size"),
            ({"hidden_act": "gelu"}, "hidden_act"),
            ({"normalization_function": "layernorm"},
             "normalization_function"),
            ({"position_embedding_type": "rope"}, "position_embedding_type"),
            ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
            ({"tie_word_embeddings": False}, "an untied head"),
            ({"mamba_expand": 4}, "mamba_expand"),
            ({"mamba_chunk_size": 128}, "mamba_chunk_size"),
            ({"layer_types": cfg["layer_types"][:-1]}, "another length"),
            ({"layer_types": ["mamba"] * 39 + ["window"]},
             "other than mamba and attention"),
            ({"serving": dict(cfg["serving"], state_dtype="bfloat16")},
             "other than float32")]:
        with pytest.raises(ValueError, match=says):
            builder.transformer_config(dict(cfg, **change))


def test_the_builder_fails_at_once_without_the_rule(monkeypatch):
    """On a tree without the state-space rule (the parent commit) the
    builder says so before it builds anything."""
    from autodist_tpu.models.transformer import LinearMixerSpec

    monkeypatch.delattr(LinearMixerSpec, "ssd")
    builder = loader.load_module("builders", BUILDER)
    with pytest.raises(NotImplementedError, match="no state-space mixer"):
        builder.transformer_config(_cfg())


def test_params_and_bytes():
    flops = loader.load_module("flops", NAME)
    cfg = _cfg()
    # in 2,048 x 8,512 = 17,432,576; out 4,096 x 2,048 = 8,388,608; taps
    # and bias 5 x 4,352 = 21,760; the norm 4,096; A_log, D, dt_bias 192
    assert flops.conv_channels(cfg) == 4_352
    assert flops.ssd_mixer_param_count(cfg) == 25_847_232
    assert flops.attention_mixer_param_count(cfg) == 10_485_760
    assert flops.ffn_param_count(cfg) == 50_331_648
    total = 36 * (25_847_232 + 50_331_648 + 4_096) \
        + 4 * (10_485_760 + 50_331_648 + 4_096) + 100_352 * 2_048 + 2_048
    assert flops.param_count(cfg) == flops.dense_param_count(cfg) == total
    assert 3.19e9 < total < 3.20e9 and 6.38e9 < 2 * total < 6.39e9
    assert flops.kv_bytes_per_token(cfg) == 4 * 2 * 8 * 64 * 2 == 8_192
    # a slot's state in one layer: 64 x 64 x 128 x 4 B = 2.10 MB; over
    # the 36 layers 75.5 MB, and 0.94 MB of tails
    assert flops.state_bytes_per_layer(cfg) == 2_097_152
    assert 36 * flops.state_bytes_per_layer(cfg) == 75_497_472
    assert 36 * flops.tail_bytes_per_layer(cfg) == 940_032
    assert flops.state_update_bytes(cfg, 12) == 12 * 2 * 2_097_152
    # a step at 64 decoding slots: weights 6.38 GB, state 9.66 GB there
    # and back, 0.79 GB of keys and values at 1,500 live positions a slot
    step = flops.decode_step_bytes(cfg, 64 * 1500, 64)
    assert step == 2 * total + 64 * 36 * 2 * 2_097_152 + 64 * 1500 * 8_192
    assert 16.8e9 < step < 16.9e9
    # the grouped kernel reads a block once for the four heads of a group
    assert flops.decode_attention_kernel_bytes(cfg, 10, 3, 128) \
        == 2 * 4 * 8 * 64 * 2 * (10 * 128 + 3)
    assert flops.logits_flops(cfg, 8) == 2 * 8 * 2_048 * 100_352
    per = flops.forward_flops(cfg, 1, 100.0)
    assert per == 2.0 * 4 * (10_485_760 + 2.0 * 100.0 * 2_048) \
        + 36 * (2.0 * 25_847_232 + 6.0 * 524_288) \
        + 2.0 * 40 * 50_331_648


def test_what_the_manager_holds_is_what_flops_counts():
    from autodist_tpu.serving import kv_cache

    flops = loader.load_module("flops", NAME)
    builder = loader.load_module("builders", BUILDER)
    cfg = _cfg()
    tc = builder.transformer_config(cfg)
    mixer = tc.block.linear
    assert mixer.state_shape == (1, 128, 4096)
    assert mixer.state_floats * 4 == flops.state_bytes_per_layer(cfg)
    held = kv_cache.bytes_held((4, 64, 8, 64, 3072), tc.dtype, (36, mixer))
    assert held["kv_bytes_per_token"] == flops.kv_bytes_per_token(cfg)
    assert held["state_bytes_per_slot"] == 36 * (
        flops.state_bytes_per_layer(cfg) + flops.tail_bytes_per_layer(cfg))


def test_reference_tree_is_the_programs():
    import jax

    from autodist_tpu.models import pipeline_lm as lm

    ref = loader.load_module("reference", NAME)
    builder = loader.load_module("builders", BUILDER)
    flops = loader.load_module("flops", NAME)
    for rehearse in (False, True):
        cfg = _cfg(rehearse)
        want = lm.param_shapes(builder.transformer_config(cfg))
        got = jax.tree.map(lambda s: s[0], ref.param_shapes(cfg),
                           is_leaf=lambda x: isinstance(x, tuple)
                           and isinstance(x[1], str))
        assert got == want
        # shapes only: nothing of the cell's size is made here
        assert sum(int(np.prod(s)) for s in jax.tree.leaves(
            want, is_leaf=lambda x: isinstance(x, tuple))) \
            == flops.param_count(cfg)


def test_the_reference_imports_nothing_of_the_program():
    import os

    with open(os.path.join(loader.BENCH_DIR, "reference", NAME + ".py")) as f:
        text = f.read()
    assert "autodist_tpu" not in text
    assert 'default_matmul_precision("highest")' in text
    assert "chunk" not in text.split('"""', 2)[2]    # position by position


def test_controls_fail_and_bf16_passes():
    """At the rehearsal's size with bf16 weights: the reference in fp8
    put in the program's place is NOT correct under the cell's limits,
    nor is the reference with any of its faults; rounded to bf16, as the
    program computes, it passes."""
    ref = loader.load_module("reference", NAME)
    cfg = _cfg(True)
    cfg["serving"] = dict(cfg["serving"], weights_dtype="bfloat16",
                          max_len=64)
    for seed in (1, 2):
        params = weights.seeded_fill(ref.param_shapes(cfg), seed,
                                     cfg["initializer_range"])
        r = np.random.default_rng(seed)
        served = [(r.integers(0, 509, 20).tolist(),
                   r.integers(0, 509, 30).tolist()) for _ in range(3)]
        sound = ref.compare(ref.served_gaps(params, served, cfg,
                                            control="bfloat16"))
        assert all(row[3] for row in sound), sound
        for kw in (dict(control="fp8"), dict(fault="no_skip"),
                   dict(fault="norm_before_gate"), dict(fault="no_softplus"),
                   dict(fault="residual_one"), dict(fault="head_scale"),
                   dict(fault="rotary"),
                   dict(fault="no_embedding_multiplier")):
            control = ref.compare(ref.served_gaps(params, served, cfg, **kw))
            assert not all(row[3] for row in control), (kw, control)


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


@pytest.mark.parametrize("plant", [
    "stale_state", "stale_tail", "no_skip", "norm_before_gate",
    "no_softplus", "residual_one", "head_scale", "rotary",
    "no_embedding_multiplier"])
def test_serving_with_a_planted_fault(capsys, plant):
    """A state or a tail not overwritten at admission, ``D`` dropped, the
    norm before the gate, ``softplus`` dropped, a residual multiplier of
    1, the scale ``head_dim ** -0.5``, rotary on the attention layers and
    the embedding multiplier dropped (``tools/planted_ssd.py``) each fail
    at least one of the cell's limits in the rehearsal."""
    with loader.load_module("tools", "planted_ssd").PLANTS[plant]():
        rc, line, out = _last_line(capsys, RUN)
    assert rc == 1 and line["correct"] is False
    assert any("logit_gap" in l and "FAILED" in l for l in out)


def test_a_fault_of_the_first_tokens_alone_fails():
    """Four requests of 750 tokens whose first four are each 0.5 below
    the reference's best: the mean over the first tokens sees it."""
    ref = loader.load_module("reference", NAME)
    gaps = [np.r_[np.full(4, 0.5), np.zeros(746)] for _ in range(4)]
    ok = {row[0]: row[3] for row in ref.compare(gaps)}
    assert ok["logit_gap_first8_mean"] is False
    assert ok["logit_gap_p99"] is True
