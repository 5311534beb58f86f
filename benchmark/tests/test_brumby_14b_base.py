"""``brumby-14b-base``'s part of the yardstick: its flops module against a
hand count from the published sizes, its controls at a size a test run
holds, and a whole rehearsed run of its cell — sound, and with the gate,
the heads' grouping, the rotation or the state broken underneath."""
import json

import numpy as np
import pytest

import run as bench
from harness import loader, weights

NAME = "brumby-14b-base"
CELL = NAME + ".closed-loop-16-decode-heavy"
RUN = ["--workload", CELL, "--seed", "2147483659", "--seconds", "1",
       "--rehearse"]


def _cfg(rehearse=False):
    spec = loader.benchmark_spec()
    return loader.sized(loader.config_of(spec, {"name": NAME,
                                                "config": NAME}), rehearse)


def test_published_widths_and_the_cut():
    cfg = _cfg()
    published = {
        "hidden_size": 5120, "num_attention_heads": 40,
        "num_key_value_heads": 8, "head_dim": 128,
        "intermediate_size": 17408, "vocab_size": 151936,
        "rope_theta": 1000000, "rms_norm_eps": 1e-6,
        "max_position_embeddings": 32768, "max_window_layers": 40,
        "num_hidden_layers_published": 40, "tie_word_embeddings": False,
        "attention_bias": False, "use_sliding_window": False}
    assert {k: cfg[k] for k in published} == published
    assert sorted(cfg["reduced"]) == ["num_hidden_layers", "serving.max_len"]
    # one of five pipeline stages of whole layers, the whole vocabulary
    assert cfg["num_hidden_layers"] * 5 == cfg["num_hidden_layers_published"]
    assert cfg["num_hidden_layers"] >= 4
    assert cfg["serving"]["state_dtype"] == "float32"
    assert cfg["serving"]["num_slots"] == 16
    for key in ("deployment", "assumed", "departures"):
        assert cfg[key]
    for reading in ("degree p = 2", "log sigmoid", "sum of its weights",
                    "eps", "RMSNorm a head", "128^-0.5", "i // 5"):
        assert reading in cfg["assumed"]["recalled"], reading


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
                   if r["name"] == "Brumby-14B-Base")
    cfg = _cfg()
    assert cfg["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differ == {"num_hidden_layers"}


def test_the_builder_refuses_what_the_block_does_not_implement():
    builder = loader.load_module("builders", "retention_lm_serving")
    cfg = _cfg()
    builder.transformer_config(cfg)
    for change, says in [
            ({"use_sliding_window": True}, "use_sliding_window"),
            ({"sliding_window": 4096}, "a sliding_window"),
            ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
            ({"attention_bias": True}, "attention_bias"),
            ({"tie_word_embeddings": True}, "tie_word_embeddings"),
            ({"max_window_layers": 28}, "max_window_layers"),
            ({"hidden_act": "gelu"}, "hidden_act")]:
        with pytest.raises(ValueError, match=says):
            builder.transformer_config(dict(cfg, **change))


def test_params_and_bytes():
    flops = loader.load_module("flops", NAME)
    cfg = _cfg()
    # q and o 2 * 5120 * 5120 = 52,428,800; k and v 2 * 5120 * 1024 =
    # 10,485,760; the q/k norms 256; the gate 5120 * 8 = 40,960
    assert flops.mixer_param_count(cfg) == 62_955_776
    assert flops.ffn_param_count(cfg) == 267_386_880
    # 330.3 M a layer
    assert flops.layer_param_count(cfg) == 330_352_896
    # embedding and head: 1.556 B
    assert 2 * cfg["vocab_size"] * cfg["hidden_size"] == 1_555_824_640
    dense = 8 * 330_352_896 + 151_936 * 5120 + 5120
    assert flops.dense_param_count(cfg) == dense
    assert flops.param_count(cfg) == dense + 151_936 * 5120
    assert 8.39e9 < 2 * flops.param_count(cfg) < 8.41e9
    assert flops.kv_bytes_per_token(cfg) == 0
    # a slot's state in one layer: 8 heads x 8,256 packed rows x 128 x
    # 4 B = 33.8 MB, and the normaliser's 8 x 8,256 x 4 B = 0.26 MB
    assert flops.state_rows(cfg) == 8_256
    assert 8 * 8_256 * 128 * 4 == 33_816_576
    assert flops.state_bytes_per_layer(cfg) == 33_816_576 + 264_192
    assert flops.state_update_bytes(cfg, 12) == 12 * 2 * 34_080_768
    # a step at 16 decoding slots: weights 6.84 GB, state 8.72 GB there
    # and back, whatever is live
    step = flops.decode_step_bytes(cfg, 16 * 1000, 16)
    assert step == 2 * dense + 16 * 8 * 2 * 34_080_768
    assert step == flops.decode_step_bytes(cfg, 0, 16)
    assert 15.5e9 < step < 15.6e9
    assert flops.logits_flops(cfg, 8) == 2 * 8 * 5120 * 151_936
    # a position and layer: 660.7 MFLOP of products, 102 of retention
    per = flops.forward_flops(cfg, 1) / 8
    assert per == 2.0 * (62_955_776 + 267_386_880) \
        + 2.0 * 8_256 * 129 * 48
    assert 100e6 < per - 2.0 * 330_342_656 < 105e6


def test_the_programs_layout_holds_the_packed_rows():
    """The state as the program lays it out against what ``flops``
    counts: 8,320 rows for 8,256 distinct ones."""
    from autodist_tpu.serving import kv_cache

    flops = loader.load_module("flops", NAME)
    builder = loader.load_module("builders", "retention_lm_serving")
    cfg = _cfg()
    tc = builder.transformer_config(cfg)
    mixer = tc.block.linear
    assert mixer.state_rows_packed == flops.state_rows(cfg) == 8_256
    assert mixer.state_rows == 8_320
    held = kv_cache.bytes_held((0, 16, 8, 128, 2560), tc.dtype, (8, mixer))
    assert held["kv_bytes_per_token"] == 0
    assert held["state_bytes_per_slot"] == 8 * (8 * 8_320 * 128 + 8_320 * 8) * 4
    # 0.8% more than the packed rows
    assert 1.007 < held["state_bytes_per_slot"] \
        / (8 * flops.state_bytes_per_layer(cfg)) < 1.008


def test_reference_tree_is_the_programs():
    import jax

    from autodist_tpu.models import pipeline_lm as lm

    ref = loader.load_module("reference", NAME)
    builder = loader.load_module("builders", "retention_lm_serving")
    flops = loader.load_module("flops", NAME)
    for rehearse in (False, True):
        cfg = _cfg(rehearse)
        want = lm.param_shapes(builder.transformer_config(cfg))
        got = jax.tree.map(lambda s: s[0], ref.param_shapes(cfg),
                           is_leaf=lambda x: isinstance(x, tuple)
                           and isinstance(x[1], str))
        assert got == want
        assert sum(int(np.prod(s)) for s in jax.tree.leaves(
            want, is_leaf=lambda x: isinstance(x, tuple))) \
            == flops.param_count(cfg)


def test_the_reference_imports_nothing_of_the_program():
    import os

    with open(os.path.join(loader.BENCH_DIR, "reference", NAME + ".py")) as f:
        text = f.read()
    assert "autodist_tpu" not in text
    assert 'default_matmul_precision("highest")' in text


def test_the_recurrence_is_the_attention_form():
    """The reference's ``state_bf16`` control runs the recurrence over
    the packed rows; with the rounding taken out it is the attention form
    (float32 against float32)."""
    import jax

    ref = loader.load_module("reference", NAME)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    T, n, kv, d = 50, 4, 2, 16
    q = jax.random.normal(ks[0], (T, n, d)) / d ** 0.5
    k, v = (jax.random.normal(key, (T, kv, d)) for key in ks[1:3])
    gamma = -jax.random.uniform(ks[3], (T, kv))
    with jax.default_matmul_precision("highest"):
        want = ref._retention_attention_form(q, k, v, gamma, "")
        got = ref._retention_recurrence(q, k, v, gamma, lambda x: x)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)


def test_controls_fail_and_bf16_passes():
    """At the rehearsal's size with bf16 weights: the reference in fp8
    put in the program's place is NOT correct under the cell's limits,
    nor is the reference with a gate that decays its own write, a query
    head reading the wrong key/value head, or no rotation; rounded to
    bf16, as the program computes, it passes."""
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
        for kw in (dict(control="fp8"), dict(fault="gate_after_write"),
                   dict(fault="wrong_group"), dict(fault="no_rotary")):
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


# what each fault reads in the rehearsal (float32, logits of size ~3):
# the mean gap over the sampled tokens at least this, where a sound run
# reads under 1e-4
@pytest.mark.parametrize("plant,at_least", [
    ("gate_after_write", 0.01), ("wrong_group", 0.05), ("no_rotary", 0.01),
    ("stale_state", 0.01)])
def test_serving_with_a_planted_fault(capsys, plant, at_least):
    """A gate that decays its own write, a query head on the wrong
    key/value head, rotary left off and a state not overwritten at
    admission (``tools/planted_retention.py``) each read far above a
    sound rehearsal."""
    with loader.load_module("tools", "planted_retention").PLANTS[plant]():
        rc, line, out = _last_line(capsys, RUN)
    assert line["checks"]["logit_gap_mean"]["value"] > at_least
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
