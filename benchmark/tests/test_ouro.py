"""``ouro-2.6b``'s part of the yardstick: its flops module against a
hand count from the published sizes, its fp8 control at a size a test
run holds, and a whole rehearsed run of its cell, sound and with the
loop count (or the cache's index) broken underneath."""
import json

import numpy as np

import run as bench
from harness import loader, weights

CELL = "ouro-2.6b.closed-loop-decode-heavy"
RUN = ["--workload", CELL, "--seed", "8", "--seconds", "1", "--rehearse"]


def _cfg():
    spec = loader.benchmark_spec()
    return loader.config_of(spec, {"name": "ouro-2.6b",
                                   "config": "ouro-2.6b"})


def test_ouro_params_and_bytes():
    flops = loader.load_module("flops", "ouro-2.6b")
    cfg = _cfg()
    # a layer: q, k, v, o 4 * 2048^2 = 16,777,216; gate, up, down
    # 3 * 2048 * 5632 = 34,603,008; four norm scales 8,192
    assert flops.layer_param_count(cfg) == 51_388_416
    # 48 layers 2,466,643,968; embedding and head 2 * 49152 * 2048 =
    # 201,326,592; the final norm 2,048; the exit gate 2,048 + 1
    assert flops.param_count(cfg) == 2_667_974_657
    # keys and values of one position: 4 loop steps x 48 layers x 2 x
    # 16 heads x 128 x 2 bytes
    assert flops.cache_layers(cfg) == 192
    assert flops.kv_bytes_per_token(cfg) == 1_572_864
    # a decode step reads the layers' weights once per loop step (4 x
    # 2,466,643,968 x 2 B = 19,733,151,744) and the head with the final
    # norm once ((100,663,296 + 2,048) x 2 B = 201,330,688): 19.93 GB
    assert flops.decode_step_bytes(cfg, 0) == 19_733_151_744 + 201_330_688
    assert flops.decode_step_bytes(cfg, 2000) == \
        19_934_482_432 + 2000 * 1_572_864
    # the kernel: a block of 128 positions of one slot is 128 x 16 x 128
    # x 2 B = 524,288 B for K, as much for V, in each of 192 layers; a
    # written row is 4,096 B for K, as much for V
    assert flops.decode_attention_kernel_bytes(cfg, 10, 8, 128) == \
        192 * 2 * (10 * 524_288 + 8 * 4_096)
    # forward over 256 tokens attending 128.5 on average, per token and
    # layer application 8*2048*2048 + 6*2048*5632 + 4*128.5*2048
    assert flops.forward_flops(cfg, 256, 128.5) == \
        256 * 192 * (33_554_432 + 69_206_016 + 1_052_672)
    assert flops.logits_flops(cfg, 8) == 2 * 8 * 2048 * 49152


def test_ouro_control_fails_and_bf16_passes():
    """The reference in fp8 put in the program's place is NOT correct
    under the cell's limit; rounded to bf16, as the program computes, it
    passes.  Width 128 (two heads of 64) and ALL of the depth, 48 layers
    x 4 loop steps: the limits are set for what 192 layer applications
    do to a rounding error, and at 6 layers the fp8 control reads 0.7,
    under them.  ``initializer_range`` scaled up by the root of the ratio
    of widths."""
    ref = loader.load_module("reference", "ouro-2.6b")
    H = 128
    cfg = {"hidden_size": H, "num_hidden_layers": 48,
           "num_attention_heads": 2, "num_key_value_heads": 2,
           "head_dim": 64, "intermediate_size": 352, "vocab_size": 8192,
           "rms_norm_eps": 1e-6, "rope_theta": 1000000,
           "total_ut_steps": 4, "early_exit_threshold": 1,
           "initializer_range": 0.02 * (2048 / H) ** 0.5,
           "serving": {"weights_dtype": "bfloat16", "dtype": "bfloat16",
                       "max_len": 64}}
    for seed in (1, 2):
        params = weights.seeded_fill(ref.param_shapes(cfg), seed,
                                     cfg["initializer_range"])
        r = np.random.default_rng(seed)
        served = [(r.integers(0, 8192, 24).tolist(),
                   r.integers(0, 8192, 40).tolist()) for _ in range(3)]
        sound = ref.compare(ref.served_gaps(params, served, cfg,
                                            control="bfloat16"))
        control = ref.compare(ref.served_gaps(params, served, cfg,
                                              control="fp8"))
        assert all(row[3] for row in sound), sound
        for s, c in zip(sound[:2], control[:2]):    # widest, mean
            assert not c[3], control
            assert c[1] > 3 * max(s[1], 0.01)
        # the exit pdf leaves at the last loop step under either
        assert control[2][3], control


def _last_line(capsys, argv):
    rc = bench.main(argv)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


def test_ouro_serving_sound(capsys):
    rc, line, out = _last_line(capsys, RUN)
    assert rc == 0 and line["correct"] is True
    assert line["counts"]["requests_completed"] > 0
    assert any("exit_steps_early: 0" in l and "ok" in l for l in out)


def test_ouro_serving_with_a_loop_step_left_out(capsys, monkeypatch):
    """The program runs three of the four loop steps: every token comes
    from an earlier loop step's state."""
    from autodist_tpu.models.transformer import BlockSpec

    real = BlockSpec.__init__

    def one_fewer(self, *a, **kw):
        real(self, *a, **kw)
        if self.loop_steps > 1:
            object.__setattr__(self, "loop_steps", self.loop_steps - 1)

    monkeypatch.setattr(BlockSpec, "__init__", one_fewer)
    rc, line, out = _last_line(capsys, RUN)
    assert rc == 1 and line["correct"] is False
    assert any("logit_gap_mean" in l and "FAILED" in l for l in out)


def test_ouro_serving_with_the_cache_indexed_by_the_layer_alone(
        capsys, monkeypatch):
    """Every loop step writes and reads layer ``l``'s rows: a cache of
    48 layers' worth where 192 are needed."""
    from autodist_tpu.serving.engine import ServingEngine

    real = ServingEngine._run_layers

    def by_layer_alone(self, shared, stages, x, kc, vc, layer_fn):
        return real(self, shared, stages, x, kc, vc,
                    lambda chunk, x, kc, vc, l, _: layer_fn(
                        chunk, x, kc, vc, l, l))

    monkeypatch.setattr(ServingEngine, "_run_layers", by_layer_alone)
    rc, line, out = _last_line(capsys, RUN)
    assert rc == 1 and line["correct"] is False
    assert any("logit_gap_mean" in l and "FAILED" in l for l in out)
