"""Each ``flops/<config>.py`` against counts made by hand from the
published sizes."""
from harness import loader


def _cfg(name):
    spec = loader.benchmark_spec()
    return loader.config_of(spec, {"name": name, "config": name})


def test_bert_base_step_flops():
    flops = loader.load_module("flops", "bert-base-mlm")
    cfg = _cfg("bert-base-mlm")
    # per token and layer: qkv+out 8*768^2 = 4,718,592; MLP 4*768*3072 =
    # 9,437,184; scores+values 4*512*768 = 1,572,864 -> 15,728,640
    # encoder forward: 512 * 12 * 15,728,640 = 96,636,764,160
    # head forward: 76 * (2*768^2 + 2*768*30522) = 3,652,669,440
    # forward + backward = 3 x forward
    assert flops.train_flops_per_sequence(cfg, 512, 76) == \
        3 * (96_636_764_160 + 3_652_669_440)
    traffic = {"sequences_per_chip": 64, "seq_len": 512, "num_masked": 76}
    assert flops.train_flops_per_step(cfg, traffic, 4) == \
        256 * 3 * (96_636_764_160 + 3_652_669_440)


def test_gpt2_large_params_and_bytes():
    flops = loader.load_module("flops", "gpt2-large-postln")
    cfg = _cfg("gpt2-large-postln")
    # GPT-2 large as published: 774,030,080 parameters
    # = 36 * (12*1280^2 + 13*1280) + 50257*1280 + 1024*1280 + 2*1280
    assert flops.param_count(cfg) == 774_030_080
    # keys and values of one position: 2 * 36 * 1280 values of 2 bytes
    assert flops.kv_bytes_per_token(cfg) == 184_320
    # a decode step: every weight but the position table, in bf16, and
    # 10,000 live positions
    assert flops.decode_step_bytes(cfg, 10_000) == \
        (774_030_080 - 1024 * 1280) * 2 + 10_000 * 184_320
    # forward over 512 tokens attending 256.5 on average, per token and
    # layer 8*1280^2 + 4*1280*5120 + 4*256.5*1280
    assert flops.forward_flops(cfg, 512, 256.5) == \
        512 * 36 * (13_107_200 + 26_214_400 + 1_313_280)
    assert flops.logits_flops(cfg, 48) == 2 * 48 * 1280 * 50257
