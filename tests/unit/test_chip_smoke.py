"""``chip_smoke.py``'s phases at toy width on the simulated CPU mesh —
the same functions the chip runs at full width — and its refusal to run
without a TPU.  The kernel and multi-device phases interpret Pallas
kernels, so they are ``slow``."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import optax
import pytest

import chip_smoke
from autodist_tpu.models.pipeline_lm import make_pipeline_lm_trainable
from autodist_tpu.models.transformer import TransformerConfig

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SIZES = dict(num_slots=2, prefill_len=16, decode_steps=4, max_new_tokens=8)
PAGED = dict(kv_layout="paged", kv_block_len=8)


@pytest.fixture(scope="module")
def lm():
    cfg = TransformerConfig(vocab_size=128, hidden_size=32, num_layers=2,
                            num_heads=2, mlp_dim=64, max_len=64,
                            dtype=jnp.float32, dropout_rate=0.0,
                            attention_dropout_rate=0.0)
    params = make_pipeline_lm_trainable(
        cfg, optax.adam(1e-3), jax.random.PRNGKey(0)).params
    return cfg, params, chip_smoke.make_prompts(cfg.vocab_size, 16)


def test_training_and_serving_phases_at_toy_width(lm):
    bert = TransformerConfig(vocab_size=512, hidden_size=32, num_layers=2,
                             num_heads=2, mlp_dim=64, max_len=32,
                             dropout_rate=0.0, attention_dropout_rate=0.0)
    out = chip_smoke.training_phase(
        bert, resource_spec={"topology": {"platform": "cpu",
                                          "num_devices": 4}},
        batch_per_device=2, seq_len=32, num_masked=4, platform="cpu")
    assert out["devices"] == 4 and len(out["losses"]) == 3 + 4 + 4

    cfg, params, prompts = lm
    dense = chip_smoke.serving_phase(cfg, params, prompts, label="dense",
                                     **SIZES)
    paged = chip_smoke.serving_phase(cfg, params, prompts, label="paged",
                                     **PAGED, **SIZES)
    assert dense["tokens"] == paged["tokens"]      # float32: exact
    chip_smoke.serving_parity(cfg, params, prompts[0],
                              dense["tokens"][0][0])
    # a check that fails raises — nothing lets a phase fail quietly
    with pytest.raises(chip_smoke.SmokeFailure, match="first token"):
        wrong = (dense["tokens"][0][0] + 1) % cfg.vocab_size
        chip_smoke.serving_parity(cfg, params, prompts[0], wrong)


def test_chip_smoke_refuses_to_run_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


@pytest.mark.parametrize("tile,fused", [((16, 8), False),
                                        ((128, 128), True)])
def test_mixed_block_phase_at_toy_width(tile, fused):
    """Tiles of ``[128, 128]`` bring the delta-step kernel in (here
    under the interpreter), widths in whole lanes of 128 the
    grouped-matmul kernel at a decode step's rows; narrower ones they
    cannot take.  The dense decode kernel takes a group of query heads a
    key/value head either way: heads of 8 on ``[d, block]`` tiles, heads
    of 128 on ``[block, d]`` tiles as stored."""
    import jax.numpy as jnp

    done = chip_smoke.mixed_block_phase(
        slots=3, value_heads=8 if fused else 2, key_dim=tile[0],
        value_dim=tile[1],
        window=37, hidden=128 if fused else 32, experts=16, held=8, top_k=4,
        width=128 if fused else 8, linear_layers=3, kv_heads=2,
        group=2 if fused else 4, head_dim=128 if fused else 8, lane_len=64,
        full_layers=2, blocks=(16, 32, 48), dtype=jnp.float32)
    assert done == ["gated_delta_step"] \
        + ["gated_delta_step_fused"] * fused \
        + ["gated_delta_chunked", "grouped_decode_kernel",
           "routed_experts"] \
        + ["grouped_matmul"] * fused


@pytest.mark.parametrize("hidden,width,fused", [(32, 8, False),
                                                (128, 128, True)])
def test_latent_block_phase_at_toy_width(hidden, width, fused):
    """Float32 at a toy width: the absorbed step is the expanded form to
    the order of the sums, and the decode kernel (interpreted) the
    composed step at every block; the routed layer's bf16 experts go
    through the grouped-matmul kernel too where their widths are whole
    lanes of 128."""
    import jax.numpy as jnp

    done = chip_smoke.latent_block_phase(
        slots=3, heads=4, hidden=hidden, kv_rank=16, nope_dim=8, rope_dim=4,
        value_dim=8, window=21, max_len=32, dtype=jnp.float32, rtol=1e-4,
        lane_slots=5, lane_len=32, blocks=(8, 16, 24, 32), experts=16,
        held=4, top_k=3, width=width)
    assert done == ["latent_expanded", "latent_absorbed",
                    "latent_decode_kernel", "routed_experts"] \
        + ["grouped_matmul"] * fused


def test_hybrid_latent_phase_at_toy_width():
    """The two kernels of a mixed latent / delta-rule stack's decode
    step under the interpreter: the state kernel with a decay a head and
    a decay a row of each ``[128, 128]`` tile, the latent decode kernel
    at two counts of absorbed heads."""
    import jax.numpy as jnp

    done = chip_smoke.hybrid_latent_phase(
        states=((2, 8, 2, "head"), (3, 8, 3, "channel"), (2, 4, 2, "head")),
        latent_heads=(2, 4), kv_rank=16, rope_dim=4, lane_slots=3,
        lane_len=32, block=16, dtype=jnp.float32, rtol=1e-4, reps=1)
    assert done == ["delta_step:head", "delta_step:channel",
                    "latent_decode:2", "latent_decode:4"]


def test_retention_block_phase_at_toy_width():
    """A power-retention stack's state step at heads of 16 (2 key/value
    heads, 2 query heads each): the symmetric square, the chunked form
    and the recurrence against the attention form; the kernel's tiles are
    heads of 128, so the phase stops where it would run
    (``tests/unit/test_retention_block.py`` runs it under the
    interpreter)."""
    done = chip_smoke.retention_block_phase(
        slots=2, kv_heads=2, group=2, head_dim=16, layers=2, check_slots=2,
        window=70)
    assert done == ["retention_chunked", "retention_step"]


def test_ssd_block_phase_at_toy_width():
    """A state-space stack's state step at 8 heads of 16 over a state of
    16 (one lane tile of width, so the kernel runs under the interpreter
    too): the chunked form and the recurrence position by position, the
    kernel against the composed step through the cache manager's seam;
    the times are the chip's."""
    done = chip_smoke.ssd_block_phase(
        slots=2, heads=8, head_dim=16, state=16, groups=1, layers=3,
        check_slots=2, window=70)
    assert done == ["ssd_chunked", "ssd_step", "ssd_step_fused"]
    # half a lane tile of width: the phase stops where the kernel would run
    assert chip_smoke.ssd_block_phase(
        slots=2, heads=4, head_dim=16, state=16, layers=2, check_slots=2,
        window=20) == ["ssd_chunked", "ssd_step"]


@pytest.mark.slow
def test_kernel_phase_at_toy_width_under_the_interpreter(lm):
    done = chip_smoke.kernels_phase(
        interpret=True, seq_len=64, heads=2, head_dim=16, cache_len=64,
        block_len=8, chunk=16, slots=3, hop_elems=5000,
        matmul_shape=(300, 600, 130))
    assert len(done) == 8
    cfg, params, prompts = lm
    for marker, kw in (("flash_decode", {}), ("flash_decode", PAGED),
                       ("flash_prefill", dict(PAGED, prefill_chunk=8))):
        chip_smoke.serving_phase(cfg, params, prompts, label=marker,
                                 kernel={marker: True}, marker_of=marker,
                                 **kw, **SIZES)


@pytest.mark.slow
def test_multichip_phase_on_the_cpu_mesh(lm):
    cfg, params, prompts = lm
    dense = chip_smoke.serving_phase(cfg, params, prompts, label="dense",
                                     **SIZES)
    chip_smoke.multichip_phase(cfg, params, prompts, dense["tokens"],
                               serve_sizes=SIZES, interpret=True)
