"""The decode kernel and the prefill program of the main serving path,
compiled at real widths for a TPU that is described and not attached
(the TPU's compiler is installed; nothing runs, so this says nothing
about results or times).

What the interpreter cannot show: that Mosaic takes the kernel's tiles
as they are sliced, that its VMEM fits, and that the compiled call holds
the cache it is given — no copy of it, no temporary — in the layout the
chip keeps it in.  One file, one fixture: only the worker that is given
this file loads the TPU's library, inside a test.
"""
import hashlib
import importlib
import re

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# (cache layers, slots, heads, max_len, head_dim): the two serving
# configurations of the benchmark
@pytest.mark.parametrize("L,B,H,T,d", [
    (36, 8, 20, 1024, 64),      # gpt2-large-postln: [d, block] tiles
    (192, 8, 16, 512, 128),     # ouro-2.6b: [block, d] tiles, as stored
], ids=["heads64", "heads128"])
def test_dense_decode_kernel_compiles_in_place(one_chip, L, B, H, T, d):
    from autodist_tpu.kernel.pallas.flash_decode import (
        flash_decode_attention_dense, fused_decode_block)

    assert fused_decode_block(T, d) == 128
    bf16 = jnp.bfloat16
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)

    def step(q, kc, vc, layer, lengths, k, v):
        return flash_decode_attention_dense(
            q, kc, vc, layer, lengths, new_kv=(k, v), dtype=bf16,
            interpret=False)

    row, cache = sds((B, 1, H, d), bf16), sds((L, B, H, T, d), bf16)
    # the chip's own default precision, not the CPU goldens' "highest"
    # (tests/conftest.py), which Mosaic refuses for bf16 operands
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
            row, cache, cache, sds((), jnp.int32), sds((B,), jnp.int32),
            row, row).compile()
    mem = compiled.memory_analysis()
    cache_bytes = 2 * L * B * H * T * d * 2
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < 1 << 20
    text = compiled.as_text()
    assert "adtk_flash_decode" in text
    # nothing of the cache's size but the kernel's own operands
    assert not re.findall(rf"= bf16\[{L},{B},{H},\d+,\d+\][^ ]* "
                          r"(?:copy|transpose|fusion)\(", text)


# the two serving configurations of the benchmark at their own widths,
# slots, lane and prompt bucket, cut to two layers: what the compiler
# does with the one cache array does not depend on the depth
@pytest.mark.parametrize("hidden,heads,mlp,vocab,max_len,bucket,block", [
    (1280, 20, 5120, 50257, 1024, 512, {}),     # gpt2-large-postln
    (2048, 16, 5632, 49152, 512, 256, dict(     # ouro-2.6b: 4 passes
        norm="rmsnorm", norm_placement="sandwich", positions="rope",
        rope_theta=1e6, ffn="swiglu", bias=False, tied_head=False,
        loop_steps=4)),
], ids=["heads64", "heads128-looped"])
def test_one_row_prefill_compiles_in_place(one_chip, hidden, heads, mlp,
                                           vocab, max_len, bucket, block):
    """The prefill program holds the cache it is given, in the layout the
    decode kernel reads it in: no op of the whole cache's shape but the
    in-place writes, and temporaries the size of one row's activations
    (PR 26 met a 6.4 GB copy here, on the chip, after the fact)."""
    from autodist_tpu.models import pipeline_lm as lm
    from autodist_tpu.models.transformer import BlockSpec, TransformerConfig
    from autodist_tpu.serving import ServingEngine

    bf16, slots = jnp.bfloat16, 8
    cfg = TransformerConfig(
        vocab_size=vocab, hidden_size=hidden, num_layers=2,
        num_heads=heads, mlp_dim=mlp, max_len=max_len, dtype=bf16,
        dropout_rate=0.0, attention_dropout_rate=0.0,
        block=BlockSpec(**block))
    params = jax.tree.map(lambda shape: jnp.zeros(shape, bf16),
                          lm.param_shapes(cfg),
                          is_leaf=lambda x: isinstance(x, tuple))
    engine = ServingEngine(cfg, params, num_slots=slots, max_len=max_len,
                           prefill_len=bucket, decode_steps=8)
    sds = lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype,
                                         sharding=one_chip)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    c = engine.cache
    with jax.default_matmul_precision("default"):
        compiled = engine._prefill_jit.lower(
            jax.tree.map(sds, engine.params), sds(c.k), sds(c.v),
            i32(slots), i32(slots), i32(), i32(1, 1), i32(1),
            i32(1, bucket), i32(1)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * c.k.size * 2
    # one row: [1, bucket, mlp] activations and a layer's weights at most
    assert mem.temp_size_in_bytes < 256 << 20
    text = compiled.as_text()
    L, d = engine.cache_layers, cfg.head_dim
    whole = rf"bf16\[{L},{slots},{heads},\d+,\d+\]"
    assert not re.findall(rf"= {whole}[^ ]* (?:copy|transpose)\(", text)
    # the cache as the decode kernel reads it: positions minor-most for
    # heads under 128 (the chip's own choice), head_dim minor-most above
    minor = "{3,4,2,1,0" if d < 128 else "{4,3,2,1,0"
    layouts = set(re.findall(rf"{whole}(\{{[\d,]+)", text))
    assert layouts == {minor}, layouts


# one period of the benchmark's mixed stack (three gated-DeltaNet layers
# and a gated attention layer on 2 of 16 heads of 256, 64 of 512 experts
# held) at its published widths, 8 slots of 512 positions
# The composed decode program of this stack as the TPU's compiler leaves
# it (``_program_text`` of tests/unit/test_looped_block.py, hashed), read
# on the parent commit of PR 33 (c77cb69) with this test: the recurrent
# state's decode step moved behind ``kv_cache.DenseLayout.advance_state``
# and the program that declines the kernel had to come out as it went in.
COMPOSED_DECODE_HLO = "ccf433935aa6acb5"


def _experts_run_in(text, lowered, fused, layers, ragged):
    """A compiled program's routed layers: this repo's grouped-matmul
    kernel, one custom call a layer out of one lowering and neither a
    ``ragged-dot`` nor the ``conditional`` on the pairs' bound beside it
    (``fused``: a decode step on a TPU), or the compiler's grouped
    matmul, at least ``ragged`` of them."""
    found = len(re.findall(r"ROOT %ragged-dot-none|= \S+ custom-call\("
                           r"[^\n]*ragged-dot-none", text))
    calls = len(re.findall(r"custom-call\([^\n]*adtk_grouped_matmul", text))
    if not fused:
        assert found >= ragged and not calls
        assert "adtk_grouped_matmul" not in text
        return
    assert calls == layers and not found
    assert "ragged-dot" not in text and " conditional(" not in text
    stablehlo = lowered.as_text()
    assert stablehlo.count("func.func private @grouped_matmul_layer") == 1
    assert len(re.findall(r"call @grouped_matmul_layer\(",
                          stablehlo)) == layers


@pytest.mark.parametrize("program", ["decode", "decode-kernel", "prefill"])
def test_mixed_stack_compiles_in_place(one_chip, program, monkeypatch):
    """Both programs hold what they are given — the keys and values and
    the recurrent state alias their outputs, no op copies the state or a
    layer's experts (a stack of experts sliced by layer reached the
    grouped matmul as a copy of all of them: 5.4 GB at 16 layers), and
    the experts run in the compiler's grouped-matmul kernel, twice a
    layer.  ``decode-kernel``: the decode program a TPU process elects
    (here forced through the kernel slot, the backend being the CPU's) —
    Mosaic takes the delta-step kernel's tiles at the cell's 32 slots of
    32 heads of ``[128, 128]``, and nothing of a layer's state but the
    kernel's aliased operand is left: no slice in, no
    ``dynamic_update_slice`` out; and Mosaic takes the grouped-matmul
    kernel at the cell's 320 pairs through ``[64, 2048, 1024]`` experts,
    one call a layer over the experts as they are held, with no
    ``ragged-dot`` and no ``conditional`` on the pairs' bound left; and
    Mosaic takes the dense decode kernel with eight query heads in the
    rows of each of the 2 key/value heads of 256 at the cell's lane of
    2,560 positions — one call a full layer out of one lowering, its
    ``[256, 256]`` tiles read from the cache as stored, the step's rows
    written inside it: no op of a layer's lanes and no
    ``dynamic-update-slice`` of a row is left."""
    from autodist_tpu.models import pipeline_lm as lm
    from autodist_tpu.models.transformer import (BlockSpec, LinearMixerSpec,
                                                 RoutedFFNSpec,
                                                 TransformerConfig)
    from autodist_tpu.serving import ServingEngine

    from tests.unit.test_looped_block import _program_text

    fused = program == "decode-kernel"
    bf16, slots, bucket = jnp.bfloat16, 32 if fused else 8, 256
    T = 2560 if fused else 512
    if fused:
        for kernel in ("delta_step", "flash_decode", "grouped_matmul"):
            monkeypatch.setattr(
                importlib.import_module(
                    f"autodist_tpu.kernel.pallas.{kernel}"),
                "default_interpret", lambda: False)
    cfg = TransformerConfig(
        vocab_size=18992, hidden_size=2048, num_layers=4, num_heads=16,
        mlp_dim=512, max_len=T, dtype=bf16, dropout_rate=0.0,
        attention_dropout_rate=0.0,
        block=BlockSpec(
            norm="rmsnorm", norm_placement="pre", norm_zero_centred=True,
            positions="rope", rope_theta=1e7, rope_fraction=0.25,
            ffn="swiglu", bias=False, tied_head=False, kv_heads=2,
            head_dim=256, qk_norm=True, attn_gate=True,
            layer_period=("linear", "linear", "linear", "full"),
            linear=LinearMixerSpec(16, 32, 128, 128),
            moe=RoutedFFNSpec(512, 10, 512, experts_held=64, shared_width=512)))
    params = jax.tree.map(lambda shape: jnp.zeros(shape, bf16),
                          lm.param_shapes(cfg),
                          is_leaf=lambda x: isinstance(x, tuple))
    engine = ServingEngine(cfg, params, num_slots=slots, max_len=T,
                           prefill_len=bucket, decode_steps=8,
                           kernel={"delta_step": fused,
                                   **({"flash_decode": True,
                                       "grouped_matmul": True} if fused
                                      else {})})
    assert engine.kv.state_kernel(engine.cache.state.ssm) == fused
    assert engine.kv.fused_block == (256 if fused else None)
    sds = lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype,
                                         sharding=one_chip)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    c = engine.cache
    head = (jax.tree.map(sds, engine.params), sds(c.k), sds(c.v),
            i32(slots), i32(slots))
    state = tuple(sds(a) for a in engine._state_args())
    with jax.default_matmul_precision("default"):
        if program.startswith("decode"):
            lowered = engine._decode_jit.lower(
                *head, i32(slots, 1), i32(slots), jax.ShapeDtypeStruct(
                    (slots,), jnp.bool_, sharding=one_chip),
                *state)
        else:
            lowered = engine._prefill_jit.lower(
                *head, i32(), i32(1, 1), i32(1), i32(1, bucket), i32(1),
                *state)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    held = 2 * c.k.size * 2 + sum(a.size * a.dtype.itemsize
                                  for a in engine._state_args())
    assert abs(mem.alias_size_in_bytes - held) < 4096
    assert mem.temp_size_in_bytes < 128 << 20
    text = compiled.as_text()
    _experts_run_in(text, lowered, fused, layers=4, ragged=8)
    assert not re.findall(
        rf"= f32\[3,{slots},32,128,128\][^ ]* (?:copy|transpose)\(", text)
    assert not re.findall(r"= bf16\[64,2048,1024\][^ ]* (?:copy|fusion)\(",
                          text)
    layer_state = rf"f32\[(?:1,)?{slots},32,128,128\]"
    # a full layer's lanes, and a step's rows going into them
    lanes = re.findall(rf"bf16\[{slots},2,{T},256\]", text)
    row_writes = re.findall(r",256\][^ ]* dynamic-update-slice\(", text)
    if fused:
        assert text.count("adtk_delta_step") >= 3
        assert not re.findall(layer_state, text)
        assert len(re.findall(r"custom-call\([^\n]*adtk_flash_decode",
                              text)) == 1
        assert not lanes and not row_writes
        # the cache as stored, head_dim minor-most: the kernel's view
        layouts = set(re.findall(
            rf"bf16\[1,{slots},2,{T},256\](\{{[\d,]+)", text))
        assert layouts == {"{4,3,2,1,0"}, layouts
        assert not re.findall(rf"= bf16\[1,{slots},2,{T},256\][^ ]* "
                              r"(?:copy|transpose|fusion)\(", text)
        # one lowering, the layer an operand of its calls
        stablehlo = lowered.as_text()
        assert stablehlo.count("func.func private "
                               "@flash_decode_layer") == 1
        assert len(re.findall(r"call @flash_decode_layer\(",
                              stablehlo)) == 1
    elif program == "decode":
        assert re.findall(layer_state, text)     # the slice, the write
        assert "adtk_delta_step" not in text
        assert lanes and row_writes and "adtk_flash_decode" not in text
        assert hashlib.sha256(_program_text(text).encode()) \
            .hexdigest()[:16] == COMPOSED_DECODE_HLO


# the benchmark's latent-attention stack at its published widths (16
# heads, a row of 512 + 64, a dense layer of 10,944 then routed layers of
# 8 of 64 experts), three layers deep, at the cell's 64 slots of 3,072
# positions and its [1, 1024] prompt row
# The composed decode program of this stack as the TPU's compiler leaves
# it (hashed as ``COMPOSED_DECODE_HLO`` above), read on the parent commit
# of PR 36 (e1b02ce) with this test: the latent decode kernel came in
# behind ``kv_cache.LatentLayout.decode_attend`` and the program that
# declines it had to come out as it went in.
COMPOSED_LATENT_DECODE_HLO = "6bacd6846372c948"


@pytest.mark.parametrize("program", ["decode", "decode-kernel", "prefill"])
def test_latent_stack_compiles_in_place(one_chip, program, monkeypatch):
    """Both programs hold the lanes of latent rows they are given: the
    rows alias their output, neither program keeps a second copy of them
    (no temporary of a lane's size a layer, let alone the cache's), both
    take the cache in the one layout the chip chooses for a row that is
    no multiple of its 128 lanes — positions minor-most, so that no
    program converts it for the other — and the experts run in the
    compiler's grouped-matmul kernel.  ``decode-kernel``: the decode
    program a TPU process elects (here forced through the kernel slot,
    the backend being the CPU's) — Mosaic takes the latent kernel's
    ``[576, 256]`` tiles, its view of the cache is the array (a bitcast,
    no copy, transpose or convert of the cache's or a lane's shape), one
    lowering serves every layer, and the step's rows go in inside it: no
    ``dynamic-update-slice`` of a 576-wide row is left; and Mosaic takes
    the grouped-matmul kernel at the cell's 384 pairs through ``[8, 2048,
    2816]`` experts, one call a routed layer over the experts as they
    are held."""
    from autodist_tpu.models import pipeline_lm as lm
    from autodist_tpu.models.transformer import (BlockSpec,
                                                 LatentAttentionSpec,
                                                 RopeScaling, RoutedFFNSpec,
                                                 TransformerConfig)
    from autodist_tpu.serving import ServingEngine

    from tests.unit.test_looped_block import _program_text

    fused = program == "decode-kernel"
    if fused:
        for kernel in ("flash_decode", "grouped_matmul"):
            monkeypatch.setattr(
                importlib.import_module(
                    f"autodist_tpu.kernel.pallas.{kernel}"),
                "default_interpret", lambda: False)
    bf16, slots, bucket, T, L = jnp.bfloat16, 64, 1024, 3072, 3
    yarn = RopeScaling(40.0, 4096, mscale=0.707, mscale_all_dim=0.707)
    cfg = TransformerConfig(
        vocab_size=12800, hidden_size=2048, num_layers=L, num_heads=16,
        mlp_dim=10944, max_len=T, dtype=bf16, dropout_rate=0.0,
        attention_dropout_rate=0.0,
        block=BlockSpec(
            norm="rmsnorm", norm_placement="pre", positions="rope",
            rope_scaling=yarn, ffn="swiglu", bias=False, tied_head=False,
            latent=LatentAttentionSpec(512, 128, 64, 128),
            dense_layers=1,
            moe=RoutedFFNSpec(64, 6, 1408, experts_held=8,
                              shared_width=2816, renormalise=False,
                              shared_gate=False)))
    params = jax.tree.map(lambda shape: jnp.zeros(shape, bf16),
                          lm.param_shapes(cfg),
                          is_leaf=lambda x: isinstance(x, tuple))
    engine = ServingEngine(cfg, params, num_slots=slots, max_len=T,
                           prefill_len=bucket, decode_steps=8,
                           kernel={"flash_decode": True,
                                   "grouped_matmul": True} if fused
                           else None)
    assert engine.kv.fused_block == (256 if fused else None)
    sds = lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype,
                                         sharding=one_chip)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    c = engine.cache
    assert c.k.shape == (L, slots, 1, T, 576) and c.v.size == 0
    head = (jax.tree.map(sds, engine.params), sds(c.k), sds(c.v),
            i32(slots), i32(slots))
    with jax.default_matmul_precision("default"):
        if program.startswith("decode"):
            lowered = engine._decode_jit.lower(
                *head, i32(slots, 1), i32(slots), jax.ShapeDtypeStruct(
                    (slots,), jnp.bool_, sharding=one_chip))
        else:
            lowered = engine._prefill_jit.lower(
                *head, i32(), i32(1, 1), i32(1), i32(1, bucket), i32(1))
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    rows = c.k.size * 2
    assert abs(mem.alias_size_in_bytes - rows) < 4096
    # a layer's lanes are 226 MB, in float32 453
    assert mem.temp_size_in_bytes < 160 << 20
    text = compiled.as_text()
    # ({3,4,1,2,0 is the same bytes: the axis of the one key head is 1)
    layouts = set(re.findall(
        rf"bf16\[{L},{slots},1,{T},576\](\{{[\d,]+)", text))
    assert layouts and all(l.startswith("{3,4,") for l in layouts), layouts
    # ... as the cache, as a layer's lanes, or as the kernel views them
    assert not re.findall(
        rf"= (?:bf16|f32)\[(?:{L},)?{slots},1,(?:{T},576|576,{T})\][^ ]* "
        r"(?:copy|transpose|convert)\(", text)
    _experts_run_in(text, lowered, fused, layers=L - 1, ragged=4)
    # a layer's experts reach the grouped matmul as they are held
    assert not re.findall(r"= bf16\[8,2048,2816\][^ ]* (?:copy|fusion)\(",
                          text)
    row_writes = re.findall(r"576\][^ ]* dynamic-update-slice\(", text)
    if fused:
        # the kernel's view: the same bytes, positions minor-most
        views = set(re.findall(
            rf"bf16\[{L},{slots},1,576,{T}\](\{{[\d,]+)", text))
        assert views == {"{4,3,2,1,0"}, views
        assert len(re.findall(r"custom-call\([^\n]*adtk_flash_decode",
                              text)) == L
        assert not row_writes
        # one lowering, the layer an operand of its L calls
        stablehlo = lowered.as_text()
        assert stablehlo.count("func.func private "
                               "@flash_decode_latent_layer") == 1
        assert len(re.findall(r"call @flash_decode_latent_layer\(",
                              stablehlo)) == L
    elif program == "decode":
        assert row_writes and "adtk_flash_decode" not in text
        assert hashlib.sha256(_program_text(text).encode()) \
            .hexdigest()[:16] == COMPOSED_LATENT_DECODE_HLO


# one period of the benchmark's mixed latent / delta-rule stack at its
# published widths (five KDA layers, 32 heads of a [128, 128] state whose
# decay is a vector over the key channels, closed by a latent-attention
# layer of 32 heads on a row of 512 + 64; two dense layers of 6,144 then
# routed ones of 64 of 512 experts of 768, sigmoid scores, 4 of 8 groups)
# at the cell's 96 slots of 3,072 positions and its [1, 1024] prompt row
@pytest.mark.parametrize("program", ["decode", "decode-kernel", "prefill"])
def test_hybrid_latent_stack_compiles_in_place(one_chip, program,
                                               monkeypatch):
    """Both programs hold the two kinds of state they are given — the
    lanes of latent rows and the stacked float32 recurrent state alias
    their outputs — and no op copies, slices or converts the stacked
    state, the row cache or a layer's ``[64, 2560, 1536]`` experts.
    ``decode-kernel``: the decode program a TPU process elects (here
    forced through the kernel slot, the backend being the CPU's) — Mosaic
    takes the delta-step kernel with a decay a row of each ``[128, 128]``
    tile at the cell's 96 slots of 32 heads, the latent kernel at 32
    absorbed heads on its ``[576, 256]`` tiles, and the grouped-matmul
    kernel at the cell's 768 pairs, one call a layer each."""
    from autodist_tpu.models import pipeline_lm as lm
    from autodist_tpu.models.transformer import (BlockSpec,
                                                 LatentAttentionSpec,
                                                 LinearMixerSpec,
                                                 RoutedFFNSpec,
                                                 TransformerConfig)
    from autodist_tpu.serving import ServingEngine

    fused = program == "decode-kernel"
    if fused:
        for kernel in ("delta_step", "flash_decode", "grouped_matmul"):
            monkeypatch.setattr(
                importlib.import_module(
                    f"autodist_tpu.kernel.pallas.{kernel}"),
                "default_interpret", lambda: False)
    bf16, slots, bucket, T, L = jnp.bfloat16, 96, 1024, 3072, 6
    cfg = TransformerConfig(
        vocab_size=19648, hidden_size=2560, num_layers=L, num_heads=32,
        mlp_dim=6144, max_len=T, dtype=bf16, dropout_rate=0.0,
        attention_dropout_rate=0.0,
        block=BlockSpec(
            norm="rmsnorm", norm_placement="pre", positions="rope",
            rope_theta=6e6, rope_interleave=True, ffn="swiglu", bias=False,
            tied_head=False, attn_gate=True,
            layer_period=("linear",) * 5 + ("latent",),
            linear=LinearMixerSpec(32, 32, 128, 128, gate="channel",
                                   gate_floor=-5.0),
            latent=LatentAttentionSpec(512, 128, 64, 128), dense_layers=2,
            moe=RoutedFFNSpec(512, 8, 768, experts_held=64,
                              shared_width=768, shared_gate=False,
                              scores="sigmoid", groups=8, groups_kept=4,
                              scale=2.5, correction=True)))
    params = jax.tree.map(lambda shape: jnp.zeros(shape, bf16),
                          lm.param_shapes(cfg),
                          is_leaf=lambda x: isinstance(x, tuple))
    engine = ServingEngine(cfg, params, num_slots=slots, max_len=T,
                           prefill_len=bucket, decode_steps=8,
                           kernel={"delta_step": True, "flash_decode": True,
                                   "grouped_matmul": True} if fused
                           else {"delta_step": False})
    assert engine.kv.state_kernel(engine.cache.state.ssm) == fused
    assert engine.kv.fused_block == (256 if fused else None)
    sds = lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype,
                                         sharding=one_chip)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    c = engine.cache
    assert c.k.shape == (1, slots, 1, T, 576) and c.v.size == 0
    assert c.state.ssm.shape == (5, slots, 32, 128, 128)
    assert c.state.ssm.dtype == jnp.float32
    head = (jax.tree.map(sds, engine.params), sds(c.k), sds(c.v),
            i32(slots), i32(slots))
    state = tuple(sds(a) for a in engine._state_args())
    with jax.default_matmul_precision("default"):
        if program.startswith("decode"):
            lowered = engine._decode_jit.lower(
                *head, i32(slots, 1), i32(slots), jax.ShapeDtypeStruct(
                    (slots,), jnp.bool_, sharding=one_chip),
                *state)
        else:
            lowered = engine._prefill_jit.lower(
                *head, i32(), i32(1, 1), i32(1), i32(1, bucket), i32(1),
                *state)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    held = c.k.size * 2 + sum(a.size * a.dtype.itemsize
                              for a in engine._state_args())
    assert abs(mem.alias_size_in_bytes - held) < 4096
    print(program, "temp bytes", mem.temp_size_in_bytes)
    # the composed step works on a layer's slice of the state (201 MB)
    # and a layer's lanes in float32 (680 MB); the kernels on neither
    assert mem.temp_size_in_bytes < (256 << 20 if fused else 1 << 30)
    text = compiled.as_text()
    _experts_run_in(text, lowered, fused, layers=L - 2, ragged=4)
    # the stacked state, the row cache, a layer's experts: as they are held
    assert not re.findall(
        rf"= f32\[5,{slots},32,128,128\][^ ]* "
        r"(?:copy|slice|transpose|convert)\(", text)
    assert not re.findall(
        rf"= (?:bf16|f32)\[(?:1,)?{slots},1,(?:{T},576|576,{T})\][^ ]* "
        r"(?:copy|slice|transpose|convert)\(", text)
    assert not re.findall(
        r"= (?:bf16|f32)\[64,2560,1536\][^ ]* "
        r"(?:copy|slice|fusion|convert)\(", text)
    layer_state = rf"f32\[(?:1,)?{slots},32,128,128\]"
    if fused:
        assert len(re.findall(r"custom-call\([^\n]*adtk_delta_step",
                              text)) == 5
        assert not re.findall(layer_state, text)
        assert len(re.findall(r"custom-call\([^\n]*adtk_flash_decode",
                              text)) == 1
        assert not re.findall(r"576\][^ ]* dynamic-update-slice\(", text)
    elif program == "decode":
        assert re.findall(layer_state, text)     # the slice, the write
        assert "adtk_delta_step" not in text
        assert "adtk_flash_decode" not in text


# two layers of the benchmark's power-retention stack at its published
# widths (40 query heads on 8 key/value heads of 128, an FFN of 17,408; an
# eighth of the vocabulary: the head's size does not bear on what the
# compiler does with the state) at the cell's 16 slots of 2,560 positions
# and its [1, 1024] prompt row: no layer caches keys and values
@pytest.mark.parametrize("program", ["decode-kernel", "prefill"])
def test_retention_stack_compiles_in_place(one_chip, program, monkeypatch):
    """Both programs hold the state they are given — the stacked float32
    state and its normaliser alias their outputs, the empty key/value
    arrays take no byte — and no op copies, slices, transposes or
    converts the stacked state: what is left of its whole shape is the
    in-place write (the kernel's aliased operand in decode, the
    ``dynamic-update-slice`` of the admitted slot's rows in prefill).
    ``decode-kernel``: the decode program a TPU process elects (here
    forced through the kernel slot, the backend being the CPU's) — Mosaic
    takes the retention-step kernel at the cell's 16 slots x 8 heads of
    ``[65, 128, 128]``, a slab of 13 offsets a grid step, one call a
    layer out of one lowering.  (The composed decode step does not fit
    the chip beside the cell's 12.8 GB at all: its temporaries are 4 GB.)"""
    from autodist_tpu.models import pipeline_lm as lm
    from autodist_tpu.models.transformer import (BlockSpec, LinearMixerSpec,
                                                 TransformerConfig)
    from autodist_tpu.serving import ServingEngine

    fused = program == "decode-kernel"
    if fused:
        monkeypatch.setattr(
            importlib.import_module(
                "autodist_tpu.kernel.pallas.retention_step"),
            "default_interpret", lambda: False)
    bf16, slots, bucket, T, L = jnp.bfloat16, 16, 1024, 2560, 2
    cfg = TransformerConfig(
        vocab_size=18992, hidden_size=5120, num_layers=L, num_heads=40,
        mlp_dim=17408, max_len=32768, dtype=bf16, dropout_rate=0.0,
        attention_dropout_rate=0.0,
        block=BlockSpec(
            norm="rmsnorm", norm_placement="pre", positions="rope",
            rope_theta=1e6, ffn="swiglu", bias=False, tied_head=False,
            kv_heads=8, head_dim=128, qk_norm=True,
            layer_period=("linear",),
            linear=LinearMixerSpec.retention(8, 128)))
    params = jax.tree.map(lambda shape: jnp.zeros(shape, bf16),
                          lm.param_shapes(cfg),
                          is_leaf=lambda x: isinstance(x, tuple))
    engine = ServingEngine(cfg, params, num_slots=slots, max_len=T,
                           prefill_len=bucket, decode_steps=8,
                           kernel={"retention_step": fused})
    c = engine.cache
    assert engine.kv.state_kernel(c.state.ssm, 5) == fused
    assert engine.kv.fused_block is None and c.k.size == c.v.size == 0
    assert c.state.conv is None
    assert c.state.ssm.shape == (L, slots, 8, 65, 128, 128)
    assert c.state.norm.shape == (L, slots, 65, 8, 128)
    assert c.state.ssm.dtype == c.state.norm.dtype == jnp.float32
    sds = lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype,
                                         sharding=one_chip)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    head = (jax.tree.map(sds, engine.params), sds(c.k), sds(c.v),
            i32(slots), i32(slots))
    state = tuple(sds(a) for a in engine._state_args())
    with jax.default_matmul_precision("default"):
        if fused:
            lowered = engine._decode_jit.lower(
                *head, i32(slots, 1), i32(slots), jax.ShapeDtypeStruct(
                    (slots,), jnp.bool_, sharding=one_chip),
                *state)
        else:
            lowered = engine._prefill_jit.lower(
                *head, i32(), i32(1, 1), i32(1), i32(1, bucket), i32(1),
                *state)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    held = sum(a.size * a.dtype.itemsize for a in engine._state_args())
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves(engine.params))
    # 2 layers x 16 slots x (8 x 65 x 128 x 128 + 65 x 8 x 128) x 4 B
    assert held == 2 * 16 * (8_519_680 + 66_560) * 4
    assert abs(mem.alias_size_in_bytes - held) < 4096
    assert abs(mem.argument_size_in_bytes - held - weights) < 1 << 20
    # a prompt row's FFN activations, its [40, 1024, 1024] scores and one
    # block's phi(k); never a layer's slice of the state (570 MB)
    assert mem.temp_size_in_bytes < (16 << 20 if fused else 400 << 20)
    text = compiled.as_text()
    # a prompt's pass expands its keys, a block at a time, and no query
    # (a group's five heads of [positions, 65, 128] a key/value head)
    assert fused or re.findall(r"f32\[(?:1,)?8,\d+,65,128\]", text)
    assert not re.findall(r"f32\[(?:1,)?8,5,\d+,65,128\]", text)
    whole = rf"f32\[{L},{slots},8,65,128,128\]"
    assert not re.findall(
        rf"= {whole}[^ ]* (?:copy|slice|transpose|convert)\(", text)
    assert not re.findall(
        rf"= f32\[{L},{slots},65,8,128\][^ ]* "
        r"(?:copy|slice|transpose|convert)\(", text)
    layer_state = rf"f32\[(?:1,)?{slots},8,65,128,128\]"
    assert not re.findall(layer_state, text)
    if fused:
        assert len(re.findall(r"custom-call\([^\n]*adtk_retention_step",
                              text)) == L
        assert not re.findall(rf"{whole}[^ ]* dynamic-update-slice\(",
                              text)
        stablehlo = lowered.as_text()
        assert stablehlo.count("func.func private "
                               "@retention_step_layer") == 1
        assert len(re.findall(r"call @retention_step_layer\(",
                              stablehlo)) == L
    else:
        # the admitted slot's rows, written where they lie: once a layer
        assert len(re.findall(
            rf"ROOT [^ ]+ = {whole}[^ ]* dynamic-update-slice\(",
            text)) == L
        assert "adtk_retention_step" not in text


# three layers of the benchmark's state-space stack at its published
# widths (64 heads of 64 over one shared B/C of 128; 32 query heads on 8
# key/value heads of 64; an FFN of 8,192; an eighth of the vocabulary) at
# the cell's 64 slots of 3,072 positions and its [1, 1024] prompt row: a
# state-space layer, an attention layer, a state-space layer
@pytest.mark.parametrize("program", ["decode-kernel", "prefill"])
def test_ssd_stack_compiles_in_place(one_chip, program, monkeypatch):
    """Both programs hold what they are given — the stacked float32
    matrices, the convolution tails and the attention layer's lanes alias
    their outputs — and no op copies, slices, transposes or converts the
    stacked matrices: what is left of their whole shape is the in-place
    write (the kernel's aliased operand in decode, the
    ``dynamic-update-slice`` of the admitted slot's rows in prefill).
    ``decode-kernel``: the decode program a TPU process elects (here
    forced through the kernel slot, the backend being the CPU's) — Mosaic
    takes the ssd-step kernel at the cell's 64 slots of one ``[128,
    4096]`` matrix, one call a state-space layer out of one lowering, and
    the grouped dense decode kernel at four query heads a key/value head
    of 64 with the block's own softmax scale."""
    from autodist_tpu.models import pipeline_lm as lm
    from autodist_tpu.models.transformer import (BlockSpec, LinearMixerSpec,
                                                 TransformerConfig)
    from autodist_tpu.serving import ServingEngine

    fused = program == "decode-kernel"
    if fused:
        for name in ("ssd_step", "flash_decode"):
            monkeypatch.setattr(
                importlib.import_module(
                    "autodist_tpu.kernel.pallas." + name),
                "default_interpret", lambda: False)
    bf16, slots, bucket, T, L = jnp.bfloat16, 64, 1024, 3072, 3
    cfg = TransformerConfig(
        vocab_size=12544, hidden_size=2048, num_layers=L, num_heads=32,
        mlp_dim=8192, max_len=131072, dtype=bf16, dropout_rate=0.0,
        attention_dropout_rate=0.0,
        block=BlockSpec(
            norm="rmsnorm", norm_placement="pre", norm_eps=1e-5,
            positions="none", ffn="swiglu", bias=False, tied_head=True,
            kv_heads=8, head_dim=64,
            layer_period=("linear", "full", "linear"),
            linear=LinearMixerSpec.ssd(64, 64, 128),
            embedding_multiplier=12.0, residual_multiplier=0.22,
            logits_scaling=8.0, softmax_scale=0.015625))
    params = jax.tree.map(lambda shape: jnp.zeros(shape, bf16),
                          lm.param_shapes(cfg),
                          is_leaf=lambda x: isinstance(x, tuple))
    engine = ServingEngine(
        cfg, params, num_slots=slots, max_len=T, prefill_len=bucket,
        decode_steps=8,
        kernel={"ssd_step": fused, "flash_decode": fused})
    c = engine.cache
    assert engine.kv.state_kernel(c.state.ssm) == fused
    assert bool(engine.kv.fused_block) == fused
    assert c.state.ssm.shape == (2, slots, 1, 128, 4096)
    assert c.state.conv.shape == (2, slots, 3 * 4352)
    assert c.state.ssm.dtype == jnp.float32 and c.state.norm is None
    assert c.k.shape == (1, slots, 8, T, 64)
    sds = lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype,
                                         sharding=one_chip)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    head = (jax.tree.map(sds, engine.params), sds(c.k), sds(c.v),
            i32(slots), i32(slots))
    state = tuple(sds(a) for a in engine._state_args())
    with jax.default_matmul_precision("default"):
        if fused:
            lowered = engine._decode_jit.lower(
                *head, i32(slots, 1), i32(slots), jax.ShapeDtypeStruct(
                    (slots,), jnp.bool_, sharding=one_chip),
                *state)
        else:
            lowered = engine._prefill_jit.lower(
                *head, i32(), i32(1, 1), i32(1), i32(1, bucket), i32(1),
                *state)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    held = sum(a.size * a.dtype.itemsize
               for a in (*engine._state_args(), c.k, c.v))
    # 2 x 64 matrices of 2 MB, 2 x 64 tails, a layer of lanes each way
    assert held == 2 * 64 * (2_097_152 + 26_112) + 2 * 64 * 8 * T * 64 * 2
    assert abs(mem.alias_size_in_bytes - held) < 4096
    # a prompt row's FFN activations and its chunks' [64, 256, 256]
    # weights; a decode step's rows; never a layer's slice of the state
    # (134 MB)
    assert mem.temp_size_in_bytes < (24 << 20 if fused else 320 << 20)
    text = compiled.as_text()
    whole = rf"f32\[2,{slots},1,128,4096\]"
    assert not re.findall(
        rf"= {whole}[^ ]* (?:copy|slice|transpose|convert)\(", text)
    assert not re.findall(rf"f32\[(?:1,)?{slots},1,128,4096\]", text)
    if fused:
        assert len(re.findall(r"custom-call\([^\n]*adtk_ssd_step",
                              text)) == 2
        assert len(re.findall(r"custom-call\([^\n]*adtk_flash_decode",
                              text)) == 1
        assert not re.findall(rf"{whole}[^ ]* dynamic-update-slice\(",
                              text)
        stablehlo = lowered.as_text()
        assert stablehlo.count("func.func private @ssd_step_layer") == 1
        assert len(re.findall(r"call @ssd_step_layer\(", stablehlo)) == 2
    else:
        # the admitted slot's matrix, written where it lies: once a layer
        assert len(re.findall(
            rf"ROOT [^ ]+ = {whole}[^ ]* dynamic-update-slice\(",
            text)) == 2
        assert "adtk_ssd_step" not in text


# one encoder layer's attention at the training cell's widths (BERT-base:
# 12 heads of 64, 512 positions) and at heads of 128, forward and
# backward: Mosaic takes the one-pass kernels' tiles, and no array of the
# operands' size is copied or transposed around the calls
@pytest.mark.parametrize("hidden,heads,length", [
    (768, 12, 512), (1024, 8, 1024)], ids=["heads64", "heads128"])
def test_training_attention_compiles_without_copies(one_chip, hidden,
                                                    heads, length,
                                                    monkeypatch):
    import importlib

    from autodist_tpu.models.transformer import (SelfAttention,
                                                 TransformerConfig)

    fa = importlib.import_module("autodist_tpu.ops.flash_attention")
    # what a one-chip TPU process observes (the election asks the
    # backend and the device count, which here are the CPU's)
    monkeypatch.setattr(fa, "_backend_is_tpu", lambda: True)
    monkeypatch.setattr(fa, "default_interpret", lambda: False)
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    cfg = TransformerConfig(hidden_size=hidden, num_heads=heads,
                            dtype=jnp.bfloat16, dropout_rate=0.0,
                            attention_dropout_rate=0.0)
    layer = SelfAttention(cfg)
    B = 4
    params = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8, hidden), jnp.bfloat16), None,
                           True))
    sds = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                         sharding=one_chip)

    def loss(params, x):
        return jnp.sum(layer.apply(params, x, None, True)
                       .astype(jnp.float32) ** 2)

    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            jax.tree.map(sds, params),
            sds(jax.ShapeDtypeStruct((B, length, hidden),
                                     jnp.bfloat16))).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2       # forward, backward
    assert "adtk_flash_attention" in text
    assert f"[{B},{heads},{length},{length}]" not in text
    sized = rf"= bf16\[{B},{length},(?:{hidden}|{3 * hidden}|{heads},\d+)\]"
    assert not re.findall(sized + r"[^ ]* (?:copy|transpose)\(", text)


def _stored(text):
    """``[(name, shape)]`` of the entry computation's instructions: what
    the program keeps in memory between its fusions (a tuple's parts
    each; what a fusion computes inside itself is not here)."""
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    out = []
    for m in re.finditer(r"^\s*(?:ROOT )?(%[\w.\-]+) = (.*?) [\w\-]+\(",
                         entry, re.M):
        out.extend((m.group(1), s) for s in re.findall(
            r"\w+\[[\d,]*\](?:\{[^}]*\})?", m.group(2)))
    return out


def _padded_share(shape):
    """Elements the tiled layout keeps over the elements of ``shape``
    (``bf16[64,76,30522]{2,1,0:T(8,128)(2,1)}``: the two minor
    dimensions are padded to whole tiles)."""
    m = re.fullmatch(r"\w+\[([\d,]+)\]\{([\d,]+):T\((\d+),(\d+)\).*\}",
                     shape)
    dims = [int(d) for d in m.group(1).split(",")]
    minor, second = (int(i) for i in m.group(2).split(",")[:2])
    share = 1.0
    for axis, tile in ((minor, int(m.group(4))), (second, int(m.group(3)))):
        share *= -(-dims[axis] // tile) * tile / dims[axis]
    return share


# one encoder layer of the training cell's step at its own widths (64
# sequences of 512, 76 predictions a sequence, BERT-base's 30,522-row
# tied table, adamw with a bf16 first moment): the head's 64 x 76 rows
# are one axis of 4,864 = 38 x 128, so the logits are stored once, in
# bf16, with no padding; nothing of their size is written in float32;
# and the table's gradient is one contraction over all 4,864 rows
def test_training_head_stores_the_logits_once(one_chip, monkeypatch):
    import dataclasses

    import optax

    from autodist_tpu.models import bert

    B, L, P = 64, 512, 76
    cfg = dataclasses.replace(bert.bert_base(dtype=jnp.bfloat16),
                              num_layers=1)
    V = cfg.vocab_size
    opt = optax.adamw(1e-4, weight_decay=0.01, mu_dtype=jnp.bfloat16)
    trainable = bert.make_mlm_trainable(
        cfg, opt, jax.random.PRNGKey(0), batch_size=2, seq_len=L,
        num_masked=P, with_input_mask=False)
    fa = importlib.import_module("autodist_tpu.ops.flash_attention")
    # what a one-chip TPU process observes, once the init has run here
    monkeypatch.setattr(fa, "_backend_is_tpu", lambda: True)
    monkeypatch.setattr(fa, "default_interpret", lambda: False)
    monkeypatch.setattr(jax, "device_count", lambda: 1)

    def step(params, opt_state, batch, rng):
        def loss(p):
            l, _, metrics = trainable.loss(p, None, batch, rng)
            return l, metrics
        (_, metrics), grads = jax.value_and_grad(loss, has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, metrics

    sds = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((B, L), jnp.int32, sharding=one_chip)
    picks = jax.ShapeDtypeStruct((B, P), jnp.int32, sharding=one_chip)
    batch = {"input_ids": ids, "segment_ids": ids,
             "masked_positions": picks, "masked_ids": picks,
             "masked_weights": jax.ShapeDtypeStruct(
                 (B, P), jnp.float32, sharding=one_chip)}
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
            jax.tree.map(sds, trainable.params),
            jax.tree.map(sds, jax.eval_shape(opt.init, trainable.params)),
            batch, jax.ShapeDtypeStruct((2,), jnp.uint32,
                                        sharding=one_chip)).compile()
    text = compiled.as_text()

    # the logits: every stored array with the vocabulary beside the rows
    rows = rf"(?:{B * P}|{B},{P}|{B},\d+,{P}|{P},{B})"
    logits = [(n, s) for n, s in _stored(text)
              if re.match(rf"\w+\[(?:{rows},{V}|{V},{rows})\]", s)]
    assert len({n for n, _ in logits}) == 1, logits
    (_, shape), = logits
    assert shape.startswith(f"bf16[{B * P},{V}]"), shape
    assert _padded_share(shape) < 1.003, shape
    assert _padded_share("bf16[64,76,30522]{2,1,0:T(8,128)(2,1)}") > 1.05

    # every product with the table or the logits among its operands or as
    # its result is a plain contraction: no window over the batch
    products = 0
    for body in re.split(r"\n(?=%|ENTRY )", text):
        shapes = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\S+) ", body,
                                 re.M))
        for line in body.splitlines():
            if " convolution(" not in line:
                continue
            result, operands = line.split(" convolution(", 1)
            names = re.findall(r"%[\w.\-]+", operands.split(")", 1)[0])
            if str(V) in result + " ".join(shapes.get(n, "") for n in names):
                products += 1
                assert "window={size=" not in line, line
    assert products == 3        # the logits, dx, dW
