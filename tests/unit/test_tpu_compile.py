"""The decode kernel of the main serving path, compiled at real widths
for a TPU that is described and not attached (the TPU's compiler is
installed; nothing runs, so this says nothing about results or times).

What the interpreter cannot show: that Mosaic takes the kernel's tiles
as they are sliced, that its VMEM fits, and that the compiled call holds
the cache it is given — no copy of it, no temporary — in the layout the
chip keeps it in.  One file, one fixture: only the worker that is given
this file loads the TPU's library, inside a test.
"""
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
