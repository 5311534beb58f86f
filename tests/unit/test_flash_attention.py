"""Flash-attention kernel: numeric parity with plain einsum attention.

Runs the Pallas interpreter on the CPU harness; on TPU the same code
compiles to the fused kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.models.transformer import dot_product_attention
from autodist_tpu.ops import flash_attention, make_attention_fn


pytestmark = pytest.mark.slow

def _inputs(b=2, l=128, h=4, d=32, dtype=jnp.float32, seed=0):
    r = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(r.randn(b, l, h, d) * 0.3, dtype)
    return mk(), mk(), mk()


def _reference(q, k, v, causal):
    mask = None
    if causal:
        l = q.shape[1]
        mask = jnp.tril(jnp.ones((l, l), bool))[None, None]
    return dot_product_attention(q, k, v, mask, dtype=jnp.float32)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(causal):
    q, k, v = _inputs()
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = _reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_forward_uneven_blocks():
    """Sequence split into multiple q and k blocks of different sizes."""
    q, k, v = _inputs(l=96)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=16)
    ref = _reference(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("l", [100, 127, 4])
def test_indivisible_seq_pads_and_masks(l, causal):
    """Arbitrary sequence lengths (incl. prime and sub-tile) are padded to
    a block multiple and masked — numerics must still match, forward and
    backward."""
    q, k, v = _inputs(l=l, d=16)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = _reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        o = _reference(q, k, v, causal)
        return jnp.sum(o * jnp.cos(o))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=1e-4, rtol=1e-3,
                                   err_msg=f"d{name} mismatch at l={l}")


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_reference(causal):
    q, k, v = _inputs(l=64, d=16)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        o = _reference(q, k, v, causal)
        return jnp.sum(o * jnp.cos(o))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=1e-4, rtol=1e-3,
                                   err_msg=f"d{name} mismatch")


def test_bfloat16_forward():
    q, k, v = _inputs(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    assert out.dtype == jnp.bfloat16
    ref = _reference(q.astype(jnp.float32), k.astype(jnp.float32),
                     v.astype(jnp.float32), True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=3e-2, rtol=3e-2)


def test_transformer_integration():
    """TransformerLM with the flash attention_fn matches plain attention."""
    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)

    def make(attention_fn):
        cfg = TransformerConfig(
            vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
            mlp_dim=64, max_len=64, dropout_rate=0.0,
            attention_dropout_rate=0.0, causal=True, dtype=jnp.float32,
            attention_fn=attention_fn)
        return TransformerLM(cfg)

    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 128, (2, 64)),
                         jnp.int32)
    params = make(None).init(jax.random.PRNGKey(0), tokens)["params"]
    plain = make(None).apply({"params": params}, tokens)
    flash = make(make_attention_fn(causal=True, block_q=32, block_k=32)).apply(
        {"params": params}, tokens)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(plain),
                               atol=1e-4, rtol=1e-4)


def test_attention_fn_rejects_dropout():
    q, k, v = _inputs(l=32)
    fn = make_attention_fn(causal=True)
    with pytest.raises(ValueError, match="dropout"):
        fn(q, k, v, None, jax.random.PRNGKey(0))


def test_attention_fn_rejects_padding_mask():
    """A non-causal adapter must not silently drop a padding mask."""
    q, k, v = _inputs(l=32)
    fn = make_attention_fn(causal=False)
    mask = jnp.ones((2, 1, 32, 32), bool)
    with pytest.raises(ValueError, match="mask"):
        fn(q, k, v, mask, None)


def test_flash_attention_default_blocks_run():
    """block_q/block_k=None resolve to DEFAULT_BLOCK and the kernel
    still matches the reference einsum."""
    import numpy as np

    from autodist_tpu.ops.flash_attention import flash_attention

    r = np.random.RandomState(0)
    q, k, v = (jnp.asarray(r.randn(2, 64, 2, 16), jnp.float32)
               for _ in range(3))
    out = flash_attention(q, k, v, causal=True)

    s = jnp.einsum("blhd,bmhd->bhlm", q, k) / np.sqrt(16)
    mask = jnp.tril(jnp.ones((64, 64), bool))
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -1e30), -1)
    ref = jnp.einsum("bhlm,bmhd->blhd", p, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_bf16_inputs_match_einsum_reference():
    """bf16 q/k/v (the bench/crossover operating dtype): matmul inputs
    stay bf16 (full MXU rate) with fp32 accumulation + fp32 softmax —
    forward and grads match a reference that computes the same
    mixed-precision einsum attention."""
    r = np.random.RandomState(3)
    B, L, H, D = 2, 128, 2, 32
    q, k, v = (jnp.asarray(r.randn(B, L, H, D), jnp.bfloat16)
               for _ in range(3))

    def ref(q, k, v):
        s = jnp.einsum("blhd,bmhd->bhlm", q, k,
                       preferred_element_type=jnp.float32) / np.sqrt(D)
        mask = jnp.tril(jnp.ones((L, L), bool))
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -1e30), -1)
        return jnp.einsum("bhlm,bmhd->blhd", p.astype(jnp.bfloat16), v,
                          preferred_element_type=jnp.float32)

    out = flash_attention(q, k, v, causal=True).astype(jnp.float32)
    expected = ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=0.05, atol=0.02)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(ref(q, k, v) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, e in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a, dtype=np.float32), np.asarray(e, np.float32),
            rtol=0.1, atol=0.05)
