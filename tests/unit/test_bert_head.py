"""The masked-LM head on one flat axis of ``B*P`` rows against a plain
``[B, P, V]`` restatement of the head it replaced (PR 49): the same
parameters, the same loss, accuracy and per-position log-likelihoods bit
for bit, the same gradients but for the order the table's gradient sums
its ``B*P`` terms in.
"""
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.models import bert
from autodist_tpu.models.transformer import Encoder, TransformerConfig

VOCAB, HIDDEN, LENGTH = 97, 32, 16


class RowsByPredictionsBert(nn.Module):
    """``BertModel`` as it stood until PR 49: the head's rows stay
    ``[B, P, .]`` and the logits leave in float32 with the bias added."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, batch):
        cfg = self.cfg
        tokens = batch["input_ids"]
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         name="token_embed")
        x = embed(tokens)
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (cfg.max_len, cfg.hidden_size), jnp.float32)
        x = x + pos[None, :tokens.shape[1]].astype(cfg.dtype)
        x = x + nn.Embed(cfg.type_vocab_size, cfg.hidden_size,
                         dtype=cfg.dtype,
                         name="segment_embed")(batch["segment_ids"])
        x = nn.LayerNorm(dtype=cfg.dtype, name="ln_embed")(x)
        x = Encoder(cfg, name="encoder")(x, None, True)
        gathered = jnp.take_along_axis(
            x, batch["masked_positions"][..., None], axis=1)    # [B, P, H]
        h = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                     name="mlm_dense")(gathered)
        h = nn.LayerNorm(dtype=cfg.dtype, name="mlm_ln")(nn.gelu(h))
        logits = embed.attend(h).astype(jnp.float32)            # [B, P, V]
        return logits + self.param("mlm_bias", nn.initializers.zeros,
                                   (cfg.vocab_size,), jnp.float32)


def rows_by_predictions_loss(logits, batch):
    """``mlm_loss_head`` as it stood until PR 49, over ``[B, P, V]``
    float32 logits; also each position's log-likelihood."""
    labels, weights = batch["masked_ids"], batch["masked_weights"]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    target = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ll = target - lse
    denom = jnp.maximum(weights.sum(), 1.0)
    loss = -(ll * weights).sum() / denom
    acc = ((logits.argmax(-1) == labels) * weights).sum() / denom
    return loss, acc, ll


def _case(dtype, B, P):
    cfg = TransformerConfig(
        vocab_size=VOCAB, hidden_size=HIDDEN, num_layers=1, num_heads=2,
        mlp_dim=64, max_len=LENGTH, dtype=dtype, dropout_rate=0.0,
        attention_dropout_rate=0.0)
    batch = bert.synthetic_mlm_batch(7, B, LENGTH, P, VOCAB)
    batch.pop("input_mask")
    r = np.random.RandomState(11)
    # uneven weights, two predictions padded out
    batch["masked_weights"] = r.uniform(0.5, 1.5, (B, P)).astype(np.float32)
    batch["masked_weights"][0, -2:] = 0.0
    model = bert.BertModel(cfg)
    params = model.init(jax.random.PRNGKey(3), batch)["params"]
    # a bias and an embedding scale that the argmax and the loss can see
    params["mlm_bias"] = jnp.asarray(r.normal(0, 0.5, VOCAB), jnp.float32)
    params["token_embed"]["embedding"] = params["token_embed"][
        "embedding"] * 8.0
    return cfg, model, params, batch


SHAPES = [(4, 6), (3, 5)]       # B*P = 24, and 15: not a whole sublane tile
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("B,P", SHAPES, ids=["rows24", "rows15"])
@pytest.mark.parametrize("dtype", DTYPES, ids=["float32", "bfloat16"])
def test_flat_head_is_the_rows_by_predictions_head(dtype, B, P):
    cfg, model, params, batch = _case(dtype, B, P)
    old_model = RowsByPredictionsBert(cfg)

    def new(p):
        logits, bias = model.apply({"params": p}, batch)
        loss, metrics = bert.mlm_loss_head(logits, batch, bias)
        return loss, (metrics["mlm_accuracy"], logits, bias)

    def old(p):
        loss, acc, ll = rows_by_predictions_loss(
            old_model.apply({"params": p}, batch), batch)
        return loss, (acc, ll)

    # op by op, so that each side is the same sequence of small programs
    # but for the head's shapes (a whole-model jit fuses the two heads'
    # neighbours differently, and in bf16 rounds them differently)
    (loss, (acc, logits, bias)), grads = jax.value_and_grad(
        new, has_aux=True)(params)
    (want_loss, (want_acc, want_ll)), want_grads = jax.value_and_grad(
        old, has_aux=True)(params)
    assert logits.shape == (B * P, VOCAB) and logits.dtype == dtype
    assert bias.shape == (VOCAB,) and bias.dtype == jnp.float32
    assert np.asarray(loss) == np.asarray(want_loss)        # bit for bit
    assert np.asarray(acc) == np.asarray(want_acc)

    # each position's log-likelihood (its target logit less the row's
    # logsumexp), bit for bit: a weight of one on that position alone
    def ll_of(weights):
        return -bert.mlm_loss_head(
            logits, dict(batch, masked_weights=weights.reshape(B, P)),
            bias)[0]

    ll = jax.vmap(ll_of)(jnp.eye(B * P, dtype=jnp.float32))
    np.testing.assert_array_equal(np.asarray(ll),
                                  np.asarray(want_ll).reshape(-1))

    # the gradients: the table's and the bias's sum their B*P terms in
    # another order (float32 rounding), and in bf16 the label's -w/denom
    # meets the softmax's term already rounded (one ulp of bf16 on what
    # flows back through the head)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    want = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    assert len(flat) == len(want)
    for path, g in flat:
        w = np.asarray(want[path], np.float32)
        assert g.dtype == want[path].dtype == jnp.float32
        gap = np.abs(np.asarray(g, np.float32) - w).max()
        assert gap <= tol * max(np.abs(w).max(), 1e-3), (
            jax.tree_util.keystr(path), gap, np.abs(w).max())


@pytest.mark.parametrize("dtype", DTYPES, ids=["float32", "bfloat16"])
def test_loss_head_takes_either_shape_of_logits(dtype):
    B, P = 3, 5
    _, model, params, batch = _case(dtype, B, P)
    logits, bias = model.apply({"params": params}, batch)
    flat = bert.mlm_loss_head(logits, batch, bias)
    rows = bert.mlm_loss_head(logits.reshape(B, P, VOCAB), batch, bias)
    assert np.asarray(rows[0]) == np.asarray(flat[0])
    assert (np.asarray(rows[1]["mlm_accuracy"])
            == np.asarray(flat[1]["mlm_accuracy"]))


def test_parameter_tree_is_pinned():
    """``benchmark/builders/bert_mlm.py`` refuses any other tree: the
    head's names, shapes and dtypes are the contract."""
    _, _, params, _ = _case(jnp.bfloat16, 2, 3)
    f32 = "float32"
    layer = "encoder/layer_0/"
    want = {
        "token_embed/embedding": ((VOCAB, HIDDEN), f32),
        "segment_embed/embedding": ((2, HIDDEN), f32),
        "pos_embed": ((LENGTH, HIDDEN), f32),
        "ln_embed/scale": ((HIDDEN,), f32),
        "ln_embed/bias": ((HIDDEN,), f32),
        layer + "attention/qkv/kernel": ((HIDDEN, 3, 2, 16), f32),
        layer + "attention/qkv/bias": ((3, 2, 16), f32),
        layer + "attention/out/kernel": ((2, 16, HIDDEN), f32),
        layer + "attention/out/bias": ((HIDDEN,), f32),
        layer + "ln_attention/scale": ((HIDDEN,), f32),
        layer + "ln_attention/bias": ((HIDDEN,), f32),
        layer + "mlp/wi/kernel": ((HIDDEN, 64), f32),
        layer + "mlp/wi/bias": ((64,), f32),
        layer + "mlp/wo/kernel": ((64, HIDDEN), f32),
        layer + "mlp/wo/bias": ((HIDDEN,), f32),
        layer + "ln_mlp/scale": ((HIDDEN,), f32),
        layer + "ln_mlp/bias": ((HIDDEN,), f32),
        "mlm_dense/kernel": ((HIDDEN, HIDDEN), f32),
        "mlm_dense/bias": ((HIDDEN,), f32),
        "mlm_ln/scale": ((HIDDEN,), f32),
        "mlm_ln/bias": ((HIDDEN,), f32),
        "mlm_bias": ((VOCAB,), f32),
    }
    have = {"/".join(k.key for k in path): (tuple(x.shape), str(x.dtype))
            for path, x in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert have == want
