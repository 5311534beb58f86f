"""Plain float32 reference of ``bert-base-mlm``: forward, loss, gradients
and AdamW in straightforward ``jax.numpy``, no flax, no program code.

The equations are the repo's BERT block (``departures`` in the
configuration file): post-LN encoder, tanh GELU, LayerNorm eps from the
file, an MLM head of dense -> GELU -> LayerNorm -> tied decode + bias, the
loss a mean of ``logsumexp - logit[target]`` over the masked positions.
Weights arrive in the layout the program consumes (a nested dict made by
``harness/weights.py`` from the seed); nothing the program made is read.

``precision``: ``"float32"`` is the reference (every matmul at
``highest``); ``"fp8"`` is the control — each linear layer's operands
rounded to e4m3 and the gradient arriving at its output to e5m2, each
under a per-tensor scale (the usual 8-bit training recipe, the step
below bf16 that would tempt a later PR); ``"bfloat16"`` rounds the
operands to bf16.
"""
from __future__ import annotations

import statistics

# --------------------------------------------------------------------- #
# limits, each set from readings on the v5e at the cell's own size, 64
# sequences a chip, through the window's own program of 8 fused steps
# (tools/readings.py and the cell's runs; my chip runs, PR 23; PERF.md
# section 2).  Control = this reference in fp8, put in the program's place.
# --------------------------------------------------------------------- #
LIMITS = {
    # |program loss - reference loss| at each step of the first window.
    # Precision hardly moves it (sound runs read at most 2.5e-4 over 15
    # seeds through the window's program and 3.6e-4 over 12 more through
    # the same step body one step to a call; the control 7.7e-4 to
    # 2.5e-3), so it is held against a part of the batch left out (which
    # moves a 64-row loss by ~1e-2): three times the sound runs' largest.
    "loss_gap": 1.1e-3,
    # worst leaf of | ||s_prog|| - ||s_ref|| | / max(||s_ref||, median),
    # s the root of Adam's second moment after the window: second-order
    # in rounding error (sound at most 0.0035, control from 0.0152), held
    # against a gradient that missed the exchange between chips or part
    # of the batch: three times the sound largest, under the control's
    # smallest.
    "grad_norm_gap": 0.011,
    # the same of the parameters' change after the window: there for a
    # window that returns its state unchanged (the gap is then 1.0).
    # Precision hardly moves it (sound at most 0.0232 over 15 seeds, the
    # bf16 first moment and rounding noise in the key bias's all-but-zero
    # gradient, which Adam scales up; control 0.017-0.029).  Three times
    # the sound largest is 0.07; the limit stays at 0.09 because the
    # rehearsal's toy size, where that noise is a larger part of the
    # leaf, reads up to 0.076 on the tests' seeds.
    "delta_norm_gap": 0.09,
    # worst leaf of || s_prog - s_ref || / max(||s_ref||, median):
    # first-order in rounding error, the number the control fails.  Sound
    # 0.0102-0.0117 over 15 seeds, control 0.1026-0.1069 over 3: the limit
    # sits a factor three from each.
    "grad_abs_gap": 0.035,
}


def param_shapes(cfg: dict) -> dict:
    """The parameter tree, ``(shape, dtype)`` leaves, float32."""
    H, M = cfg["hidden_size"], cfg["intermediate_size"]
    n, V = cfg["num_attention_heads"], cfg["vocab_size"]
    d = H // n
    f = "float32"
    ln = lambda: {"scale": ((H,), f), "bias": ((H,), f)}
    layer = lambda: {
        "attention": {"qkv": {"kernel": ((H, 3, n, d), f),
                              "bias": ((3, n, d), f)},
                      "out": {"kernel": ((n, d, H), f), "bias": ((H,), f)}},
        "ln_attention": ln(),
        "mlp": {"wi": {"kernel": ((H, M), f), "bias": ((M,), f)},
                "wo": {"kernel": ((M, H), f), "bias": ((H,), f)}},
        "ln_mlp": ln()}
    return {
        "token_embed": {"embedding": ((V, H), f)},
        "segment_embed": {"embedding": ((cfg["type_vocab_size"], H), f)},
        "pos_embed": ((cfg["max_position_embeddings"], H), f),
        "ln_embed": ln(),
        "encoder": {f"layer_{i}": layer()
                    for i in range(cfg["num_hidden_layers"])},
        "mlm_dense": {"kernel": ((H, H), f), "bias": ((H,), f)},
        "mlm_ln": ln(),
        "mlm_bias": ((V,), f),
    }


# --------------------------------------------------------------------- #
# the model
# --------------------------------------------------------------------- #
def _rounder(precision: str):
    """``(act, weight, out)``: what rounds a linear layer's input, its
    weight and — on the way back — the gradient arriving at its output.
    Forward rounding passes gradients straight through, as training in a
    low precision does."""
    import jax
    import jax.numpy as jnp

    def ste(x, xq):
        return x + jax.lax.stop_gradient(xq - x)

    ident = lambda x: x
    if precision == "float32":
        return ident, ident, ident
    if precision == "bfloat16":
        r = lambda x: ste(x, x.astype(jnp.bfloat16).astype(jnp.float32))
        return r, r, ident
    if precision == "fp8":
        def q(x, dtype):
            # per-tensor scale to the format's largest finite value
            s = jnp.max(jnp.abs(x)) / float(jnp.finfo(dtype).max)
            s = jnp.where(s == 0, 1.0, s)
            return (x / s).astype(dtype).astype(jnp.float32) * s

        r = lambda x: ste(x, q(x, jnp.float8_e4m3fn))

        @jax.custom_vjp
        def out(y):
            return y

        out.defvjp(lambda y: (y, None),
                   lambda _, g: (q(g, jnp.float8_e5m2),))
        return r, r, out
    raise ValueError(f"unknown precision {precision!r}")


def _layer_norm(x, p, eps):
    import jax.numpy as jnp

    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def loss_fn(params, batch, cfg: dict, precision: str = "float32"):
    """Mean masked-LM cross entropy of ``batch`` (all rows, all masked
    positions weighted 1)."""
    import jax
    import jax.numpy as jnp

    act, wq, out = _rounder(precision)
    eps = cfg["layer_norm_eps"]
    n = cfg["num_attention_heads"]
    ids, seg = batch["input_ids"], batch["segment_ids"]
    L = ids.shape[1]
    emb = params["token_embed"]["embedding"]
    x = emb[ids] + params["pos_embed"][None, :L] \
        + params["segment_embed"]["embedding"][seg]
    x = _layer_norm(x, params["ln_embed"], eps)
    for i in range(cfg["num_hidden_layers"]):
        p = params["encoder"][f"layer_{i}"]
        a = p["attention"]
        qkv = out(jnp.einsum("blh,hcnd->blcnd", act(x),
                             wq(a["qkv"]["kernel"]))) + a["qkv"]["bias"]
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / (q.shape[-1] ** 0.5)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bnqk,bknd->bqnd", probs, v)
        attn = out(jnp.einsum("bqnd,ndh->bqh", act(ctx),
                              wq(a["out"]["kernel"]))) + a["out"]["bias"]
        x = _layer_norm(x + attn, p["ln_attention"], eps)
        h = _gelu_tanh(out(act(x) @ wq(p["mlp"]["wi"]["kernel"]))
                       + p["mlp"]["wi"]["bias"])
        m = out(act(h) @ wq(p["mlp"]["wo"]["kernel"])) + p["mlp"]["wo"]["bias"]
        x = _layer_norm(x + m, p["ln_mlp"], eps)
    g = jnp.take_along_axis(x, batch["masked_positions"][..., None], axis=1)
    h = _gelu_tanh(out(act(g) @ wq(params["mlm_dense"]["kernel"]))
                   + params["mlm_dense"]["bias"])
    h = _layer_norm(h, params["mlm_ln"], eps)
    logits = out(act(h) @ wq(emb).T) + params["mlm_bias"]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    target = jnp.take_along_axis(logits, batch["masked_ids"][..., None],
                                 axis=-1)[..., 0]
    return jnp.mean(lse - target)


# --------------------------------------------------------------------- #
# three optimizer steps
# --------------------------------------------------------------------- #
def _leaf_norms(tree, prefix=()):
    import jax.numpy as jnp

    out = {}
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            out.update(_leaf_norms(v, prefix + (k,)))
        else:
            out["/".join(prefix + (k,))] = jnp.sqrt(jnp.sum(
                jnp.square(v.astype(jnp.float32))))
    return out


def first_steps(params, batches: list, cfg: dict, opt: dict,
                precision: str = "float32", row_block: int = 16) -> dict:
    """Follow the first ``len(batches)`` AdamW steps from ``params``:
    the first window of the program's fused steps.

    Returns the readings ``compare`` takes: each step's loss; the root
    of Adam's second moment after the last step (``grad_abs``: the
    gradients the optimizer was handed, element by element, as the
    decayed root of the sum of their squares) and its norm leaf by leaf;
    and the norm of every leaf of the parameters' change.  Gradients are
    summed over blocks of ``row_block`` rows so that float32 activations
    of a large batch fit one chip."""
    import jax
    import jax.numpy as jnp

    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    lr, wd = opt["learning_rate"], opt["weight_decay"]
    rows = batches[0]["input_ids"].shape[0]
    row_block = min(row_block, rows)
    if rows % row_block:
        raise ValueError(f"{rows} rows do not divide into blocks of "
                         f"{row_block}")

    with jax.default_matmul_precision("highest"):
        block_grad = jax.jit(jax.value_and_grad(
            lambda p, b: loss_fn(p, b, cfg, precision)))

        @jax.jit
        def adamw(p, mu, nu, g, t):
            mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
            nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
            c1, c2 = 1 - b1 ** t, 1 - b2 ** t
            p = jax.tree.map(
                lambda w, m, v: w - lr * (m / c1 / (jnp.sqrt(v / c2) + eps)
                                          + wd * w), p, mu, nu)
            return p, mu, nu

        p0 = params
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        losses = []
        for t, batch in enumerate(batches, start=1):
            loss, grad = 0.0, None
            for r in range(0, rows, row_block):
                blk = {k: jnp.asarray(v[r:r + row_block])
                       for k, v in batch.items()}
                l, g = block_grad(params, blk)
                w = row_block / rows
                loss = loss + w * l
                g = jax.tree.map(lambda x: w * x, g)
                grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
            losses.append(float(loss))
            params, mu, nu = adamw(params, mu, nu, grad, float(t))
        grad_abs = jax.jit(lambda t: jax.tree.map(jnp.sqrt, t))(nu)
        delta = jax.jit(lambda a, b: _leaf_norms(
            jax.tree.map(jnp.subtract, a, b)))(params, p0)
        grad_norm = jax.jit(_leaf_norms)(grad_abs)
    return {"loss": losses, "grad_abs": grad_abs,
            "grad_norm": {k: float(v) for k, v in grad_norm.items()},
            "delta_norm": {k: float(v) for k, v in delta.items()}}


# --------------------------------------------------------------------- #
# the comparison that decides ``correct``
# --------------------------------------------------------------------- #
def _worst_leaf_gap(got: dict, ref: dict):
    """Largest over the leaves of ``| ||got|| - ||ref|| |`` against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero); and that leaf's name."""
    floor = statistics.median(ref.values())
    worst, name = 0.0, ""
    for k, r in ref.items():
        gap = abs(got[k] - r) / max(r, floor)
        if gap >= worst:
            worst, name = gap, k
    return worst, name


def compare(program: dict, reference: dict) -> list:
    """``[(name, value, limit, ok, note)]`` for the numbers compared.
    ``program`` and ``reference`` are what ``first_steps`` returns, the
    program's worked out from its losses and its state after the
    window."""
    rows = []
    gaps = [abs(a - b) for a, b in zip(program["loss"], reference["loss"],
                                       strict=True)]
    rows.append(("loss_gap", max(gaps), LIMITS["loss_gap"],
                 f"program {program['loss']} reference {reference['loss']}"))
    for key in ("grad_norm", "delta_norm"):
        gap, leaf = _worst_leaf_gap(program[key], reference[key])
        rows.append((key + "_gap", gap, LIMITS[key + "_gap"],
                     f"worst leaf {leaf}: program {program[key][leaf]:.6g} "
                     f"reference {reference[key][leaf]:.6g}"))
    rows.append(_grad_abs_row(program, reference))
    return [(n, v, lim, bool(v <= lim), note) for n, v, lim, note in rows]


def _grad_abs_row(program: dict, reference: dict):
    """Worst leaf of ``|| s_prog - s_ref ||`` against the reference's
    norm of that leaf or of the median leaf, where ``s`` is the root of
    Adam's second moment after the window: the gradients the optimizer
    was handed, element by element but for their signs.  Unlike the gap
    between two norms this is first-order in rounding error, so it is
    the number that tells precisions apart."""
    import jax
    import jax.numpy as jnp

    diff = jax.jit(lambda a, b: _leaf_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y, a, b)))(
            program["grad_abs"], reference["grad_abs"])
    ref_norm = reference["grad_norm"]
    floor = statistics.median(ref_norm.values())
    worst, name = 0.0, ""
    for k, d in diff.items():
        gap = float(d) / max(ref_norm[k], floor)
        if gap >= worst:
            worst, name = gap, k
    return ("grad_abs_gap", worst, LIMITS["grad_abs_gap"],
            f"worst leaf {name}")
