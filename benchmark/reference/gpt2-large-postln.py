"""Plain float32 reference of ``gpt2-large-postln``: the full causal
forward over a prompt and the tokens served after it, in straightforward
``jax.numpy`` — no cache, no batching tricks, no program code.

The equations are GPT-2 large's with ONE departure, named in the
configuration: each LayerNorm sits after its residual add (the repo's
block), not before the sub-block.  Learned positions, biases everywhere,
tanh GELU, a final LayerNorm, the output projection tied to the token
embedding.  Weights arrive in the layout the program consumes (stacked
over layers), made by ``harness/weights.py`` from the seed in the type
they are served in; the reference widens them to float32 and multiplies
at ``highest``.

``precision``: ``"float32"`` is the reference; ``"fp8"`` is the control
(each linear layer's operands rounded to e4m3 under a per-tensor scale,
the step below bf16 that would tempt a later PR); ``"bfloat16"`` rounds
them to bf16, as the program does.
"""
from __future__ import annotations

LIMITS = {
    # widest gap, over every sampled served token, by which the served
    # token's reference logit lies below the reference's best.  On the
    # v5e at the cell's size (tools/readings.py; my chip runs, PR 23):
    # sound runs at most 0.042 over 17 seeds (a bf16 near-tie that flips),
    # the fp8 control at least 0.42 over 4.  The limit sits a factor
    # three from each.
    "logit_gap": 0.13,
}


def param_shapes(cfg: dict) -> dict:
    H, V, L = cfg["n_embd"], cfg["vocab_size"], cfg["n_layer"]
    M = cfg["n_inner"] or 4 * H
    n = cfg["n_head"]
    d = H // n
    f = cfg["serving"]["weights_dtype"]
    ln = lambda: {"scale": ((L, H), f), "bias": ((L, H), f)}
    return {
        "stages": {
            "attention": {
                "qkv": {"kernel": ((L, H, 3, n, d), f),
                        "bias": ((L, 3, n, d), f)},
                "out": {"kernel": ((L, n, d, H), f), "bias": ((L, H), f)}},
            "ln_attention": ln(),
            "mlp": {"wi": {"kernel": ((L, H, M), f), "bias": ((L, M), f)},
                    "wo": {"kernel": ((L, M, H), f), "bias": ((L, H), f)}},
            "ln_mlp": ln()},
        "shared": {"embedding": ((V, H), f),
                   "pos_embed": ((cfg["n_positions"], H), f),
                   "ln_final_scale": ((H,), f), "ln_final_bias": ((H,), f)},
    }


def _rounder(precision: str):
    """What rounds a linear layer's operands."""
    import jax.numpy as jnp

    if precision == "float32":
        return lambda x: x
    if precision == "bfloat16":
        return lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        def q(x):
            # e4m3 under a per-tensor scale to its largest finite value
            s = jnp.max(jnp.abs(x)) / float(jnp.finfo(jnp.float8_e4m3fn).max)
            s = jnp.where(s == 0, 1.0, s)
            return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
        return q
    raise ValueError(f"unknown precision {precision!r}")


def _layer_norm(x, scale, bias, eps):
    import jax.numpy as jnp

    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _gelu_tanh(x):
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def logits_fn(params, tokens, cfg: dict, precision: str = "float32"):
    """``[B, T, V]`` float32 next-token logits of ``tokens`` ``[B, T]``."""
    import jax
    import jax.numpy as jnp

    act = wq = _rounder(precision)
    eps = cfg["layer_norm_epsilon"]
    f32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)
    shared = f32(params["shared"])
    B, T = tokens.shape
    x = shared["embedding"][tokens] + shared["pos_embed"][None, :T]
    causal = jnp.tril(jnp.ones((T, T), bool))[None, None]

    def layer(x, p):
        p = f32(p)
        a = p["attention"]
        qkv = jnp.einsum("blh,hcnd->blcnd", act(x),
                         wq(a["qkv"]["kernel"])) + a["qkv"]["bias"]
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / (q.shape[-1] ** 0.5)
        scores = jnp.where(causal, scores, -jnp.inf)
        ctx = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, -1), v)
        attn = jnp.einsum("bqnd,ndh->bqh", act(ctx),
                          wq(a["out"]["kernel"])) + a["out"]["bias"]
        x = _layer_norm(x + attn, p["ln_attention"]["scale"],
                        p["ln_attention"]["bias"], eps)
        h = _gelu_tanh(act(x) @ wq(p["mlp"]["wi"]["kernel"])
                       + p["mlp"]["wi"]["bias"])
        m = act(h) @ wq(p["mlp"]["wo"]["kernel"]) + p["mlp"]["wo"]["bias"]
        x = _layer_norm(x + m, p["ln_mlp"]["scale"], p["ln_mlp"]["bias"], eps)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["stages"])
    x = _layer_norm(x, shared["ln_final_scale"], shared["ln_final_bias"], eps)
    return act(x) @ wq(shared["embedding"]).T


def served_gaps(params, served: list, cfg: dict, precision: str = "float32",
                control: str = "") -> list:
    """For each ``(prompt, tokens)`` the program served, teacher-forced
    so that one flipped near-tie does not cascade: at every served
    position, how far the served token's reference logit lies below the
    reference's best.  One ``[n_tokens]`` array per request.

    With ``control`` set (a lower precision), the token judged at each
    position is the one that precision puts first, not the served one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    T = cfg["n_positions"]
    rows = np.zeros((len(served), T), np.int32)
    for i, (prompt, tokens) in enumerate(served):
        seq = list(prompt) + list(tokens[:-1])
        rows[i, :len(seq)] = seq

    with jax.default_matmul_precision("highest"):
        fn = jax.jit(lambda p, t, prec: logits_fn(p, t, cfg, prec),
                     static_argnums=2)
        out = []
        for i, (prompt, tokens) in enumerate(served):
            logits = fn(params, jnp.asarray(rows[i:i + 1]), precision)[0]
            lo = len(prompt) - 1
            at = logits[lo:lo + len(tokens)]
            judged = jnp.asarray(np.asarray(tokens, np.int32))
            if control:
                low = fn(params, jnp.asarray(rows[i:i + 1]), control)[0]
                judged = jnp.argmax(low[lo:lo + len(tokens)], axis=-1)
            gap = at.max(-1) - jnp.take_along_axis(
                at, judged[:, None], axis=-1)[:, 0]
            out.append(np.asarray(gap))
    return out


def compare(gaps: list) -> list:
    """``[(name, value, limit, ok, note)]``."""
    import numpy as np

    allg = np.concatenate(gaps)
    worst = float(allg.max())
    note = (f"{allg.size} served tokens of {len(gaps)} requests; "
            f"median gap {float(np.median(allg)):.4g}, "
            f"{int((allg > 0).sum())} tokens not the reference's first")
    return [("logit_gap", worst, LIMITS["logit_gap"],
             bool(worst <= LIMITS["logit_gap"]), note)]
