"""Plain float32 reference of ``ouro-2.6b``: the full causal forward of
the looped decoder over a prompt and the tokens served after it, in
straightforward ``jax.numpy`` — no cache, no batching, no kernel, no
program code.

The block, from the model's ``config.json``, its ``modeling_ouro.py`` and
paper (arXiv 2510.25741) as the configuration file's ``assumed`` lists
them::

    h = E[tokens]                              # no position table
    for u in 1..T:                             # the SAME L layers each u
      for l in 1..L:
        x = RMS(h; g1_l); q, k, v = Wq x, Wk x, Wv x      # no biases
        q, k = rope(q, pos), rope(k, pos)      # rotate-half, theta 1e6
        h = h + RMS(Wo softmax_causal(q k^T / sqrt(d)) v; g2_l)
        x = RMS(h; g3_l)
        h = h + RMS(Wdown (silu(Wgate x) * (Wup x)); g4_l)
      h = RMS(h; g_final)                      # inside the loop
      lambda_u = sigmoid(w_exit . h + b_exit)
    p_u = lambda_u prod_{j<u} (1 - lambda_j), p_T the remainder
    u_exit = the first u whose cdf >= early_exit_threshold
    logits = W_head h_{u_exit}

Departures from the published code, each without effect on the function
in exact arithmetic: the rotary angles and every norm are computed in
float32 throughout (the published code computes them in the model's
type); the three projections arrive as one ``qkv`` matrix (columns q,
k, v; within each, heads; within a head, d) and gate and up as one ``wi``
(gate's columns, then up's): the layout the program consumes.  Weights are
made by ``harness/weights.py`` from the seed in the type they are served
in; the reference widens them to float32 a layer at a time (2.67 B
parameters at once in float32 would leave the chip 5 GB) and multiplies
at ``highest``.

``precision``: ``"float32"`` is the reference; ``"fp8"`` is the control
(each linear layer's operands rounded to e4m3 under a per-tensor scale,
the step below bf16 that would tempt a later PR); ``"bfloat16"`` rounds
them to bf16, as the program does.
"""
from __future__ import annotations

LIMITS = {
    # Over every sampled served token, how far the served token's
    # reference logit lies below the reference's best: the widest such
    # gap, and their mean.  192 layer applications of random weights
    # amplify rounding: the bf16 program flips about half of the
    # near-ties (median gap ~0.005) and its widest gap reads ~1, where
    # the post-LN 36-layer decoder's reads 0.04.  Read on the v5e at the
    # cell's size (tools/readings.py and the cell's runs; my chip runs,
    # PR 26; PERF.md section 2 has both readings of each): sound runs at
    # most 1.32 (widest) and 0.183 (mean) over 20 seeds, the fp8 control
    # at least 4.45 over 4 seeds and 2.32 over 2.  Each limit is the
    # geometric middle of its two readings: a factor 1.8 from each for
    # the widest gap, 3.6 for the mean.  The mean is the one a loop step
    # left out, or a cache indexed by the layer alone, fails at the
    # rehearsal's size (tests/test_ouro.py).
    "logit_gap": 2.4,
    "logit_gap_mean": 0.65,
}


def param_shapes(cfg: dict) -> dict:
    H, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    M, n, d = cfg["intermediate_size"], cfg["num_attention_heads"], \
        cfg["head_dim"]
    if cfg["num_key_value_heads"] != n:
        raise ValueError("the reference is written for as many key/value "
                         "heads as query heads, as published")
    f = cfg["serving"]["weights_dtype"]
    norm = lambda: {"scale": ((L, H), f)}
    return {
        "stages": {
            "ln_attention_in": norm(),                       # g1
            "attention": {"qkv": {"kernel": ((L, H, 3 * n * d), f)},
                          "out": {"kernel": ((L, n, d, H), f)}},
            "ln_attention": norm(),                          # g2
            "ln_mlp_in": norm(),                             # g3
            "mlp": {"wi": {"kernel": ((L, H, 2 * M), f)},    # gate, up
                    "wo": {"kernel": ((L, M, H), f)}},
            "ln_mlp": norm()},                               # g4
        "shared": {"embedding": ((V, H), f), "lm_head": ((V, H), f),
                   "ln_final_scale": ((H,), f),
                   "exit_gate": {"kernel": ((H,), f), "bias": ((), f)}},
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


def _rms(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """Rotate-half rotary embedding of ``[B, T, n, d]`` at positions
    ``0..T-1``."""
    import jax.numpy as jnp

    T, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def forward(params, tokens, cfg: dict, precision: str = "float32",
            loops=None):
    """``(logits [B, T, V], exit_step [B, T], exit_pdf [B, T, loops])``
    of ``tokens`` ``[B, T]``, all float32 (``exit_step`` counts from 1).
    ``loops`` overrides ``total_ut_steps`` (the tests' unlooped stack)."""
    import jax
    import jax.numpy as jnp

    act = wq = _rounder(precision)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    U = int(loops or cfg["total_ut_steps"])
    f32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)
    shared = f32(params["shared"])
    B, T = tokens.shape
    n, d = cfg["num_attention_heads"], cfg["head_dim"]
    causal = jnp.tril(jnp.ones((T, T), bool))[None, None]

    def layer(h, p):
        p = f32(p)
        x = _rms(h, p["ln_attention_in"]["scale"], eps)
        qkv = (act(x) @ wq(p["attention"]["qkv"]["kernel"])) \
            .reshape(B, T, 3, n, d)
        q, k, v = _rope(qkv[:, :, 0], theta), _rope(qkv[:, :, 1], theta), \
            qkv[:, :, 2]
        scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / (q.shape[-1] ** 0.5)
        scores = jnp.where(causal, scores, -jnp.inf)
        ctx = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, -1), v)
        a = jnp.einsum("bqnd,ndh->bqh", act(ctx),
                       wq(p["attention"]["out"]["kernel"]))
        h = h + _rms(a, p["ln_attention"]["scale"], eps)
        x = _rms(h, p["ln_mlp_in"]["scale"], eps)
        gu = act(x) @ wq(p["mlp"]["wi"]["kernel"])
        M = gu.shape[-1] // 2
        m = act(jax.nn.silu(gu[..., :M]) * gu[..., M:]) \
            @ wq(p["mlp"]["wo"]["kernel"])
        return h + _rms(m, p["ln_mlp"]["scale"], eps), None

    h = shared["embedding"][tokens]
    hs, lambdas = [], []
    for _ in range(U):
        h, _ = jax.lax.scan(layer, h, params["stages"])
        h = _rms(h, shared["ln_final_scale"], eps)
        hs.append(h)
        lambdas.append(jax.nn.sigmoid(
            h @ shared["exit_gate"]["kernel"] + shared["exit_gate"]["bias"]))
    # the exit distribution: leave at u with lambda_u of what is left
    left = jnp.ones_like(lambdas[0])
    pdf = []
    for lam in lambdas[:-1]:
        pdf.append(lam * left)
        left = left * (1.0 - lam)
    pdf.append(left)                                   # the remainder
    pdf = jnp.stack(pdf, -1)                           # [B, T, U]
    reached = jnp.cumsum(pdf, -1) >= cfg["early_exit_threshold"]
    reached = reached.at[..., -1].set(True)
    step = jnp.argmax(reached, -1)                     # first True, from 0
    h_exit = jnp.take_along_axis(jnp.stack(hs, 2),
                                 step[:, :, None, None], axis=2)[:, :, 0]
    logits = act(h_exit) @ wq(shared["lm_head"]).T
    return logits, step + 1, pdf


def logits_fn(params, tokens, cfg: dict, precision: str = "float32"):
    """``[B, T, V]`` float32 next-token logits of ``tokens`` ``[B, T]``."""
    return forward(params, tokens, cfg, precision)[0]


def served_gaps(params, served: list, cfg: dict, precision: str = "float32",
                control: str = "") -> list:
    """For each ``(prompt, tokens)`` the program served, teacher-forced
    so that one flipped near-tie does not cascade: at every served
    position, how far the served token's reference logit lies below the
    reference's best, and how many loop steps before the last the
    reference leaves the loop there.  One ``(gaps [n_tokens],
    steps_early [n_tokens])`` pair per request.

    With ``control`` set (a lower precision), the token judged at each
    position is the one that precision puts first, not the served one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    T = cfg["serving"]["max_len"]
    rows = np.zeros((len(served), T), np.int32)
    for i, (prompt, tokens) in enumerate(served):
        seq = list(prompt) + list(tokens[:-1])
        rows[i, :len(seq)] = seq

    with jax.default_matmul_precision("highest"):
        fn = jax.jit(lambda p, t, prec: forward(p, t, cfg, prec)[:2],
                     static_argnums=2)
        out = []
        for i, (prompt, tokens) in enumerate(served):
            logits, steps = fn(params, jnp.asarray(rows[i:i + 1]), precision)
            lo = len(prompt) - 1
            at = logits[0, lo:lo + len(tokens)]
            judged = jnp.asarray(np.asarray(tokens, np.int32))
            if control:
                low = fn(params, jnp.asarray(rows[i:i + 1]), control)[0][0]
                judged = jnp.argmax(low[lo:lo + len(tokens)], axis=-1)
            gap = at.max(-1) - jnp.take_along_axis(
                at, judged[:, None], axis=-1)[:, 0]
            out.append((np.asarray(gap), cfg["total_ut_steps"] - np.asarray(
                steps[0, lo:lo + len(tokens)])))
    return out


def compare(gaps: list) -> list:
    """``[(name, value, limit, ok, note)]``: the widest and the mean
    logit gap against their limits, and the most loop steps any token left early (at the
    published threshold every token leaves at the last step, which is
    all the program runs)."""
    import numpy as np

    allg = np.concatenate([g for g, _ in gaps])
    early = int(max(s.max() for _, s in gaps))
    worst, mean = float(allg.max()), float(allg.mean())
    note = (f"{allg.size} served tokens of {len(gaps)} requests; "
            f"median gap {float(np.median(allg)):.4g}, "
            f"{int((allg > 0).sum())} tokens not the reference's first")
    return [("logit_gap", worst, LIMITS["logit_gap"],
             bool(worst <= LIMITS["logit_gap"]), note),
            ("logit_gap_mean", mean, LIMITS["logit_gap_mean"],
             bool(mean <= LIMITS["logit_gap_mean"]), ""),
            ("exit_steps_early", early, 0, early == 0,
             "the reference's exit pdf selects the last loop step for "
             "every served token")]
