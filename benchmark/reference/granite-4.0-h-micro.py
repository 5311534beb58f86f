"""Plain float32 reference of ``granite-4.0-h-micro``, whole: the full
causal forward over a prompt and the tokens served after it, in
straightforward ``jax.numpy`` — the state-space layers position by
position, no chunk, no cache, no batching, no kernel, no program code.

The layers, from the model's ``config.json`` and the HF
``GraniteMoeHybrid`` / Bamba modeling code as the configuration file's
``assumed.recalled`` lists them (recalled: there is no network here)::

    N(x; w) = x / sqrt(mean(x^2) + 1e-5) * w
    h = 12 E[tokens]                               # no positions anywhere
    for l in 1..40:
      u = N(h; w1_l)
      if layer_types[l] == "mamba":
        [z | xBC | dt] = u W_in                    # [4096 | 4352 | 64]
        xBC_t = silu(b_c + sum_j w_c[j] xBC_{t-3+j})    # 4 taps, causal
        [x | B | C] = xBC                          # [4096 | 128 | 128]
        Delta_t = softplus(dt_t + dt_bias)         # [64], float32
        a_t = exp(-exp(A_log) Delta_t)             # [64]
        S_t[h] = a_t[h] S_{t-1}[h] + (Delta_t[h] x_t[h]) (x) B_t   # [64, 128]
        y_t[h] = S_t[h] C_t + D[h] x_t[h]
        g = y * silu(z)                            # the gate, THEN the norm
        o = g / sqrt(mean(g^2) + 1e-5) * w_n       # over all 4096 (1 group)
        h = h + 0.22 (o W_out)
      else:                                        # layers 5, 15, 25, 35
        q, k, v = u Wq, u Wk, u Wv                 # 32 / 8 / 8 heads of 64
        p = softmax_causal(0.015625 q k^T)         # head i reads KV i // 4
        h = h + 0.22 ((p v) Wo)
      [g | v] = N(h; w2_l) W_i                     # [8192 | 8192]
      h = h + 0.22 ((silu(g) * v) W_o)
    logits = N(h; w_final) E^T / 8                 # tied

The state here is ``[heads, head size, state size]`` a layer and lives
for one sequence (the program keeps a group's heads as one ``[state
size, heads x head size]`` matrix a slot).  The attention is computed a
block of query rows at a time so that 3,072 positions fit.  The
projections arrive as the program consumes them: ``in_proj`` = z | x | B
| C | dt; ``qkv`` = the query heads, then the key heads, then the value
heads, flat; ``wi`` = gate | up.  Weights are made by
``harness/weights.py`` from the seed in the type they are served in; the
reference widens them to float32 a layer at a time and multiplies at
``highest``.

``precision``: ``"float32"`` is the reference; ``"fp8"`` is the control
(each projection's operands rounded to e4m3 under a per-tensor scale, the
step below bf16); ``"bfloat16"`` rounds them to bf16, as the program
does; ``"state_bf16"`` is float32 but for the state ``S``, rounded to
bf16 after every position: what a program that kept its state in bf16
would compute.  ``Delta``, the decay, the state, the gate and the norms
stay float32 under every precision but the last: the configuration
states float32 for them.

``fault`` (a control planted in the reference's place, read against the
limits by ``tests/test_granite_4_0_h_micro.py``): ``"no_skip"`` drops ``D
x``, ``"norm_before_gate"`` norms ``y`` and gates after, ``"no_softplus"``
takes ``Delta = dt + dt_bias``, ``"residual_one"`` adds the sub-blocks'
outputs unscaled, ``"head_scale"`` scales the scores by ``head_dim **
-0.5``, ``"rotary"`` rotates q and k at ``rope_theta``,
``"no_embedding_multiplier"`` embeds without the 12.
"""
from __future__ import annotations

LIMITS = {
    # Over every sampled served token, how far the served token's
    # reference logit lies below the reference's best: the 99th
    # percentile of those gaps and their mean, and the mean over each
    # request's first FIRST_TOKENS alone (a state or a tail not
    # overwritten at admission, or a prefill's state built wrongly, shows
    # there and is forgotten within a few positions at the decay most
    # heads have under these weights).  Read on the v5e at the cell's size
    # (tools/readings.py, then tools/limits_from_readings.py; my chip
    # runs, PR 48; PERF.md section 2 has both readings of each): sound
    # runs at most 0.0426 (p99), 0.00264 (mean) and 0.0060 (first 8) over
    # the 4 seeds the limits were set from (the 7 runs after them
    # 0.038-0.047, 0.0024-0.0027, 0.0003-0.0051), the fp8 control at
    # least 0.866, 0.317 and 0.283 over 2.  Each limit is the geometric
    # middle of its two readings: a factor 4.5 from each for the 99th
    # percentile, 11 for the mean, 6.9 for the first tokens.
    "logit_gap_p99": 0.19,
    "logit_gap_mean": 0.029,
    "logit_gap_first8_mean": 0.041,
}
FIRST_TOKENS = 8
ROW_BLOCK = 512        # query rows a block of the attention spans
HEAD_BLOCK = 1 << 15   # rows of the head widened to float32 at a time


def _sizes(cfg: dict) -> dict:
    heads = cfg["mamba_n_heads"]
    return dict(
        L=cfg["num_hidden_layers"], H=cfg["hidden_size"],
        V=cfg["vocab_size"], n=cfg["num_attention_heads"],
        kv=cfg["num_key_value_heads"],
        d=cfg["hidden_size"] // cfg["num_attention_heads"],
        M=cfg["intermediate_size"], heads=heads, P=cfg["mamba_d_head"],
        N=cfg["mamba_d_state"], G=cfg["mamba_n_groups"],
        taps=cfg["mamba_d_conv"], I=heads * cfg["mamba_d_head"],
        Lm=cfg["layer_types"].count("mamba"),
        La=cfg["layer_types"].count("attention"))


def param_shapes(cfg: dict) -> dict:
    z = _sizes(cfg)
    L, H, n, kv, d = z["L"], z["H"], z["n"], z["kv"], z["d"]
    Lm, La, I, heads = z["Lm"], z["La"], z["I"], z["heads"]
    C = I + 2 * z["G"] * z["N"]
    f = cfg["serving"]["weights_dtype"]
    return {
        "stages": {
            "ln_attention_in": {"scale": ((L, H), f)},
            "ln_mlp_in": {"scale": ((L, H), f)},
            "attention": {
                "qkv": {"kernel": ((La, H, (n + 2 * kv) * d), f)},
                "out": {"kernel": ((La, n, d, H), f)}},
            "linear_attention": {
                "in_proj": {"kernel": ((Lm, H, I + C + heads), f)},
                "conv": {"kernel": ((Lm, z["taps"], C), f),
                         "bias": ((Lm, C), f)},
                "A_log": ((Lm, heads), f),
                "D": ((Lm, heads), f),
                "dt_bias": ((Lm, heads), f),
                "norm": {"scale": ((Lm, I), f)},
                "out": {"kernel": ((Lm, I, H), f)}},
            "mlp": {"wi": {"kernel": ((L, H, 2 * z["M"]), f)},
                    "wo": {"kernel": ((L, z["M"], H), f)}}},
        "shared": {"embedding": ((z["V"], H), f),
                   "ln_final_scale": ((H,), f)},
    }


def _rounder(precision: str):
    """What rounds a projection's operands: ``round(x, amax=None)``,
    ``amax`` the largest magnitude of the tensor ``x`` is a part of
    (its own where not given)."""
    import jax.numpy as jnp

    if precision in ("float32", "state_bf16"):
        return lambda x, amax=None: x
    if precision == "bfloat16":
        # not a pair of converts: on the TPU the compiler keeps the
        # excess precision and drops such a pair
        import jax

        return lambda x, amax=None: jax.lax.reduce_precision(
            x, exponent_bits=8, mantissa_bits=7)
    if precision == "fp8":
        def q(x, amax=None):
            # e4m3 under a per-tensor scale to its largest finite value
            amax = jnp.max(jnp.abs(x)) if amax is None else amax
            s = amax / float(jnp.finfo(jnp.float8_e4m3fn).max)
            s = jnp.where(s == 0, 1.0, s)
            return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
        return q
    raise ValueError(f"unknown precision {precision!r}")


def _norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotate-half rotary embedding of ``[T, n, d]`` at positions
    ``0..T-1`` (the ``rotary`` fault alone: the model has none)."""
    import jax.numpy as jnp

    T, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _mamba(u, mix, z, cfg, act, wq, precision: str, fault: str):
    """The state-space mixer's output (before the residual's multiplier)
    on normed rows ``u`` ``[T, H]``, position by position."""
    import jax
    import jax.numpy as jnp

    heads, P, N, G, I = z["heads"], z["P"], z["N"], z["G"], z["I"]
    taps, eps = z["taps"], cfg["rms_norm_eps"]
    T = u.shape[0]
    proj = act(u) @ wq(mix["in_proj"]["kernel"])
    zg, xbc, dt = proj[:, :I], proj[:, I:I + I + 2 * G * N], \
        proj[:, I + I + 2 * G * N:]
    # depthwise and causal: position t sees t-3 .. t, the last tap its own
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1])), xbc], 0)
    conv = mix["conv"]["bias"] + sum(
        mix["conv"]["kernel"][j] * padded[j:j + T] for j in range(taps))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :I].reshape(T, heads, P)
    B = xbc[:, I:I + G * N].reshape(T, G, N)
    C = xbc[:, I + G * N:].reshape(T, G, N)
    # every head reads its group's B and C
    of = jnp.arange(heads) // (heads // G)
    B, C = B[:, of], C[:, of]                            # [T, heads, N]
    step = dt + mix["dt_bias"]
    delta = step if fault == "no_softplus" else jax.nn.softplus(step)
    decay = jnp.exp(-jnp.exp(mix["A_log"]) * delta)      # [T, heads]
    narrow = _rounder("bfloat16") if precision == "state_bf16" \
        else (lambda s: s)

    def one(S, at):
        x_t, B_t, C_t, d_t, a_t = at
        S = narrow(a_t[:, None, None] * S
                   + (d_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return S, (S * C_t[:, None, :]).sum(-1)          # [heads, P]

    _, y = jax.lax.scan(one, jnp.zeros((heads, P, N), jnp.float32),
                        (x, B, C, delta, decay))
    if fault != "no_skip":
        y = y + mix["D"][:, None] * x
    y, gate = y.reshape(T, G, I // G), jax.nn.silu(zg).reshape(T, G, I // G)
    w = mix["norm"]["scale"].reshape(G, I // G)
    rms = lambda t: t / jnp.sqrt(jnp.mean(t * t, -1, keepdims=True) + eps)
    if fault == "norm_before_gate":
        o = rms(y) * w * gate
    else:
        o = rms(y * gate) * w
    return act(o.reshape(T, I)) @ wq(mix["out"]["kernel"])


def _attention(u, att, z, cfg, act, wq, fault: str):
    """Causal grouped-query attention without positions on normed rows
    ``u`` ``[T, H]``, a block of query rows at a time."""
    import jax
    import jax.numpy as jnp

    n, kv, d = z["n"], z["kv"], z["d"]
    T = u.shape[0]
    qkv = act(u) @ wq(att["qkv"]["kernel"])
    q = qkv[:, :n * d].reshape(T, n, d)
    k = qkv[:, n * d:(n + kv) * d].reshape(T, kv, d)
    v = qkv[:, (n + kv) * d:].reshape(T, kv, d)
    if fault == "rotary":
        q, k = (_rope(t, float(cfg["rope_theta"])) for t in (q, k))
    scale = d ** -0.5 if fault == "head_scale" \
        else cfg["attention_multiplier"]
    reads = jnp.arange(n) // (n // kv)
    k, v = k[:, reads], v[:, reads]                      # [T, n, d]
    out = []
    for lo in range(0, T, ROW_BLOCK):
        rows = slice(lo, min(lo + ROW_BLOCK, T))
        seen = jnp.arange(T)[None, :] <= jnp.arange(T)[rows][:, None]
        s = jnp.einsum("tnd,snd->nts", q[rows], k) * scale
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("nts,snd->tnd", p, v))
    y = jnp.concatenate(out, 0)
    return jnp.einsum("tnd,ndh->th", act(y), wq(att["out"]["kernel"]))


def _layer(h, p, kind: str, z, cfg, precision: str, fault: str):
    """One layer on ``h`` ``[T, H]``: ``p`` = the layer's norms, its
    mixer (of ``kind``) and FFN, widened here."""
    import jax
    import jax.numpy as jnp

    act = wq = _rounder(precision)
    eps, M = cfg["rms_norm_eps"], z["M"]
    m = 1.0 if fault == "residual_one" else cfg["residual_multiplier"]
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    u = _norm(h, p["ln_attention_in"]["scale"], eps)
    if kind == "mamba":
        h = h + m * _mamba(u, p["mixer"], z, cfg, act, wq, precision, fault)
    else:
        h = h + m * _attention(u, p["mixer"], z, cfg, act, wq, fault)
    u = _norm(h, p["ln_mlp_in"]["scale"], eps)
    gu = act(u) @ wq(p["mlp"]["wi"]["kernel"])
    return h + m * (act(jax.nn.silu(gu[:, :M]) * gu[:, M:])
                    @ wq(p["mlp"]["wo"]["kernel"]))


_LAYER_JIT: dict = {}
_MIXERS = {"mamba": "linear_attention", "attention": "attention"}


def forward(params, tokens, cfg: dict, precision: str = "float32",
            fault: str = "", rows=None):
    """``[B, rows, V]`` float32 next-token logits of ``tokens`` ``[B,
    T]`` (``rows``: a slice of the positions whose logits are wanted;
    all of them where not given).  The layers run one after the other,
    each kind's layer function compiled once a shape."""
    import json

    import jax
    import jax.numpy as jnp

    z = _sizes(cfg)
    key = (json.dumps(cfg, sort_keys=True, default=str), precision, fault)
    if key not in _LAYER_JIT:
        _LAYER_JIT[key] = {
            kind: jax.jit(lambda h, p, kind=kind: _layer(
                h, p, kind, z, cfg, precision, fault))
            for kind in _MIXERS}
    layer = _LAYER_JIT[key]
    act = wq = _rounder(precision)
    shared, stages = params["shared"], params["stages"]
    f32 = lambda a: a.astype(jnp.float32)
    head = shared["embedding"]
    emb = 1.0 if fault == "no_embedding_multiplier" \
        else cfg["embedding_multiplier"]
    # the head a block of the vocabulary at a time
    amax = f32(jnp.max(jnp.abs(head)))
    blocks = [slice(lo, min(lo + HEAD_BLOCK, z["V"]))
              for lo in range(0, z["V"], HEAD_BLOCK)]
    out = []
    for row in tokens:
        h = emb * f32(head[row])
        seen = {kind: 0 for kind in _MIXERS}
        for l, kind in enumerate(cfg["layer_types"]):
            nth, seen[kind] = seen[kind], seen[kind] + 1
            p = {name: jax.tree.map(lambda a: a[l], stages[name])
                 for name in ("ln_attention_in", "ln_mlp_in", "mlp")}
            p["mixer"] = jax.tree.map(lambda a: a[nth],
                                      stages[_MIXERS[kind]])
            h = layer[kind](h, p)
        if rows is not None:
            h = h[rows]
        h = act(_norm(h, f32(shared["ln_final_scale"]), cfg["rms_norm_eps"]))
        out.append(jnp.concatenate(
            [h @ wq(f32(head[b]), amax).T for b in blocks], -1)
            / cfg["logits_scaling"])
    return jnp.stack(out)


def logits_fn(params, tokens, cfg: dict, precision: str = "float32"):
    return forward(params, tokens, cfg, precision)


def served_gaps(params, served: list, cfg: dict, precision: str = "float32",
                control: str = "", fault: str = "") -> list:
    """For each ``(prompt, tokens)`` the program served, teacher-forced
    so that one flipped near-tie does not cascade: at every served
    position, how far the served token's reference logit lies below the
    reference's best.  One ``gaps [n_tokens]`` array per request.

    With ``control`` set (a lower precision) or ``fault``, the token
    judged at each position is the one that precision, or the reference
    with that fault, puts first, not the served one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    T = cfg["serving"]["max_len"]
    # the logits of as many rows as a request can generate, from the
    # prompt's last position on: one shape for every request
    span = T - cfg["serving"]["prefill_len"]
    out = []
    with jax.default_matmul_precision("highest"):
        for prompt, tokens in served:
            seq = list(prompt) + list(tokens[:-1])
            row = np.zeros((1, T), np.int32)
            row[0, :len(seq)] = seq
            row = jnp.asarray(row)
            at = slice(len(prompt) - 1, len(prompt) - 1 + span)
            ref = forward(params, row, cfg, precision,
                          rows=at)[0, :len(tokens)]
            judged = jnp.asarray(np.asarray(tokens, np.int32))
            if control or fault:
                low = forward(params, row, cfg, control or precision, fault,
                              rows=at)[0, :len(tokens)]
                judged = jnp.argmax(low, axis=-1)
            gap = ref.max(-1) - jnp.take_along_axis(
                ref, judged[:, None], axis=-1)[:, 0]
            out.append(np.asarray(gap))
    return out


def compare(gaps: list) -> list:
    """``[(name, value, limit, ok, note)]``: the 99th percentile and the
    mean of the logit gaps, and the mean over each request's first
    ``FIRST_TOKENS`` alone, against their limits; the widest gap and the
    other quantiles in the note."""
    import numpy as np

    allg = np.concatenate(gaps)
    first = np.concatenate([g[:FIRST_TOKENS] for g in gaps])
    q = {p: float(np.percentile(allg, p)) for p in (50, 90, 95, 99.9)}
    note = (f"{allg.size} served tokens of {len(gaps)} requests; "
            f"{int((allg > 0).sum())} tokens not the reference's first; "
            f"gap p50 {q[50]:.4g} p90 {q[90]:.4g} p95 {q[95]:.4g} "
            f"p99.9 {q[99.9]:.4g} widest {float(allg.max()):.4g}")
    first_note = (f"{first.size} tokens; {int((first > 0).sum())} not the "
                  f"reference's first; widest {float(first.max()):.4g}")
    values = {"logit_gap_p99": (float(np.percentile(allg, 99)), note),
              "logit_gap_mean": (float(allg.mean()), ""),
              "logit_gap_first8_mean": (float(first.mean()), first_note)}
    return [(name, value, LIMITS[name], bool(value <= LIMITS[name]), text)
            for name, (value, text) in values.items()]
