"""Plain float32 reference of ``brumby-14b-base``, one pipeline stage of
it: the full causal forward over a prompt and the tokens served after it,
in straightforward ``jax.numpy`` — no state, no cache, no batching, no
kernel, no chunked scan, no program code.

The layer, from the model's ``config.json`` and the published definition
of power retention (arXiv:2507.04239 and the model's release notes) as
the configuration file's ``assumed.recalled`` lists them (recalled: there
is no network here; * marks what the ``config`` does not carry)::

    N(x; w) = x / sqrt(mean(x^2) + eps) * w
    h = E[tokens]                                  # no position table
    for l in 1..L:                                 # every layer alike
      x = N(h; w1_l)
      q, k, v = Wq x, Wk x, Wv x                   # 40 / 8 / 8 heads of 128
      q, k = rope(N(q; wq_l)), rope(N(k; wk_l))    # a norm a head, theta 1e6
      gamma = log sigmoid(Wg x)                    # * [5120, 8], float32
      G_t = sum_{r <= t} gamma_r                   # a key/value head
      a_ts = exp(G_t - G_s) (q_t . k_s / sqrt(128))^2   for s <= t   # * p = 2
      y_t = sum_s a_ts v_s / (sum_s a_ts + eps')   # * head i reads KV i // 5
      h = h + Wo y
      x = N(h; w2_l)
      h = h + Wdown (silu(Wgate x) * (Wup x))
    logits = Whead N(h; w_final)                   # untied

The attention form, in blocks of query rows so that 2,560 positions fit:
a row block's ``[40, rows, T]`` scores are squared, gated by ``exp(G_t -
G_s)`` (the difference taken before the ``exp``; 0 above the diagonal)
and normalised by their own sum.  No softmax, no window, no convolution,
no output gate.  The fused projection arrives as the program consumes
it: ``qkv`` = the 40 query heads, then the 8 key heads, then the 8 value
heads, each flat over heads of 128; ``wi`` = gate | up.  Weights are made
by ``harness/weights.py`` from the seed in the type they are served in;
the reference widens them to float32 a layer at a time and multiplies at
``highest``.

``precision``: ``"float32"`` is the reference; ``"fp8"`` is the control
(each projection's operands rounded to e4m3 under a per-tensor scale, the
step below bf16); ``"bfloat16"`` rounds them to bf16, as the program
does; ``"state_bf16"`` is float32 but for the mixer, which runs as the
RECURRENCE — ``S_t = exp(gamma_t) S_{t-1} + phi(k_t) v_t^T`` over the
8,256 distinct products ``phi`` of a key, a normaliser beside it — with
``S`` and the normaliser rounded to bf16 after every position: what a
program that kept its state in bf16 would compute.  The gate and the
retention's sums stay float32 under every precision but the last: the
configuration states float32 for them.

``fault`` (a control planted in the reference's place, read against the
limits by ``tests/test_brumby_14b_base.py``): ``"gate_after_write"``
decays a position's own write too (``exp(G_t - G_s + gamma_s)``),
``"wrong_group"`` lets query head ``i`` read key/value head ``i % 8``,
``"no_rotary"`` leaves q and k unrotated.
"""
from __future__ import annotations

LIMITS = {
    # Over every sampled served token, how far the served token's
    # reference logit lies below the reference's best: the 99th
    # percentile of those gaps and their mean, and the mean over each
    # request's first FIRST_TOKENS alone (a state not overwritten at
    # admission, or a prefill's state built wrongly, shows there and is
    # forgotten within a few positions at the gate these weights give).
    # Read on the v5e at the cell's size (tools/readings.py and the
    # cell's runs; my chip runs, PR 43; PERF.md section 2 has both
    # readings of each): sound runs at most 0.122 (p99), 0.0049 (mean) and
    # 0.0144 (first 8) over 6 seeds, the fp8 control at least 2.29, 0.620
    # and 0.617 over 2.  Each limit is the geometric middle of its two
    # readings: a factor 4.3 from each for the 99th percentile, 11 for
    # the mean, 6.5 for the first tokens.
    "logit_gap_p99": 0.53,
    "logit_gap_mean": 0.055,
    "logit_gap_first8_mean": 0.094,
}
FIRST_TOKENS = 8
ROW_BLOCK = 512        # query rows a block of the attention form spans
HEAD_BLOCK = 1 << 15   # rows of the head widened to float32 at a time
EPS = 1e-6             # what the sum of weights is kept above (assumed)


def _sizes(cfg: dict) -> dict:
    return dict(
        L=cfg["num_hidden_layers"], H=cfg["hidden_size"],
        V=cfg["vocab_size"], n=cfg["num_attention_heads"],
        kv=cfg["num_key_value_heads"], d=cfg["head_dim"],
        M=cfg["intermediate_size"])


def param_shapes(cfg: dict) -> dict:
    z = _sizes(cfg)
    L, H, n, kv, d = z["L"], z["H"], z["n"], z["kv"], z["d"]
    f = cfg["serving"]["weights_dtype"]
    return {
        "stages": {
            "ln_attention_in": {"scale": ((L, H), f)},
            "ln_mlp_in": {"scale": ((L, H), f)},
            "linear_attention": {
                "qkv": {"kernel": ((L, H, (n + 2 * kv) * d), f)},
                "q_norm": {"scale": ((L, d), f)},
                "k_norm": {"scale": ((L, d), f)},
                "gate": {"kernel": ((L, H, kv), f)},
                "out": {"kernel": ((L, n, d, H), f)}},
            "mlp": {"wi": {"kernel": ((L, H, 2 * z["M"]), f)},
                    "wo": {"kernel": ((L, z["M"], H), f)}}},
        "shared": {"embedding": ((z["V"], H), f), "lm_head": ((z["V"], H), f),
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
    ``0..T-1``, over the whole head."""
    import jax.numpy as jnp

    T, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _retention_attention_form(q, k, v, gamma, fault: str):
    """``y`` ``[T, n, d]`` of ``q`` ``[T, n, d]`` (scaled), ``k``, ``v``
    ``[T, kv, d]`` and ``gamma`` ``[T, kv]``, a block of rows at a
    time."""
    import jax.numpy as jnp

    T, n, _ = q.shape
    kv = k.shape[1]
    if fault == "wrong_group":
        reads = jnp.arange(n) % kv
    else:
        reads = jnp.arange(n) // (n // kv)
    k, v = k[:, reads], v[:, reads]                      # [T, n, d]
    G = jnp.cumsum(gamma, 0)[:, reads]                   # [T, n]
    # a write decayed by its own gate: G_s taken before position s
    G_s = G - gamma[:, reads] if fault == "gate_after_write" else G
    out = []
    for lo in range(0, T, ROW_BLOCK):
        rows = slice(lo, min(lo + ROW_BLOCK, T))
        t = jnp.arange(T)[rows]
        seen = jnp.arange(T)[None, :] <= t[:, None]      # [rows, T]
        diff = G[rows].T[:, :, None] - G_s.T[:, None, :]   # [n, rows, T]
        w = jnp.where(seen[None], jnp.exp(jnp.where(seen[None], diff, 0.0)),
                      0.0)
        a = jnp.einsum("tnd,snd->nts", q[rows], k) ** 2 * w
        y = jnp.einsum("nts,snd->tnd", a, v)
        out.append(y / (a.sum(-1).T[..., None] + EPS))
    return jnp.concatenate(out, 0)


def _retention_recurrence(q, k, v, gamma, state_round):
    """The same as a recurrence over the distinct products of a key,
    ``S`` and its normaliser rounded by ``state_round`` after every
    position (the ``state_bf16`` control)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    T, n, d = q.shape
    kv = k.shape[1]
    i, j = np.tril_indices(d)
    c = jnp.asarray(np.where(i == j, 1.0, 2.0 ** 0.5), jnp.float32)
    phi = lambda x: x[..., i] * x[..., j] * c            # [.., d (d + 1) / 2]

    def step(carry, at):
        S, z = carry
        q_t, k_t, v_t, g_t = at
        pk, pq = phi(k_t), phi(q_t).reshape(kv, n // kv, -1)
        S = state_round(S * jnp.exp(g_t)[:, None, None]
                        + pk[:, :, None] * v_t[:, None, :])
        z = state_round(z * jnp.exp(g_t)[:, None] + pk)
        num = jnp.einsum("gpv,ghp->ghv", S, pq)
        den = jnp.einsum("gp,ghp->gh", z, pq)
        return (S, z), (num / (den[..., None] + EPS)).reshape(n, d)

    D = d * (d + 1) // 2
    _, y = jax.lax.scan(step, (jnp.zeros((kv, D, d), jnp.float32),
                               jnp.zeros((kv, D), jnp.float32)),
                        (q, k, v, gamma))
    return y


def _layer(h, p, z, cfg, precision: str, fault: str):
    """One layer on ``h`` ``[T, H]``: ``p`` = the layer's norms, mixer
    and FFN, widened here."""
    import jax
    import jax.numpy as jnp

    act = wq = _rounder(precision)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    n, kv, d, M = z["n"], z["kv"], z["d"], z["M"]
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    mix = p["linear_attention"]
    T = h.shape[0]
    x = _norm(h, p["ln_attention_in"]["scale"], eps)
    qkv = act(x) @ wq(mix["qkv"]["kernel"])
    q = qkv[:, :n * d].reshape(T, n, d)
    k = qkv[:, n * d:(n + kv) * d].reshape(T, kv, d)
    v = qkv[:, (n + kv) * d:].reshape(T, kv, d)
    q = _norm(q, mix["q_norm"]["scale"], eps)
    k = _norm(k, mix["k_norm"]["scale"], eps)
    if fault != "no_rotary":
        q, k = _rope(q, theta), _rope(k, theta)
    gamma = jax.nn.log_sigmoid(x @ mix["gate"]["kernel"])    # [T, kv]
    q = q / d ** 0.5
    if precision == "state_bf16":
        y = _retention_recurrence(q, k, v, gamma, _rounder("bfloat16"))
    else:
        y = _retention_attention_form(q, k, v, gamma, fault)
    h = h + jnp.einsum("tnd,ndh->th", act(y), wq(mix["out"]["kernel"]))
    x = _norm(h, p["ln_mlp_in"]["scale"], eps)
    gu = act(x) @ wq(p["mlp"]["wi"]["kernel"])
    return h + act(jax.nn.silu(gu[:, :M]) * gu[:, M:]) \
        @ wq(p["mlp"]["wo"]["kernel"])


_LAYER_JIT: dict = {}


def forward(params, tokens, cfg: dict, precision: str = "float32",
            fault: str = "", rows=None):
    """``[B, rows, V]`` float32 next-token logits of ``tokens`` ``[B,
    T]`` (``rows``: a slice of the positions whose logits are wanted;
    all of them where not given).  The layers run one after the other,
    the one layer function compiled once a shape."""
    import json

    import jax
    import jax.numpy as jnp

    z = _sizes(cfg)
    key = (json.dumps(cfg, sort_keys=True, default=str), precision, fault)
    if key not in _LAYER_JIT:
        _LAYER_JIT[key] = jax.jit(
            lambda h, p: _layer(h, p, z, cfg, precision, fault))
    layer = _LAYER_JIT[key]
    act = wq = _rounder(precision)
    shared = params["shared"]
    f32 = lambda a: a.astype(jnp.float32)
    head = shared["lm_head"]
    # the head a block of the vocabulary at a time: 778 M values widened
    # at once would not fit beside the weights
    amax = f32(jnp.max(jnp.abs(head)))
    blocks = [slice(lo, min(lo + HEAD_BLOCK, z["V"]))
              for lo in range(0, z["V"], HEAD_BLOCK)]
    out = []
    for row in tokens:
        h = f32(shared["embedding"][row])
        for l in range(z["L"]):
            h = layer(h, jax.tree.map(lambda a: a[l], params["stages"]))
        if rows is not None:
            h = h[rows]
        h = act(_norm(h, f32(shared["ln_final_scale"]), cfg["rms_norm_eps"]))
        out.append(jnp.concatenate(
            [h @ wq(f32(head[b]), amax).T for b in blocks], -1))
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
