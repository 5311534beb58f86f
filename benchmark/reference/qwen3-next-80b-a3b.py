"""Plain float32 reference of ``qwen3-next-80b-a3b``, one chip's share:
the full causal forward over a prompt and the tokens served after it, in
straightforward ``jax.numpy`` — no cache, no batching, no kernel, no
chunked scan, no sort, no program code.

The layer, from the model's ``config.json`` and its
``modeling_qwen3_next.py`` as the configuration file's ``assumed`` lists
them (recalled: there is no network here)::

    N(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)        # zero-centred
    h = E[tokens]                                        # no position table
    for l in 1..L:
      x = N(h; w1_l)
      if l % full_attention_interval == 0:               # full attention
        [q | gate] per head = Wq x;  k, v = Wk x, Wv x   # 16 / 2 heads of 256
        q, k = N(q; wq_l), N(k; wk_l)                    # per head, over 256
        q, k = rope(q), rope(k)                          # first 64 dims only
        a = softmax_causal(q k^T / sqrt(256)) v          # head i reads KV i // 8
        h = h + Wo (a * sigmoid(gate))
      else:                                              # gated DeltaNet
        [q, k, v, z] = Wqkvz x;  [b, a] = Wba x
        [q, k, v] = silu(causal_conv4([q | k | v]))      # depthwise, no bias
        beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
        q = q / |q| / sqrt(128);  k = k / |k|            # a key head serves 2
        for t:  S = exp(g_t) S;  d = beta_t (v_t - S^T k_t)
                S = S + k_t d^T;  o_t = S^T q_t          # S [128, 128] a head
        h = h + Wout (rms(o) * w * silu(z))              # per head, plain w
      x = N(h; w2_l)
      p = softmax(Wg x) over ALL 512;  top 10, renormalised to sum 1
      h = h + sum_{e chosen AND held} p_e down_e(silu(gate_e x) * up_e x)
            + sigmoid(ws . x) * SharedExpert(x)
    logits = Whead N(h; w_final)                         # untied, the slice

The share: experts ``0 .. num_experts - 1`` of ``num_experts_published``
are held here; the router keeps its published width and its 10 a token,
renormalised over the 10 wherever they live, and what the absent experts
would have added is left out — that partial result is what goes on to
the next layer, here as in the program.  The vocabulary is the slice
``0 .. vocab_size - 1``.

The fused projections arrive as the program consumes them: ``qkv`` = each
query head's q then its gate, then the key heads, then the value heads;
``qkvz`` = q | k | v | z, each flat over heads; ``ba`` = b | a; an
expert's ``wi`` = gate | up.  Weights are made by ``harness/weights.py``
from the seed in the type they are served in; the reference widens them
to float32 a layer at a time and multiplies at ``highest``.

``precision``: ``"float32"`` is the reference; ``"fp8"`` is the control
(each projection's and expert's operands rounded to e4m3 under a
per-tensor scale, the step below bf16); ``"bfloat16"`` rounds them to
bf16, as the program does; ``"state_bf16"`` is float32 but for the
recurrent state, rounded to bf16 after every position.  The router, the
gates and the recurrence stay float32 under every precision but the last:
the configuration states float32 for them.
"""
from __future__ import annotations

LIMITS = {
    # Over every sampled served token, how far the served token's
    # reference logit lies below the reference's best: the 99th
    # percentile of those gaps and their mean.  Read on the v5e at the
    # cell's size (tools/readings.py and the cell's runs; my chip runs,
    # PR 32; PERF.md section 2 has both readings of each): sound runs at
    # most 0.92 (p99) and 0.077 (mean), the fp8 control at least 2.82 and
    # 0.982.  Each limit is the geometric middle of its two readings: a
    # factor 1.8 from each for the 99th percentile, 3.6 for the mean.
    # The WIDEST gap, which ouro-2.6b's cell limits, carries no limit
    # here and rides in the first row's note: a tenth expert that a
    # near-tie among 512 router scores flips under bf16 moves one row's
    # logits as far as fp8 moves every row's, so the widest gap of a
    # sound run (1.3 to 3.4 of ~3,000 tokens) and of the control (3.8 to
    # 5.1) nearly meet, while nine tokens in ten of the control are not
    # the reference's first and three in ten of a sound run.
    "logit_gap_p99": 1.6,
    "logit_gap_mean": 0.27,
    # The mean over the FIRST tokens of each sampled request alone
    # (FIRST_TOKENS: the one the prefill gave and the decode steps after
    # it).  With weights as harness/weights.py draws them every head
    # forgets half its state a position, so what admission or a reused
    # slot spoils shows in a request's first few tokens and nowhere else:
    # a few dozen of ~3,000, too few to move the two numbers above (a
    # state not overwritten at admission read 1.39 and 0.15 there, and
    # 1.33 here).  Sound runs at most 0.104 over 11 seeds, the fp8
    # control at least 0.77 over 4: the geometric middle, a factor 2.7
    # from each.
    "logit_gap_first8_mean": 0.28,
}
FIRST_TOKENS = 8


def _sizes(cfg: dict) -> dict:
    L, every = cfg["num_hidden_layers"], cfg["full_attention_interval"]
    if L % every:
        raise ValueError("the reference walks whole periods of "
                         "full_attention_interval layers")
    kh, vh = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return dict(
        L=L, every=every, periods=L // every, H=cfg["hidden_size"],
        V=cfg["vocab_size"], n=cfg["num_attention_heads"],
        kv=cfg["num_key_value_heads"], d=cfg["head_dim"], kh=kh, vh=vh,
        dk=dk, dv=dv, taps=cfg["linear_conv_kernel_dim"],
        conv=2 * kh * dk + vh * dv, held=cfg["num_experts"],
        router=cfg["num_experts_published"], top=cfg["num_experts_per_tok"],
        M=cfg["moe_intermediate_size"],
        Ms=cfg["shared_expert_intermediate_size"])


def param_shapes(cfg: dict) -> dict:
    z = _sizes(cfg)
    L, H, Lf = z["L"], z["H"], z["periods"]
    Ll = L - Lf
    f = cfg["serving"]["weights_dtype"]
    inner = z["vh"] * z["dv"]
    return {
        "stages": {
            "ln_attention_in": {"weight": ((L, H), f)},
            "ln_mlp_in": {"weight": ((L, H), f)},
            "attention": {
                "qkv": {"kernel": ((Lf, H, 2 * z["n"] * z["d"]
                                    + 2 * z["kv"] * z["d"]), f)},
                "q_norm": {"weight": ((Lf, z["d"]), f)},
                "k_norm": {"weight": ((Lf, z["d"]), f)},
                "out": {"kernel": ((Lf, z["n"], z["d"], H), f)}},
            "linear_attention": {
                "qkvz": {"kernel": ((Ll, H, z["conv"] + inner), f)},
                "ba": {"kernel": ((Ll, H, 2 * z["vh"]), f)},
                "conv": {"kernel": ((Ll, z["taps"], z["conv"]), f)},
                "A_log": ((Ll, z["vh"]), f),
                "dt_bias": ((Ll, z["vh"]), f),
                "norm": {"scale": ((Ll, z["dv"]), f)},
                "out": {"kernel": ((Ll, inner, H), f)}},
            "moe": {
                "router": {"kernel": ((L, H, z["router"]), f)},
                "experts": {f"layer_{l:02d}": {
                    "wi": ((z["held"], H, 2 * z["M"]), f),
                    "wo": ((z["held"], z["M"], H), f)} for l in range(L)},
                "shared": {"wi": {"kernel": ((L, H, 2 * z["Ms"]), f)},
                           "wo": {"kernel": ((L, z["Ms"], H), f)}},
                "shared_gate": {"kernel": ((L, H), f)}}},
        "shared": {"embedding": ((z["V"], H), f), "lm_head": ((z["V"], H), f),
                   "ln_final_weight": ((H,), f)},
    }


def _rounder(precision: str):
    """What rounds a projection's or an expert's operands."""
    import jax.numpy as jnp

    if precision in ("float32", "state_bf16"):
        return lambda x: x
    if precision == "bfloat16":
        # not a pair of converts: on the TPU the compiler keeps the
        # excess precision and drops such a pair (read there as a
        # state_bf16 control with not one token moved)
        import jax

        return lambda x: jax.lax.reduce_precision(x, exponent_bits=8,
                                                  mantissa_bits=7)
    if precision == "fp8":
        def q(x):
            # e4m3 under a per-tensor scale to its largest finite value
            s = jnp.max(jnp.abs(x)) / float(jnp.finfo(jnp.float8_e4m3fn).max)
            s = jnp.where(s == 0, 1.0, s)
            return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
        return q
    raise ValueError(f"unknown precision {precision!r}")


def _norm(x, w, eps):
    """The zero-centred RMSNorm."""
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, theta, rotary):
    """Rotate-half rotary embedding of the first ``rotary`` dimensions of
    ``[B, T, n, d]`` at positions ``0..T-1``; the rest pass through."""
    import jax.numpy as jnp

    T = x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32)
                          / rotary)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    r, rest = x[..., :rotary], x[..., rotary:]
    rot = jnp.concatenate([-r[..., rotary // 2:], r[..., :rotary // 2]], -1)
    return jnp.concatenate([r * cos + rot * sin, rest], -1)


def _moe(x, p, z, act, wq, first_expert=0):
    """The routed block on ``x`` ``[B, T, H]``: the held experts, one
    after the other over every row, each weighted by what the router
    gave it there (0 where it was not among the row's 10), and the
    shared expert."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(x @ p["router"]["kernel"], -1)   # all experts
    top_w, top_e = jax.lax.top_k(probs, z["top"])
    top_w = top_w / top_w.sum(-1, keepdims=True)            # norm_topk_prob
    M = z["M"]

    def one(y, e):
        wi, wo, index = e
        w = jnp.where(top_e == index, top_w, 0.0).sum(-1)   # [B, T]
        gu = act(x) @ wq(wi)
        out = act(jax.nn.silu(gu[..., :M]) * gu[..., M:]) @ wq(wo)
        return y + w[..., None] * out, None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (p["experts"]["wi"], p["experts"]["wo"],
         first_expert + jnp.arange(p["experts"]["wi"].shape[0])))
    Ms = z["Ms"]
    gu = act(x) @ wq(p["shared"]["wi"]["kernel"])
    shared = act(jax.nn.silu(gu[..., :Ms]) * gu[..., Ms:]) \
        @ wq(p["shared"]["wo"]["kernel"])
    gate = jax.nn.sigmoid(x @ p["shared_gate"]["kernel"])
    return y + gate[..., None] * shared


def _full_attention(x, p, z, cfg, act, wq):
    import jax
    import jax.numpy as jnp

    B, T, _ = x.shape
    n, kv, d = z["n"], z["kv"], z["d"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    qkv = act(x) @ wq(p["qkv"]["kernel"])
    qg = qkv[..., :2 * n * d].reshape(B, T, n, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = qkv[..., 2 * n * d:2 * n * d + kv * d].reshape(B, T, kv, d)
    v = qkv[..., 2 * n * d + kv * d:].reshape(B, T, kv, d)
    rotary = int(d * cfg["partial_rotary_factor"])
    q = _rope(_norm(q, p["q_norm"]["weight"], eps), theta, rotary)
    k = _rope(_norm(k, p["k_norm"]["weight"], eps), theta, rotary)
    k, v = (jnp.repeat(t, n // kv, axis=2) for t in (k, v))
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / d ** 0.5
    causal = jnp.tril(jnp.ones((T, T), bool))[None, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    ctx = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, -1), v)
    return jnp.einsum("bqnd,ndh->bqh", act(ctx * jax.nn.sigmoid(gate)),
                      wq(p["out"]["kernel"]))


def _gated_delta_net(x, p, z, cfg, act, wq, state_round):
    import jax
    import jax.numpy as jnp

    B, T, _ = x.shape
    kh, vh, dk, dv, taps = z["kh"], z["vh"], z["dk"], z["dv"], z["taps"]
    mixed = act(x) @ wq(p["qkvz"]["kernel"])
    qkv, zg = mixed[..., :z["conv"]], mixed[..., z["conv"]:]
    ba = x @ p["ba"]["kernel"]
    beta = jax.nn.sigmoid(ba[..., :vh])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., vh:] + p["dt_bias"])
    # depthwise causal convolution: position t sees t - taps + 1 .. t
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, j:j + T] * p["conv"]["kernel"][j]
                          for j in range(taps)))
    q = qkv[..., :kh * dk].reshape(B, T, kh, dk)
    k = qkv[..., kh * dk:2 * kh * dk].reshape(B, T, kh, dk)
    v = qkv[..., 2 * kh * dk:].reshape(B, T, vh, dv)
    unit = lambda t: t / jnp.sqrt((t * t).sum(-1, keepdims=True) + 1e-6)
    q, k = unit(q) / dk ** 0.5, unit(k)
    q, k = (jnp.repeat(t, vh // kh, axis=2) for t in (q, k))

    def step(S, at):
        q_t, k_t, v_t, g_t, b_t = at
        S = S * jnp.exp(g_t)[..., None, None]
        d = (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t)) * b_t[..., None]
        S = state_round(S + jnp.einsum("bhk,bhv->bhkv", k_t, d))
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    time_first = lambda t: jnp.moveaxis(t, 1, 0)
    _, o = jax.lax.scan(step, jnp.zeros((B, vh, dk, dv), jnp.float32),
                        tuple(map(time_first, (q, k, v, g, beta))))
    o = jnp.moveaxis(o, 0, 1)                              # [B, T, vh, dv]
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True)
                     + cfg["rms_norm_eps"]) * p["norm"]["scale"]
    o = o * jax.nn.silu(zg.reshape(B, T, vh, dv))
    return act(o.reshape(B, T, vh * dv)) @ wq(p["out"]["kernel"])


def _layer(kind: str, h, p, z, cfg, precision: str, first_expert: int):
    """One layer: ``p`` = the layer's norms, its mixer (``kind``:
    ``"full"`` or ``"linear"``) and its routed block, widened here."""
    import jax
    import jax.numpy as jnp

    act = wq = _rounder(precision)
    state_round = _rounder("bfloat16" if precision == "state_bf16"
                           else "float32")
    eps = cfg["rms_norm_eps"]
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    x = _norm(h, p["ln_attention_in"]["weight"], eps)
    if kind == "full":
        h = h + _full_attention(x, p["mixer"], z, cfg, act, wq)
    else:
        h = h + _gated_delta_net(x, p["mixer"], z, cfg, act, wq,
                                 state_round)
    return h + _moe(_norm(h, p["ln_mlp_in"]["weight"], eps), p["moe"], z,
                    act, wq, first_expert)


def _layer_params(stages, z, l: int):
    """``(kind, parameters)`` of layer ``l`` out of the program's tree:
    the mixers are stacked over the layers of their kind, the experts
    are arrays of their own a layer, everything else is stacked over
    all layers."""
    import jax

    full = (l + 1) % z["every"] == 0
    nth = (l + 1) // z["every"] - 1 if full else l - l // z["every"]
    mixer = stages["attention" if full else "linear_attention"]
    moe = stages["moe"]
    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
    return "full" if full else "linear", {
        "ln_attention_in": at(stages["ln_attention_in"], l),
        "ln_mlp_in": at(stages["ln_mlp_in"], l),
        "mixer": at(mixer, nth),
        "moe": {"experts": moe["experts"][f"layer_{l:02d}"],
                **at({k: v for k, v in moe.items() if k != "experts"}, l)}}


_LAYER_JIT: dict = {}


def forward(params, tokens, cfg: dict, precision: str = "float32",
            first_expert: int = 0):
    """``[B, T, V]`` float32 next-token logits of ``tokens`` ``[B, T]``.
    ``first_expert``: the index, among the router's outputs, of the first
    expert held (0: the share the configuration states).  The layers run
    one after the other, each kind's function compiled once."""
    import json

    import jax
    import jax.numpy as jnp

    z = _sizes(cfg)
    key = (json.dumps(cfg, sort_keys=True, default=str), precision,
           first_expert)
    if key not in _LAYER_JIT:
        _LAYER_JIT[key] = jax.jit(
            lambda kind, h, p: _layer(kind, h, p, z, cfg, precision,
                                      first_expert), static_argnums=0)
    layer = _LAYER_JIT[key]
    act = wq = _rounder(precision)
    shared = jax.tree.map(lambda a: a.astype(jnp.float32), params["shared"])
    h = shared["embedding"][tokens]
    for l in range(z["L"]):
        kind, p = _layer_params(params["stages"], z, l)
        h = layer(kind, h, p)
    h = _norm(h, shared["ln_final_weight"], cfg["rms_norm_eps"])
    return act(h) @ wq(shared["lm_head"]).T


def logits_fn(params, tokens, cfg: dict, precision: str = "float32"):
    return forward(params, tokens, cfg, precision)


def served_gaps(params, served: list, cfg: dict, precision: str = "float32",
                control: str = "") -> list:
    """For each ``(prompt, tokens)`` the program served, teacher-forced
    so that one flipped near-tie does not cascade: at every served
    position, how far the served token's reference logit lies below the
    reference's best.  One ``gaps [n_tokens]`` array per request.

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
        fn = lambda p, t, prec: forward(p, t, cfg, prec)
        out = []
        for i, (prompt, tokens) in enumerate(served):
            row = jnp.asarray(rows[i:i + 1])
            lo = len(prompt) - 1
            at = fn(params, row, precision)[0, lo:lo + len(tokens)]
            judged = jnp.asarray(np.asarray(tokens, np.int32))
            if control:
                low = fn(params, row, control)[0]
                judged = jnp.argmax(low[lo:lo + len(tokens)], axis=-1)
            gap = at.max(-1) - jnp.take_along_axis(
                at, judged[:, None], axis=-1)[:, 0]
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
