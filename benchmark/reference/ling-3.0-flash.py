"""Plain float32 reference of ``ling-3.0-flash``, one chip's share: the
full causal forward over a prompt and the tokens served after it, in
straightforward ``jax.numpy`` — no cache, no batching, no kernel, no
chunked scan, no absorbed products, no sort, no program code.  The
recurrence runs position by position; latent attention is in the
EXPANDED form at every position over the whole prefix.

The layer, from the model's ``config.json`` and the published
descriptions of Kimi Delta Attention, latent attention and
group-limited routing, as the configuration file's ``assumed`` lists
them (recalled: there is no network here)::

    N(x; w) = x / sqrt(mean(x^2) + eps) * w
    h = E[tokens]                                        # no position table
    for l in 0..L-1:
      x = N(h; w1_l)
      if (l + 1) % layer_group_size == 0:                # latent attention
        q = Wq x                       # 32 heads of [q_nope 128 | q_pe 64]
        [c | k_pe] = Wkva x            # 512 | 64: k_pe ONE head for all
        c = N(c; wc_l)
        [k_nope | v] per head = Wkvb c                   # 128 | 128
        q_pe, k_pe = rope(q_pe), rope(k_pe)   # pairs (2i, 2i + 1), theta 6e6
        a_h = softmax_causal(([q_nope,h | q_pe,h] . [k_nope,h | k_pe]) / sqrt(192))
        h = h + Wo concat_h(sigmoid(x . wa_h) * a_h v_h)
      else:                                              # KDA, 32 heads
        q, k, v = Wq x, Wk x, Wv x                       # 4096 each
        [q | k | v] = silu(causal_conv4([q | k | v]))    # depthwise, no bias
        q = q / |q| / sqrt(128);  k = k / |k|            # per head, eps 1e-6
        g = -5 * sigmoid(exp(A_log_h) * (Wf x + dt_bias))    # [32, 128]
        beta = sigmoid(wb x)                             # [32]
        for t:  S = Diag(exp(g_t)) S;  d = beta_t (v_t - S^T k_t)
                S = S + k_t d^T;  o_t = S^T q_t          # S [128, 128] a head
        h = h + Wout (rms(o) * w * sigmoid(Wg x))        # norm a head
      x = N(h; w2_l)
      if l < first_k_dense_replace:
        h = h + Wdown (silu(Wgate x) * (Wup x))          # 6,144 wide
      else:
        s = sigmoid(Wr x) over ALL 512;  s' = s + correction
        group score = sum of its two largest s';  4 of 8 groups kept
        the 8 largest s' inside them chosen;  p = s / sum(s) * 2.5 over them
        h = h + sum_{e chosen AND held} p_e down_e(silu(gate_e x) * up_e x)
              + SharedExpert(x)                          # 768 wide, no gate
    logits = Whead N(h; w_final)                         # untied, the slice

The share: experts ``0 .. num_experts - 1`` of ``num_experts_published``
(one routing group of the 8) are held here; the router keeps its
published width, its groups and its 8 a token, and what the absent
experts would have added is left out — that partial result is what goes
on to the next layer, here as in the program.  The vocabulary is the
slice ``0 .. vocab_size - 1``.

The fused projections arrive as the program consumes them (the
configuration file's ``assumed.fused_projections``).  Weights are made by
``harness/weights.py`` from the seed in the type they are served in; the
reference widens them to float32 a layer at a time and multiplies at
``highest``.  A long row is computed in blocks: the expanded attention a
block of query positions at a time, so that no ``[heads, T, T]`` array is
ever whole.

``precision``: ``"float32"`` is the reference; ``"fp8"`` is the control
(each projection's and expert's operands rounded to e4m3 under a
per-tensor scale, the step below bf16); ``"bfloat16"`` rounds them to
bf16, as the program does; ``"state_bf16"`` is float32 but for the
recurrent state, rounded to bf16 after every position.  The router, the
gates, the decay and the recurrence stay float32 under every precision
but the last: the configuration states float32 for them.
"""
from __future__ import annotations

LIMITS = {
    # Over every sampled served token, how far the served token's
    # reference logit lies below the reference's best: the 99th
    # percentile of those gaps, their mean, and the mean over the first
    # FIRST_TOKENS of each sampled request alone (what a fault of the
    # prefill's state or rows, or of a reused slot, spoils first).  Read
    # on the v5e at the cell's size (tools/readings.py,
    # tools/planted_hybrid_latent.py and the cell's runs; my chip runs,
    # PR 40; PERF.md section 2 has both readings of each): sound runs at
    # most 0.209 (p99), 0.0081 (mean) and 0.0090 (first 8) over four
    # seeds before the limits were set; the fp8 control at least 0.863,
    # 0.148 and 0.088 over two.  Each limit is the geometric middle of
    # its two readings: a factor 2 from each for the percentile, 4.3 for
    # the mean, 3.1 for the first tokens.  Twelve layers of weights at
    # 0.02 under a router whose eighth expert a near-tie among 512
    # sigmoid scores flips under bf16 (one served token in ten is not
    # the reference's first, the widest gap 0.65) put the percentile
    # between deepseek-v2-lite's 0.2 and qwen3-next-80b-a3b's 1.6.  At
    # the cell's size one gate a head reads 1.54 / 0.325 / 0.303, a share
    # offset by one 1.17 / 0.218 / 0.228, groups not limited 0.459 /
    # 0.046 / 0.066, a stale state 0.30 / 0.020 / 1.55 (the first tokens
    # alone); the correction used as a weight, the heads' gate dropped
    # and a row read one position short stay under all three (at most
    # 0.25 / 0.011 / 0.009): the CPU tests hold those.
    "logit_gap_p99": 0.42,
    "logit_gap_mean": 0.035,
    "logit_gap_first8_mean": 0.028,
}
FIRST_TOKENS = 8
QUERY_BLOCK = 512      # query positions the expanded attention takes at once


def _sizes(cfg: dict) -> dict:
    L, every = cfg["num_hidden_layers"], cfg["layer_group_size"]
    n = cfg["num_attention_heads"]
    lin_heads = cfg["num_kv_heads_for_linear_attn"] or n
    dk = cfg["head_dim"]
    return dict(
        L=L, every=every, dense=cfg["first_k_dense_replace"],
        H=cfg["hidden_size"], V=cfg["vocab_size"], n=n,
        rank=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"],
        lh=lin_heads, dk=dk, dv=dk, taps=cfg["short_conv_kernel_size"],
        Md=cfg["intermediate_size"], M=cfg["moe_intermediate_size"],
        Ms=cfg["num_shared_experts"]
        * cfg["moe_shared_expert_intermediate_size"],
        held=cfg["num_experts"], router=cfg["num_experts_published"],
        top=cfg["num_experts_per_tok"], groups=cfg["n_group"],
        kept=cfg["topk_group"])


def _kinds(z: dict) -> list:
    """``"latent"`` closes every group of ``layer_group_size`` layers."""
    return ["latent" if (l + 1) % z["every"] == 0 else "linear"
            for l in range(z["L"])]


def param_shapes(cfg: dict) -> dict:
    z = _sizes(cfg)
    L, H, n, Ld = z["L"], z["H"], z["n"], z["dense"]
    kinds = _kinds(z)
    Lt, Ll, Lr = kinds.count("latent"), kinds.count("linear"), L - Ld
    f = cfg["serving"]["weights_dtype"]
    inner = z["lh"] * z["dv"]
    keys = z["lh"] * z["dk"]
    return {
        "stages": {
            "ln_attention_in": {"scale": ((L, H), f)},
            "ln_mlp_in": {"scale": ((L, H), f)},
            "latent_attention": {
                "q": {"kernel": ((Lt, H, n * (z["nope"] + z["rope"])), f)},
                "kv_a": {"kernel": ((Lt, H, z["rank"] + z["rope"]), f)},
                "kv_norm": {"scale": ((Lt, z["rank"]), f)},
                "kv_b": {"kernel": ((Lt, z["rank"],
                                     n * (z["nope"] + z["v"])), f)},
                "gate": {"kernel": ((Lt, H, n), f)},
                "out": {"kernel": ((Lt, n * z["v"], H), f)}},
            "linear_attention": {
                "qkv": {"kernel": ((Ll, H, 2 * keys + inner), f)},
                "decay": {"kernel": ((Ll, H, keys), f)},
                "gate": {"kernel": ((Ll, H, inner), f)},
                "beta": {"kernel": ((Ll, H, z["lh"]), f)},
                "conv": {"kernel": ((Ll, z["taps"], 2 * keys + inner), f)},
                "A_log": ((Ll, z["lh"]), f),
                "dt_bias": ((Ll, keys), f),
                "norm": {"scale": ((Ll, z["dv"]), f)},
                "out": {"kernel": ((Ll, inner, H), f)}},
            "mlp": {"wi": {"kernel": ((Ld, H, 2 * z["Md"]), f)},
                    "wo": {"kernel": ((Ld, z["Md"], H), f)}},
            "moe": {
                "router": {"kernel": ((Lr, H, z["router"]), f),
                           "correction": ((Lr, z["router"]), f)},
                "experts": {f"layer_{l:02d}": {
                    "wi": ((z["held"], H, 2 * z["M"]), f),
                    "wo": ((z["held"], z["M"], H), f)}
                    for l in range(Ld, L)},
                "shared": {"wi": {"kernel": ((Lr, H, 2 * z["Ms"]), f)},
                           "wo": {"kernel": ((Lr, z["Ms"], H), f)}}}},
        "shared": {"embedding": ((z["V"], H), f), "lm_head": ((z["V"], H), f),
                   "ln_final_scale": ((H,), f)},
    }


def _rounder(precision: str):
    """What rounds a projection's or an expert's operands."""
    import jax
    import jax.numpy as jnp

    if precision in ("float32", "state_bf16"):
        return lambda x: x
    if precision == "bfloat16":
        # not a pair of converts: on the TPU the compiler keeps the
        # excess precision and drops such a pair
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
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotary embedding of ``[B, T, n, d]`` at positions ``0..T-1``, the
    pairs interleaved: dimensions ``(2i, 2i + 1)`` turn by ``t *
    theta^(-2i/d)``."""
    import jax.numpy as jnp

    T, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1) \
        .reshape(x.shape)


def _swiglu(x, wi, wo, act, wq):
    import jax

    gu = act(x) @ wq(wi)
    M = gu.shape[-1] // 2
    return act(jax.nn.silu(gu[..., :M]) * gu[..., M:]) @ wq(wo)


def route(x, p, z, cfg):
    """``(experts [.., top], weights [.., top])`` of rows ``x``: sigmoid
    scores over all the router's outputs, the correction added to choose
    (groups, then experts) and never to weigh."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(x @ p["router"]["kernel"])           # all experts
    choose = s + p["router"]["correction"]
    G, E = z["groups"], z["router"]
    grouped = choose.reshape(*choose.shape[:-1], G, E // G)
    group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)       # [.., G]
    _, keep = jax.lax.top_k(group_score, z["kept"])
    kept = (keep[..., None] == jnp.arange(G)).any(-2)        # [.., G]
    masked = jnp.where(jnp.repeat(kept, E // G, axis=-1), choose, -jnp.inf)
    _, top_e = jax.lax.top_k(masked, z["top"])
    top_w = jnp.take_along_axis(s, top_e, axis=-1)
    if cfg["norm_topk_prob"]:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    return top_e, top_w * cfg["routed_scaling_factor"]


def _moe(x, p, z, cfg, act, wq, first_expert=0):
    """The routed block on ``x`` ``[B, T, H]``: the held experts, one
    after the other over every row, each weighted by what the router
    gave it there (0 where it was not among the row's 8), and the
    shared expert."""
    import jax
    import jax.numpy as jnp

    top_e, top_w = route(x, p, z, cfg)

    def one(y, e):
        wi, wo, index = e
        w = jnp.where(top_e == index, top_w, 0.0).sum(-1)   # [B, T]
        return y + w[..., None] * _swiglu(x, wi, wo, act, wq), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (p["experts"]["wi"], p["experts"]["wo"],
         first_expert + jnp.arange(p["experts"]["wi"].shape[0])))
    return y + _swiglu(x, p["shared"]["wi"]["kernel"],
                       p["shared"]["wo"]["kernel"], act, wq)


def _latent_attention(x, p, z, cfg, act, wq):
    import jax
    import jax.numpy as jnp

    B, T, _ = x.shape
    n, rank, nope, rope, v = z["n"], z["rank"], z["nope"], z["rope"], z["v"]
    theta = float(cfg["rope_theta"])
    q = (act(x) @ wq(p["q"]["kernel"])).reshape(B, T, n, nope + rope)
    down = act(x) @ wq(p["kv_a"]["kernel"])
    c = _norm(down[..., :rank], p["kv_norm"]["scale"], cfg["rms_norm_eps"])
    k_pe = _rope(down[..., None, rank:], theta)             # one head
    q_pe = _rope(q[..., nope:], theta)
    kv = (act(c) @ wq(p["kv_b"]["kernel"])).reshape(B, T, n, nope + v)
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (B, T, n, rope))], -1)
    values = kv[..., nope:]
    blocks = []
    for lo in range(0, T, QUERY_BLOCK):     # a block of query positions
        hi = min(lo + QUERY_BLOCK, T)
        scores = jnp.einsum("bqnd,bknd->bnqk", q[:, lo:hi], k[:, :hi]) \
            * (nope + rope) ** -0.5
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        blocks.append(jnp.einsum("bnqk,bknd->bqnd",
                                 jax.nn.softmax(scores, -1), values[:, :hi]))
    ctx = jnp.concatenate(blocks, 1)
    gate = jax.nn.sigmoid(x @ p["gate"]["kernel"])          # [B, T, n]
    return act((ctx * gate[..., None]).reshape(B, T, n * v)) \
        @ wq(p["out"]["kernel"])


def _kda(x, p, z, cfg, act, wq, state_round):
    import jax
    import jax.numpy as jnp

    B, T, _ = x.shape
    lh, dk, dv, taps = z["lh"], z["dk"], z["dv"], z["taps"]
    keys = lh * dk
    qkv = act(x) @ wq(p["qkv"]["kernel"])
    beta = jax.nn.sigmoid(x @ p["beta"]["kernel"])          # [B, T, lh]
    f = (act(x) @ wq(p["decay"]["kernel"]) + p["dt_bias"]) \
        .reshape(B, T, lh, dk)
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p["A_log"])[:, None] * f)                   # [B, T, lh, dk]
    # depthwise causal convolution: position t sees t - taps + 1 .. t
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, j:j + T] * p["conv"]["kernel"][j]
                          for j in range(taps)))
    q = qkv[..., :keys].reshape(B, T, lh, dk)
    k = qkv[..., keys:2 * keys].reshape(B, T, lh, dk)
    v = qkv[..., 2 * keys:].reshape(B, T, lh, dv)
    unit = lambda t: t / jnp.sqrt((t * t).sum(-1, keepdims=True) + 1e-6)
    q, k = unit(q) / dk ** 0.5, unit(k)

    def step(S, at):
        q_t, k_t, v_t, g_t, b_t = at
        S = S * jnp.exp(g_t)[..., None]                     # a decay a row
        d = (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t)) * b_t[..., None]
        S = state_round(S + jnp.einsum("bhk,bhv->bhkv", k_t, d))
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    time_first = lambda t: jnp.moveaxis(t, 1, 0)
    _, o = jax.lax.scan(step, jnp.zeros((B, lh, dk, dv), jnp.float32),
                        tuple(map(time_first, (q, k, v, g, beta))))
    o = jnp.moveaxis(o, 0, 1)                               # [B, T, lh, dv]
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True)
                     + cfg["rms_norm_eps"]) * p["norm"]["scale"]
    gate = jax.nn.sigmoid(act(x) @ wq(p["gate"]["kernel"]))
    o = o * gate.reshape(B, T, lh, dv)
    return act(o.reshape(B, T, lh * dv)) @ wq(p["out"]["kernel"])


def _layer(kind: str, routed: bool, h, p, z, cfg, precision: str,
           first_expert: int):
    """One layer: ``p`` = the layer's norms, its mixer (``kind``:
    ``"latent"`` or ``"linear"``) and its feed-forward block (``routed``
    or the dense one), widened here."""
    import jax
    import jax.numpy as jnp

    act = wq = _rounder(precision)
    state_round = _rounder("bfloat16" if precision == "state_bf16"
                           else "float32")
    eps = cfg["rms_norm_eps"]
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    x = _norm(h, p["ln_attention_in"]["scale"], eps)
    if kind == "latent":
        h = h + _latent_attention(x, p["mixer"], z, cfg, act, wq)
    else:
        h = h + _kda(x, p["mixer"], z, cfg, act, wq, state_round)
    x = _norm(h, p["ln_mlp_in"]["scale"], eps)
    if routed:
        return h + _moe(x, p["ffn"], z, cfg, act, wq, first_expert)
    return h + _swiglu(x, p["ffn"]["wi"]["kernel"], p["ffn"]["wo"]["kernel"],
                       act, wq)


def _layer_params(stages, z, l: int):
    """``(kind, routed, parameters)`` of layer ``l`` out of the program's
    tree: each mixer is stacked over the layers of its kind, the dense
    FFN over the leading layers, the routed block over the rest with its
    experts arrays of their own a layer, the norms over all layers."""
    import jax

    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
    kinds = _kinds(z)
    kind, nth = kinds[l], kinds[:l].count(kinds[l])
    routed = l >= z["dense"]
    if routed:
        moe = stages["moe"]
        ffn = {"experts": moe["experts"][f"layer_{l:02d}"],
               **at({k: v for k, v in moe.items() if k != "experts"},
                    l - z["dense"])}
    else:
        ffn = at(stages["mlp"], l)
    mixer = stages["latent_attention" if kind == "latent"
                   else "linear_attention"]
    return kind, routed, {
        "ln_attention_in": at(stages["ln_attention_in"], l),
        "ln_mlp_in": at(stages["ln_mlp_in"], l),
        "mixer": at(mixer, nth), "ffn": ffn}


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
            lambda kind, routed, h, p: _layer(kind, routed, h, p, z, cfg,
                                              precision, first_expert),
            static_argnums=(0, 1))
    layer = _LAYER_JIT[key]
    act = wq = _rounder(precision)
    shared = jax.tree.map(lambda a: a.astype(jnp.float32), params["shared"])
    h = shared["embedding"][tokens]
    for l in range(z["L"]):
        kind, routed, p = _layer_params(params["stages"], z, l)
        h = layer(kind, routed, h, p)
    h = _norm(h, shared["ln_final_scale"], cfg["rms_norm_eps"])
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
    with jax.default_matmul_precision("highest"):
        out = []
        for prompt, tokens in served:
            seq = list(prompt) + list(tokens[:-1])
            row = np.zeros((1, T), np.int32)
            row[0, :len(seq)] = seq
            row = jnp.asarray(row)
            lo = len(prompt) - 1
            at = forward(params, row, cfg, precision)[0, lo:lo + len(tokens)]
            judged = jnp.asarray(np.asarray(tokens, np.int32))
            if control:
                low = forward(params, row, cfg, control)[0]
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
