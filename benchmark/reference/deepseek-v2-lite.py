"""Plain float32 reference of ``deepseek-v2-lite``, one chip's share: the
full causal forward over a prompt and the tokens served after it, in
straightforward ``jax.numpy`` — no cache, no batching, no kernel, no
absorbed products, no sort, no program code.  Attention is in the
EXPANDED form at every position over the whole prefix: every position's
latent is projected up to its heads' keys and values, which is how the
model's published code computes it.

The layer, from the model's ``config.json`` and its
``modeling_deepseek.py`` as the configuration file's ``assumed`` lists
them (recalled: there is no network here)::

    N(x; w) = x / sqrt(mean(x^2) + eps) * w
    h = E[tokens]                                        # no position table
    for l in 0..L-1:
      x = N(h; w1_l)
      q = Wq x                       # 16 heads of [q_nope 128 | q_pe 64]
      [c | k_pe] = Wkva x            # 512 | 64: k_pe ONE head for all
      c = N(c; wc_l)                 # kv_a_layernorm
      [k_nope | v] per head = Wkvb c                     # 128 | 128
      q_pe, k_pe = yarn_rope(q_pe), yarn_rope(k_pe)      # the 64 alone
      a_h = softmax_causal(([q_nope,h | q_pe,h] . [k_nope,h | k_pe]) * s)
      h = h + Wo concat_h(a_h v_h)       # s = 192^-1/2 * m^2, m = 1.2608
      x = N(h; w2_l)
      if l < first_k_dense_replace:
        h = h + Wdown (silu(Wgate x) * (Wup x))          # 10,944 wide
      else:
        p = softmax(Wg x) over ALL 64;  top 6, as they are, * scale
        h = h + sum_{e chosen AND held} p_e down_e(silu(gate_e x) * up_e x)
              + SharedExperts(x)                         # 2,816 wide, no gate
    logits = Whead N(h; w_final)                         # untied, the slice

YaRN (dimension 64, theta 1e4, factor 40, original length 4096, beta 32
and 1): ``f_i = theta^(-2i/64)``; ``d(r) = 64 ln(4096 / (2 pi r)) / (2 ln
theta)``; ``low = floor(d(32))``, ``high = ceil(d(1))``; ``ramp_i =
clip((i - low) / (high - low), 0, 1)``; ``inv_freq_i = f_i / 40 * ramp_i
+ f_i (1 - ramp_i)``; cos and sin times ``mscale(40, 0.707) / mscale(40,
0.707) = 1``; ``m = 0.1 * 0.707 * ln 40 + 1``.

The share: experts ``0 .. n_routed_experts - 1`` of
``n_routed_experts_published`` are held here; the router keeps its
published width and its 6 a token, and what the absent experts would have
added is left out — that partial result is what goes on to the next
layer, here as in the program.  The vocabulary is the slice ``0 ..
vocab_size - 1``.

The fused projections arrive as the program consumes them (the
configuration file's ``assumed.fused_projections``).  Weights are made by
``harness/weights.py`` from the seed in the type they are served in; the
reference widens them to float32 a layer at a time and multiplies at
``highest``.

``precision``: ``"float32"`` is the reference; ``"fp8"`` is the control
(each projection's and expert's operands rounded to e4m3 under a
per-tensor scale, the step below bf16); ``"bfloat16"`` rounds them to
bf16, as the program does.  The router, the softmaxes and the norms stay
float32 under every precision: the configuration states float32 for
them.
"""
from __future__ import annotations

import math

LIMITS = {
    # Over every sampled served token, how far the served token's
    # reference logit lies below the reference's best: the 99th
    # percentile of those gaps, their mean, and the mean over the first
    # FIRST_TOKENS of each sampled request alone (what a fault of the
    # prefill's rows or of a reused lane spoils first).  Read on the v5e
    # at the cell's size (tools/readings.py, tools/planted_latent.py and
    # the cell's runs; my chip runs, PR 35; PERF.md section 2 has both
    # readings of each): sound runs at most 0.049 (p99), 0.0016 (mean)
    # and 0.0043 (first 8); the fp8 control at least 0.83, 0.140 and
    # 0.166.  Each limit is the geometric middle of its two readings: a
    # factor 4 from each for the percentile, 9 for the mean, 6 for the
    # first tokens.  27 layers of weights at 0.02 keep bf16's rounding
    # small (one served token in fifteen is not the reference's first,
    # the widest gap 0.12), so the limits sit far below the other serving
    # cells'.  They have to: with these weights six unrenormalised top-k
    # weights sum to ~0.25, the routed experts give a few per cent of a
    # layer's FFN, and a fault of the routing alone moves the logits
    # little — top-6 weights renormalised read 0.52 / 0.060 / 0.038 and
    # a share offset by one 0.30 / 0.026 / 0.026 at the cell's size, each
    # failed by the first two; a latent cached before its norm 1.0 /
    # 0.18 / 0.19, a softmax scale without m ** 2 2.8 / 1.04 / 1.18, a
    # positional key cached unrotated 4.4 / 2.2 / 2.3.
    "logit_gap_p99": 0.2,
    "logit_gap_mean": 0.015,
    "logit_gap_first8_mean": 0.027,
}
FIRST_TOKENS = 8


def _sizes(cfg: dict) -> dict:
    return dict(
        L=cfg["num_hidden_layers"], dense=cfg["first_k_dense_replace"],
        H=cfg["hidden_size"], V=cfg["vocab_size"],
        n=cfg["num_attention_heads"], rank=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        v=cfg["v_head_dim"], Md=cfg["intermediate_size"],
        M=cfg["moe_intermediate_size"],
        Ms=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        held=cfg["n_routed_experts"],
        router=cfg["n_routed_experts_published"],
        top=cfg["num_experts_per_tok"])


def param_shapes(cfg: dict) -> dict:
    z = _sizes(cfg)
    L, H, n, Ld = z["L"], z["H"], z["n"], z["dense"]
    Lr = L - Ld
    f = cfg["serving"]["weights_dtype"]
    return {
        "stages": {
            "ln_attention_in": {"scale": ((L, H), f)},
            "ln_mlp_in": {"scale": ((L, H), f)},
            "latent_attention": {
                "q": {"kernel": ((L, H, n * (z["nope"] + z["rope"])), f)},
                "kv_a": {"kernel": ((L, H, z["rank"] + z["rope"]), f)},
                "kv_norm": {"scale": ((L, z["rank"]), f)},
                "kv_b": {"kernel": ((L, z["rank"],
                                     n * (z["nope"] + z["v"])), f)},
                "out": {"kernel": ((L, n * z["v"], H), f)}},
            "mlp": {"wi": {"kernel": ((Ld, H, 2 * z["Md"]), f)},
                    "wo": {"kernel": ((Ld, z["Md"], H), f)}},
            "moe": {
                "router": {"kernel": ((Lr, H, z["router"]), f)},
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

    if precision == "float32":
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


def yarn(cfg: dict) -> tuple:
    """``(inv_freq [rope / 2] float64, cos/sin factor, softmax m)`` by
    the published formulas."""
    sc, dim, theta = cfg["rope_scaling"], cfg["qk_rope_head_dim"], \
        float(cfg["rope_theta"])
    factor, orig = sc["factor"], sc["original_max_position_embeddings"]

    def dim_of(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(sc["beta_fast"])), 0)
    high = min(math.ceil(dim_of(sc["beta_slow"])), dim - 1)
    span = (high - low) or 0.001
    inv = []
    for i in range(dim // 2):
        f = theta ** (-2.0 * i / dim)
        ramp = min(max((i - low) / span, 0.0), 1.0)
        inv.append(f / factor * ramp + f * (1.0 - ramp))

    def mscale(m):
        return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0

    return inv, mscale(sc["mscale"]) / mscale(sc["mscale_all_dim"]), \
        mscale(sc["mscale_all_dim"])


def _rope(x, inv, factor):
    """Rotate-half rotary embedding of ``[B, T, n, d]`` at positions
    ``0..T-1`` with the inverse frequencies ``inv``."""
    import jax.numpy as jnp

    T, d = x.shape[1], x.shape[-1]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :] * factor
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :] * factor
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _swiglu(x, wi, wo, act, wq):
    import jax

    gu = act(x) @ wq(wi)
    M = gu.shape[-1] // 2
    return act(jax.nn.silu(gu[..., :M]) * gu[..., M:]) @ wq(wo)


def _moe(x, p, z, cfg, act, wq, first_expert=0):
    """The routed block on ``x`` ``[B, T, H]``: the held experts, one
    after the other over every row, each weighted by what the router
    gave it there (0 where it was not among the row's 6), and the
    shared experts."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(x @ p["router"]["kernel"], -1)   # all experts
    top_w, top_e = jax.lax.top_k(probs, z["top"])
    if cfg["norm_topk_prob"]:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    top_w = top_w * cfg["routed_scaling_factor"]

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


def _attention(x, p, z, cfg, act, wq):
    import jax
    import jax.numpy as jnp

    B, T, _ = x.shape
    n, rank, nope, rope, v = z["n"], z["rank"], z["nope"], z["rope"], z["v"]
    inv, factor, m = yarn(cfg)
    q = (act(x) @ wq(p["q"]["kernel"])).reshape(B, T, n, nope + rope)
    down = act(x) @ wq(p["kv_a"]["kernel"])
    c = _norm(down[..., :rank], p["kv_norm"]["scale"], cfg["rms_norm_eps"])
    k_pe = _rope(down[..., None, rank:], inv, factor)       # one head
    q_pe = _rope(q[..., nope:], inv, factor)
    kv = (act(c) @ wq(p["kv_b"]["kernel"])).reshape(B, T, n, nope + v)
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (B, T, n, rope))], -1)
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) \
        * ((nope + rope) ** -0.5 * m * m)
    causal = jnp.tril(jnp.ones((T, T), bool))[None, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    ctx = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, -1),
                     kv[..., nope:])
    return act(ctx.reshape(B, T, n * v)) @ wq(p["out"]["kernel"])


def _layer(routed: bool, h, p, z, cfg, precision: str, first_expert: int):
    """One layer: ``p`` = the layer's norms, its attention and its
    feed-forward block (``routed`` or the dense one), widened here."""
    import jax
    import jax.numpy as jnp

    act = wq = _rounder(precision)
    eps = cfg["rms_norm_eps"]
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    h = h + _attention(_norm(h, p["ln_attention_in"]["scale"], eps),
                       p["attention"], z, cfg, act, wq)
    x = _norm(h, p["ln_mlp_in"]["scale"], eps)
    if routed:
        return h + _moe(x, p["ffn"], z, cfg, act, wq, first_expert)
    return h + _swiglu(x, p["ffn"]["wi"]["kernel"], p["ffn"]["wo"]["kernel"],
                       act, wq)


def _layer_params(stages, z, l: int):
    """``(routed, parameters)`` of layer ``l`` out of the program's tree:
    the norms and the attention are stacked over all layers, the dense
    FFN over the leading layers, the routed block over the rest with its
    experts arrays of their own a layer."""
    import jax

    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
    routed = l >= z["dense"]
    if routed:
        moe = stages["moe"]
        ffn = {"experts": moe["experts"][f"layer_{l:02d}"],
               **at({k: v for k, v in moe.items() if k != "experts"},
                    l - z["dense"])}
    else:
        ffn = at(stages["mlp"], l)
    return routed, {
        "ln_attention_in": at(stages["ln_attention_in"], l),
        "ln_mlp_in": at(stages["ln_mlp_in"], l),
        "attention": at(stages["latent_attention"], l), "ffn": ffn}


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
            lambda routed, h, p: _layer(routed, h, p, z, cfg, precision,
                                        first_expert), static_argnums=0)
    layer = _LAYER_JIT[key]
    act = wq = _rounder(precision)
    shared = jax.tree.map(lambda a: a.astype(jnp.float32), params["shared"])
    h = shared["embedding"][tokens]
    for l in range(z["L"]):
        routed, p = _layer_params(params["stages"], z, l)
        h = layer(routed, h, p)
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
    rows = np.zeros((len(served), T), np.int32)
    for i, (prompt, tokens) in enumerate(served):
        seq = list(prompt) + list(tokens[:-1])
        rows[i, :len(seq)] = seq

    with jax.default_matmul_precision("highest"):
        out = []
        for i, (prompt, tokens) in enumerate(served):
            row = jnp.asarray(rows[i:i + 1])
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
