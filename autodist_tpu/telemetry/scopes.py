"""The scopes the compiled programs wear on their device ops.

One small vocabulary, applied with :func:`scope` at the one place each
thing is computed.  A scope is a ``jax.named_scope``: it names the ops
traced under it (``jit(decode)/while/body/attention/dot_general``) and
changes nothing the compiler schedules, so a profiler trace can say
which part of this system a device op belongs to, and keep saying it
after a recompile renames every fusion.  The flax models name their
modules the same way (``SelfAttention(name="attention")``,
``MlpBlock(name="mlp")``), so the training forward wears ``attention``
and ``mlp`` without a call here; a backward op carries
``transpose(jvp(...))`` ahead of the same scope.
"""
from __future__ import annotations

SCOPES = (
    "embed",       # token + position (+ segment) embedding lookup
    "attention",   # qkv projection .. output projection, no cache write
    "mlp",         # wi, GELU, wo
    "kv_write",    # keys / values written into the serving cache
    "lm_head",     # final norm, logits, argmax / sampling; MLM head + loss
    "norm",        # RMSNorm of a non-default block (BlockSpec), a looped
                   # stack's per-pass final norm
    "rope",        # rotary rotation of q and k
    "grad_sync",   # gradient buckets' all-reduce, the reduce-scatters
    "optimizer",   # the update, its application, the gather to storage
    "linear_attention",  # a recurrent mixer: a gated-DeltaNet layer's
                   # projections, convolution, gated norm and output
                   # projection; a power-retention layer's q/k/v, gate and
                   # output projections; a state-space (ssd) layer's input
                   # projection, step, gate, norm and output projection
    "state_update",  # inside linear_attention: the recurrent state read,
                   # decayed, written (one step, or a window by chunks)
    "state_conv",  # inside linear_attention, of a state-space (ssd)
                   # layer: the causal convolution over [x | B | C] — the
                   # tail's shift and write, the taps, the bias, the SiLU
    "moe",         # a routed FFN: router, sort, experts, shared expert
    "moe_experts",  # inside moe: routing and the held experts' grouped
                   # matmuls over the (row, expert) pairs that hit them
    "moe_route",   # inside moe_experts: the router's scores, the groups
                   # kept, the top-k and its weights
    "latent_attention",  # a latent-attention mixer: the down- and
                   # up-projections (absorbed into q and the output in a
                   # decode step), rotary, the output projection
    "latent_attend",  # inside latent_attention: the read of the cached
                   # rows (scores, softmax, weighted sum); in prefill the
                   # expanded attention
)


def scope(name: str):
    """``jax.named_scope(name)`` for a name of :data:`SCOPES`."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not a scope of the vocabulary "
                         f"{SCOPES}")
    import jax

    return jax.named_scope(name)
