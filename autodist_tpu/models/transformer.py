"""Transformer encoder/decoder blocks — the shared modeling stack.

Counterpart of the reference's bundled transformer layers
(``examples/benchmark/utils/modeling/layers/`` ~1,000 LoC on
TF/Keras), rebuilt TPU-first in flax:

* bfloat16 activations by default (MXU-native), fp32 params + softmax
* ``jax.checkpoint`` (remat) per layer to trade FLOPs for HBM
* attention pluggable: ``attend`` picks the fused Pallas kernels of
  ``autodist_tpu.ops`` where it observes that they fit and the local
  einsum attention elsewhere; ring attention (or anything else) slots
  in via ``attention_fn``
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class LinearMixerSpec:
    """A recurrent mixer's sizes, and which recurrence it runs (``rule``).

    ``"delta"`` — gated DeltaNet: ``key_heads`` key (and query) heads of
    ``key_dim``, ``value_heads`` value heads of ``value_dim`` (each key
    head serves ``value_heads // key_heads`` of them), a depthwise causal
    convolution of ``conv_taps`` taps over the ``[q, k, v]`` channels.
    The state is one ``[key_dim, value_dim]`` float32 matrix a value
    head.  ``gate`` says what the state's decay is: ``"head"`` — one log
    decay a head and position, ``-exp(A_log) softplus(a + dt_bias)``,
    beside a fused ``qkvz`` / ``ba`` pair of projections and a SiLU
    output gate (gated DeltaNet) — or ``"channel"`` — ``key_dim`` a head,
    row ``i`` of the state decaying by its own ``gate_floor *
    sigmoid(exp(A_log) (f_i + dt_bias_i))``, bounded below by
    ``gate_floor`` (< 0), with q, k and v projected each on its own
    beside the decay's and a sigmoid output gate's projections (Kimi
    Delta Attention).

    ``"retention"`` — power retention (:meth:`retention` builds it): q, k
    and v are attention's own (``BlockSpec.kv_heads`` key/value heads of
    ``BlockSpec.head_dim``, q/k norm and rotary as the block says, the
    query heads grouped over them the way grouped-query attention groups
    them), no convolution, no write strength, one log gate a key/value
    head and position.  A KEY/VALUE head keeps the state: the sum of
    ``phi(k) v^T`` with ``phi`` the key's symmetric ``power``-th (2nd)
    power, ``key_dim (key_dim + 1) / 2`` distinct products, and a
    normaliser, the sum of ``phi(k)``, beside it.  They are laid out by
    offset (:attr:`state_offsets`): row ``(o, i)`` is ``k_i k_{(i + o)
    mod key_dim}`` for ``o = 0 .. key_dim / 2`` — a lane rotation builds
    a whole row — so the last offset's pairs are held twice:
    ``(key_dim / 2 + 1) key_dim`` rows (:attr:`state_rows`) for
    :attr:`state_rows_packed` distinct ones.

    ``"ssd"`` — a Mamba-2 state-space layer (:meth:`ssd` builds it):
    ``value_heads`` heads of ``value_dim`` (the layer's inner width),
    ``key_heads`` GROUPS, each with one B (the write's key) and one C (the
    read's query) of ``key_dim`` — the state size — that all its
    ``value_heads // key_heads`` heads share; a depthwise causal
    convolution of ``conv_taps`` taps, with a bias where ``conv_bias``,
    over ``[x | B | C]``; one scalar decay a head and position,
    ``exp(Delta A)`` with ``Delta = softplus(dt + dt_bias)`` the write's
    strength too; a skip ``D`` a head; no l2 norm, no normaliser; the
    output gated by ``SiLU(z)`` BEFORE one RMSNorm a group.  A group's
    state is ONE ``[key_dim, heads a group * value_dim]`` float32 matrix:
    B down the rows, every head's values side by side along the
    columns, the decay a row vector that is constant over a head's
    ``value_dim`` columns."""

    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv_taps: int = 4
    gate: str = "head"
    gate_floor: float = 0.0
    rule: str = "delta"
    power: int = 1
    conv_bias: bool = False

    def __post_init__(self):
        if self.rule not in ("delta", "retention", "ssd"):
            raise ValueError(f"LinearMixerSpec.rule={self.rule!r}: one of "
                             "('delta', 'retention', 'ssd')")
        if self.value_heads % self.key_heads:
            raise ValueError(
                "LinearMixerSpec.value_heads must be a multiple of "
                "key_heads (a state-space layer's groups divide its "
                f"heads): {self.value_heads} on {self.key_heads}")
        if self.gate not in ("head", "channel"):
            raise ValueError(f"LinearMixerSpec.gate={self.gate!r}: one of "
                             "('head', 'channel')")
        if (self.gate == "channel") != (self.gate_floor < 0):
            raise ValueError("LinearMixerSpec.gate_floor (< 0) is the "
                             "'channel' gate's lower bound: both or "
                             "neither")
        if self.rule == "retention" and (
                self.power != 2 or self.conv_taps or self.gate != "head"
                or self.key_heads != self.value_heads
                or self.key_dim != self.value_dim or self.key_dim % 2):
            raise ValueError(
                "power retention is served at degree 2, with no "
                "convolution and no gate kind, a state a key/value head "
                "of even size (LinearMixerSpec.retention builds it)")
        if self.rule != "retention" and self.power != 1:
            raise ValueError("the delta rule and the state-space layer "
                             "read their key as it is: "
                             "LinearMixerSpec.power is retention's degree")
        if self.rule == "ssd" and (self.gate != "head"
                                   or self.conv_taps < 2):
            raise ValueError(
                "a state-space (ssd) layer decays by one scalar a head and "
                "convolves its input over at least two taps "
                "(LinearMixerSpec.ssd builds it)")
        if self.conv_bias and self.rule != "ssd":
            raise ValueError("LinearMixerSpec.conv_bias is the state-space "
                             "(ssd) layer's convolution's")

    @classmethod
    def retention(cls, kv_heads: int, head_dim: int, power: int = 2):
        """Power retention over ``kv_heads`` key/value heads of
        ``head_dim`` (the block's own ``kv_heads`` and ``head_dim``)."""
        return cls(kv_heads, kv_heads, head_dim, head_dim, conv_taps=0,
                   rule="retention", power=power)

    @classmethod
    def ssd(cls, heads: int, head_dim: int, state_dim: int, groups: int = 1,
            conv_taps: int = 4, conv_bias: bool = True):
        """A Mamba-2 state-space layer by its config's own keys: ``heads``
        (``mamba_n_heads``) of ``head_dim`` (``mamba_d_head``), a state of
        ``state_dim`` (``mamba_d_state``), ``groups`` (``mamba_n_groups``)
        of B and C, ``conv_taps`` (``mamba_d_conv``) and ``conv_bias``
        (``mamba_conv_bias``)."""
        return cls(groups, heads, state_dim, head_dim, conv_taps=conv_taps,
                   rule="ssd", conv_bias=conv_bias)

    @property
    def conv_channels(self) -> int:
        """Channels the convolution runs over: q, k and v (of a
        state-space layer C, B and x), flat."""
        return 2 * self.key_heads * self.key_dim \
            + self.value_heads * self.value_dim

    # ---- what the cache manager holds a slot and layer: read from here,
    # never spelled as shapes there ------------------------------------
    @property
    def has_conv(self) -> bool:
        """Whether a slot keeps a convolution tail (``conv_taps - 1``
        rows of its input)."""
        return self.rule != "retention"

    @property
    def tail_shape(self) -> tuple:
        """A slot's convolution tail in one layer (``has_conv``): the
        ``conv_taps - 1`` inputs before the next position, ``[taps - 1,
        channels]`` — of a state-space layer FLAT, ``[(taps - 1) *
        channels]``, oldest first: its channels are whole lanes, so a
        decode step shifts and convolves by lane-aligned slices, and the
        chip keeps the stacked array as declared (three rows second-minor
        it pads to a sublane tile, and re-lays the whole array out around
        every layer's write: 18% of a decode step, PERF.md section 6,
        PR 48)."""
        if self.rule == "ssd":
            return ((self.conv_taps - 1) * self.conv_channels,)
        return (self.conv_taps - 1, self.conv_channels)

    @property
    def has_normaliser(self) -> bool:
        """Whether a normaliser rides beside the state matrix."""
        return self.rule == "retention"

    @property
    def state_heads(self) -> int:
        """Heads that hold a state: the value heads of the delta rule
        and of a state-space layer, the key/value heads of retention."""
        return self.value_heads

    @property
    def group_width(self) -> int:
        """Columns of a state-space group's matrix: its heads' values,
        side by side."""
        return self.value_heads // self.key_heads * self.value_dim

    @property
    def state_offsets(self) -> int:
        """Retention's offsets ``o`` (see the class): ``key_dim / 2 + 1``."""
        return self.key_dim // 2 + 1

    @property
    def state_rows(self) -> int:
        """Rows of ``value_dim`` a head's state holds, as laid out."""
        if self.rule != "retention":
            return self.key_dim
        return self.state_offsets * self.key_dim

    @property
    def state_rows_packed(self) -> int:
        """The distinct rows among them: what the recurrence needs."""
        if self.rule != "retention":
            return self.key_dim
        return self.key_dim * (self.key_dim + 1) // 2

    @property
    def state_shape(self) -> tuple:
        """A slot's state matrix in one layer: ``[heads, key_dim,
        value_dim]``, or retention's ``[heads, offsets, value_dim,
        key_dim]`` (an offset's tile holds ``key_dim`` on the lanes), or
        a state-space layer's ``[groups, key_dim, group_width]``."""
        if self.rule == "delta":
            return (self.value_heads, self.key_dim, self.value_dim)
        if self.rule == "ssd":
            return (self.key_heads, self.key_dim, self.group_width)
        return (self.key_heads, self.state_offsets, self.value_dim,
                self.key_dim)

    @property
    def normaliser_shape(self) -> tuple:
        """A slot's normaliser in one layer (``has_normaliser``):
        ``[offsets, heads, key_dim]`` — the heads inside the offsets, so
        that the two minor dimensions are whole ``(8, 128)`` tiles at 8
        heads of 128 and the chip keeps the array as it is declared (65
        offsets second-minor it pads, and re-lays the array out around
        every kernel call)."""
        return (self.state_offsets, self.key_heads, self.key_dim)

    @property
    def state_floats(self) -> int:
        """float32 values a slot holds in one layer, normaliser and all."""
        n = int(np.prod(self.state_shape))
        if self.has_normaliser:
            n += int(np.prod(self.normaliser_shape))
        return n


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN scaling of the rotary frequencies: a frequency is divided by
    ``factor`` where it turns fewer than ``beta_slow`` times over
    ``original_max_len`` positions, kept where it turns more than
    ``beta_fast`` times, and blended linearly between.  ``mscale`` and
    ``mscale_all_dim`` give the factors that cos and sin (their ratio)
    and the softmax scale (:attr:`attention_mscale`, squared) are
    multiplied by."""

    factor: float
    original_max_len: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def correction_range(self, dim: int, theta: float) -> tuple:
        """``(low, high)``: the frequencies below ``low`` are kept, those
        above ``high`` divided by ``factor``."""
        def turns_at(rotations):
            return dim * np.log(self.original_max_len
                                / (rotations * 2 * np.pi)) \
                / (2 * np.log(theta))
        return (max(int(np.floor(turns_at(self.beta_fast))), 0),
                min(int(np.ceil(turns_at(self.beta_slow))), dim - 1))

    def inv_freq(self, dim: int, theta: float) -> np.ndarray:
        """The ``dim // 2`` scaled inverse frequencies, float32."""
        f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
        low, high = self.correction_range(dim, theta)
        ramp = np.clip((np.arange(dim // 2) - low)
                       / ((high - low) or 0.001), 0.0, 1.0)
        return (f / self.factor * ramp + f * (1.0 - ramp)) \
            .astype(np.float32)

    def _mscale(self, mscale: float) -> float:
        if self.factor <= 1.0:
            return 1.0
        return 0.1 * mscale * float(np.log(self.factor)) + 1.0

    @property
    def cos_sin_scale(self) -> float:
        return self._mscale(self.mscale) / self._mscale(self.mscale_all_dim)

    @property
    def attention_mscale(self) -> float:
        """``m``: the softmax scale is multiplied by its square."""
        return self._mscale(self.mscale_all_dim)


@dataclasses.dataclass(frozen=True)
class LatentAttentionSpec:
    """Latent (compressed) attention's sizes: the input is projected
    down to ONE row a position, ``[c | k_pe]`` — a latent of ``kv_rank``,
    normed, and a rotated positional key of ``rope_dim`` that every
    query head shares — and that row is what the cache holds.  Each head
    projects ``c`` up to a key of ``nope_dim`` and a value of
    ``value_dim``; its query is ``[q_nope (nope_dim) | q_pe (rope_dim)]``
    with rotary on ``q_pe`` alone
    (:attr:`BlockSpec.latent_softmax_scale` is the scores' scale)."""

    kv_rank: int
    nope_dim: int
    rope_dim: int
    value_dim: int

    @property
    def row(self) -> int:
        """Values a cached position holds."""
        return self.kv_rank + self.rope_dim


@dataclasses.dataclass(frozen=True)
class RoutedFFNSpec:
    """A routed feed-forward block's sizes.  The router scores all
    ``num_experts`` (``scores``: a ``"softmax"`` over them, or a
    ``"sigmoid"`` of each) and keeps ``top_k`` a token, renormalised to
    sum 1 (``renormalise``; as the scores left them where false), times
    ``scale``.  With ``groups`` > 1 the experts lie in that many equal
    groups in order, a token keeps the ``groups_kept`` groups whose two
    best scores sum highest, and its ``top_k`` come from those alone.
    ``correction``: a learned term an expert (the leaf ``correction``) is
    added to the scores that CHOOSE groups and experts; the weights are
    of the scores without it.
    this device holds experts ``first_expert .. first_expert +
    experts_held`` and adds up their terms alone — what the absent ones
    would have given is some other device's.  Experts are SiLU-gated at
    ``expert_width``; a shared expert of ``shared_width``, behind a
    sigmoid gate where ``shared_gate``, is computed everywhere (0:
    none)."""

    num_experts: int
    top_k: int
    expert_width: int
    experts_held: int
    shared_width: int = 0
    first_expert: int = 0
    renormalise: bool = True
    shared_gate: bool = True
    scores: str = "softmax"
    groups: int = 1
    groups_kept: int = 1
    scale: float = 1.0
    correction: bool = False

    def __post_init__(self):
        if not 0 < self.top_k <= self.num_experts:
            raise ValueError("RoutedFFNSpec.top_k must lie in "
                             "1..num_experts")
        if self.scores not in ("softmax", "sigmoid"):
            raise ValueError(f"RoutedFFNSpec.scores={self.scores!r}: one "
                             "of ('softmax', 'sigmoid')")
        if self.groups < 1 or self.num_experts % self.groups \
                or not 0 < self.groups_kept <= self.groups \
                or self.top_k > self.groups_kept \
                * (self.num_experts // self.groups) \
                or (self.groups > 1 and self.num_experts // self.groups < 2):
            raise ValueError(
                f"RoutedFFNSpec keeps {self.groups_kept} of {self.groups} "
                f"groups of {self.num_experts} experts for {self.top_k} a "
                "token: equal groups of at least two, enough kept to "
                "choose from")
        if self.first_expert < 0 or self.experts_held < 1 \
                or self.first_expert + self.experts_held > self.num_experts:
            raise ValueError(
                f"RoutedFFNSpec holds experts {self.first_expert}.."
                f"{self.first_expert + self.experts_held} of "
                f"{self.num_experts}")


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """What one layer of the stack IS, said once: the serving engine's
    prefill, decode and chunk layers and the pipelined LM's
    ``_tp_encoder_layer`` all read it (``cfg.block``).  The default is
    the block the flax :class:`EncoderLayer` defines — LayerNorm after
    each residual add, learned positions, a two-matrix GELU MLP, biases,
    the head tied to the embedding, one pass over the layers.

    * ``norm`` ``"layernorm"`` | ``"rmsnorm"`` (scale only), at
      ``norm_eps``; ``norm_placement`` ``"post"`` (``LN(x + f(x))``) or
      ``"sandwich"`` (``x + N(f(N(x)))``: a norm before AND after each
      sub-block, four a layer) or ``"pre"`` (``x + f(N(x))``, two a
      layer).  ``norm_zero_centred``: the RMSNorm multiplies by ``1 +
      weight`` (its leaf is ``weight``, near 0, not ``scale``).
    * ``positions`` ``"learned"`` (a table added to the embedding),
      ``"rope"`` (rotate-half rotary at ``rope_theta`` on q and k inside
      attention; the key is rotated before it is cached) or ``"none"``
      (nothing positional anywhere: causal attention over what recurrent
      layers beside it have ordered).
    * ``embedding_multiplier``, ``residual_multiplier``,
      ``logits_scaling`` — the embedding's rows TIMES the first, every
      sub-block's output TIMES the second before it is added to the
      stream, the logits DIVIDED by the third; ``softmax_scale`` — what
      attention multiplies its scores by (``None``: ``head_dim **
      -0.5``), read by every path that attends (:attr:`TransformerConfig
      .softmax_scale`).
    * ``ffn`` ``"gelu"`` (``wo(gelu(wi x))``) or ``"swiglu"``
      (``wo(silu(gate x) * (up x))``, gate and up stacked in ``wi``).
    * ``bias`` — whether the projections carry biases.
    * ``tied_head`` — logits from the embedding table, or from
      ``shared["lm_head"]``.
    * ``layer_period`` — the kinds of layer the stack repeats in order:
      ``"full"`` (softmax attention over cached keys and values),
      ``"linear"`` (``linear``'s recurrent mixer) and ``"latent"``
      (``latent``'s attention); empty: every layer alike.  ``("linear",)``
      is a stack with no caching layer at all.  Where the mixer is power
      retention (``linear.rule``), ``qk_norm``, ``kv_heads``,
      ``head_dim`` and rotary ``positions`` are the LINEAR layers' q and
      k (attention's own projections feed the recurrence); the delta
      rule has its own and refuses them in a stack without full layers.
    * ``latent`` — the sizes of the ``"latent"`` layers' attention
      (:class:`LatentAttentionSpec`): the cache holds one row a position
      and not keys and values a head.  With an empty ``layer_period``
      every layer is latent.  ``attn_gate`` on such a layer is a gate a
      head: its output times ``sigmoid(x w_h)`` before the output
      projection.  ``rope_scaling`` scales the rotary frequencies
      (:class:`RopeScaling`); ``rope_interleave``: the rotary pairs are
      neighbours ``(2i, 2i + 1)`` and not halves ``(i, i + d / 2)``.
    * ``dense_layers`` — of a routed stack (``moe``), how many leading
      layers carry the dense ``ffn`` at ``TransformerConfig.mlp_dim``
      and no router.
    * ``loop_steps`` ``T`` — the layers run ``T`` times with the same
      weights; the final norm closes every pass (it is pass ``u``'s
      output and pass ``u + 1``'s input), the head reads the last, and
      the serving cache holds ``T * num_layers`` layers, pass ``u``'s
      layer ``l`` at ``u * num_layers + l``.  ``exit_threshold`` is the
      published early-exit knob: at 1.0 every token leaves at pass
      ``T``, which is all the engine runs.
    """

    norm: str = "layernorm"
    norm_placement: str = "post"
    norm_eps: float = 1e-6
    positions: str = "learned"
    rope_theta: float = 10000.0
    ffn: str = "gelu"
    bias: bool = True
    tied_head: bool = True
    loop_steps: int = 1
    exit_threshold: float = 1.0
    norm_zero_centred: bool = False
    kv_heads: Optional[int] = None
    head_dim: Optional[int] = None
    rope_fraction: float = 1.0
    qk_norm: bool = False
    attn_gate: bool = False
    layer_period: tuple = ()
    linear: Optional[LinearMixerSpec] = None
    moe: Optional[RoutedFFNSpec] = None
    latent: Optional[LatentAttentionSpec] = None
    rope_scaling: Optional[RopeScaling] = None
    dense_layers: int = 0
    rope_interleave: bool = False
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    softmax_scale: Optional[float] = None

    def __post_init__(self):
        for name, allowed in (("norm", ("layernorm", "rmsnorm")),
                              ("norm_placement", ("post", "sandwich",
                                                  "pre")),
                              ("positions", ("learned", "rope", "none")),
                              ("ffn", ("gelu", "swiglu"))):
            if getattr(self, name) not in allowed:
                raise ValueError(f"BlockSpec.{name}={getattr(self, name)!r}"
                                 f": one of {allowed}")
        if self.loop_steps < 1:
            raise ValueError("BlockSpec.loop_steps must be >= 1")
        if set(self.layer_period) - {"full", "linear", "latent"}:
            raise ValueError(f"BlockSpec.layer_period={self.layer_period}"
                             ": kinds are 'full', 'linear' and 'latent'")
        if {"full", "latent"} <= set(self.layer_period):
            raise ValueError(
                "'full' and 'latent' layers in one layer_period: the cache "
                "manager holds keys and values a head or a latent row a "
                "position beside the recurrent state, not both")
        if self.layer_period and ("latent" in self.layer_period) \
                != (self.latent is not None):
            raise ValueError("BlockSpec.latent gives the sizes of the "
                             "'latent' layers of layer_period: both or "
                             "neither")
        if ("linear" in self.layer_period) != (self.linear is not None):
            raise ValueError("BlockSpec.linear gives the sizes of the "
                             "'linear' layers of layer_period: both or "
                             "neither")
        if (self.norm_zero_centred or self.qk_norm) \
                and self.norm != "rmsnorm":
            raise ValueError("norm_zero_centred and qk_norm are RMSNorms")
        if self.moe is not None and self.ffn != "swiglu":
            raise ValueError("the routed experts are SiLU-gated: "
                             "BlockSpec.moe needs ffn='swiglu'")
        if (self.linear or self.moe) and (self.loop_steps != 1
                                          or self.bias):
            raise ValueError("a linear mixer or a routed FFN runs in one "
                             "pass and without biases")
        if self.dense_layers and self.moe is None:
            raise ValueError("BlockSpec.dense_layers counts the leading "
                             "layers of a routed stack (moe) that are not "
                             "routed")
        if self.latent is not None and (
                self.positions != "rope" or self.bias
                or self.loop_steps != 1 or self.qk_norm
                or self.kv_heads or self.head_dim
                or self.rope_fraction != 1.0):
            raise ValueError(
                "latent attention has its own head sizes and one shared "
                "rotary key: BlockSpec.latent goes with positions='rope' "
                "and none of bias, loop_steps, qk_norm, kv_heads, "
                "head_dim, rope_fraction")
        if self.linear is not None:
            retention = self.linear.rule == "retention"
            attends = "full" in self.layer_period
            if retention and (attends or self.attn_gate or (
                    self.linear.key_heads, self.linear.key_dim)
                    != (self.kv_heads, self.head_dim)):
                raise ValueError(
                    "power retention reads the block's own q, k and v: "
                    "BlockSpec.kv_heads and head_dim are its "
                    "LinearMixerSpec's heads and size, with no 'full' "
                    "layer beside it and no attn_gate")
            if not retention and not attends and (
                    self.qk_norm or self.kv_heads or self.head_dim):
                raise ValueError(
                    "qk_norm, kv_heads and head_dim are attention's (or "
                    "power retention's) q and k: a delta-rule mixer has "
                    "its own heads and l2 norm, a state-space (ssd) layer "
                    "its B and C as they leave the convolution, and this "
                    "layer_period has no 'full' layer")
            if self.linear.rule == "ssd" and not attends \
                    and self.positions == "rope":
                raise ValueError(
                    "rotary positions turn attention's q and k: a "
                    "state-space (ssd) layer has none to turn, and this "
                    "layer_period has no 'full' layer")
        if self.latent is not None and self.softmax_scale is not None:
            raise ValueError("latent attention's scale is its own "
                             "(BlockSpec.latent_softmax_scale): "
                             "softmax_scale is the 'full' layers'")
        if min(self.embedding_multiplier, self.residual_multiplier,
               self.logits_scaling) <= 0 or (
                   self.softmax_scale is not None
                   and self.softmax_scale <= 0):
            raise ValueError("BlockSpec's multipliers, logits_scaling and "
                             "softmax_scale are positive")
        if self.rope_interleave and self.latent is None:
            raise ValueError("BlockSpec.rope_interleave pairs the rotary "
                             "dimensions of latent attention's positional "
                             "slices")
        if self.rope_scaling is not None and self.positions != "rope":
            raise ValueError("BlockSpec.rope_scaling scales rotary "
                             "positions")

    @property
    def is_default(self) -> bool:
        return self == BlockSpec()

    @property
    def latent_softmax_scale(self) -> float:
        """What latent attention multiplies its scores by: ``(nope_dim +
        rope_dim) ** -0.5``, times YaRN's ``m ** 2`` where the rotary
        frequencies are scaled (:attr:`RopeScaling.attention_mscale`)."""
        m = self.rope_scaling.attention_mscale if self.rope_scaling else 1.0
        return (self.latent.nope_dim + self.latent.rope_dim) ** -0.5 * m * m

    def layer_kinds(self, num_layers: int) -> tuple:
        """The kind of each of ``num_layers`` layers."""
        period = self.layer_period or (
            ("latent",) if self.latent is not None else ("full",))
        return tuple(period[l % len(period)] for l in range(num_layers))


@dataclasses.dataclass(unsafe_hash=True)
class TransformerConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    max_len: int = 512
    type_vocab_size: int = 2
    dropout_rate: float = 0.1
    attention_dropout_rate: float = 0.1
    dtype: Any = jnp.bfloat16
    remat: bool = False
    attention_fn: Optional[Callable] = None  # (q, k, v, mask, dropout_rng) -> out
    # (local_len) -> position ids; None = arange.  Sequence-parallel
    # models pass parallel.sequence.global_positions so shards embed
    # their true offsets instead of restarting at 0.  max_len must cover
    # the GLOBAL sequence (shards x local_len): ids beyond it are
    # NaN-poisoned at the gather (loss turns NaN immediately) instead of
    # clamping to silently wrong embeddings; global_positions(max_len=...)
    # additionally rejects the mismatch statically at trace time.
    position_fn: Optional[Callable] = None
    causal: bool = False
    # What a layer is (norms, positions, FFN, biases, head, loops).  The
    # flax modules below are the default block only; the pipelined LM's
    # ``_tp_encoder_layer`` and the serving engine read the spec.
    block: BlockSpec = BlockSpec()

    @property
    def head_dim(self) -> int:
        return self.block.head_dim or self.hidden_size // self.num_heads

    @property
    def kv_heads(self) -> int:
        return self.block.kv_heads or self.num_heads

    @property
    def softmax_scale(self) -> float:
        """What attention multiplies its scores by: the block's, or
        ``head_dim ** -0.5``."""
        if self.block.softmax_scale is not None:
            return float(self.block.softmax_scale)
        return self.head_dim ** -0.5


def dot_product_attention(q, k, v, mask, *, dropout_rate=0.0,
                          dropout_rng=None, dtype=jnp.bfloat16, scale=None):
    """Plain einsum attention (softmax in fp32 for stability).  ``scale``:
    what the scores are multiplied by, in float32, where it is not
    ``head_dim ** -0.5`` (``BlockSpec.softmax_scale``)."""
    depth = q.shape[-1]
    scores = jnp.einsum("...qhd,...khd->...hqk", q, k)
    if scale is None:
        scores = (scores / np.sqrt(depth)).astype(jnp.float32)
    else:
        scores = scores.astype(jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate,
                                    probs.shape)
        probs = probs * keep / (1.0 - dropout_rate)
    return jnp.einsum("...hqk,...khd->...qhd", probs, v)


def _fused(cfg: TransformerConfig, q, k, v, mask, dropout_rng) -> bool:
    """Whether attention over these projections (arrays or shapes) takes
    the fused kernels: what :func:`attend` documents."""
    if cfg.attention_fn is not None or mask is not None \
            or dropout_rng is not None \
            or cfg.block.softmax_scale is not None:
        return False
    from autodist_tpu.ops.flash_attention import fused_attention_fits

    return fused_attention_fits(q, k, v)


def _call_fused(traced: bool, kernel, *args):
    """``kernel(*args)`` under the marker ``attention_device_pct.train``
    and the program rules find it by, counted once per traced call."""
    from autodist_tpu import telemetry
    from autodist_tpu.kernel.pallas import kernel_marker

    if traced:
        telemetry.counter("kernel/flash_attention_calls").inc()
        telemetry.gauge("kernel/flash_attention_elected").set(1)
    with jax.named_scope(kernel_marker("flash_attention")):
        return kernel(*args)


def attend(cfg: TransformerConfig, q, k, v, mask, *, dropout_rng=None,
           dropout_rate=0.0):
    """A layer's attention over ``[batch, length, heads, head_dim]``
    projections: ``cfg.attention_fn`` where the user set one, else ONE
    attention whose implementation follows what this call observes.
    Unmasked and without dropout, on operands the fused kernels were
    measured to win on (``ops.flash_attention.fused_attention_fits``:
    a TPU, bf16 or float32, whole per-device arrays, head width and
    length inside the measured range), the scores stay in VMEM forward
    and backward, under ``kernel_marker("flash_attention")``; everything
    else — a padding mask, a causal triangle, attention dropout, another
    type or shape, a GSPMD lowering over several devices — is
    :func:`dot_product_attention`.  Same mathematics either way:
    float32 scores and softmax, probabilities in the operand type."""
    if cfg.attention_fn is not None:
        if cfg.block.softmax_scale is not None:
            raise ValueError(
                "cfg.attention_fn scales its scores by head_dim ** -0.5: "
                f"BlockSpec.softmax_scale={cfg.block.softmax_scale} is "
                "served by the einsum attention alone")
        return cfg.attention_fn(q, k, v, mask, dropout_rng)
    traced = isinstance(q, jax.core.Tracer)   # count programs, not init
    if _fused(cfg, q, k, v, mask, dropout_rng):
        from autodist_tpu.ops.flash_attention import flash_attention

        return _call_fused(traced, flash_attention, q, k, v)
    if traced:
        from autodist_tpu import telemetry

        telemetry.counter("kernel/einsum_attention_calls").inc()
    return dot_product_attention(q, k, v, mask, dropout_rate=dropout_rate,
                                 dropout_rng=dropout_rng, dtype=cfg.dtype,
                                 scale=cfg.block.softmax_scale)


class SelfAttention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask, deterministic: bool):
        cfg = self.cfg
        B, L, _ = x.shape
        dropout_rng = (None if deterministic or cfg.attention_dropout_rate == 0
                       else self.make_rng("dropout"))
        heads = (cfg.num_heads, cfg.head_dim)
        like = jax.ShapeDtypeStruct((B, L, *heads), cfg.dtype)
        if not self.is_initializing() \
                and _fused(cfg, like, like, like, mask, dropout_rng):
            from autodist_tpu.ops.flash_attention import one_pass_fits

            if one_pass_fits(L):
                return self._attend_packed(x)
        qkv = nn.DenseGeneral(
            features=(3, *heads), axis=-1, dtype=cfg.dtype, name="qkv")(x)
        q, k, v = jnp.moveaxis(qkv, -3, 0)
        out = attend(cfg, q, k, v, mask, dropout_rng=dropout_rng,
                     dropout_rate=(0.0 if deterministic
                                   else cfg.attention_dropout_rate))
        return nn.DenseGeneral(features=cfg.hidden_size, axis=(-2, -1),
                               dtype=cfg.dtype, name="out")(out)

    def _attend_packed(self, x):
        """The layer where the fused kernels are elected: the same two
        projections as flat matmuls, so that q, k, v, the output and
        their gradients stay ``[B, L, heads * head_dim]`` from one
        matmul to the kernels to the next.  The chip lays a ``[B, L,
        heads, head_dim]`` array out with ``L`` minor-most where
        ``head_dim`` is under 128, and a kernel's row-major operand then
        costs a whole-tensor copy each (PERF.md section 6, PR 31)."""
        from autodist_tpu.ops.flash_attention import flash_attention_packed

        cfg, params = self.cfg, self.variables["params"]
        width = cfg.num_heads * cfg.head_dim

        def dense(x, name, features):
            w, b = (jnp.asarray(params[name][leaf], cfg.dtype)
                    for leaf in ("kernel", "bias"))
            return x @ w.reshape(-1, features) + b.reshape(features)

        qkv = dense(x.astype(cfg.dtype), "qkv", 3 * width)
        out = _call_fused(isinstance(x, jax.core.Tracer),
                          flash_attention_packed, qkv, cfg.num_heads)
        return dense(out, "out", cfg.hidden_size)


class MlpBlock(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, deterministic: bool):
        cfg = self.cfg
        h = nn.Dense(cfg.mlp_dim, dtype=cfg.dtype, name="wi")(x)
        h = nn.gelu(h)
        h = nn.Dropout(cfg.dropout_rate)(h, deterministic=deterministic)
        return nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="wo")(h)


class EncoderLayer(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask, deterministic: bool):
        cfg = self.cfg
        a = SelfAttention(cfg, name="attention")(x, mask, deterministic)
        a = nn.Dropout(cfg.dropout_rate)(a, deterministic=deterministic)
        x = nn.LayerNorm(dtype=cfg.dtype, name="ln_attention")(x + a)
        m = MlpBlock(cfg, name="mlp")(x, deterministic)
        m = nn.Dropout(cfg.dropout_rate)(m, deterministic=deterministic)
        return nn.LayerNorm(dtype=cfg.dtype, name="ln_mlp")(x + m)


class Encoder(nn.Module):
    """Stack of encoder layers, optionally rematerialized per layer."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask, deterministic: bool):
        cfg = self.cfg
        layer_cls = EncoderLayer
        if cfg.remat:
            layer_cls = nn.remat(EncoderLayer, static_argnums=(3,))
        for i in range(cfg.num_layers):
            x = layer_cls(cfg, name=f"layer_{i}")(x, mask, deterministic)
        return x


class TransformerLM(nn.Module):
    """Decoder-only causal LM (the flagship model for benchmarking)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, *, deterministic: bool = True):
        cfg = self.cfg
        B, L = tokens.shape
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                         dtype=cfg.dtype, name="token_embed")
        pos_embed = self.param(
            "pos_embed", nn.initializers.normal(0.02),
            (cfg.max_len, cfg.hidden_size), jnp.float32)
        if cfg.position_fn is not None:
            pos_ids = cfg.position_fn(L)
            pos = pos_embed[pos_ids]
            # The gather clamps out-of-range ids (repeating the last row —
            # silently wrong embeddings when max_len does not cover
            # shards x local_len); poison them to NaN so the loss goes
            # NaN on the first step instead.
            oob = (pos_ids < 0) | (pos_ids >= cfg.max_len)
            pos = jnp.where(oob[:, None], jnp.nan, pos)
        else:
            pos = pos_embed[:L]
        x = embed(tokens) + pos[None].astype(cfg.dtype)
        x = nn.Dropout(cfg.dropout_rate)(x, deterministic=deterministic)
        causal = nn.make_causal_mask(tokens, dtype=jnp.bool_)
        x = Encoder(cfg, name="encoder")(x, causal, deterministic)
        x = nn.LayerNorm(dtype=cfg.dtype, name="ln_final")(x)
        # weight-tied readout
        logits = embed.attend(x.astype(jnp.float32))
        return logits


def lm_loss_head(logits, batch):
    """Next-token cross entropy with optional per-token weights.

    ``ll = logit[target] - logsumexp``: same math as log_softmax + take,
    minus one full [B, L, V] materialization (HBM traffic)."""
    targets = batch["y"]
    weights = batch.get("w")
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    target = jnp.take_along_axis(logits, targets[..., None],
                                 axis=-1)[..., 0]
    ll = target - lse
    if weights is None:
        weights = jnp.ones_like(ll)
    loss = -(ll * weights).sum() / jnp.maximum(weights.sum(), 1.0)
    acc = ((logits.argmax(-1) == targets) * weights).sum() \
        / jnp.maximum(weights.sum(), 1.0)
    return loss, {"accuracy": acc}
