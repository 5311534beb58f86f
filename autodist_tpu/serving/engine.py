"""Batched-inference engine on the Strategy IR's tensor-parallel specs.

The decode program reuses the training stack's hard parts instead of
growing a second model implementation:

* **Prefill** runs the prompt through the same column/row-parallel
  matmul boundaries as the training stage_fn
  (:mod:`autodist_tpu.parallel.tensor` — the ``PartitionerConfig`` spec
  table that answers "how do I train this" also answers "how do I serve
  it", the GSPMD one-IR property), filling the TP-sharded KV cache and
  emitting the first token from *last-position-only* logits.
* **Decode** runs a fused multi-step loop — the ``run_steps``
  steps-per-loop idea repurposed for token steps: one ``lax.scan`` body
  per token, one host dispatch per ``decode_steps`` tokens — attending
  over the cache via in-place ``dynamic_update_slice`` writes.  The
  greedy epilogue (:func:`~autodist_tpu.parallel.tensor
  .vocab_parallel_greedy_token`) keeps the live logits at ``[B, V/tp]``,
  so a decode step never materializes a full-vocab or full-sequence
  buffer (``tools/hlo_probe.py --probe decode`` asserts both
  structurally).

Parameters arrive in the *logical* layout every fetch path produces —
``runner.get_params()`` from a live pipelined-LM runner, or the
``params/`` tree of a ``checkpoint/export.py`` artifact — and the
engine shards them itself from the same rule tables the ``Pipeline``
builder records in the Strategy IR (``PIPELINE_TP_RULES`` /
``PIPELINE_VOCAB_RULES``).  Use :func:`autodist_tpu.serving.serve` for
the entry-point conveniences.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import weakref
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from autodist_tpu import const, telemetry
from autodist_tpu.serving import kv_cache
from autodist_tpu.telemetry import account
from autodist_tpu.utils.stack_room import (FirstCallWithRoom,
                                           call_with_stack_room)
from autodist_tpu.parallel.tensor import (normalize_comm_overlap, vocab_pad,
                                          vocab_parallel_embedding,
                                          vocab_parallel_greedy_token)


@dataclasses.dataclass
class DecodeWindow:
    """One decode window's host-visible outcome — the batcher's unit of
    emission.  ``tokens`` is ``[n, B]`` with column ``i`` valid through
    ``counts[i]`` (vanilla windows emit a fixed ``decode_steps`` per
    active slot; speculative windows emit ``accepted + 1`` — variable,
    but never zero for an active slot, so forward progress is
    unconditional).  ``spec_proposed``/``spec_accepted`` feed the
    acceptance-rate telemetry; both all-zero on vanilla windows."""

    tokens: np.ndarray
    counts: np.ndarray
    spec_proposed: np.ndarray
    spec_accepted: np.ndarray


# The shortest prompt length a prefill program is built for.  A rung is
# worth a program of its own only where the shorter row is shorter in
# time, and under 256 positions no served stack's is: a routed row reads
# every held expert from ~128 positions x 8-10 experts a token up, GPT-2
# large's [1, 128] row would be 1.4 ms of products under 1.9 ms of weight
# reads, and Ouro's [1, 256] row (42.4 ms) is already within about 2x of
# its four passes' weight reads, so a [1, 128] rung there is worth under
# 3% of device time (PERF.md section 6, PR 42).  The delta rule's chunk
# of 64 and the attention kernels' blocks all divide 256.
MIN_PREFILL_RUNG = 256


# the kernel that advances a linear mixer's state by a position, by rule
_STATE_KERNELS = {"delta": "delta_step", "retention": "retention_step",
                  "ssd": "ssd_step"}


def prefill_rungs(prefill_len: int) -> tuple:
    """The prompt lengths an engine builds a one-row prefill program for,
    ascending: ``prefill_len`` and its halves down to
    :data:`MIN_PREFILL_RUNG` (512 -> ``(256, 512)``; 1024 -> ``(256, 512,
    1024)``; 256 and anything shorter: itself alone)."""
    rungs = [int(prefill_len)]
    while rungs[-1] % 2 == 0 and rungs[-1] // 2 >= MIN_PREFILL_RUNG:
        rungs.append(rungs[-1] // 2)
    return tuple(reversed(rungs))


def serving_param_specs(params, tp: int, vocab_parallel: bool):
    """Per-leaf ``PartitionSpec`` tree for the serving mesh, from the
    SAME rule tables the ``Pipeline`` builder writes into the Strategy
    IR: stage leaves keep their stacked leading layer dim unsharded and
    shard the Megatron dims the tp rules name; the shared tied table
    shards its vocab dim iff ``vocab_parallel``; everything else
    replicates."""
    import re

    from autodist_tpu.kernel import common
    from autodist_tpu.strategy.parallel_builders import (
        PIPELINE_TP_RULES, PIPELINE_VOCAB_RULES)

    tp_rules = [(re.compile(p), s) for p, s in PIPELINE_TP_RULES]
    v_rules = [(re.compile(p), s) for p, s in PIPELINE_VOCAB_RULES]

    def spec_for(name, leaf):
        shape = tuple(np.shape(leaf))
        if tp > 1 and name.startswith("stages/"):
            for pat, spec in tp_rules:
                if pat.search(name) and len(spec) == len(shape) - 1:
                    for dim, axis in zip(shape[1:], spec):
                        if axis == const.MODEL_AXIS and dim % tp:
                            raise ValueError(
                                f"{name}: dim {dim} does not divide by "
                                f"tensor_parallel={tp}")
                    return P(None, *spec)
        if tp > 1 and vocab_parallel and name.startswith("shared/"):
            short = name[len("shared/"):]
            for pat, spec in v_rules:
                if pat.search(short) and len(spec) == len(shape):
                    return P(*spec)
        return P()

    return common.tree_from_names(params, spec_for)


def seed_engine_kwargs(engine_kwargs: dict, strategy) -> dict:
    """Default the serving parallelism knobs from a training strategy's
    Strategy-IR ``parallel`` record (explicit kwargs win) — the single
    definition behind every ``strategy=`` entry point, so a new
    Strategy-IR serving knob cannot be seeded by one path and missed by
    another."""
    if strategy is not None:
        from autodist_tpu.strategy.ir import (normalize_kv_layout,
                                              normalize_prefill_chunk,
                                              normalize_prefix_caching,
                                              normalize_speculative)

        par = strategy.graph_config.parallel or {}
        engine_kwargs.setdefault(
            "tensor_parallel", int(par.get("tensor_parallel", 1) or 1))
        engine_kwargs.setdefault(
            "vocab_parallel", bool(par.get("vocab_parallel", False)))
        engine_kwargs.setdefault("comm_overlap", par.get("comm_overlap"))
        engine_kwargs.setdefault(
            "kv_layout", normalize_kv_layout(par.get("kv_layout")))
        # The throughput-ladder knobs (PR 16) ride the same parallel
        # record; all three normalize to OFF when absent, so pre-PR-16
        # strategies seed exactly the pre-PR-16 engine.  A speculative
        # election still needs the caller to hand the engine its draft
        # model (draft_cfg/draft_params) — the IR records the decision,
        # not the weights.
        engine_kwargs.setdefault(
            "prefill_chunk",
            normalize_prefill_chunk(par.get("prefill_chunk")))
        engine_kwargs.setdefault(
            "prefix_caching",
            normalize_prefix_caching(par.get("prefix_caching")))
        engine_kwargs.setdefault(
            "speculative", normalize_speculative(par.get("speculative")))
        kern = getattr(strategy.graph_config, "kernel", None)
        if kern:
            engine_kwargs.setdefault("kernel", dict(kern))
    return engine_kwargs


class ServingEngine:
    """Prefill/decode engine for the pipelined transformer LM family.

    ``params``: the logical ``{"stages": ..., "shared": ...}`` tree of
    :func:`~autodist_tpu.models.pipeline_lm.make_pipeline_lm_trainable`
    (stacked per-layer leaves + tied embedding/unembedding).  Slots,
    prompt bucket, and the fused-decode width are static so the whole
    serving loop is a fixed handful of compiled programs — a prefill
    over ONE ``[1, S]`` row for each rung ``S`` of
    :func:`prefill_rungs` (``prefill_len`` and its halves down to 256),
    with the slot it is admitted to a traced scalar, run once per
    admitted slot at the shortest rung the prompt fits, and the fused
    decode over all slots.  The engine's first dispatch makes them all
    (:meth:`_prepare`), so no program compiles at its first row:

    * ``num_slots`` — batch slots the continuous batcher fills;
    * ``prefill_len`` — the prompt bucket, and the top rung (prompts
      zero-padded up to their rung; padded positions write garbage k/v
      that masked reads never see and forward decode overwrites);
    * ``decode_steps`` — tokens per fused decode dispatch (``K``).

    ``tensor_parallel``/``vocab_parallel``/``comm_overlap`` mirror the
    training ``Pipeline`` knobs; with ``tensor_parallel == 1`` the same
    code runs unsharded with zero collectives (the decode goldens'
    sequential-reference property).

    ``kv_layout`` (Strategy-IR serving knob, ``"dense"``/``"paged"``):
    ``"paged"`` replaces the per-slot ``max_len`` lanes with a block
    pool of ``kv_num_blocks`` blocks of ``kv_block_len`` positions and
    a per-slot block table — requests reserve only the blocks their
    ``prompt + budget`` span needs (:meth:`blocks_needed` /
    :meth:`reserve_slot` / :meth:`release_slot`), so the batcher admits
    against free blocks, not slots, and ``num_slots`` may exceed what
    the pool could hold at ``max_len``.

    ``temperature``/``top_k`` (the sampling rung): ``temperature == 0``
    (default) compiles the exact greedy program; ``> 0`` samples via
    the shard-invariant gumbel-max epilogue keyed per (request seed,
    context length) — see
    :func:`~autodist_tpu.parallel.tensor.vocab_parallel_sample_token`.
    """

    def __init__(self, cfg, params, *, tensor_parallel: int = 1,
                 vocab_parallel: bool = False, comm_overlap=None,
                 kernel=None,
                 num_slots: int = 4, max_len: Optional[int] = None,
                 prefill_len: Optional[int] = None, decode_steps: int = 8,
                 kv_layout: str = "dense",
                 kv_block_len: Optional[int] = None,
                 kv_num_blocks: Optional[int] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 prefill_chunk: Optional[int] = None,
                 prefix_caching: bool = False,
                 speculative: Optional[int] = None,
                 draft_cfg=None, draft_params=None,
                 devices=None):
        t_init = account.constructing("engine")
        from autodist_tpu.strategy.ir import (normalize_kernel,
                                              normalize_kv_layout,
                                              normalize_prefill_chunk,
                                              normalize_prefix_caching,
                                              normalize_speculative)

        self.cfg = cfg
        # The fused-kernel election (Strategy IR kernel slot): only
        # flash_decode changes the serving programs — prefill/decode
        # have no grad sync or matmul-overlap ring for the training
        # kernels to replace.
        self.kernel = normalize_kernel(kernel)
        # {"flash_decode": False} forbids the decode kernel, and the
        # canonical slot keeps no False for it: the word goes with the
        # others to the layout's election (with no word on it, that
        # elects from what it observes).
        if isinstance(kernel, dict) and "flash_decode" in kernel:
            self.kernel.setdefault("flash_decode", False)
        attn_fn = getattr(cfg, "attention_fn", None)
        if attn_fn is not None:
            from autodist_tpu.ops.flash_attention import \
                is_flash_attention_fn
            if not is_flash_attention_fn(attn_fn):
                # The decode step attends over the cache with its own
                # masked kernel; an unrecognized attention_fn (ring,
                # hand-rolled) would serve different numerics than it
                # trained with — reject rather than drift, naming the
                # supported kernel.
                raise NotImplementedError(
                    "serving supports cfg.attention_fn only for the "
                    "flash-attention family (autodist_tpu.ops."
                    "make_attention_fn / flash_attention — numerics-"
                    "equivalent to the trained einsum path, decode "
                    "served by the flash-decode cache kernel); got "
                    f"{getattr(attn_fn, '__name__', attn_fn)!r} — "
                    "clear attention_fn or use the supported kernel")
            # Flash prefill ⇒ flash decode: the decode-parity gate (the
            # greedy goldens pin decode token-for-token against the
            # sequential_logits reference, which runs the same
            # attention_fn).
            self.kernel = dict(self.kernel, flash_decode=True)
        if cfg.dropout_rate or cfg.attention_dropout_rate:
            raise ValueError(
                "serving requires dropout_rate == "
                "attention_dropout_rate == 0 (inference mode)")
        # ---- the block (cfg.block): every layer function below reads
        # it; the default is the post-LN block and its programs --------
        spec = cfg.block
        if spec.exit_threshold != 1.0:
            raise ValueError(
                f"exit_threshold={spec.exit_threshold}: the engine runs "
                f"all {spec.loop_steps} passes for every token, which is "
                "what the threshold 1.0 selects; adaptive exit depth "
                "(the cache rows of skipped passes) is not served")
        if spec.latent is not None and int(tensor_parallel) > 1:
            raise ValueError(
                f"tensor_parallel={tensor_parallel} with a latent KV row: "
                "the cache holds one row a position that every query head "
                "reads, and a row has no head axis for the model axis to "
                "split (heads split with the row replicated is not "
                "served)")
        if not spec.is_default and int(tensor_parallel) > 1:
            raise ValueError(
                f"tensor_parallel={tensor_parallel} with a non-default "
                f"block ({spec}): the Megatron rule tables name the "
                "default block's leaves only (no rule splits a linear "
                "mixer's heads, its recurrent state — a delta rule's "
                "value heads, power retention's key/value heads, a "
                "state-space (ssd) layer's groups — or a routed FFN's "
                "experts)")
        # a mixed stack: the full layers cache keys and values (the
        # latent ones a row), the linear ones keep a recurrent state
        # (kv_cache.RecurrentState)
        self._kinds = spec.layer_kinds(cfg.num_layers)
        self.linear_layers = self._kinds.count("linear")
        # of the layers that cache, those whose position is a latent row
        # (the batcher counts the rows a window reads by it)
        self.latent_layers = self._kinds.count("latent")
        if cfg.num_heads % cfg.kv_heads:
            raise ValueError(f"num_heads={cfg.num_heads} must be a "
                             f"multiple of kv_heads={cfg.kv_heads}")
        tp = int(tensor_parallel)
        if tp < 1:
            raise ValueError("tensor_parallel must be >= 1")
        if tp > 1 and cfg.num_heads % tp:
            raise ValueError(
                f"num_heads={cfg.num_heads} must divide by "
                f"tensor_parallel={tp}")
        self.tensor_parallel = tp
        self.vocab_parallel = bool(vocab_parallel) and tp > 1
        self.comm_overlap = normalize_comm_overlap(comm_overlap)
        self.num_slots = int(num_slots)
        self.max_len = int(max_len or cfg.max_len)
        if self.max_len > cfg.max_len:
            raise ValueError(
                f"max_len={self.max_len} exceeds the model's trained "
                f"position table ({cfg.max_len})")
        self.prefill_len = int(prefill_len or min(self.max_len, 16))
        if self.prefill_len > self.max_len:
            raise ValueError("prefill_len must be <= max_len")
        self.decode_steps = int(decode_steps)
        # ---- KV layout (Strategy-IR serving knob): dense per-slot
        # lanes, or the block-paged pool + table --------------------------
        self.kv_layout = normalize_kv_layout(kv_layout)
        self.kv_block_len = int(kv_block_len or min(16, self.max_len))
        if self.kv_block_len < 1:
            raise ValueError("kv_block_len must be >= 1")
        self.max_blocks = kv_cache.blocks_for(self.max_len,
                                              self.kv_block_len)
        # Default pool: byte parity with the dense cache (num_slots full
        # lanes) — the capacity win comes from admitting MORE slots than
        # the pool could hold at max_len, gated on free blocks.
        self.kv_num_blocks = int(kv_num_blocks
                                 or self.num_slots * self.max_blocks)
        # ---- throughput-ladder knobs (PR 16): chunked prefill, prefix
        # caching, speculative decoding — all Strategy-IR seeded ---------
        self.prefill_chunk = normalize_prefill_chunk(prefill_chunk)
        self.prefix_caching = normalize_prefix_caching(prefix_caching)
        self.speculative = normalize_speculative(speculative)
        # ---- the cache layout (kv_cache.py's seam): decided there, once,
        # from the block and the knobs, with the decode-attention kernel;
        # what it does not serve of the knobs asked for, it refuses by
        # name.  Everything below meets it through ``self.kv`` alone ------
        self.kv = kv_cache.layout_for(
            cfg, self.kernel, num_slots=self.num_slots,
            max_len=self.max_len, kv_layout=self.kv_layout,
            kv_block_len=self.kv_block_len,
            kv_num_blocks=self.kv_num_blocks,
            prefix_caching=self.prefix_caching,
            prefill_chunk=self.prefill_chunk, speculative=self.speculative)
        # the words as the layout's elections left them
        self.kernel = self.kv.kernel
        # the layers that cache: every pass's (none where all are linear)
        self.cache_layers = self.kv.dims[0]
        if self.prefill_chunk is not None \
                and self.prefill_chunk % self.kv_block_len:
            raise ValueError(
                f"prefill_chunk={self.prefill_chunk} must be a "
                f"multiple of kv_block_len={self.kv_block_len} so "
                "chunk writes stay block-granular")
        if self.speculative is not None \
                and (draft_cfg is None or draft_params is None):
            raise ValueError(
                "speculative decoding needs a draft model: pass "
                "draft_cfg and draft_params (the Strategy IR records "
                "the k election, not the weights)")
        # ---- sampling rung (temperature == 0 is the exact greedy
        # program: the sampler is never traced, so the compiled decode
        # stays bit-identical to the greedy goldens) ----------------------
        self.temperature = float(temperature)
        if self.temperature < 0.0:
            raise ValueError("temperature must be >= 0")
        self.top_k = int(top_k)
        if self.top_k < 0:
            raise ValueError("top_k must be >= 0")
        self._axis = const.MODEL_AXIS if tp > 1 else None

        # ``devices=`` places the engine: a tp>1 engine's mesh is its
        # first tp entries; a tp=1 engine commits its params and cache
        # to devices[0], and the compiled programs follow their
        # operands there — so N one-chip engines in one process can sit
        # on N chips.  With no ``devices`` a tp=1 engine stays
        # uncommitted on jax's default device.
        self._device = devices[0] if devices and tp == 1 else None
        if devices is None:
            devices = jax.devices()
        if tp > len(devices):
            raise ValueError(
                f"tensor_parallel={tp} needs {tp} devices; "
                f"{len(devices)} visible")
        self.mesh = (Mesh(np.array(devices[:tp]), (const.MODEL_AXIS,))
                     if tp > 1 else None)

        # ---- parameters: pad the vocab-sharded table, shard per the
        # Strategy-IR rule tables, place once ---------------------------
        params = jax.tree.map(jnp.asarray, params)
        if self.vocab_parallel:
            pad = vocab_pad(cfg.vocab_size, tp)
            if pad:
                emb = params["shared"]["embedding"]
                params = dict(params, shared=dict(
                    params["shared"],
                    embedding=jnp.pad(emb, ((0, pad), (0, 0)))))
        self._param_specs = serving_param_specs(params, tp,
                                                self.vocab_parallel)
        if self.mesh is not None:
            shardings = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), self._param_specs,
                is_leaf=lambda x: isinstance(x, P))
            params = jax.tree.map(jax.device_put, params, shardings)
        elif self._device is not None:
            params = jax.device_put(params, self._device)
        self.params = params

        # ---- cache + per-slot decode state -----------------------------
        # The current-token vector is placed where the programs will
        # return it: a placed engine whose first prefill saw an
        # uncommitted ``_tok`` and whose second saw the program's own
        # committed output compiled its prefill twice (seen on the chip:
        # ~6 s of the second batch, PR 21).
        self._tok = jnp.zeros((self.num_slots,), jnp.int32)
        if self.mesh is not None:
            self._tok = jax.device_put(self._tok,
                                       NamedSharding(self.mesh, P()))
        elif self._device is not None:
            self._tok = jax.device_put(self._tok, self._device)
        self._sample_seeds = np.zeros((self.num_slots,), np.int32)
        cache = self.kv.init_cache(self.kv.dims, cfg.dtype)
        if self.mesh is not None:
            # the k/v arrays split by heads, everything else replicated
            csh = NamedSharding(self.mesh, kv_cache.cache_spec())
            rep = NamedSharding(self.mesh, P())
            cache = jax.tree.map(lambda a: jax.device_put(
                a, csh if a.ndim == 5 else rep), cache)
        if self._device is not None:
            cache = jax.device_put(cache, self._device)
        self.cache = cache
        telemetry.gauge("engine/cache_layers").set(self.cache_layers)
        # what the layout holds for a token and for a slot, in its words
        for name, value in self.kv.gauges(cfg.dtype).items():
            telemetry.gauge(name).set(value)
        if spec.moe is not None:
            telemetry.gauge("engine/experts_held").set(spec.moe.experts_held)
            # a decode step's routed layer: 1 this repo's grouped-matmul
            # kernel over the experts hit, 0 two ragged_dot (what the
            # call in parallel.moe.routed_experts will observe: the last
            # layer of a routed stack is a routed one)
            from autodist_tpu.kernel.pallas.grouped_matmul import \
                grouped_matmul_elected
            from autodist_tpu.models.pipeline_lm import layer_chunk
            experts = jax.eval_shape(
                lambda stages: layer_chunk(cfg, stages, cfg.num_layers - 1)
                ["moe"]["experts"], self.params["stages"])
            telemetry.gauge("kernel/grouped_matmul_elected").set(int(
                experts["wi"].dtype == experts["wo"].dtype == cfg.dtype
                and grouped_matmul_elected(
                    self.kernel.get("grouped_matmul"),
                    self.num_slots * spec.moe.top_k, cfg.hidden_size,
                    spec.moe.expert_width, cfg.dtype)))

        # ---- the programs: a one-row prefill a rung (chunked: the one
        # window program) and the fused decode.  ``_prefill_jit`` is the
        # top rung's; ``_compiled`` holds every program's executable
        # once the first dispatch has prepared them -----------------------
        self._rung_jits = ({} if self.prefill_chunk is not None else
                           {S: self._build_prefill(S)
                            for S in prefill_rungs(self.prefill_len)})
        self.prefill_rungs = tuple(self._rung_jits)
        self._prefill_jit = (self._build_chunk_prefill()
                             if self.prefill_chunk is not None
                             else self._rung_jits[self.prefill_len])
        self._decode_jit = self._build_decode()
        self._compiled = None
        self._decode1_jit = None           # lazy K=1 program (catch-up)
        self.last_prefill_chunks = 0
        self._counts_prefill = True

        # ---- speculative draft: a nested engine sharing the cache
        # layout (same block scheme, own pool/params), run unsharded —
        # the draft is small by construction and its choices are shard-
        # invariant anyway (the gumbel keys are (seed, position)) -------
        self.draft = None
        if self.speculative is not None:
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab_size={draft_cfg.vocab_size} must "
                    f"match the target's {cfg.vocab_size} — accept/"
                    "reject compares token ids")
            self.draft = ServingEngine(
                draft_cfg, draft_params, tensor_parallel=1,
                vocab_parallel=False, num_slots=self.num_slots,
                max_len=self.max_len, prefill_len=self.prefill_len,
                decode_steps=self.speculative, kv_layout=self.kv_layout,
                kv_block_len=self.kv_block_len,
                temperature=self.temperature, top_k=self.top_k,
                prefill_chunk=self.prefill_chunk,
                devices=self._device and [self._device])
            # the prefill counters say what the TARGET's program
            # computed for the prompts it admitted
            self.draft._counts_prefill = False
            self._spec_verify_jit = self._build_spec_verify()
            self._spec_catch = np.zeros((self.num_slots,), bool)
            self._spec_catch_tok = np.zeros((self.num_slots,), np.int32)

        gauges = {k: True for k in ("flash_decode", "flash_prefill")
                  if self.kernel.get(k)}
        if gauges:
            # The serving-side kernel/<name>_elected gauge (the pipeline
            # lowering emits the training kernels' gauges) — schema-
            # gated by `tools/telemetry_report.py --check`.
            from autodist_tpu.parallel._spmd import emit_kernel_gauges
            emit_kernel_gauges(gauges)
        if self.linear_layers:
            # the recurrent state's decode step: 1 where the layout's
            # seam takes the rule's fused kernel, 0 where the composed step
            telemetry.gauge(
                f"kernel/{_STATE_KERNELS[spec.linear.rule]}_elected").set(
                int(self.kv.state_kernel(self.cache.state.ssm,
                                         cfg.num_heads // cfg.kv_heads)))
        account.constructed(t_init)

    def __setattr__(self, name, value):
        """Handing the engine one of its own methods back (a caller that
        wrapped ``prefill`` or ``decode_window`` to observe them, and
        restores them) removes the override: stored, the bound method
        would tie the engine to itself, and its device memory to the
        cycle collector (see :meth:`_wrap`)."""
        if getattr(value, "__self__", None) is self and getattr(
                value, "__func__", None) is getattr(type(self), name, None):
            self.__dict__.pop(name, None)
        else:
            object.__setattr__(self, name, value)

    # ------------------------------------------------------------------ #
    # constructors from the training stack
    # ------------------------------------------------------------------ #
    @classmethod
    def from_runner(cls, runner, cfg, *, strategy=None, **kw):
        """Serve a live runner's parameters (fetched through the
        gather/unpad path, any training strategy).  When the training
        ``strategy`` is given, its Strategy-IR parallel knobs
        (``tensor_parallel``/``vocab_parallel``/``comm_overlap``) seed
        the serving config unless overridden."""
        return cls(cfg, runner.get_params(),
                   **seed_engine_kwargs(kw, strategy))

    @classmethod
    def from_artifact(cls, path: str, cfg, **kw):
        """Serve a ``checkpoint/export.py`` artifact's ``params/``
        tree (logical names, unpadded shapes)."""
        from autodist_tpu.checkpoint.export import load_exported_params

        return cls(cfg, load_exported_params(path), **kw)

    # ------------------------------------------------------------------ #
    # the model math (one definition serves tp=1 and the shard_map path)
    # ------------------------------------------------------------------ #
    @telemetry.scope("embed")
    def _embed(self, shared, tokens, positions):
        """Token (+ learned position) embedding for ``[B, S]`` token ids
        at per-token ``positions`` (``[B, S]`` or a static ``[S]``); a
        rotary block's positions enter inside attention instead, and a
        block without positions has none to add.  The rows times the
        block's ``embedding_multiplier``."""
        from autodist_tpu.models.pipeline_lm import embedding_rows

        cfg = self.cfg
        x = embedding_rows(cfg, vocab_parallel_embedding(
            tokens, shared["embedding"], model_axis=self._axis
            if self.vocab_parallel else None,
            comm_overlap=self.comm_overlap).astype(cfg.dtype))
        if cfg.block.positions != "learned":
            return x
        pos = jnp.take(shared["pos_embed"], positions, axis=0)
        return x + pos.astype(cfg.dtype)

    def _layer_prefill(self, chunk, x, mask, positions, valid=None):
        """One encoder layer over the whole prompt — the training
        :func:`~autodist_tpu.models.pipeline_lm._tp_encoder_layer`
        itself (``return_kv=True`` hands back the layer's k/v
        projections for the cache fill — of a latent-attention layer,
        which attends the prompt in the expanded form, the rows), so the
        serving forward cannot drift from the trained math."""
        from autodist_tpu.models.pipeline_lm import _tp_encoder_layer

        return _tp_encoder_layer(self.cfg, chunk, x, mask, self._axis,
                                 comm_overlap=self.comm_overlap,
                                 return_kv=True, positions=positions,
                                 valid=valid)

    def _layer_cached(self, chunk, x, kc, vc, positions, attend,
                      valid=None, tally=None):
        """One encoder layer for tokens at ``positions`` against the
        live cache — a single-token decode step, or the ``[B, C]``
        window chunked prefill and the speculative verify pass share.
        Project qkv, then ``attend(q, k, v, kc, vc) -> (out, kc, vc)``
        writes this layer's k/v in place and attends over the cache,
        which by then holds every earlier position AND these rows
        (write-then-attend) — how, and whether as one kernel, is the
        cache layout's (``self.kv``).  The sub-blocks around the
        attention are the pipelined LM's own (``attention_inputs`` ..
        ``ffn_residual``; ``valid`` and ``tally`` are the latter's).  A
        latent-attention layer hands ``attend`` its absorbed queries and
        the position's row (``latent_absorbed``) where the others hand
        q, k and v."""
        from autodist_tpu.models import pipeline_lm as lm

        cfg, axis, overlap = self.cfg, self._axis, self.comm_overlap
        if "latent_attention" in chunk:
            # the absorbed form: the row is written, then every query
            # head attends over the rows as they are cached
            def over_rows(q, row):
                out, *caches = attend(q, row, None, kc, vc)
                return out, caches

            x, (kc, vc) = lm.latent_absorbed(cfg, chunk, x, positions,
                                             over_rows)
            return self._ffn(chunk, x, valid, tally), kc, vc
        x, q, k, v, gate = lm.attention_inputs(
            cfg, chunk, x, positions, axis, overlap)     # [B, C, heads, dh]
        out, kc, vc = attend(q, k, v, kc, vc)
        x = lm.attention_residual(cfg, chunk, x, out, axis, overlap, gate)
        return self._ffn(chunk, x, valid, tally), kc, vc

    def _ffn(self, chunk, x, valid=None, tally=None):
        from autodist_tpu.models.pipeline_lm import ffn_residual

        # a routed layer elects its grouped matmul where it is called;
        # the kernel slot's word (True, False or none) goes with it
        return ffn_residual(self.cfg, chunk, x, self._axis,
                            self.comm_overlap, valid=valid, tally=tally,
                            kernel=self.kernel.get("grouped_matmul"))

    def _layer_linear(self, chunk, x, state, layer, positions, *,
                      valid=None, tally=None):
        """A decode step of one linear layer against the recurrent state
        the cache manager holds (``state``: its arrays; ``layer``: the
        layer's place among the linear ones; ``positions``: the rows',
        for a rule whose q and k are rotated).  It advances every slot's
        rows: the recurrent matrix (and power retention's normaliser)
        where the manager keeps it, through the layout's seam
        (``self.kv.advance_state`` / ``advance_retention`` /
        ``advance_ssd``: the stacked arrays go in and come out, no slice
        of them here), the convolution tail of the rules that have one
        read and written here.  (The prompt's pass through such a layer is
        :meth:`_build_prefill`'s.)"""
        from autodist_tpu.models import pipeline_lm as lm

        rule = self.cfg.block.linear.rule
        if rule == "retention":
            x, state = lm.retention_attention(
                self.cfg, chunk, x, state, positions,
                step=functools.partial(self.kv.advance_retention,
                                       layer=layer))
            return self._ffn(chunk, x, valid, tally), state
        # both arrays, as the benchmark's planted faults wrap it (the
        # slice of the matrices is dead code, and compiled away)
        tail, _ = kv_cache.read_state(state, layer)
        # the rule's mixer, its seam, and what the tail's write back is a
        # part of (a state-space layer's: the convolution's shift)
        mixer, seam, write = {
            "delta": (lm.linear_attention, self.kv.advance_state,
                      "state_update"),
            "ssd": (lm.ssd_attention, self.kv.advance_ssd, "state_conv"),
        }[rule]
        x, (tail, ssm) = mixer(
            self.cfg, chunk, x, (tail, state[1]),
            step=functools.partial(seam, layer=layer))
        with telemetry.scope("linear_attention"), telemetry.scope(write):
            state = (*kv_cache.write_state(state[:1], layer, (tail,)), ssm)
        return self._ffn(chunk, x, valid, tally), state

    def _run_layers(self, shared, stages, x, kc, vc, layer_fn, state=(),
                    linear_fn=None):
        """Every layer of the stack over ``(x, kc, vc, state)``, once or
        — a looped stack — ``loop_steps`` times
        (:func:`~autodist_tpu.models.pipeline_lm.run_stack`), whatever
        kinds of layer it mixes.  A layer that caches (a full or a latent
        one) goes to ``layer_fn(chunk, x, kc, vc, l, cache_layer)``:
        ``l`` is the weights' layer (static), and ``cache_layer`` where
        its keys and values (its rows) live in the cache manager's
        arrays, the ``nth`` caching layer of pass ``u`` at ``u *
        (caching layers a pass) + nth`` (traced for a looped stack,
        ``nth`` for one pass).  A linear one goes to ``linear_fn(chunk,
        x, state, nth)`` with the recurrent ``state`` arrays, ``nth`` its
        place among the linear ones; a stack without any hands ``state``
        back the empty tuple it came as."""
        from autodist_tpu.models.pipeline_lm import layer_chunk, run_stack

        kinds = self._kinds
        caching = len(kinds) - self.linear_layers

        def layers(u, carry):
            x, kc, vc, state = carry
            for l, kind in enumerate(kinds):
                chunk = layer_chunk(self.cfg, stages, l)
                nth = kinds[:l].count(kind)
                if kind == "linear":
                    x, state = linear_fn(chunk, x, state, nth)
                else:
                    x, kc, vc = layer_fn(chunk, x, kc, vc, l,
                                         u * caching + nth)
            return x, kc, vc, state

        return run_stack(self.cfg, shared, (x, kc, vc, state), layers)

    def _head(self, shared, h):
        """``(rows, table)`` of the output projection for ``[B, H]``
        last-position hidden states: the final norm of the rows (the
        training loss head's; a looped stack's last pass has applied it)
        and the tied or untied table."""
        from autodist_tpu.models.pipeline_lm import head_rows, head_table

        return head_rows(self.cfg, shared, h), head_table(self.cfg, shared)

    def _greedy(self, shared, h):
        """Next token from ``[B, H]`` last-position hidden states."""
        x, table = self._head(shared, h)
        return vocab_parallel_greedy_token(
            x, table, vocab_size=self.cfg.vocab_size,
            model_axis=self._axis if self.vocab_parallel else None)

    @telemetry.scope("lm_head")
    def _next_token(self, shared, h, seeds, positions):
        """The decode epilogue: greedy at ``temperature == 0`` (the
        exact pre-sampling program — the sampler is never traced), else
        shard-invariant gumbel-max sampling keyed per (request seed,
        context length), so a sampled stream is identical interleaved,
        run-alone, and against the sequential reference."""
        if self.temperature == 0.0:
            return self._greedy(shared, h)
        from autodist_tpu.parallel.tensor import \
            vocab_parallel_sample_token

        x, table = self._head(shared, h)
        return vocab_parallel_sample_token(
            x, table, vocab_size=self.cfg.vocab_size,
            seeds=seeds, positions=positions,
            temperature=self.temperature, top_k=self.top_k,
            model_axis=self._axis if self.vocab_parallel else None)

    # ------------------------------------------------------------------ #
    # compiled programs
    # ------------------------------------------------------------------ #
    def _wrap(self, fn, n_in_rest: int, n_out_rest: int):
        """jit ``fn(params, k, v, *rest)``, shard_mapped over the model
        mesh at tp>1, with the cache arrays donated so updates alias in
        place.  ``n_in_rest``/``n_out_rest`` count the replicated
        non-cache operands/results after ``(params, k, v)`` /
        ``(k, v)``.  A stack with linear layers hands the recurrent
        state's arrays over last, after those (tp=1 only), and they are
        donated like the cache.  Whatever traces and lowers these
        unrolled programs — :meth:`_prepare`, or the first call of one it
        does not make (the lazy K=1 decode) — gets stack room of its own:
        where it stands on the interpreter's frame stack otherwise decides
        whether lowering takes half a second or twenty
        (``utils/stack_room``).

        The builders hand ``fn`` a weak proxy of the engine: a program
        that held the engine that holds it would be a cycle, and an
        engine in a cycle gives its parameters and cache back only when
        the cycle collector finds it — never, on a heap its owner has
        frozen (``gc.freeze``), as a benchmark does before it times."""
        if self.mesh is None:
            first = 3 + n_in_rest
            state = tuple(range(first, first + len(self._state_args())))
            return FirstCallWithRoom(jax.jit(
                fn, donate_argnums=(1, 2) + state))
        cspec = kv_cache.cache_spec()
        sm = jax.shard_map(
            fn, mesh=self.mesh,
            in_specs=(self._param_specs, cspec, cspec)
            + (P(),) * n_in_rest,
            out_specs=(cspec, cspec) + (P(),) * n_out_rest,
            check_vma=False)
        return FirstCallWithRoom(jax.jit(sm, donate_argnums=(1, 2)))

    def _build_prefill(self, S: int):
        """A rung's single-shot prefill program: ONE row, ``[1, S]``,
        with the slot it is admitted to a traced scalar —
        :meth:`prefill` runs it once per admitted slot whose prompt fits
        ``S`` and no shorter rung.  The row's keys and values land in the
        slot's lane (its blocks, paged) and ``tok[slot]`` /
        ``lengths[slot]`` are set inside the program; nothing of any
        other slot is read or written, and the rung's padding (positions
        ``p_len`` to ``S``) reaches neither the recurrent state, nor an
        expert, nor a paged block past the prompt's own."""
        from autodist_tpu.models import pipeline_lm as lm

        self = weakref.proxy(self)      # see _wrap: no cycle through jit
        prefix = self.prefix_caching

        routed = self.cfg.block.moe is not None

        def prefill(params, kc, vc, lengths, tok, slot, table_row, seed,
                    prompt, p_len, *rest):
            # ``slot`` a scalar; ``table_row`` [1, max_blocks]; ``seed``,
            # ``p_len`` [1]; ``prompt`` [1, S].  Prefix-caching engines
            # thread the row's novel-write floor, [1]; a stack with
            # linear layers its recurrent state's arrays.
            wf = rest[0] if prefix else None
            state = rest[1 if prefix else 0:]
            stages, shared = params["stages"], params["shared"]
            positions = jnp.arange(S)
            x = self._embed(shared, prompt, positions)
            mask = jnp.tril(jnp.ones((S, S), bool))[None, None]
            # the bucket's padding: out of the recurrence, and of the
            # routing (a padded row chooses no expert)
            valid = (positions[None, :] < p_len[:, None]
                     if state or routed else None)

            # Traced once a kind of layer: the layers of a kind differ in
            # their weights alone, which are operands, so jax traces such
            # a function at the first of them and binds its equations
            # again at every later one (``inline``: the program is
            # equation for equation what the unrolled trace gives).
            # Nothing in one may depend on the layer's index, so the
            # writes into the cache manager's arrays stay outside.
            once = functools.partial(jax.jit, inline=True)
            attend_prompt = once(lambda chunk, x: self._layer_prefill(
                chunk, x, mask, positions, valid))
            # a linear layer starts from no state — the slot's previous
            # occupant left one that is nobody's — masks the padding out
            # of the recurrence and cuts the convolution's tail at p_len
            mix_prompt = once(lambda chunk, x: lm.mix_linear(
                self.cfg, chunk, x, None, positions, valid=valid,
                length=p_len))
            ffn = once(lambda chunk, x: self._ffn(chunk, x, valid))

            def layer_fn(chunk, x, kc, vc, _, layer):
                x, k, v = attend_prompt(chunk, x)
                kc, vc = self.kv.write_prompt(kc, vc, layer, k, v, slot,
                                              table_row, p_len, wf)
                return x, kc, vc

            def linear_fn(chunk, x, state, layer):
                x, after = mix_prompt(chunk, x)
                with telemetry.scope("linear_attention"), \
                        telemetry.scope("state_update"):
                    state = kv_cache.write_state(state, layer, after, slot)
                return ffn(chunk, x), state

            # (a stack without a linear layer hands the walk its six
            # operands alone: the benchmark's planted fault wraps those)
            x, kc, vc, state = self._run_layers(
                shared, stages, x, kc, vc, layer_fn,
                *((state, linear_fn) if state else ()))
            last = jnp.take_along_axis(
                x, (p_len - 1)[:, None, None], axis=1)[:, 0]
            # The first emitted token conditions on the p_len prompt
            # tokens — its sampling key position.
            first_tok, _ = self._next_token(shared, last, seed, p_len)
            tok = lax.dynamic_update_slice(
                tok, first_tok.astype(tok.dtype), (slot,))
            lengths = lax.dynamic_update_slice(lengths, p_len, (slot,))
            return (kc, vc, lengths, tok, *state)

        return self._wrap(prefill, n_in_rest=7 + (1 if prefix else 0),
                          n_out_rest=2)

    def _build_chunk_prefill(self):
        """The chunked prefill program: ONE compiled ``[B, C]`` window
        serves every chunk of every prompt length (``chunk_start`` is a
        traced scalar), writing k/v block-granularly through the table
        and attending across chunks via the paged chunk attention (the
        flash-prefill kernel when elected).  The slot whose final
        prompt token falls inside this chunk emits its first generated
        token here — other slots pass through — so the host loop's last
        relevant chunk completes exactly what single-shot prefill does,
        token-for-token (the parity golden)."""
        self = weakref.proxy(self)
        C = self.prefill_chunk
        prefix = self.prefix_caching

        def chunk_prefill(params, kc, vc, lengths, tok, table, seeds,
                          chunk_toks, chunk_start, p_lens, admit, *rest):
            wf = rest[0] if prefix else None
            stages, shared = params["stages"], params["shared"]
            x = self._embed(shared, chunk_toks,
                            chunk_start + jnp.arange(C))
            starts = jnp.zeros_like(p_lens) + chunk_start

            def layer_fn(chunk, x, kc, vc, _, layer):
                def attend(q, k, v, kc, vc):    # block-granular write
                    kc, vc = self.kv.write_chunk(
                        kc, vc, layer, k, v, admit, table, chunk_start,
                        p_lens, wf)
                    return self.kv.attend_window(
                        q, kc, vc, layer, starts, table,
                        dtype=self.cfg.dtype), kc, vc

                return self._layer_cached(
                    chunk, x, kc, vc,
                    starts[:, None] + jnp.arange(C)[None, :], attend)

            x, kc, vc, _ = self._run_layers(shared, stages, x, kc, vc,
                                            layer_fn)
            emit_here = admit & (p_lens > chunk_start) \
                & (p_lens <= chunk_start + C)
            last_idx = jnp.clip(p_lens - 1 - chunk_start, 0, C - 1)
            last = jnp.take_along_axis(
                x, last_idx[:, None, None], axis=1)[:, 0]
            first_tok, _ = self._next_token(shared, last, seeds, p_lens)
            tok = jnp.where(emit_here, first_tok, tok)
            lengths = jnp.where(emit_here, p_lens, lengths)
            return kc, vc, lengths, tok

        return self._wrap(chunk_prefill,
                          n_in_rest=8 + (1 if prefix else 0),
                          n_out_rest=2)

    def _build_spec_verify(self):
        """The speculative verify program: feed the current token plus
        the draft's k proposals as one ``[B, k+1]`` window starting at
        each slot's own length, write their k/v token-granularly, and
        return the target's choice at EVERY window position — computed
        by the same epilogue and the same (seed, position) keys vanilla
        decode would use, so the accepted prefix is token-for-token
        (greedy) and draw-for-draw (sampled) what vanilla would have
        emitted.  Lengths do NOT advance here: the host applies the
        accept/reject rule and rolls the rejected tail back by setting
        lengths, which un-materializes the stale rows behind the length
        mask (their blocks stay within the slot's reservation)."""
        self = weakref.proxy(self)
        C = self.speculative + 1

        def verify(params, kc, vc, lengths, tok, table, seeds,
                   tokens_in, active):
            stages, shared = params["stages"], params["shared"]
            positions = lengths[:, None] + jnp.arange(C)[None, :]
            x = self._embed(shared, tokens_in, positions)

            def layer_fn(chunk, x, kc, vc, _, layer):
                def attend(q, k, v, kc, vc):    # token-granular write
                    for c in range(C):
                        kc, vc = self.kv.write_token(
                            kc, vc, layer, k[:, c:c + 1], v[:, c:c + 1],
                            lengths + c, table, active)
                    return self.kv.attend_window(
                        q, kc, vc, layer, lengths, table,
                        dtype=self.cfg.dtype), kc, vc

                return self._layer_cached(chunk, x, kc, vc, positions,
                                          attend)

            x, kc, vc, _ = self._run_layers(shared, stages, x, kc, vc,
                                            layer_fn)
            # Choice at window row c conditions on lengths + 1 + c
            # tokens — exactly the position key the c-th vanilla decode
            # step would use.
            choices = jnp.stack(
                [self._next_token(shared, x[:, c], seeds,
                                  lengths + 1 + c)[0]
                 for c in range(C)], axis=1)         # [B, C]
            return kc, vc, lengths, tok, choices

        return self._wrap(verify, n_in_rest=6, n_out_rest=3)

    def _build_decode(self, steps: Optional[int] = None):
        self = weakref.proxy(self)
        K = int(steps or self.decode_steps)

        routed = self.cfg.block.moe is not None

        def decode(params, kc, vc, lengths, tok, table, seeds, active,
                   *state):
            stages, shared = params["stages"], params["shared"]
            # a slot that is not decoding chooses no expert
            valid = active[:, None] if routed else None

            def body(carry, _):
                kc, vc, lengths, tok, state, routing = carry
                tally = [] if routed else None
                x = self._embed(shared, tok[:, None], lengths[:, None])

                def layer_fn(chunk, x, kc, vc, _, layer):
                    return self._layer_cached(
                        chunk, x, kc, vc, lengths[:, None],
                        lambda q, k, v, kc, vc: self.kv.decode_attend(
                            q, k, v, kc, vc, layer, lengths, table, active,
                            dtype=self.cfg.dtype), valid, tally)

                def linear_fn(chunk, x, state, layer):
                    return self._layer_linear(
                        chunk, x, state, layer, lengths[:, None],
                        valid=valid, tally=tally)

                x, kc, vc, state = self._run_layers(
                    shared, stages, x, kc, vc, layer_fn,
                    *((state, linear_fn) if state else ()))
                # The emitted token conditions on lengths + 1 tokens
                # (the one just written included) — its sampling key.
                nxt, _ = self._next_token(shared, x[:, 0], seeds,
                                          lengths + 1)
                nxt = jnp.where(active, nxt, tok)
                lengths = lengths + active.astype(jnp.int32)
                if routed:
                    routing = (routing[0] + sum(tally),)
                return (kc, vc, lengths, nxt, state, routing), nxt

            # [rows_held, experts_hit] (and, of a router that keeps
            # groups, groups_hit) over the window's steps and layers
            routing = (jnp.zeros((3 if self.cfg.block.moe.groups > 1
                                  else 2,), jnp.int32),) if routed else ()
            (kc, vc, lengths, tok, state, routing), toks = lax.scan(
                body, (kc, vc, lengths, tok, tuple(state), routing), None,
                length=K)
            return (kc, vc, lengths, tok, toks, *state, *routing)

        return self._wrap(decode, n_in_rest=5, n_out_rest=3)

    def _prepare(self) -> dict:
        """Make every program the constructor built, here and now: each
        is traced, lowered (with stack room: see :meth:`_wrap`) and
        compiled in turn, and from then on every dispatch goes through
        the executables — a rung never compiles at its first row,
        whichever lengths the first prompts have.  The operands are typed
        as the host path hands them over (an executable takes no others),
        and donation is the jitted functions' own.

        In turn, on this thread, because that is what the chip's host
        gives: a cached executable's retrieval holds the interpreter as
        tracing does, and the three of the GPT-2 cell's engine on worker
        threads beside the lowering took 7.8 s where one after another
        they take 6.3 (PERF.md section 6, PR 42).  The cycle collector
        is paused meanwhile: a trace allocates containers by the million,
        none of them garbage before it ends, and the collector's passes
        over them were 0.2 s of that engine's 1.7 s of tracing."""
        c = self.cache
        head = (self.params, c.k, c.v, c.lengths, self._tok)
        # numpy where the host path hands a placed copy over: typed the
        # same, and made without a program of its own
        table, seeds = self.kv.table_arg(c), self._sample_seeds
        slots = np.zeros((self.num_slots,), bool)
        todo = {"decode": (self._decode_jit, (
            table, seeds, slots, *self._state_args()))}
        if self.prefill_chunk is not None:
            todo["prefill"] = (self._prefill_jit, self._blank_prefill_args())
        for S, jitted in reversed(self._rung_jits.items()):
            todo[S] = (jitted, self._blank_prefill_args(length=S))
        if self.speculative is not None:
            todo["verify"] = (self._spec_verify_jit, (
                table, seeds,
                np.zeros((self.num_slots, self.speculative + 1), np.int32),
                slots))
        collecting = gc.isenabled()
        gc.disable()
        try:
            with telemetry.span("engine/prepare", programs=len(todo)):
                self._compiled = {
                    key: call_with_stack_room(
                        jitted.lower, *head, *args).compile()
                    for key, (jitted, args) in todo.items()}
        finally:
            if collecting:
                gc.enable()
        return self._compiled

    @property
    def _programs(self) -> dict:
        """The programs' executables, by rung (``"decode"``, ``"verify"``
        and a chunked engine's ``"prefill"`` by name): prepared at the
        first dispatch, whichever program it asks for."""
        return self._compiled or self._prepare()

    # ------------------------------------------------------------------ #
    # host-side block accounting (the batcher's admission predicate),
    # delegated to the layout: kv_cache.PagedLayout holds the pool
    # ------------------------------------------------------------------ #
    def blocks_needed(self, prompt_len: int, max_new_tokens: int,
                      prompt=None) -> int:
        """Pool blocks a request reserves: its worst-case occupancy
        ``min(prompt + budget, max_len)`` rounded up to blocks (0 under
        the dense layout — admission gates on slots alone there).
        Under prefix caching, pass ``prompt`` and the charge drops to
        the NOVEL suffix — shared leading blocks cost nothing (plus one
        pre-funded copy-on-write reserve when the partial tail is
        shared: the block decode writes into must have a private copy
        standing by, or a full pool could deadlock the write)."""
        return self.kv.blocks_needed(prompt_len, max_new_tokens, prompt)

    @property
    def free_blocks(self) -> int:
        """Unreserved pool blocks (dense: the pool concept is vacuous —
        reported as 0 used / 0 free is wrong either way, so dense
        returns a sentinel no admission check consults)."""
        return self.kv.accounting()[0]

    def reserve_slot(self, slot: int, prompt_len: int,
                     max_new_tokens: int, prompt=None) -> int:
        """Map a request's blocks into ``slot``'s table row (paged;
        dense is a no-op).  Under prefix caching (``prompt`` given) the
        leading shared blocks are reference-bumped instead of
        allocated; only the novel suffix (plus one copy-on-write
        reserve for a shared partial tail) draws on the pool.  Returns
        the number of prefix-hit blocks.  Raises
        :class:`~autodist_tpu.serving.kv_cache.PoolExhaustedError` when
        the pool cannot cover it — the batcher checks
        :meth:`blocks_needed` against :attr:`free_blocks` first, so a
        raise here is a bookkeeping bug surfacing loudly (and it raises
        BEFORE any refcount is bumped, so a failed admission leaves the
        pool untouched)."""
        self.cache, n_hit = self.kv.reserve(
            self.cache, slot, prompt_len, max_new_tokens, prompt)
        if self.draft is not None:
            self.draft.reserve_slot(slot, prompt_len, max_new_tokens)
        return n_hit

    def release_slot(self, slot: int) -> None:
        """Return ``slot``'s blocks to the free list (paged; dense is a
        no-op) — under prefix caching this drops ONE reference per
        block, so shared prefixes survive their sharers."""
        self.cache = self.kv.release(self.cache, slot)
        if self.speculative is not None:
            self._spec_catch[slot] = False
        if self.draft is not None:
            self.draft.release_slot(slot)

    def block_accounting(self) -> tuple:
        """``(free, used, total)`` pool blocks — the invariant every
        terminal state must restore is ``free + used == total`` (and
        ``free == total`` once no request is resident).  Dense engines
        report the vacuous ``(0, 0, 0)``."""
        return self.kv.accounting()

    def release_all_slots(self) -> None:
        """Return EVERY slot's blocks to the free list — the abandon
        path: a fleet replica declared dead releases its engine
        wholesale (a real crashed host frees its HBM with it; the
        in-process model must not let the bookkeeping say otherwise)."""
        for slot in range(self.num_slots):
            self.release_slot(slot)

    # ------------------------------------------------------------------ #
    # host-side driver API (the batcher's contract)
    # ------------------------------------------------------------------ #
    @property
    def max_prompt_tokens(self) -> int:
        """Longest admissible prompt: the prefill bucket single-shot;
        the whole context minus one generated token once chunked
        prefill makes long prompts first-class."""
        return (self.max_len - 1 if self.prefill_chunk is not None
                else self.prefill_len)
    def prefill(self, prompts, p_lens, admit, seeds=None):
        """Prefill the admitted slots of the ``[B, S]`` slot batch; each
        adopts its prompt's cache/length and first generated token
        (greedy, or sampled at the engine's temperature under the slot's
        ``seeds`` entry).  Single-shot engines run a rung's ``[1, S]``
        program once per admitted slot — the shortest rung the row's
        prompt fits, an integer comparison here on the host — back to
        back, the cache donated from one dispatch to the next: only
        admitted rows are computed, and of each no more padding than its
        rung's; chunked engines walk the prompt in
        ``prefill_chunk`` windows through ONE compiled ``[B, C]``
        program (``chunk_start`` is traced), skipping leading chunks
        every admitted slot already has cached via prefix hits.  Returns
        the per-slot current token ``[B]`` (numpy), fetched once."""
        with telemetry.span("engine/prefill/stage"):
            prompts_np = np.asarray(prompts)
            p_lens_np = np.asarray(p_lens)
            admit_np = np.asarray(admit, bool)
            if seeds is not None:
                self._sample_seeds = np.where(
                    admit_np, np.asarray(seeds, np.int32),
                    self._sample_seeds).astype(np.int32)
            if self.prefill_chunk is None:
                # One row's operands each, picked here on the host (a
                # slice made with jnp would be a small program of its
                # own, compiled at its first use) into arrays that
                # nothing mutates while a dispatch is in flight.
                rows = np.flatnonzero(admit_np)
                table, seed, prompt, p_len, *wf = (
                    np.asarray(a[rows], np.int32) for a in (
                        self.kv.table, self._sample_seeds, prompts_np,
                        p_lens_np, *((self.kv.write_from,)
                                     if self.prefix_caching else ())))
                # each row's rung: the shortest that holds its prompt
                rungs = self.prefill_rungs
                fits = np.minimum(np.searchsorted(rungs, p_len),
                                  len(rungs) - 1)
                by_rung = {rungs[r]: int(n)
                           for r, n in enumerate(np.bincount(fits)) if n}
                positions = sum(S * n for S, n in by_rung.items())
        if self.prefill_chunk is None:
            with telemetry.span("engine/prefill/dispatch",
                                loop_steps=self.cfg.block.loop_steps,
                                rows=len(rows), positions=positions):
                for i, (slot, r) in enumerate(zip(rows, fits)):
                    S, one, c = rungs[r], slice(i, i + 1), self.cache
                    self._adopt(*self._programs[S](
                        self.params, c.k, c.v, c.lengths, self._tok,
                        np.int32(slot), table[one], seed[one],
                        prompt[one, :S], p_len[one],
                        *(a[one] for a in wf), *self._state_args()))
            self._count_prefill(len(rows), positions, by_rung)
            self.last_prefill_chunks = 1
        else:
            self._chunked_prefill(prompts_np, p_lens_np, admit_np)
        with telemetry.span("engine/prefill/register"):
            if self.prefix_caching:
                self.kv.register(admit_np)
            if self.draft is not None:
                # The draft mirrors the target's resident prompts so its
                # proposals condition on the same context; its
                # first-token emission is discarded (decode_window aligns
                # _tok to the target's before every proposal run).
                self.draft.prefill(prompts_np, p_lens_np, admit_np, seeds)
        with telemetry.span("engine/prefill/fetch"):
            return np.asarray(jax.device_get(self._tok))

    def _count_prefill(self, rows: int, positions: int, by_rung=()) -> None:
        """What the prefill programs computed: rows dispatched, the
        positions they span (padding included, each row at ITS rung) and
        — ``by_rung``: ``{S: rows}`` — how often each rung engaged
        (``engine/prefill_rung_rows/<S>``).  A speculative draft's nested
        engine keeps out of the count."""
        if not self._counts_prefill:
            return
        telemetry.counter("engine/prefill_rows").inc(rows)
        telemetry.counter("engine/prefill_positions").inc(positions)
        if self.linear_layers:
            # a state built a prompt and layer, and how many of them from
            # no state (all: no prompt's pass is handed one)
            for name in ("engine/state_prompts",
                         "engine/state_prompts_blank"):
                telemetry.counter(name).inc(rows * self.linear_layers)
        for S in by_rung:
            telemetry.counter(f"engine/prefill_rung_rows/{S}").inc(
                by_rung[S])

    def _blank_prefill_args(self, slot: int = 0,
                            length: Optional[int] = None) -> tuple:
        """The prefill program's operands after ``tok``, typed as
        :meth:`prefill` hands them over, for a dispatch that changes no
        request's state: the one-row program (the top rung's, or the
        rung's of ``length``) over an empty prompt (``p_len`` 0) at
        ``slot``, the chunked program's first window with no slot
        admitted."""
        if self.prefill_chunk is None:
            zero = np.zeros((1,), np.int32)
            return (np.int32(slot), np.zeros_like(self.kv.table[:1]), zero,
                    np.zeros((1, length or self.prefill_len), np.int32),
                    zero, *((zero,) if self.prefix_caching else ()),
                    *self._state_args())
        B, c = self.num_slots, self.cache
        return (self.kv.table_arg(c), jnp.asarray(self._sample_seeds),
                jnp.zeros((B, self.prefill_chunk), jnp.int32),
                jnp.int32(0), jnp.ones((B,), jnp.int32),
                jnp.zeros((B,), bool),
                *((jnp.asarray(self.kv.write_from),)
                  if self.prefix_caching else ()))

    def warm_prefill(self) -> None:
        """Make the engine's programs (:meth:`_prepare`: every rung and
        the decode program) and run the prefill once with no
        request admitted — what a replica does before it takes traffic:
        :meth:`prefill` dispatches nothing for an empty ``admit``, so
        the first request would otherwise compile inside a scheduler
        round.  The top rung's one-row program runs at a free slot over
        an empty prompt: the slot's length stays 0, a paged pool takes no
        write (no position lies under ``p_len``), and a dense lane and
        the slot's held token are read again only after the next
        admission has overwritten them; every other slot stays
        bit-for-bit.  The chunked program runs one window under an
        all-false ``admit``, which holds the whole state."""
        slot, rows, key = 0, self.num_slots, "prefill"
        span = self.prefill_chunk
        if self.prefill_chunk is None:
            free = np.flatnonzero(self.lengths == 0)
            if not free.size:
                raise RuntimeError(
                    "warm_prefill needs a free slot: the one-row "
                    "program writes the lane of the slot it runs at")
            slot, rows = int(free[0]), 1
            span = key = self.prefill_len
        c = self.cache
        with telemetry.span("engine/prefill/dispatch",
                            loop_steps=self.cfg.block.loop_steps,
                            rows=rows, positions=rows * span):
            self._adopt(*self._programs[key](
                self.params, c.k, c.v, c.lengths, self._tok,
                *self._blank_prefill_args(slot)))
        self._count_prefill(rows, rows * span,
                            {span: 1} if self.prefill_rungs else ())
        if self.draft is not None:
            self.draft.warm_prefill()

    def _chunked_prefill(self, prompts_np, p_lens_np, admit_np):
        C = self.prefill_chunk
        if not admit_np.any():
            self.last_prefill_chunks = 0
            return
        # cast on the host: jnp casting an int64 array is a program
        p_lens_j = jnp.asarray(p_lens_np.astype(np.int32))
        admit_j = jnp.asarray(admit_np)
        rest = ((jnp.asarray(self.kv.write_from),)
                if self.prefix_caching else ())
        hi_len = int(p_lens_np[admit_np].max())
        n_chunks = kv_cache.blocks_for(hi_len, C)
        padded = np.zeros((self.num_slots, n_chunks * C), np.int32)
        width = min(prompts_np.shape[1], padded.shape[1])
        padded[:, :width] = prompts_np[:, :width]
        # Chunks fully covered by prefix hits for EVERY admitted slot
        # carry no novel writes and no emission — skip them (their
        # keys/values are already resident in the shared blocks the
        # later chunks attend through).  The chunk holding a slot's
        # final prompt token always runs: it produces the activation
        # the first generated token samples from.
        first = 0
        if self.prefix_caching:
            firsts = [min(int(self.kv.write_from[i]) * self.kv_block_len,
                          int(p_lens_np[i]) - 1)
                      for i in range(self.num_slots) if admit_np[i]]
            first = min(firsts) // C
        dispatched = 0
        for ci in range(first, n_chunks):
            cs = ci * C
            c = self.cache
            with telemetry.span("engine/prefill/stage"):
                args = (self.params, c.k, c.v, c.lengths, self._tok,
                        self.kv.table_arg(c), jnp.asarray(self._sample_seeds),
                        jnp.asarray(padded[:, cs:cs + C]),
                        jnp.int32(cs), p_lens_j, admit_j, *rest)
            with telemetry.span("engine/prefill/dispatch",
                                loop_steps=self.cfg.block.loop_steps,
                                rows=self.num_slots,
                                positions=self.num_slots * C):
                self._adopt(*self._programs["prefill"](*args))
            dispatched += 1
        # the chunk program computes every slot's window
        self._count_prefill(dispatched * self.num_slots,
                            dispatched * self.num_slots * C)
        self.last_prefill_chunks = dispatched

    def decode(self, active):
        """One fused ``decode_steps``-token dispatch; inactive slots
        hold their state.  Returns the emitted tokens ``[K, B]``
        (numpy; inactive columns repeat the held token)."""
        with telemetry.span("engine/decode/stage"):
            active_np = np.asarray(active, bool)
            c = self.cache = self.kv.protect(self.cache, active_np,
                                             self.decode_steps)
            args = (self.params, c.k, c.v, c.lengths, self._tok,
                    self.kv.table_arg(c), jnp.asarray(self._sample_seeds),
                    jnp.asarray(active_np), *self._state_args())
        with telemetry.span("engine/decode/dispatch",
                            loop_steps=self.cfg.block.loop_steps):
            k, v, lengths, tok, toks, *rest = self._programs["decode"](
                *args)
            n_state = len(self._state_args())
            self._adopt(k, v, lengths, tok, *rest[:n_state])
        with telemetry.span("engine/decode/fetch"):
            # a routed block's [rows_held, experts_hit] ride the fetch
            toks, *routing = jax.device_get((toks, *rest[n_state:]))
        self._count_decode(int(active_np.sum()), self.decode_steps,
                           *routing)
        return np.asarray(toks)

    def _count_decode(self, rows: int, steps: int, routing=None) -> None:
        """What a fused decode window moved beside keys and values: the
        recurrent-state rows its linear layers read and wrote, and what
        its routed layers chose — every (row, expert) pair, those that
        landed on held experts, the held experts some row hit and (a
        router that keeps groups) the rows that kept a held expert's
        group, summed over steps and routed layers (``moe/layer_steps`` counts
        those: ``experts_hit`` can reach ``layer_steps x
        experts_held``)."""
        if self.linear_layers:
            telemetry.counter("engine/state_rows").inc(
                rows * steps * self.linear_layers)
        if routing is not None:
            routed = self.cfg.num_layers - self.cfg.block.dense_layers
            telemetry.counter("moe/layer_steps").inc(steps * routed)
            telemetry.counter("moe/rows_routed").inc(
                rows * steps * routed * self.cfg.block.moe.top_k)
            telemetry.counter("moe/rows_held").inc(int(routing[0]))
            telemetry.counter("moe/experts_hit").inc(int(routing[1]))
            if len(routing) > 2:
                telemetry.counter("moe/groups_hit").inc(int(routing[2]))

    def decode_one(self, active):
        """A single-token dispatch through a lazily-built K=1 program —
        the speculative draft's catch-up path (feeding the one proposal
        a fully-accepted window verified but the draft never wrote)."""
        if self._decode1_jit is None:
            self._decode1_jit = self._build_decode(steps=1)
        active_np = np.asarray(active, bool)
        c = self.cache = self.kv.protect(self.cache, active_np, 1)
        k, v, lengths, tok, toks, *rest = self._decode1_jit(
            self.params, c.k, c.v, c.lengths, self._tok,
            self.kv.table_arg(c), jnp.asarray(self._sample_seeds),
            jnp.asarray(active_np), *self._state_args())
        self._adopt(k, v, lengths, tok, *rest[:len(self._state_args())])
        return np.asarray(jax.device_get(toks))

    def decode_window(self, active) -> DecodeWindow:
        """The batcher's decode unit.  Vanilla engines emit a fixed
        ``decode_steps`` tokens per active slot.  Speculative engines
        run draft-propose → target-verify → host accept/reject: the
        draft proposes ``k`` tokens autoregressively, ONE target
        dispatch scores the ``k + 1`` window, and each slot keeps the
        longest prefix the target agrees with plus the target's own
        next token — token-for-token (greedy) and draw-for-draw
        (sampled) what vanilla decode would have emitted, because both
        sides sample through the same position-keyed draws.  Rejected
        tokens roll back by resetting lengths through the block table's
        masked reads — no data movement."""
        active_np = np.asarray(active, bool)
        B = self.num_slots
        if self.speculative is None:
            toks = self.decode(active_np)
            counts = np.where(active_np, self.decode_steps,
                              0).astype(np.int32)
            z = np.zeros((B,), np.int32)
            return DecodeWindow(tokens=toks, counts=counts,
                                spec_proposed=z, spec_accepted=z.copy())
        ks = self.speculative
        # 1. Catch-up: a slot whose last window accepted every proposal
        # verified token d_k but the draft never wrote it — feed it
        # through the K=1 program so the draft's cache matches the
        # target's length before proposing again.
        need = self._spec_catch & active_np
        if need.any():
            draft_tok = np.asarray(jax.device_get(self.draft._tok))
            self.draft._tok = jnp.asarray(
                np.where(need, self._spec_catch_tok,
                         draft_tok).astype(np.int32))
            self.draft.decode_one(need)
            self._spec_catch &= ~need
        # 2. Align: the draft continues from the target's current token.
        tgt_tok = np.asarray(jax.device_get(self._tok))
        self.draft._tok = jnp.asarray(tgt_tok.astype(np.int32))
        # 3. Propose: the draft's fused decode IS the k-token proposer.
        proposals = self.draft.decode(active_np)           # [k, B]
        # 4. Verify: one target dispatch over [tok, d_1..d_k].
        lengths_np = self.lengths
        self.cache = self.kv.protect(self.cache, active_np, ks + 1)
        tokens_in = np.zeros((B, ks + 1), np.int64)
        tokens_in[:, 0] = tgt_tok
        tokens_in[:, 1:] = proposals.T
        c = self.cache
        k, v, lengths, tok, choices = self._programs["verify"](
            self.params, c.k, c.v, c.lengths, self._tok,
            self.kv.table_arg(c), jnp.asarray(self._sample_seeds),
            jnp.asarray(tokens_in, jnp.int32), jnp.asarray(active_np))
        self._adopt(k, v, lengths, self._tok)
        choices_np = np.asarray(jax.device_get(choices))   # [B, k+1]
        # 5. Accept/reject + rollback (host-side lengths are the only
        # state that moves — stale verified rows hide behind them).
        tok_np = tgt_tok.copy()
        new_len = lengths_np.copy()
        draft_len = np.asarray(
            jax.device_get(self.draft.cache.lengths)).copy()
        tokens = np.zeros((ks + 1, B), np.int32)
        counts = np.zeros((B,), np.int32)
        accepted = np.zeros((B,), np.int32)
        proposed = np.zeros((B,), np.int32)
        for i in range(B):
            if not active_np[i]:
                continue
            j = 0
            while j < ks and choices_np[i, j] == proposals[j, i]:
                j += 1
            m = j + 1
            tokens[:m, i] = choices_np[i, :m]
            counts[i] = m
            accepted[i] = j
            proposed[i] = ks
            tok_np[i] = choices_np[i, j]
            new_len[i] = lengths_np[i] + m
            draft_len[i] = lengths_np[i] + min(m, ks)
            if j == ks:
                self._spec_catch[i] = True
                self._spec_catch_tok[i] = proposals[ks - 1, i]
        self._tok = jnp.asarray(tok_np.astype(np.int32))
        self.cache = dataclasses.replace(
            self.cache, lengths=jnp.asarray(new_len, jnp.int32))
        self.draft.cache = dataclasses.replace(
            self.draft.cache, lengths=jnp.asarray(draft_len, jnp.int32))
        return DecodeWindow(tokens=tokens, counts=counts,
                            spec_proposed=proposed, spec_accepted=accepted)

    def _adopt(self, k, v, lengths, tok, *state) -> None:
        """A program's outputs become the live state (a ``block_table``
        is current since the last reserve/release: the program's own);
        ``state``: the recurrent state's arrays, where the stack has
        linear layers."""
        self.cache = dataclasses.replace(self.cache, k=k, v=v,
                                         lengths=lengths)
        if state:
            self.cache.state = kv_cache.RecurrentState.of(
                self.cfg.block.linear, state)
        self._tok = tok

    def _state_args(self) -> tuple:
        """The recurrent state's arrays, the programs' last operands."""
        state = getattr(self.cache, "state", None)
        return () if state is None else state.arrays()

    @property
    def lengths(self):
        return np.asarray(jax.device_get(self.cache.lengths))

    @property
    def decode_block_len(self) -> Optional[int]:
        """Positions of a dense lane that the decode attention reads or
        skips as one: the fused kernel's block, the whole lane under
        ``cached_attention``; ``None`` for a paged cache."""
        return self.kv.decode_block_len

    # ------------------------------------------------------------------ #
    # HLO probe hooks (tools/hlo_probe.py --probe decode)
    # ------------------------------------------------------------------ #
    def compiled_decode_text(self) -> str:
        """Optimized HLO of the fused decode program."""
        c = self.cache
        active = jnp.ones((self.num_slots,), bool)
        return self._decode_jit.lower(
            self.params, c.k, c.v, c.lengths, self._tok,
            self.kv.table_arg(c), jnp.asarray(self._sample_seeds),
            active, *self._state_args()).compile().as_text()

    def compiled_prefill_text(self) -> str:
        """Optimized HLO of the prefill program (the one-row program;
        the ``[B, C]`` window program on a chunked-prefill engine)."""
        c = self.cache
        return self._prefill_jit.lower(
            self.params, c.k, c.v, c.lengths, self._tok,
            *self._blank_prefill_args()).compile().as_text()
