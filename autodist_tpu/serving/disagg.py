"""Disaggregated serving: a prefill pool and a decode pool with a
compiled KV-prefix handoff between them.

Colocated continuous batching (the :class:`ContinuousBatcher`) makes
prefill and decode fight for the same dispatch stream: one long prompt
stalls every resident request's inter-token latency for a whole prefill
(PAPERS.md: the interference DistServe/Splitwise measure).  The
disaggregated layout splits the fleet into

* a **prefill pool** — engines that only ever run the prompt pass and
  emit the first token, then give their slot back, and
* a **decode pool** — engines that only ever run the fused decode
  windows, so their inter-token cadence is never pierced by a prompt.

The request's KV prefix moves between the pools as a **handoff**: the
prefill engine's pool blocks holding positions ``[0, prompt_len)`` are
copied block-for-block into blocks the decode engine reserved, the
decode slot adopts the request's length and first token in the same
program, and the prefill slot is released.  The transfer is ONE jitted
per-block gather/scatter (``dynamic_slice`` / ``dynamic_update_slice``
along the pool's block axis, the :func:`copy_pool_block` shape, so the
model-axis head sharding passes through) — never a full-pool gather and
never a host staging:

* the compiled program is linted like an elastic reshard
  (``ADT110 no_full_gather`` at the per-device stored-shard budget of
  :func:`autodist_tpu.elastic.reshard.shard_budget`, plus
  ``ADT104 no_host_transfer``), and
* the plan is linted BEFORE compiling
  (:func:`autodist_tpu.analysis.lint_handoff`, ADT072: the per-device
  gather a handoff stages must stay under one pool shard).

Every executed handoff emits a ``kind="handoff"`` telemetry record —
route (ici/dcn), blocks, bytes moved, duration, and the **paired**
prefill/decode replica ids — schema-gated by
``tools/telemetry_report.py --check``.

The pool split itself is an election, not a guess:
:func:`elect_pool_split` ranks the ``(prefill_replicas ×
decode_replicas × tensor_parallel)`` zoo by the cost model's
``disagg_score`` (the pipeline's bottleneck stage under the traffic's
``mean_prompt_len`` / ``mean_request_len``, with the handoff priced on
the route it would ride) — prefill-heavy mixes elect bigger prefill
pools and decode-heavy mixes the reverse, pinned both ways by the unit
tests.  :func:`autodist_tpu.analysis.lint_disagg` (ADT089) gates splits
the topology cannot place before any engine is built.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from autodist_tpu import telemetry
from autodist_tpu.serving import kv_cache
from autodist_tpu.serving.batcher import (FINISH_REASONS,  # noqa: F401
                                          OverloadedError)


# --------------------------------------------------------------------------- #
# Configuration + election
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class DisaggConfig:
    """An elected (or hand-picked) pool split."""

    prefill_replicas: int
    decode_replicas: int
    tensor_parallel: int = 1
    kv_layout: str = "paged"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def elect_pool_split(trainable, resource_spec, *, candidates=None,
                     **rank_kwargs):
    """Elect the pool split for a traffic mix: rank the
    ``default_disagg_candidates`` zoo (or ``candidates``) by
    ``disagg_score`` and return ``(DisaggConfig, DecodeCost)`` for the
    winner.  Pass the traffic facts (``mean_prompt_len``,
    ``mean_request_len``, ``batch_slots``, ``max_len``) through
    ``rank_kwargs`` — they are what moves the bottleneck between the
    pools.  Raises when no candidate is feasible."""
    from autodist_tpu.simulator import rank_serving

    ranked = rank_serving(trainable, resource_spec,
                          candidates, objective="disagg", **rank_kwargs)
    for config, cost in ranked:
        if np.isfinite(cost.disagg_score):
            return DisaggConfig(
                prefill_replicas=int(config["prefill_replicas"]),
                decode_replicas=int(config["decode_replicas"]),
                tensor_parallel=int(config.get("tensor_parallel", 1)),
                kv_layout=str(config.get("kv_layout", "paged"))), cost
    raise ValueError(
        "no feasible disaggregated split for this topology/traffic — "
        "every candidate's disagg_score is infinite (check device "
        "count vs tensor_parallel, and kv_layout='paged')")


# --------------------------------------------------------------------------- #
# The handoff plan (what the ADT072 lint checks before compiling)
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class HandoffPlan:
    """One request's prefill→decode KV move, in elements and blocks —
    the planning artifact :func:`autodist_tpu.analysis.lint_handoff`
    gates (ADT072) and the ``kind="handoff"`` record serializes."""

    rid: str
    blocks: int
    bytes_moved: int              # logical k+v bytes across every layer
    per_device_gather_elems: int  # largest per-participant staging
    budget_elems: int             # one per-device stored pool shard
    prefill_replica: str
    decode_replica: str
    route: str                    # "ici" | "dcn"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def check_handoff_block(engine, name: str = "engine") -> None:
    """Refuse, by name, an engine whose slots own other than pool
    blocks of keys and values a head: the handoff copies a request's
    blocks, and what else a cache layout holds for a slot — a recurrent
    state, latent rows, lanes — it would leave behind.  The layout says
    whether it serves the handoff, and why not
    (``serving/kv_cache.py``)."""
    if "handoff" not in engine.kv.serves:
        raise ValueError(engine.kv.refusal("handoff", name))


class HandoffError(RuntimeError):
    """A handoff plan or its compiled program failed its lint — the
    transfer would stage more than the shard-granularity contract
    allows.  Raised BEFORE any block moves."""

    code = "serve/handoff_lint"


# --------------------------------------------------------------------------- #
# Internal request state
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class _DisaggRequest:
    rid: str
    prompt: list
    max_new_tokens: int
    eos_id: Optional[int]
    seed: int
    submit_s: float
    state: str = "queued"         # queued -> prefilled -> decode -> done
    tokens: list = dataclasses.field(default_factory=list)
    prefill_replica: str = ""
    decode_replica: str = ""
    _src_slot: int = -1
    _dst_slot: int = -1
    first_tok_s: float = 0.0
    trace_id: Optional[str] = None


@dataclasses.dataclass
class DisaggCompletion:
    """A finished request's output, tagged with BOTH replicas that
    served it (the pairing the handoff record schema pins)."""

    rid: str
    tokens: list
    finish_reason: str
    prefill_replica: str
    decode_replica: str
    ttft_s: float
    trace_id: Optional[str] = None


# --------------------------------------------------------------------------- #
# The server
# --------------------------------------------------------------------------- #
class DisaggServer:
    """Prefill/decode pools over a shared request queue.

    ``engine_factory`` builds ONE engine per replica (every pool member
    gets an identical geometry — the handoff copies blocks positionally
    between pools, so the block length, layer count, and pool shape
    must agree; validated at construction).  The split comes from
    ``config`` (a :class:`DisaggConfig`, e.g. from
    :func:`elect_pool_split`) or explicit ``prefill_replicas`` /
    ``decode_replicas`` counts; :func:`lint_disagg` gates it against
    ``resource_spec`` (ADT089) before any engine is built.

    :meth:`step` advances the pipeline one round: admit queued
    requests into prefill slots (one batched prefill per engine), hand
    finished prefixes to the decode pool (one compiled, linted transfer
    per request), then run one fused decode window per decode engine.
    :meth:`run` loops until every submitted request completes.
    """

    def __init__(self, engine_factory, *, prefill_replicas: int = None,
                 decode_replicas: int = None,
                 config: Optional[DisaggConfig] = None,
                 resource_spec=None, max_queue: Optional[int] = None,
                 name: str = "disagg"):
        if config is None:
            # explicit 0 must reach the >= 1 check below, not default
            config = DisaggConfig(
                prefill_replicas=1 if prefill_replicas is None
                else int(prefill_replicas),
                decode_replicas=1 if decode_replicas is None
                else int(decode_replicas))
        elif prefill_replicas is not None or decode_replicas is not None:
            raise ValueError("pass config= OR explicit pool counts, "
                             "not both")
        if config.prefill_replicas < 1 or config.decode_replicas < 1:
            raise ValueError("each pool needs >= 1 replica")
        from autodist_tpu.analysis import lint_disagg
        report = lint_disagg(config, resource_spec)
        if not report.ok:
            raise ValueError(report.render("disagg pool split"))
        self.config = config
        self.name = name
        self.prefill_pool = [(f"prefill-{i}", engine_factory())
                             for i in range(config.prefill_replicas)]
        self.decode_pool = [(f"decode-{i}", engine_factory())
                            for i in range(config.decode_replicas)]
        self._validate_pools()
        eng = self.decode_pool[0][1]
        L, NB, H, bl, dh = eng.cache.k.shape
        tp = int(getattr(eng, "tensor_parallel", 1) or 1)
        #: the ADT110/ADT072 budget: ONE per-device stored pool shard
        #: (shard_budget's rule applied to the k pool — heads divide
        #: over the model axis, every other dim is stored whole).
        self.budget_elems = L * NB * (H // tp) * bl * dh
        self._elem_bytes = int(jnp.dtype(eng.cache.k.dtype).itemsize)
        self.max_queue = max_queue
        self._queue: deque[_DisaggRequest] = deque()
        self._reqs: dict = {}
        self.completions: dict = {}
        self._handoff_jits: dict = {}
        self.last_handoff_report = None
        self._auto_rid = 0
        self.route = self._route(resource_spec)

    def _validate_pools(self) -> None:
        shapes = set()
        for pname, eng in self.prefill_pool + self.decode_pool:
            check_handoff_block(eng, pname)
            if getattr(eng, "speculative", None) is not None:
                raise ValueError(
                    f"{pname}: speculative decoding is not supported "
                    "in disaggregated pools — the draft's cache cannot "
                    "ride the handoff")
            shapes.add(tuple(eng.cache.k.shape))
        if len(shapes) > 1:
            raise ValueError(
                f"pool engines disagree on cache geometry: {shapes} — "
                "the handoff copies blocks positionally, so every "
                "replica needs the same factory output")

    def _route(self, resource_spec) -> str:
        """The wire the handoff rides: inside one slice's ICI when the
        whole split fits, DCN when the pools must span slices — the same
        predicate the cost model prices the handoff term with."""
        if resource_spec is None:
            return "ici"
        try:
            num_devices = resource_spec.num_devices()
        except (ValueError, RuntimeError):
            return "ici"
        num_slices = max(int(getattr(resource_spec, "num_slices", 1)
                             or 1), 1)
        per_slice = max(num_devices // num_slices, 1)
        total = (self.config.prefill_replicas
                 + self.config.decode_replicas) \
            * self.config.tensor_parallel
        return "dcn" if num_slices > 1 and total > per_slice else "ici"

    # ------------------------------------------------------------------ #
    # Submission
    # ------------------------------------------------------------------ #
    def submit(self, prompt, *, max_new_tokens: int = 16,
               eos_id: Optional[int] = None, rid: Optional[str] = None,
               seed: int = 0, trace_id: Optional[str] = None) -> str:
        """Queue one request; returns its id.  The same admission
        contract as the colocated batcher: prompts must fit the
        prefill engines' bucket, and a bounded queue sheds loudly
        (:class:`OverloadedError`) instead of buffering without
        bound.  ``trace_id`` (supplied, ambient, or minted here) tags
        the request's prefill span, ``kind="handoff"`` record, and
        decode span — the cross-pool hop stays one trace."""
        prompt = [int(t) for t in prompt]
        eng = self.prefill_pool[0][1]
        max_prompt = getattr(eng, "max_prompt_tokens", eng.prefill_len)
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) > max_prompt:
            raise ValueError(
                f"prompt of {len(prompt)} tokens exceeds the prefill "
                f"bucket ({max_prompt})")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if self.max_queue is not None \
                and len(self._queue) >= self.max_queue:
            raise OverloadedError(
                f"[{OverloadedError.code}] disagg queue at its bound "
                f"({self.max_queue})")
        if rid is None:
            self._auto_rid += 1
            rid = f"{self.name}-{self._auto_rid}"
        if rid in self._reqs:
            raise ValueError(f"duplicate rid {rid!r}")
        if trace_id is None:
            trace_id = telemetry.current_trace_id() \
                or telemetry.mint_trace_id()
        req = _DisaggRequest(rid=rid, prompt=prompt,
                             max_new_tokens=int(max_new_tokens),
                             eos_id=eos_id, seed=int(seed),
                             submit_s=time.perf_counter(),
                             trace_id=trace_id)
        self._reqs[rid] = req
        self._queue.append(req)
        telemetry.gauge("disagg/queue_depth").set(len(self._queue))
        return rid

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def open_requests(self) -> int:
        return sum(1 for r in self._reqs.values() if r.state != "done")

    # ------------------------------------------------------------------ #
    # The pipeline round
    # ------------------------------------------------------------------ #
    def step(self) -> None:
        """One pipeline round: admit → handoff → decode.  Each stage
        works on what the previous rounds produced, so a request takes
        (at least) three rounds end to end — and the stages of
        DIFFERENT requests overlap across rounds, which is the point."""
        self._admit_prefill()
        self._handoff_ready()
        self._decode_round()

    def run(self, max_steps: int = 10_000) -> dict:
        """Drive :meth:`step` until every submitted request completes;
        returns :attr:`completions`."""
        steps = 0
        while self.open_requests:
            if steps >= max_steps:
                raise RuntimeError(
                    f"disagg pipeline did not drain in {max_steps} "
                    f"steps ({self.open_requests} request(s) open)")
            self.step()
            steps += 1
        return self.completions

    # ---- stage 1: prefill admission ---------------------------------- #
    def _admit_prefill(self) -> None:
        """FIFO-admit queued requests into prefill slots, one batched
        prefill dispatch per engine.  A prefill slot reserves only the
        PROMPT's blocks (``max_new_tokens=0``) — generation happens in
        the other pool, against the other pool's reservation."""
        for pname, eng in self.prefill_pool:
            if not self._queue:
                return
            free = [i for i in range(eng.num_slots)
                    if not eng.kv.slot_blocks(i)
                    and not any(r._src_slot == i
                                and r.prefill_replica == pname
                                and r.state in ("prefill", "prefilled")
                                for r in self._reqs.values())]
            if not free:
                continue
            B = eng.num_slots
            S = getattr(eng, "max_prompt_tokens", eng.prefill_len)
            prompts = np.zeros((B, S), np.int32)
            p_lens = np.ones((B,), np.int32)
            admit = np.zeros((B,), bool)
            seeds = np.zeros((B,), np.int32)
            taken = []
            for i in free:
                if not self._queue:
                    break
                head = self._queue[0]
                needed = eng.blocks_needed(len(head.prompt), 0,
                                           prompt=head.prompt)
                if needed > eng.free_blocks:
                    break      # pool-bound: the head waits (FIFO)
                req = self._queue.popleft()
                eng.reserve_slot(i, len(req.prompt), 0,
                                 prompt=req.prompt)
                prompts[i, :len(req.prompt)] = req.prompt
                p_lens[i] = len(req.prompt)
                admit[i] = True
                seeds[i] = req.seed
                req.state = "prefill"
                req.prefill_replica = pname
                req._src_slot = i
                taken.append((i, req))
            if not taken:
                continue
            tids = [req.trace_id for _, req in taken if req.trace_id]
            with telemetry.span("disagg/prefill", replica=pname,
                                admitted=len(taken),
                                **({"trace_ids": tids} if tids else {})):
                toks = eng.prefill(prompts, p_lens, admit, seeds=seeds)
            t_first = time.perf_counter()
            for i, req in taken:
                req.tokens = [int(toks[i])]
                req.first_tok_s = t_first
                req.state = "prefilled"
                telemetry.histogram("serve/ttft_ms").observe(
                    (t_first - req.submit_s) * 1e3)
        telemetry.gauge("disagg/queue_depth").set(len(self._queue))

    # ---- stage 2: the compiled KV handoff ----------------------------- #
    def _handoff_fn(self, n: int):
        """The n-block transfer as ONE jitted program: gather each
        source block (a ``dynamic_slice`` along the pool's block axis —
        the :func:`copy_pool_block` shape, head sharding passes
        through), scatter it into the destination's reserved block, and
        adopt the slot's length + current token in the same dispatch.
        Destination pools/state are donated, so XLA aliases the writes.
        Compiled ONCE per block count, and linted at build: ADT110
        (no gather result above one per-device pool shard) + ADT104
        (no host transfer) over the optimized HLO."""
        fn = self._handoff_jits.get(n)
        if fn is not None:
            return fn

        def handoff(src_k, src_v, dst_k, dst_v, lengths, tok,
                    src_ids, dst_ids, slot, p_len, first_tok):
            for i in range(n):
                kb = lax.dynamic_slice_in_dim(src_k, src_ids[i], 1,
                                              axis=1)
                vb = lax.dynamic_slice_in_dim(src_v, src_ids[i], 1,
                                              axis=1)
                dst_k = lax.dynamic_update_slice_in_dim(
                    dst_k, kb, dst_ids[i], axis=1)
                dst_v = lax.dynamic_update_slice_in_dim(
                    dst_v, vb, dst_ids[i], axis=1)
            lengths = lax.dynamic_update_slice(lengths, p_len[None],
                                               (slot,))
            tok = lax.dynamic_update_slice(tok, first_tok[None], (slot,))
            return dst_k, dst_v, lengths, tok

        fn = jax.jit(handoff, donate_argnums=(2, 3, 4, 5))
        eng = self.decode_pool[0][1]
        pool = jax.ShapeDtypeStruct(eng.cache.k.shape,
                                    eng.cache.k.dtype)
        vec = jax.ShapeDtypeStruct((eng.num_slots,), jnp.int32)
        ids = jax.ShapeDtypeStruct((n,), jnp.int32)
        scalar = jax.ShapeDtypeStruct((), jnp.int32)
        text = fn.lower(pool, pool, pool, pool, vec, vec, ids, ids,
                        scalar, scalar, scalar).compile().as_text()
        from autodist_tpu.analysis import lint_program
        from autodist_tpu.analysis.program_rules import (no_full_gather,
                                                         no_host_transfer)
        report = lint_program(
            text, [no_full_gather(self.budget_elems),
                   no_host_transfer()],
            where=f"disagg.handoff[{n} block(s)]")
        self.last_handoff_report = report
        if not report.ok:
            raise HandoffError(
                f"[{HandoffError.code}]\n"
                + report.render("compiled handoff"))
        self._handoff_jits[n] = fn
        return fn

    def _pick_decode(self, blocks_needed: int):
        """Least-loaded decode engine with a free slot and room for the
        request's full reservation (name-ordered tiebreak — the same
        determinism rule the router's ``_pick`` follows)."""
        best = None
        for pname, eng in self.decode_pool:
            free = [i for i in range(eng.num_slots)
                    if not eng.kv.slot_blocks(i)]
            if not free or blocks_needed > eng.free_blocks:
                continue
            load = eng.num_slots - len(free)
            if best is None or (load, pname) < (best[0], best[1]):
                best = (load, pname, eng, free[0])
        return best

    def _handoff_ready(self) -> None:
        """Move every prefilled request whose decode reservation fits:
        plan → lint (ADT072) → one compiled transfer → release the
        prefill slot → one schema-gated ``kind="handoff"`` record."""
        from autodist_tpu.analysis import lint_handoff

        ready = sorted((r for r in self._reqs.values()
                        if r.state == "prefilled"),
                       key=lambda r: r.submit_s)
        for req in ready:
            p_len = len(req.prompt)
            src_name = req.prefill_replica
            src = dict(self.prefill_pool)[src_name]
            needed = self.decode_pool[0][1].blocks_needed(
                p_len, req.max_new_tokens)
            pick = self._pick_decode(needed)
            if pick is None:
                continue           # decode pool full: retry next round
            _, dst_name, dst, dst_slot = pick
            bl = dst.kv_block_len
            n = kv_cache.blocks_for(p_len, bl)
            L, NB, H, _, dh = dst.cache.k.shape
            tp = int(getattr(dst, "tensor_parallel", 1) or 1)
            plan = HandoffPlan(
                rid=req.rid, blocks=n,
                bytes_moved=2 * n * L * H * bl * dh * self._elem_bytes,
                per_device_gather_elems=n * L * (H // tp) * bl * dh,
                budget_elems=self.budget_elems,
                prefill_replica=src_name, decode_replica=dst_name,
                route=self.route)
            report = lint_handoff(plan)
            if not report.ok:
                raise HandoffError(
                    f"[{HandoffError.code}]\n"
                    + report.render("handoff plan"))
            dst.reserve_slot(dst_slot, p_len, req.max_new_tokens)
            src_ids = src.kv.slot_blocks(req._src_slot)[:n]
            dst_ids = dst.kv.slot_blocks(dst_slot)[:n]
            fn = self._handoff_fn(n)
            t0 = time.perf_counter()
            k, v, lengths, tok = fn(
                src.cache.k, src.cache.v, dst.cache.k, dst.cache.v,
                dst.cache.lengths, dst._tok,
                jnp.asarray(src_ids, jnp.int32),
                jnp.asarray(dst_ids, jnp.int32),
                jnp.int32(dst_slot), jnp.int32(p_len),
                jnp.int32(req.tokens[0]))
            jax.block_until_ready(k)
            dt_ms = (time.perf_counter() - t0) * 1e3
            dst.cache = dataclasses.replace(dst.cache, k=k, v=v,
                                            lengths=lengths)
            dst._tok = tok
            dst._sample_seeds[dst_slot] = req.seed
            src.release_slot(req._src_slot)
            req.state = "decode"
            req.decode_replica = dst_name
            req._dst_slot = dst_slot
            telemetry.gauge("disagg/handoff_bytes").set(plan.bytes_moved)
            telemetry.counter("disagg/handoffs").inc()
            telemetry.record_event(
                "handoff", rid=req.rid, route=plan.route,
                blocks=plan.blocks, bytes_moved=plan.bytes_moved,
                per_device_gather_elems=plan.per_device_gather_elems,
                budget_elems=plan.budget_elems,
                prefill_replica=plan.prefill_replica,
                decode_replica=plan.decode_replica,
                duration_ms=dt_ms,
                **({"trace_id": req.trace_id} if req.trace_id else {}))

    # ---- stage 3: decode windows -------------------------------------- #
    def _decode_round(self) -> None:
        """One fused decode window per decode engine holding work; the
        colocated batcher's terminal rules verbatim (budget and
        capacity caps before the EOS scan)."""
        for pname, eng in self.decode_pool:
            mine = [r for r in self._reqs.values()
                    if r.state == "decode" and r.decode_replica == pname]
            if not mine:
                continue
            active = np.zeros((eng.num_slots,), bool)
            for r in mine:
                active[r._dst_slot] = True
            tids = [r.trace_id for r in mine if r.trace_id]
            with telemetry.span("disagg/decode", replica=pname,
                                active=int(active.sum()),
                                **({"trace_ids": tids} if tids else {})):
                toks = eng.decode(active)          # [K, B]
            for r in mine:
                r.tokens.extend(int(t) for t in toks[:, r._dst_slot])
                cap = max(1, eng.max_len - len(r.prompt))
                limit = min(r.max_new_tokens, cap)
                budgeted = r.tokens[:limit]
                done = None
                if r.eos_id is not None and r.eos_id in budgeted:
                    r.tokens = budgeted[:budgeted.index(r.eos_id) + 1]
                    done = "eos"
                elif len(r.tokens) >= limit:
                    r.tokens = budgeted
                    done = ("max_tokens" if limit == r.max_new_tokens
                            else "max_len")
                if done is not None:
                    eng.release_slot(r._dst_slot)
                    r.state = "done"
                    self.completions[r.rid] = DisaggCompletion(
                        rid=r.rid, tokens=list(r.tokens),
                        finish_reason=done,
                        prefill_replica=r.prefill_replica,
                        decode_replica=r.decode_replica,
                        ttft_s=r.first_tok_s - r.submit_s,
                        trace_id=r.trace_id)
                    telemetry.counter("serve/completed").inc()

    # ------------------------------------------------------------------ #
    # Introspection
    # ------------------------------------------------------------------ #
    def describe(self) -> dict:
        """The split in :func:`lint_disagg`'s vocabulary."""
        return self.config.to_dict()

    def block_accounting(self) -> dict:
        """Per-replica ``(free, used, total)`` across BOTH pools — the
        zero-leak invariant is every pool fully free once no request is
        resident."""
        return {name: eng.block_accounting()
                for name, eng in self.prefill_pool + self.decode_pool}
