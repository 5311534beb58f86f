"""AutoStrategy: pick the best strategy by analytic cost.

The working realization of the reference's *planned* AutoSync auto-
strategy flow (strategy → cost model → choose; the reference shipped
only the dataset stub, ``autodist/simulator/dataset/README.md``): build
every candidate strategy, score with :class:`CostModel`, take the
cheapest feasible plan.
"""
from __future__ import annotations

from typing import Optional, Sequence

from autodist_tpu.simulator.cost_model import (CostModel, SpecMeshMismatch,
                                               StrategyCost)
from autodist_tpu.strategy import builders as _builders
from autodist_tpu.strategy.base import StrategyBuilder
from autodist_tpu.utils import logging


def default_candidates() -> list[StrategyBuilder]:
    from autodist_tpu.strategy import gspmd_builders, parallel_builders

    return [
        _builders.AllReduce(),
        _builders.AllReduce(chunk_size=512),   # reference's large-model default
        _builders.AllReduce(compressor="bf16"),
        _builders.PSLoadBalancing(),
        _builders.PartitionedPS(),
        _builders.Parallax(),
        _builders.ZeRO(),
        # GSPMD family: FSDP everywhere; TP scores only when the topology
        # has a model axis (otherwise its spec is rejected by the cost
        # model and the candidate is skipped).
        gspmd_builders.FSDPSharded(),
        gspmd_builders.TensorParallel(),
        # Advanced parallelisms: score only when the topology declares
        # their mesh axis (seq / pipe / expert) — and, for Pipeline,
        # when the trainable is stage-structured, or for ExpertParallel,
        # when expert tables exist; otherwise build() raises ValueError
        # and the candidate is skipped.
        parallel_builders.SequenceParallel(),
        parallel_builders.Pipeline(num_microbatches=4),
        # Remat variant: survives the memory feasibility gate when the
        # plain pipeline's activation envelope exceeds HBM (long
        # pipelines); costs recompute FLOPs the time model doesn't see,
        # so it only wins when the plain variant is infeasible.
        parallel_builders.Pipeline(num_microbatches=4, remat=True),
        # Interleaved variant matches trainables with 2 chunks per pipe
        # device (num_stages == 2 x pipe axis); mismatches are skipped.
        parallel_builders.Pipeline(num_microbatches=4, virtual_stages=2),
        # dp×pp×tp: Megatron TP inside each pipeline stage.  Scores only
        # when the topology declares a size-2 model axis AND the stage
        # variables match the tp rule table (qkv/out/wi/wo naming);
        # otherwise build() raises ValueError and the candidate is
        # skipped — the cost model then arbitrates tp=1 vs tp=2 on the
        # per-stage activation all-reduces it prices.
        parallel_builders.Pipeline(num_microbatches=4, tensor_parallel=2),
        # Latency-hiding variant: the same dp×pp×tp composition with the
        # model-axis activation collectives decomposed into the chunked
        # collective-matmul ring; the cost model prices its Megatron
        # boundaries as max(comm, compute) instead of comm + compute,
        # so it ranks at or above the blocking variant on every link
        # profile and wins whenever chunk compute can hide hop latency.
        parallel_builders.Pipeline(num_microbatches=4, tensor_parallel=2,
                                   comm_overlap=True),
        # Vocab-parallel variant: the shared embedding/unembedding
        # shards over the model axis and the loss head runs the
        # streaming fused cross-entropy epilogue — the first candidate
        # that shrinks *memory* (embedding state, opt moments, and peak
        # logits all /tp) rather than step time, so the feasibility
        # gate can elect it when the replicated head's [B,L,V] logits
        # blow HBM.  Scores only for trainables whose prologue/loss_head
        # are vocab-parallel aware; otherwise build() raises ValueError
        # and the candidate is skipped.
        parallel_builders.Pipeline(num_microbatches=4, tensor_parallel=2,
                                   vocab_parallel=True),
        # ZeRO-3 variants: parameters stored sharded over the data axis
        # and all-gathered on demand per layer.  Wire volume matches the
        # stage-1 rs+ag pair, but the per-layer gather launches price
        # strictly above it — so these rank below replication/stage-1 on
        # step time and win through the HBM feasibility gate, exactly
        # when the replicated params+grads (or their Adam moments) blow
        # the memory budget: the second memory lever after
        # vocab_parallel, and the knob AutoStrategy arbitrates against
        # raising the tp degree.
        parallel_builders.Pipeline(num_microbatches=4, zero_stage=3),
        parallel_builders.Pipeline(num_microbatches=4, tensor_parallel=2,
                                   zero_stage=3),
        # Quantized-collective variants (the per-collective precision
        # policy, EQuARX-style): the same dp×pp×tp composition with
        # every boundary narrowed.  The cost model halves/quarters each
        # policied boundary's wire bytes and charges the calibrated
        # quantize/dequantize compute against it, so these rank above
        # their fp32 siblings exactly when the plan is comm-bound —
        # bytes saved > q/dq passes — and below them on compute-bound
        # links where narrowing buys nothing.
        parallel_builders.Pipeline(num_microbatches=4, tensor_parallel=2,
                                   collective_precision="int8"),
        parallel_builders.Pipeline(num_microbatches=4, tensor_parallel=2,
                                   vocab_parallel=True,
                                   collective_precision="int8"),
        parallel_builders.ExpertParallel(),
    ]


def default_serving_candidates(num_devices: int,
                               kv_layouts=("dense", "paged"),
                               ladder: bool = False) -> list[dict]:
    """The serving-config zoo: every (tensor_parallel, vocab_parallel,
    kv_layout) shape the serving engine can lower on ``num_devices``
    devices.  Plain dicts rather than builders — the decode program has
    no pipe axis to build a full training strategy against, and the
    keys are exactly the Strategy-IR ``parallel`` knobs the engine
    reads.

    ``ladder=True`` additionally enumerates the PR-16 throughput-ladder
    rungs on every paged shape: ``prefix_caching=True``,
    ``speculative=4``, and ``prefill_chunk`` at the calibrated
    ``flash_prefill_crossover_chunk`` with the ``flash_prefill``
    kernel elected.  Opt-in — the base zoo (and every config JSON it
    ever produced) stays byte-identical with the flag off."""
    shapes = [{"tensor_parallel": 1, "vocab_parallel": False}]
    tp = 2
    while tp <= num_devices:
        shapes.append({"tensor_parallel": tp, "vocab_parallel": False})
        shapes.append({"tensor_parallel": tp, "vocab_parallel": True})
        tp *= 2
    candidates = []
    for shape in shapes:
        for layout in kv_layouts:
            cand = dict(shape)
            if layout != "dense":
                cand["kv_layout"] = layout
            candidates.append(cand)
            if ladder and layout == "paged":
                from autodist_tpu.simulator.cost_model import \
                    KERNEL_PROFILE
                chunk = int(KERNEL_PROFILE["flash_prefill_crossover_chunk"])
                candidates.append(dict(cand, prefix_caching=True))
                candidates.append(dict(cand, speculative=4))
                candidates.append(dict(cand, prefill_chunk=chunk,
                                       kernel=("flash_prefill",)))
    return candidates


def default_fleet_candidates(num_devices: int, num_slices: int = 1,
                             kv_layouts=("dense", "paged")) -> list[dict]:
    """The fleet-shape zoo: every ``(replicas × tensor_parallel ×
    kv_layout)`` the topology admits — tp bounded by a slice's ICI
    degree (tp never crosses DCN; the cost model rejects it), replicas
    bounded by ``num_devices // tp`` (they may span slices — the
    router's dispatch hop is priced, not forbidden)."""
    per_slice = max(num_devices // max(num_slices, 1), 1)
    candidates = []
    tp = 1
    while tp <= per_slice:
        r = 1
        while r * tp <= num_devices:
            for layout in kv_layouts:
                cand = {"tensor_parallel": tp, "vocab_parallel": tp > 1}
                if r > 1:
                    cand["replicas"] = r
                if layout != "dense":
                    cand["kv_layout"] = layout
                candidates.append(cand)
            r *= 2
        tp *= 2
    return candidates


def default_disagg_candidates(num_devices: int, num_slices: int = 1,
                              kv_layouts=("paged",)) -> list[dict]:
    """The pool-split zoo: every ``(prefill_replicas × decode_replicas
    × tensor_parallel)`` split the topology admits — tp bounded by a
    slice's ICI degree (decode's per-token all-reduces never cross DCN
    — the ADT089 bound), total replicas bounded by ``num_devices //
    tp``.  Handoff rides the block table, so only paged layouts
    qualify."""
    per_slice = max(num_devices // max(num_slices, 1), 1)
    candidates = []
    tp = 1
    while tp <= per_slice:
        total = num_devices // tp
        for prefill in range(1, total):
            for layout in kv_layouts:
                candidates.append({
                    "prefill_replicas": prefill,
                    "decode_replicas": total - prefill,
                    "tensor_parallel": tp,
                    "vocab_parallel": tp > 1,
                    "kv_layout": layout,
                })
        tp *= 2
    return candidates


def rank_serving(trainable, resource_spec, candidates=None, *,
                 batch_slots: int = 1, max_len: int = 2048,
                 mean_request_len=None, mean_prompt_len=None,
                 objective: str = "latency",
                 prefix_hit_rate: float = 0.0, spec_acceptance=None,
                 ladder: bool = False, block=None, **cost_model_kwargs):
    """Rank serving configs by the cost model's serving objective —
    AutoStrategy's second objective (ROADMAP: "latency under load, not
    just training step time").

    ``candidates``: serving configs (dicts with ``tensor_parallel`` /
    ``vocab_parallel`` / ``kv_layout``) or trained :class:`Strategy`
    objects whose Strategy-IR parallel knobs describe the serving
    shape; defaults to :func:`default_serving_candidates`.

    ``objective``: ``"latency"`` ranks by per-token time
    (``DecodeCost.score`` — tp/kernel elections); ``"capacity"`` ranks
    by :attr:`~autodist_tpu.simulator.cost_model.DecodeCost
    .serve_score` — per-token time over the concurrent requests the
    HBM carries under ``mean_request_len``, the objective that elects
    ``kv_layout="paged"`` exactly when length variance makes dense
    reservation wasteful; ``"fleet"`` ranks by
    :attr:`~autodist_tpu.simulator.cost_model.DecodeCost.fleet_score`
    over the ``(replicas × tp × kv_layout)`` shapes
    (:func:`default_fleet_candidates`) — aggregate throughput for the
    traffic mix, with replicas priced across DCN and tp held within a
    slice's ICI; ``"disagg"`` ranks by
    :attr:`~autodist_tpu.simulator.cost_model.DecodeCost.disagg_score`
    over the ``(prefill_replicas × decode_replicas × tp)`` pool splits
    (:func:`default_disagg_candidates`) — the request pipeline's
    bottleneck stage under the mix's ``mean_prompt_len`` /
    ``mean_request_len``, so prefill-bound and decode-bound mixes
    elect different splits (pinned both ways on the KV handoff term).
    Returns ``[(config, DecodeCost)]`` best-first
    (feasible configs before infeasible) — the same shape as
    ``AutoStrategy.report``.

    The throughput-ladder inputs describe the TRAFFIC, not the config:
    ``prefix_hit_rate`` (fraction of a typical request's blocks shared
    with a resident prefix — the caller's to measure on its own
    traffic) prices ``prefix_caching`` candidates
    both directions under the capacity objective;
    ``spec_acceptance`` (draft acceptance rate α, likewise measured by
    the caller) prices ``speculative``
    candidates both directions under latency.  ``ladder=True`` widens
    the default zoo with the rung candidates
    (:func:`default_serving_candidates` ``ladder=``).  ``block``: the
    model's ``BlockSpec`` where it is not the default — passes, layer
    kinds, key/value heads, a linear mixer's state and a routed FFN's
    experts are priced from it (:meth:`CostModel.decode_cost`)."""
    if objective not in ("latency", "capacity", "fleet", "disagg"):
        raise ValueError(
            f"unknown serving objective {objective!r}; expected "
            "'latency', 'capacity', 'fleet', or 'disagg'")
    cm = CostModel(resource_spec, **cost_model_kwargs)
    if candidates is None:
        num_slices = max(
            int(getattr(resource_spec, "num_slices", 1) or 1), 1)
        if objective == "fleet":
            candidates = default_fleet_candidates(
                resource_spec.num_devices(), num_slices)
        elif objective == "disagg":
            candidates = default_disagg_candidates(
                resource_spec.num_devices(), num_slices)
        else:
            candidates = default_serving_candidates(
                resource_spec.num_devices(), ladder=ladder)
    scored = []
    for cand in candidates:
        try:
            cost = cm.decode_cost(trainable, cand,
                                  batch_slots=batch_slots, max_len=max_len,
                                  mean_request_len=mean_request_len,
                                  mean_prompt_len=mean_prompt_len,
                                  prefix_hit_rate=prefix_hit_rate,
                                  spec_acceptance=spec_acceptance,
                                  block=block)
        except (ValueError, SpecMeshMismatch) as e:
            logging.info("serving candidate %s skipped: %s", cand, e)
            continue
        scored.append((cand, cost))
    key = {"capacity": lambda it: it[1].serve_score,
           "fleet": lambda it: it[1].fleet_score,
           "disagg": lambda it: it[1].disagg_score,
           "latency": lambda it: it[1].score}[objective]
    scored.sort(key=key)
    return scored


class AutoStrategy(StrategyBuilder):
    """Chooses among candidate builders with the analytic cost model
    (≙ the reference's declared AutoStrategy direction, SURVEY.md §2.3),
    optionally refined by *measurement* — the reference's AutoSync plan
    trained a simulator on measured step times
    (``autodist/simulator/dataset/README.md``); here the hardware itself
    is the simulator: compile the top-k analytic picks, time a few real
    steps each, keep the fastest.

    ``auto = AutoStrategy(); AutoDist(spec, auto).build(trainable)`` —
    after ``build``, ``auto.report`` holds the scored candidates and
    ``auto.measured`` the per-candidate step times (when enabled).

    Args:
      candidates: builder instances to choose among (default: the zoo).
      search: enumerate the topology-aware knob cross-product
        (:mod:`autodist_tpu.simulator.search`) in place of the fixed
        candidate zoo: every ``(dp-across-DCN, dp-within-ICI, pp, tp,
        vocab_parallel, zero_stage, comm_overlap,
        collective_precision, num_microbatches, compressor)`` point
        the topology admits is synthesized, dominance-pruned,
        plan-linted, and priced against the hierarchical (ICI/DCN)
        network model.  The zoo still seeds the frontier, so the
        searched winner never scores below the zoo winner; the same
        report/measure/multihost machinery applies, with searched
        candidates carrying descriptive knob-string names.  After
        ``build``, ``auto.search_result`` holds the full
        :class:`~autodist_tpu.simulator.search.SearchResult`.
      search_space: a :class:`~autodist_tpu.simulator.search.
        SearchSpace` bounding the cross-product (implies
        ``search=True``).
      measure_top_k: when > 1, lower + time this many of the analytically
        best feasible candidates and pick the measured winner.  Costs one
        compile per measured candidate.  Multihost: launch workers with
        ``Cluster.launch_clients(None, ...)`` (no strategy id) and give
        every process the same ``AutoStrategy(measure_top_k=...,
        example_batch=<local batch>)`` — all processes then time the
        candidates in lockstep over the coordination service and adopt
        the chief's measured winner (``_measure_multihost``).
      example_batch: a host batch pytree for the timed steps (required
        when ``measure_top_k > 1``).
      measure_steps: timed steps per candidate (after one compile step).
    """

    def __init__(self, candidates: Optional[Sequence[StrategyBuilder]] = None,
                 measure_top_k: int = 0, example_batch=None,
                 measure_steps: int = 3, search: bool = False,
                 search_space=None, **cost_model_kwargs):
        self.candidates = list(candidates) if candidates is not None \
            else default_candidates()
        self.search = bool(search) or search_space is not None
        self.search_space = search_space
        self.search_result = None
        if not self.candidates and not self.search:
            raise ValueError("AutoStrategy needs at least one candidate")
        if measure_top_k > 1 and example_batch is None:
            raise ValueError("measure_top_k needs an example_batch to time")
        self.measure_top_k = measure_top_k
        self.example_batch = example_batch
        self.measure_steps = measure_steps
        self.cost_model_kwargs = cost_model_kwargs
        self.report: list[tuple[str, StrategyCost]] = []
        self.measured: dict[str, float] = {}
        self._winner_runner = None
        self._winner_strategy_id = None

    def build(self, trainable, resource_spec):
        cm_kwargs = dict(self.cost_model_kwargs)
        if ("tokens_per_step" not in cm_kwargs
                and getattr(trainable, "tokens_per_step", None) is None
                and self.example_batch is not None):
            # Infer the activation-shape hint from the measurement batch:
            # a rank-2 *integer* leaf is a [B, L] token-id tensor.  Float
            # leaves (images, features) are not tokens — inferring from
            # them would price bogus activation collectives, so they
            # leave the hint unset (declare Trainable(tokens_per_step=)
            # to opt in explicitly).
            import numpy as _np

            import jax as _jax
            for leaf in _jax.tree.leaves(self.example_batch):
                if _np.ndim(leaf) == 2 and _np.issubdtype(
                        _np.asarray(leaf).dtype, _np.integer):
                    shape = _np.shape(leaf)
                    cm_kwargs["tokens_per_step"] = int(shape[0] * shape[1])
                    break
        model = CostModel(resource_spec, **cm_kwargs)
        self.measured = {}
        self._winner_runner = None
        self._winner_strategy_id = None
        if self.search:
            scored = self._search_candidates(trainable, resource_spec,
                                             model)
        else:
            scored = self._score_zoo(trainable, resource_spec, model)
        if not scored:
            raise ValueError("no AutoStrategy candidate produced a strategy")
        scored.sort(key=lambda t: (t[1].score, t[1].num_collectives))
        self.report = [(name, cost) for name, cost, _ in scored]
        for name, cost in self.report:
            logging.info(
                "auto-strategy candidate %-18s comm=%8.1fMB t=%7.3fms "
                "colls=%3d mem/dev=%6.2fGB%s", name,
                cost.comm_bytes / 1e6, cost.comm_time_s * 1e3,
                cost.num_collectives, cost.mem_bytes_per_device / 1e9,
                "" if cost.feasible else "  INFEASIBLE")
        best_name, best_cost, best_strategy = scored[0]
        if not best_cost.feasible:
            raise ValueError(
                "no candidate strategy fits in device memory "
                f"(best: {best_name} needs "
                f"{best_cost.mem_bytes_per_device / 1e9:.2f} GB/device)")
        if self.measure_top_k > 1:
            measured = self._measure(trainable, resource_spec, scored)
            if measured is not None:
                best_name, best_strategy = measured
        logging.info("auto-strategy picked %s", best_name)
        return best_strategy

    def _search_candidates(self, trainable, resource_spec, model):
        """The topology-aware cross-product frontier as the candidate
        set (same ``(name, cost, strategy)`` triples the zoo loop
        produces — report/measure/multihost machinery downstream is
        shared)."""
        import numpy as _np

        import jax as _jax

        from autodist_tpu.simulator.search import search_strategies

        global_batch = None
        if self.example_batch is not None:
            leaves = [l for l in _jax.tree.leaves(self.example_batch)
                      if _np.ndim(l) > 0]
            if leaves:
                global_batch = int(_np.shape(leaves[0])[0])
        self.search_result = search_strategies(
            trainable, resource_spec, self.search_space,
            cost_model=model, global_batch=global_batch,
            seed_builders=self.candidates)
        logging.info("auto-strategy search:\n%s",
                     self.search_result.report())
        return [(c.name, c.cost, c.strategy)
                for c in self.search_result.frontier]

    def _score_zoo(self, trainable, resource_spec, model):
        """Score the fixed candidate zoo (the pre-search path, and the
        compatibility default)."""
        import json

        scored = []
        seen_names: dict[str, int] = {}
        seen_content: set[str] = set()
        for builder in self.candidates:
            name = type(builder).__name__
            # Two configs of one builder class (e.g. AllReduce with and
            # without compression) must stay distinct in report/measured.
            seen_names[name] = seen_names.get(name, 0) + 1
            if seen_names[name] > 1:
                name = f"{name}#{seen_names[name]}"
            if (name.startswith("SequenceParallel")
                    and not getattr(trainable, "sequence_ready", False)):
                # Splitting the token dim under a model with plain local
                # attention silently changes the objective; only models
                # declaring sequence_ready are auto-considered.
                logging.debug("candidate %s skipped: trainable does not "
                              "declare sequence_ready", name)
                continue
            try:
                strategy = builder.build(trainable, resource_spec)
            except ValueError as e:
                logging.debug("candidate %s skipped: %s", name, e)
                continue
            if strategy.graph_config.lowering == "pipeline" \
                    and self.example_batch is not None:
                # Screen unbuildable pipeline configs: the schedule needs
                # the per-shard batch divisible by num_microbatches.
                import numpy as _np

                import jax as _jax
                M = int(strategy.graph_config.parallel.get(
                    "num_microbatches", 1))
                repl = max(strategy.graph_config.replicas, 1)
                leaves = [l for l in _jax.tree.leaves(self.example_batch)
                          if _np.ndim(l) > 0]
                if leaves and (_np.shape(leaves[0])[0] % (repl * M)):
                    logging.debug(
                        "candidate %s skipped: batch %d not divisible by "
                        "%d replicas x %d microbatches", name,
                        _np.shape(leaves[0])[0], repl, M)
                    continue
            # Distinct configs can emit byte-identical strategies (e.g.
            # two AllReduce chunk sizes on a model with few tensors):
            # keep only the first, so measurement slots never time the
            # same compiled program twice.
            content = json.dumps([n.to_dict() for n in strategy.node_configs]
                                 + [strategy.graph_config.to_dict()],
                                 sort_keys=True)
            if content in seen_content:
                logging.debug("candidate %s skipped: identical strategy",
                              name)
                continue
            seen_content.add(content)
            try:
                cost = model.strategy_cost(trainable, strategy)
            except SpecMeshMismatch as e:
                logging.debug("candidate %s skipped: %s", name, e)
                continue
            scored.append((name, cost, strategy))
        return scored

    def take_cached_runner(self, strategy_id: str):
        """Hand the measured winner's already-compiled runner to the
        facade (consulted by :meth:`AutoDist.build`) so the winning
        executable is not thrown away and recompiled.  State is re-
        initialized first: the measured steps must not leak into the
        returned runner (from-init numeric equality is a product
        guarantee; re-init is a placement, not a recompile)."""
        if (self._winner_runner is not None
                and self._winner_strategy_id == strategy_id):
            import jax

            runner, self._winner_runner = self._winner_runner, None
            runner.state = runner.lowered.init_state(
                trainable=runner.trainable)
            runner._host_step = 0
            # step() splits self.rng each call — restore the fresh-build
            # default so rng-consuming losses (dropout) also match a
            # from-init build exactly.
            runner.rng = jax.random.PRNGKey(0)
            return runner
        return None

    def drop_cached_runner(self):
        """Release the measured winner's compiled runner without handing
        it out (called by ``AutoDist.build`` when the cache is bypassed),
        freeing its device state instead of retaining HBM."""
        self._winner_runner = None
        self._winner_strategy_id = None

    # ------------------------------------------------------------------ #
    MEASURE_BARRIER_MS = 600_000   # per-candidate: covers a slow compile

    @staticmethod
    def _fence_metrics(metrics):
        import numpy as np
        leaf = np.asarray(next(iter(metrics.values())))
        return float(leaf if leaf.ndim == 0 else leaf[-1])

    @staticmethod
    def _fence_state(runner):
        # The donated-state update can outlive the metrics buffers and
        # its tail differs per candidate; AsyncPSRunner has no .state.
        import numpy as np
        state = getattr(runner, "state", None)
        if state is not None and "step" in state:
            float(np.asarray(state["step"]))

    def _lockstep_candidate(self, client, gen, i, P, runner_ctor,
                            steps: int):
        """One candidate's build + compile + timed steps, identical on
        chief and workers (ONE implementation — the two sides' SPMD
        programs must stay in exact step-count sync or the job deadlocks
        at a collective).  Returns the measured s/step, or ``None`` on
        barrier timeout (a peer died / never joined)."""
        import time

        if not client.barrier(f"autostrategy/{gen}/c{i}", P,
                              timeout_ms=self.MEASURE_BARRIER_MS):
            return None
        runner = runner_ctor()
        try:
            # Steps-per-loop when the runner supports it: the timed
            # window is ONE dispatch, so per-step host dispatch noise
            # cannot skew the candidate ranking.  hasattr is
            # class-determined, so chief and workers take the same
            # branch for the same strategy (the SPMD step-count
            # lockstep requirement).
            fused = hasattr(runner, "run_steps")
            if fused:
                from autodist_tpu.runner import stack_steps
                stacked = stack_steps([self.example_batch] * max(steps, 1))
                self._fence_metrics(runner.run_steps(stacked))  # compile
            else:
                self._fence_metrics(runner.step(self.example_batch))
            self._fence_state(runner)
            if not client.barrier(f"autostrategy/{gen}/c{i}/t", P,
                                  timeout_ms=self.MEASURE_BARRIER_MS):
                return None
            t0 = time.perf_counter()
            if fused:
                self._fence_metrics(runner.run_steps(stacked))
            else:
                for _ in range(steps):
                    metrics = runner.step(self.example_batch)
                self._fence_metrics(metrics)
            self._fence_state(runner)
            return (time.perf_counter() - t0) / max(steps, 1)
        finally:
            # No cross-process runner caching: every process must drop
            # HBM before the next candidate compiles.
            if hasattr(runner, "close"):
                runner.close()

    def _measure_multihost(self, trainable, resource_spec, scored):
        """Coordinated measured refinement across processes (closes the
        round-4 'measurement is single-process only' gap): the chief
        publishes the top-k candidate strategies on the coordination
        service; every process — workers join through
        ``AutoStrategy.join_measurement`` from
        ``AutoDist.build_or_load_strategy`` — builds and steps each
        candidate in lockstep (the SPMD collectives need all
        participants), the chief times its own steps (collective
        lockstep makes every process's wall clock agree up to launch
        skew, fenced by barriers) and publishes the winner for workers
        to adopt.  Requires a coordination service and workers launched
        *before* planning (``Cluster.launch_clients(None, ...)``);
        without one, or on barrier timeout (a peer died or was launched
        with a strategy id instead), falls back to analytic ranking —
        but always publishes a winner first so joined workers never
        hang.

        Candidate *step* failures are deliberately not caught: a
        candidate failing mid-collective on one process diverges the
        SPMD program — it must fail the job exactly as it would in
        training (the feasibility gate screens predictable OOMs first).
        """
        import json

        from autodist_tpu.autodist import AutoDist
        from autodist_tpu.runtime import coordination

        client = coordination.service_client()
        if client is None:
            logging.warning(
                "auto-strategy: multihost measurement needs a coordination "
                "service (AUTODIST_TPU_COORD_SERVICE); using analytic "
                "ranking")
            return None
        P = int(getattr(resource_spec, "num_processes", 1))
        top = [t for t in scored if t[1].feasible][: self.measure_top_k]
        gen = client.counter_add("autostrategy/gen")
        plan = {"steps": int(self.measure_steps),
                "candidates": [[name, strategy.to_json()]
                               for name, _, strategy in top]}
        client.put(f"autostrategy/plan/{gen}", json.dumps(plan).encode())
        # Queue (destructive pop), not a KV key: each worker consumes
        # exactly one gen announcement, so a second measured build in
        # the same coordination-service lifetime can never hand workers
        # a stale generation.
        for _ in range(max(P - 1, 0)):
            client.queue_put("autostrategy/gen_queue", str(gen).encode())

        # Analytic best is the fallback winner on ANY early exit — the
        # winner key must always appear or joined workers would hang.
        win_name, win_strategy = scored[0][0], scored[0][2]

        def publish_winner():
            client.put(f"autostrategy/{gen}/winner",
                       json.dumps([win_name,
                                   win_strategy.to_json()]).encode())

        if not client.barrier(f"autostrategy/{gen}/join", P,
                              timeout_ms=120_000):
            logging.warning(
                "auto-strategy: workers did not join the measurement "
                "rendezvous in 120s (launched with a fixed strategy id, "
                "or a peer died); using analytic ranking")
            publish_winner()
            return None

        ad = AutoDist(resource_spec, self)
        best = None
        for i, (name, _, strategy) in enumerate(top):
            dt = self._lockstep_candidate(
                client, gen, i, P,
                lambda s=strategy: ad.build(trainable, s), plan["steps"])
            if dt is None:
                logging.warning("auto-strategy: peer lost at candidate "
                                "%s; aborting measurement", name)
                publish_winner()
                return None
            self.measured[name] = dt
            logging.info("auto-strategy measured %-18s %7.3f ms/step "
                         "(multihost)", name, dt * 1e3)
            if best is None or dt < best[0]:
                best = (dt, name, strategy)
        if best is not None:
            _, win_name, win_strategy = best
        publish_winner()
        return win_name, win_strategy

    def join_measurement(self, trainable, autodist):
        """Worker-side measurement participant (called from
        ``AutoDist.build_or_load_strategy`` on non-chief processes when
        the builder is a measuring AutoStrategy): mirror the chief's
        candidate loop in lockstep, then adopt the published winner.
        Returns the winner :class:`Strategy`, or ``None`` when no plan
        appears (the chief fell back to analytic ranking before
        publishing — the caller then uses the normal strategy handoff).
        """
        import json

        from autodist_tpu.runtime import coordination
        from autodist_tpu.strategy.ir import Strategy

        client = coordination.service_client()
        if client is None or self.example_batch is None:
            return None
        raw = client.queue_get("autostrategy/gen_queue", timeout_ms=120_000)
        if raw is None:
            return None
        gen = int(raw.decode())
        plan_raw = client.get(f"autostrategy/plan/{gen}", timeout_ms=60_000)
        if plan_raw is None:
            return None
        plan = json.loads(plan_raw.decode())
        P = int(getattr(autodist.resource_spec, "num_processes", 1))
        if not client.barrier(f"autostrategy/{gen}/join", P,
                              timeout_ms=120_000):
            return None
        for i, (name, sjson) in enumerate(plan["candidates"]):
            strategy = Strategy.from_json(sjson)
            # autodist.build (not a bare DistributedRunner): the chief
            # dispatches async-PS node configs to AsyncPSRunner there,
            # and both sides must run the same runner type per
            # candidate.  The loop body is the chief's, verbatim
            # (_lockstep_candidate — ONE implementation).
            if self._lockstep_candidate(
                    client, gen, i, P,
                    lambda s=strategy: autodist.build(trainable, s),
                    int(plan["steps"])) is None:
                break
        win = client.get(f"autostrategy/{gen}/winner",
                         timeout_ms=self.MEASURE_BARRIER_MS)
        if win is None:
            return None
        win_name, win_json = json.loads(win.decode())
        logging.info("auto-strategy (worker): adopted measured winner %s",
                     win_name)
        return Strategy.from_json(win_json)

    def _measure(self, trainable, resource_spec, scored):
        """Time real steps of the analytically-best feasible candidates;
        return ``(name, strategy)`` of the measured winner, or ``None``
        when measurement is unavailable or every candidate failed to
        run.  Multihost dispatches to :meth:`_measure_multihost`.
        Single-process keeps at most two runners alive (the best-so-far
        and the one being timed) and caches the winner's runner for
        :meth:`take_cached_runner`."""
        import time

        from autodist_tpu.autodist import AutoDist

        if getattr(resource_spec, "is_multihost", False):
            return self._measure_multihost(trainable, resource_spec, scored)
        ad = AutoDist(resource_spec, self)

        # ONE fencing contract for single-process and multihost
        # measurement (a drifted copy would silently skew their relative
        # candidate timings): the Trainable contract guarantees scalar
        # metrics ([k]-stacked on the fused path), and the donated-state
        # update can outlive the metrics buffers with a per-candidate
        # tail (e.g. a PS param all-gather), so both window edges fence
        # state too.
        fence = self._fence_metrics
        fence_state = self._fence_state

        best = None   # (dt, name, strategy, runner)
        top = [t for t in scored if t[1].feasible][: self.measure_top_k]
        for name, _, strategy in top:
            runner = None
            try:
                runner = ad.build(trainable, strategy)
                if hasattr(runner, "run_steps"):
                    # One dispatch per window: per-step host dispatch
                    # noise cannot skew the ranking (AsyncPSRunner has
                    # no fused path — its host loop IS the thing being
                    # measured).
                    from autodist_tpu.runner import stack_steps
                    stacked = stack_steps(
                        [self.example_batch] * self.measure_steps)
                    fence(runner.run_steps(stacked))     # compile + warm
                    fence_state(runner)
                    t0 = time.perf_counter()
                    fence(runner.run_steps(stacked))
                    fence_state(runner)
                else:
                    fence(runner.step(self.example_batch))   # compile step
                    fence_state(runner)
                    t0 = time.perf_counter()
                    for _ in range(self.measure_steps):
                        metrics = runner.step(self.example_batch)
                    fence(metrics)
                    fence_state(runner)
                dt = (time.perf_counter() - t0) / self.measure_steps
                self.measured[name] = dt
                logging.info("auto-strategy measured %-18s %7.3f ms/step",
                             name, dt * 1e3)
                if best is None or dt < best[0]:
                    best, runner = (dt, name, strategy, runner), best and best[3]
            except Exception as e:  # a candidate that cannot run loses
                # ... and loses visibly: on a chip a lowering the
                # compiler refuses would otherwise drop out of the
                # election without a trace of why.
                logging.warning(
                    "auto-strategy measure %s failed and is dropped from "
                    "the election: %s: %s", name, type(e).__name__,
                    (str(e).splitlines() or [""])[0])
            finally:
                # Free the loser before the next compile; close() tears
                # down any host-side machinery (async-PS thread, in-
                # process CoordServer) that `del` would leak.
                if runner is not None and hasattr(runner, "close"):
                    runner.close()
                del runner
        if best is None:
            return None
        _, name, strategy, winner_runner = best
        if hasattr(winner_runner, "lowered"):  # resettable → cacheable
            self._winner_runner = winner_runner
            self._winner_strategy_id = strategy.id
        elif hasattr(winner_runner, "close"):  # not cacheable: tear down
            winner_runner.close()
        return name, strategy
