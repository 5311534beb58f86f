"""Render a telemetry run directory as a markdown report.

The consumer side of :mod:`autodist_tpu.telemetry`: given the directory
a run flushed (``metrics.jsonl`` + ``manifest.json`` + ``trace.json`` +
optional ``drift.json``), print a markdown summary — step-time p50/p99,
examples/sec, MFU when recorded, counter/gauge values, and the
predicted-vs-measured drift ratios.  ``--check`` validates the artifact
schema and exits non-zero on a break, so a tier-1 smoke invocation turns
a silent schema drift into a CI failure::

    python tools/telemetry_report.py /tmp/run1
    python tools/telemetry_report.py /tmp/run1 --check
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

_STEP_KEYS = {"kind", "step", "duration_ms"}
# Per-boundary precision gauges (the Strategy IR policy): the lowering
# emits `precision/<boundary>_bits` for every narrowed boundary, so a
# run whose manifest declares a collective_precision annotation but
# whose metrics lack the gauges means a lowering silently dropped the
# policy — a schema break, caught by --check in CI.
_PRECISION_BITS = {"fp32": 32, "bf16": 16, "int8": 8}
# Fused-kernel election gauges (the Strategy IR kernel slot): the
# lowering that honors an election emits `kernel/<name>_elected` = 1
# (the pipeline lowering for the training kernels, the serving engine
# for flash_decode); a manifest run.kernel annotation without its gauge
# means the election was silently dropped — --check fails it.
_KERNEL_CHOICES = ("flash_decode", "flash_prefill", "quant_ring",
                   "collective_matmul", "a2a_ring", "flash_attention",
                   "delta_step", "grouped_matmul", "retention_step",
                   "ssd_step")
# Elections made where the kernel is called, reported as 1 (the fused
# kernel) or 0 (the composed path) by the engine that makes them, and
# only by it: gauge -> (that engine's own gauge, who it is, what 0 says).
# kernel/delta_step_elected: how a decode step advances a stack's
# recurrent state (serving/kv_cache.py DenseLayout.advance_state);
# kernel/retention_step_elected: the same of a power-retention stack
# (DenseLayout.advance_retention); kernel/ssd_step_elected: of a stack
# of Mamba-2 state-space layers (DenseLayout.advance_ssd), whose decode
# program counts its traced calls of the kernel in kernel/ssd_step_calls.
# kernel/latent_decode_elected: how a decode step attends over cached
# latent rows (LatentLayout.decode_attend) — the latent kernel over the
# live blocks, which sets kernel/flash_decode_elected too (the kernel
# slot's word), or write_token and cached_attention over whole lanes.
_OBSERVED_ELECTIONS = {
    "kernel/delta_step_elected": (
        "engine/state_bytes_per_slot",
        "only an engine that holds a recurrent state elects how to "
        "advance it", "the composed step"),
    "kernel/retention_step_elected": (
        "engine/state_bytes_per_slot",
        "only an engine that holds a recurrent state elects how to "
        "advance it", "the composed step"),
    "kernel/ssd_step_elected": (
        "engine/state_bytes_per_slot",
        "only an engine that holds a recurrent state elects how to "
        "advance it", "the composed step"),
    "kernel/latent_decode_elected": (
        "engine/latent_lane_rows",
        "only an engine that caches latent rows elects how to attend "
        "over them", "the composed attention"),
    "kernel/grouped_matmul_elected": (
        "engine/experts_held",
        "only an engine whose block routes elects how to run the held "
        "experts", "two ragged_dot"),
}
# Training attention's election (autodist_tpu/models/transformer.py
# attend): every traced call advances one of the two counters, and a
# call that takes the fused kernels sets kernel/flash_attention_elected.
# The gauge without a fused call counted, or fused calls without the
# gauge, means the accounting of which path the program runs was
# dropped — --check fails it.
_ATTENTION_COUNTERS = ("kernel/flash_attention_calls",
                       "kernel/einsum_attention_calls")
# Per-request serving records (autodist_tpu/serving/batcher.py): the
# latency facts the serving section aggregates.  The PR-16 throughput-
# ladder fields are REQUIRED: every completion reports its prefix hit
# blocks, speculative proposal/acceptance tallies, and how many chunked
# prefill dispatches admitted it (1 = single-shot) — a serve record
# without them means the batcher dropped the rung accounting.
_SERVE_KEYS = {"kind", "request", "tokens", "ttft_ms", "tokens_per_sec",
               "kv_layout", "prefix_hit_blocks", "spec_proposed",
               "spec_accepted", "prefill_chunks"}
# Paged-KV pool gauges (autodist_tpu/serving/engine.py): a paged
# engine emits serve/kv_blocks_free + serve/kv_blocks_used on every
# block reservation/release.  A run whose serve records declare
# kv_layout="paged" but whose metrics lack the pool gauges means the
# block accounting silently never ran — --check fails it.
_KV_BLOCK_GAUGES = ("serve/kv_blocks_free", "serve/kv_blocks_used")
# Prefill work counters (autodist_tpu/serving/engine.py): every prefill
# dispatch advances engine/prefill_rows (rows the program computed) and
# engine/prefill_positions (rows x the positions each spans, padding
# included) together, and its engine/prefill/dispatch span says how many
# rows it carried.  One counter without the other, fewer positions than
# rows, or a dispatch span of such a run without its `rows` means the
# accounting that says what the chip computed for the prompts it
# admitted was dropped — --check fails it.
_PREFILL_COUNTERS = ("engine/prefill_rows", "engine/prefill_positions")
# A single-shot engine also says at which rung each row ran
# (engine/prefill_rung_rows/<S>: rows dispatched through the [1, S]
# program).  The rungs' rows are rows, and their positions positions: more
# of either than the two counters above hold means a row was counted at a
# rung it did not run at — --check fails it.  (Fewer is sound: a chunked
# engine in the same run counts rows and no rung.)
_RUNG_COUNTER = "engine/prefill_rung_rows/"
# Routing counters of a routed FFN (autodist_tpu/serving/engine.py): every
# decode window advances moe/layer_steps (steps x layers), moe/rows_routed
# (the decoding rows' (row, expert) pairs), moe/rows_held (the pairs that
# landed on experts this device holds) and moe/experts_hit (held experts
# with at least one row) together.  More pairs held than routed, more
# experts hit than pairs held, or more than layer_steps x the
# engine/experts_held gauge means the routing the program reports is not
# the routing it ran — --check fails it.
_ROUTING_COUNTERS = ("moe/layer_steps", "moe/rows_routed", "moe/rows_held",
                     "moe/experts_hit")
# A router that keeps some of its expert groups also advances
# moe/groups_hit: the rows, a step and a layer, whose kept groups include
# one this device holds an expert of.  It comes with the counters above;
# a pair is held only through a kept group, so pairs held with no group
# hit, or more rows with a group hit than pairs routed, is a break.
_GROUPS_COUNTER = "moe/groups_hit"
# Two kinds of state in one cache manager (a latent row a position beside
# a recurrent state a slot): an engine that holds both says so once, with
# kv/latent_layers, kv/linear_layers, kv/row_bytes and kv/state_bytes —
# the first three come with all four, every one positive.
_TWO_STATE_GAUGES = ("kv/latent_layers", "kv/linear_layers", "kv/row_bytes",
                     "kv/state_bytes")
# Any stack that keeps a recurrent state says what it holds of it:
# kv/state_rows (the rows a head's state holds as laid out: a delta
# rule's key_dim, power retention's (d / 2 + 1) d), kv/state_bytes over
# all slots and engine/state_bytes_per_slot.  kv/state_rows comes with
# the other two, every one positive.  (kv/state_bytes alone is an engine
# of before the rows were reported, or one of the four above.)
_STATE_GAUGES = ("kv/state_rows", "kv/state_bytes",
                 "engine/state_bytes_per_slot")
# engine/state_prompts: the states the prefill programs built, one a
# prompt and linear layer — whole layers of engine/prefill_rows.
_STATE_PROMPTS = "engine/state_prompts"
_SSD_CALLS = "kernel/ssd_step_calls"
# engine/state_prompts_blank: those of them built from no state (a
# prompt's pass that starts at position 0) — beside engine/state_prompts
# alone, and never more of them.
_STATE_PROMPTS_BLANK = "engine/state_prompts_blank"
# Latent rows read (autodist_tpu/serving/batcher.py): an engine whose
# cached position is a latent-attention row advances
# serve/latent_positions_read by every decode step's live positions x
# layers, beside serve/kv_blocks_resident (steps x slots x the blocks of
# a lane: one under the composed attention) and under the
# engine/latent_lane_rows (a lane's positions)
# and engine/cache_layers gauges.  More rows read than steps x slots x
# max_len x layers hold means the count is not of the rows the steps
# could read — --check fails it.
_LATENT_COUNTER = "serve/latent_positions_read"
_LATENT_BOUND = ("serve/kv_blocks_resident", "engine/latent_lane_rows",
                 "engine/cache_layers")
# Per-reshard records (autodist_tpu/elastic/reshard.py): one per
# executed reshard — route taken (compiled fast path vs host-staged),
# payload moved, and the host-memory high-water mark the staged route
# is bounded by.
_RESHARD_KEYS = {"kind", "route", "leaves", "bytes_moved",
                 "peak_host_bytes", "duration_ms"}
# Chaos/fault records (autodist_tpu/runtime/faults.py + the supervised
# recovery paths): one per injection and one per detected outcome.  A
# run whose injections have no matching terminal record is a run that
# claims chaos coverage it never proved — --check fails it.
_FAULT_KEYS = {"kind", "fault", "target", "phase"}
_FAULT_KINDS = ("worker_crash", "worker_hang", "slow_host", "coord_drop",
                "ckpt_write_fail", "preempt_signal",
                # serving plane (fleet replicas)
                "replica_crash", "replica_hang", "replica_slow")
_FAULT_PHASES = ("injected", "detected", "recovered", "degraded",
                 "escalated", "teardown")
_FAULT_TERMINAL = ("recovered", "degraded", "escalated", "teardown")
# Fleet dispatch records (autodist_tpu/serving/router.py): one per
# routing decision.  reason names why the request moved; re_emitted is
# the at-most-once contract made auditable — the router NEVER re-emits
# an already-streamed token, so any nonzero value is a broken stream
# and --check fails it.  A failover record must pair with the replica
# fault/health record the fleet emitted when it declared the source
# replica dead — a failover with no recorded cause is a recovery path
# that cannot be audited.
_DISPATCH_KEYS = {"kind", "request", "replica", "reason", "re_emitted"}
_DISPATCH_REASONS = ("route", "failover", "hedge", "drain")
# Disaggregated-serving handoff records (autodist_tpu/serving/disagg.py):
# one per prefill→decode KV-prefix transfer.  The replica ids come
# PAIRED — a handoff names both the prefill replica that produced the
# prefix and the decode replica that adopted it, or the route cannot be
# audited; and the per-device gather must sit under the shard budget
# (the executed form of the ADT072/ADT110 contract).
_HANDOFF_KEYS = {"kind", "route", "blocks", "bytes_moved", "duration_ms",
                 "prefill_replica", "decode_replica"}
_HANDOFF_ROUTES = ("ici", "dcn")
# Autoscaler transition records (autodist_tpu/serving/autoscale.py):
# one per grow/shrink.  Each names the trigger that fired and its
# measured value vs threshold; --check additionally requires the
# trigger's gauge (autoscale/queue_depth / autoscale/ttft_p99_ms) in
# the same run — a scale event whose trigger signal was never emitted
# is a decision nobody can audit.
_SCALE_KEYS = {"kind", "direction", "trigger", "value", "threshold",
               "replicas_before", "replicas_after"}
_SCALE_TRIGGERS = {"queue_depth": "autoscale/queue_depth",
                   "ttft_p99": "autoscale/ttft_p99_ms"}
# Online drift-breach records (autodist_tpu/telemetry/drift.py
# DriftMonitor): one per threshold CROSSING (edge-triggered), naming the
# cost-model term, the measured/predicted ratio that crossed, and which
# side of the band it left — the live sibling of the post-hoc
# drift.json report.
_DRIFT_KEYS = {"kind", "term", "ratio", "threshold", "step",
               "predicted", "measured", "direction"}
# The batcher's round account (autodist_tpu/serving/batcher.py): every
# `serve/step` span names its `round` and says where the round's time
# went; `serve/rounds` counts them, and a round flagged slow leaves one
# `kind="slow_round"` record with the medians it was held against and
# the durations of its engine/* children.
_ROUND_KEYS = ("round", "admitted", "active", "decode_ms", "prefill_ms",
               "own_ms", "compiles")
_SLOW_ROUND_KEYS = {"kind", *_ROUND_KEYS, "children_ms"}
_SLOW_ROUND_HELD = ("median_decode_ms", "median_own_ms")
# A round's parts are read off the clock just before its span closes:
# they may not exceed the span, and over a run fall short of it by no
# more than this much a round and this share of the whole.
_ROUND_SLACK_MS = 0.05
_SLOW_ROUNDS_SHOWN = 20     # rows of the report's table; the file has all
_ROUND_SLACK_SHARE = 1e-3
# The process's compile and start-up account
# (autodist_tpu/telemetry/account.py), the last line a flush writes.
_STARTUP_KEYS = {"kind", "since_import_s", "run_started_s", "trace_s",
                 "lower_s", "backend_s", "cache_retrieval_s", "cache_hits",
                 "cache_misses", "compile_events", "engine_s", "runner_s",
                 "engine_built_s", "runner_built_s"}
_KINDS = ("step", "serve", "reshard", "fault", "dispatch", "handoff",
          "scale", "drift", "slow_round", "startup", "counter", "gauge",
          "histogram")


def _event_trace_ids(ev: dict):
    """Trace ids a chrome-trace event is tagged with (``args.trace_id``
    for a single-request span/instant, ``args.trace_ids`` for a fused
    batch span covering several requests)."""
    args = ev.get("args") or {}
    ids = []
    if args.get("trace_id"):
        ids.append(args["trace_id"])
    ids.extend(t for t in (args.get("trace_ids") or []) if t)
    return ids


def load_jsonl(path: str) -> list[dict]:
    records = []
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError as e:
                raise ValueError(f"{path}:{i + 1}: not JSON ({e})")
            if not isinstance(rec, dict):
                raise ValueError(f"{path}:{i + 1}: not an object")
            records.append(rec)
    return records


def _pct(values, q) -> float:
    return float(np.percentile(np.asarray(values, float), q))


def rounds_summary(events: list, records: list):
    """The batcher's rounds of one run, from its chrome-trace events and
    its metrics records: how many, how long (``round_ms`` p50 / p95 /
    max), where the time went (medians of ``decode_ms``, of
    ``prefill_ms`` a row admitted, of ``own_ms``), the compile events
    that fell inside rounds, and the rounds flagged slow with the excess
    over the medians they were held against.  ``None`` for a run whose
    ``serve/step`` spans carry no account (or that served nothing)."""
    steps = [e for e in events if e.get("name") == "serve/step"
             and "decode_ms" in (e.get("args") or {})]
    if not steps:
        return None
    args = [e["args"] for e in steps]
    dur = [float(e["dur"]) * 1e-3 for e in steps]
    decoded = [a["decode_ms"] for a in args if a["active"]]
    a_row = [a["prefill_ms"] / a["admitted"] for a in args if a["admitted"]]
    slow = [r for r in records if r.get("kind") == "slow_round"]
    counted = {r["name"]: r["value"] for r in records
               if r.get("kind") == "counter"
               and r.get("name") in ("serve/rounds", "serve/slow_rounds")}
    return {
        "rounds": len(steps),
        "round_ms_p50": _pct(dur, 50), "round_ms_p95": _pct(dur, 95),
        "round_ms_max": max(dur),
        "decode_ms_p50": _pct(decoded, 50) if decoded else None,
        "prefill_ms_a_row_p50": _pct(a_row, 50) if a_row else None,
        "own_ms_p50": _pct([a["own_ms"] for a in args], 50),
        "rows_admitted": sum(a["admitted"] for a in args),
        "compiles": sum(a["compiles"] for a in args),
        "slow_rounds": counted.get("serve/slow_rounds", 0),
        "slow_excess_ms": sum(r.get("decode_excess_ms", 0.0)
                              + r.get("own_excess_ms", 0.0) for r in slow),
        "slow": slow,
    }


def rounds_line(summary: dict) -> str:
    """``rounds_summary`` on one line (the benchmark's ``[rounds]``)."""
    return "[rounds] " + " ".join(
        f"{k}={_fmt(v, 6)}" for k, v in summary.items() if k != "slow")


def startup_summary(records: list):
    """What the process did before this run's recorder was created (a
    benchmark's window): the ``kind="startup"`` account less the run's
    own ``compile/*`` instruments.  ``programs_s`` is the seconds jax
    spent on programs there — tracing, lowering and the backend's
    compile-or-retrieve, of which ``cache_retrieval_s`` is the part
    that came from the compilation cache.  ``None`` without the
    account."""
    account = next((r for r in records if r.get("kind") == "startup"), None)
    if account is None:
        return None
    in_run = {r["name"]: r.get("sum" if r["kind"] == "histogram"
                               else "value", 0)
              for r in records if r.get("kind") in ("histogram", "counter")
              and str(r.get("name", "")).startswith("compile/")}
    before = {k: account[k] - in_run.get("compile/" + k, 0)
              for k in ("trace_s", "lower_s", "backend_s",
                        "cache_retrieval_s", "cache_hits", "cache_misses")}
    return {"import_to_run_s": account["run_started_s"],
            "engine_s": account["engine_s"],
            "runner_s": account["runner_s"],
            "programs_s": before["trace_s"] + before["lower_s"]
            + before["backend_s"], **before,
            "compile_events_in_run": sum(
                r.get("count", 0) for r in records
                if r.get("kind") == "histogram" and r.get("name") in (
                    "compile/trace_s", "compile/lower_s",
                    "compile/backend_s"))}


def startup_line(summary: dict, **more) -> str:
    """``startup_summary`` on one line (the benchmark's ``[startup]``);
    ``more`` is what only the caller can know (the seconds before the
    package was imported)."""
    return "[startup] " + " ".join(
        f"{k}={_fmt(v, 6)}" for k, v in {**more, **summary}.items())


def _check_rounds(records: list, trace_events: list,
                  spans_dropped) -> list[str]:
    """The round account's gates: the counter and the spans agree, a
    round's parts add up to its span, and a slow round's record names
    what it was held against."""
    problems = []
    counters = {r.get("name"): r.get("value", 0) for r in records
                if r.get("kind") == "counter"}
    steps = [e for e in trace_events if e.get("name") == "serve/step"
             and "round" in (e.get("args") or {})]
    for ev in steps:
        missing = [k for k in _ROUND_KEYS if k not in ev["args"]]
        if missing:
            problems.append(
                f"trace.json: serve/step round {ev['args']['round']!r} "
                f"lacks {missing} — a round's account comes whole")
            return problems
    if steps and not spans_dropped \
            and counters.get("serve/rounds") != len(steps):
        problems.append(
            f"metrics.jsonl: serve/rounds = "
            f"{counters.get('serve/rounds')!r} but trace.json holds "
            f"{len(steps)} serve/step span(s) and none was dropped")
    short = whole = 0.0
    for ev in steps:
        a = ev["args"]
        parts = a["decode_ms"] + a["prefill_ms"] + a["own_ms"]
        dur = float(ev["dur"]) * 1e-3
        if parts > dur + 1e-3:
            problems.append(
                f"trace.json: round {a['round']!r}: decode_ms + prefill_ms"
                f" + own_ms = {parts:.4f} exceeds the span's {dur:.4f} ms")
            break
        short, whole = short + dur - parts, whole + dur
    if short > _ROUND_SLACK_MS * len(steps) + _ROUND_SLACK_SHARE * whole:
        problems.append(
            f"trace.json: the rounds' parts fall {short:.3f} ms short of "
            f"their {len(steps)} spans' {whole:.3f} ms — decode_ms + "
            "prefill_ms + own_ms is the span's duration")
    slow = [r for r in records if r.get("kind") == "slow_round"]
    for r in slow:
        missing = _SLOW_ROUND_KEYS - set(r)
        if missing or not any(k in r for k in _SLOW_ROUND_HELD):
            problems.append(
                f"metrics.jsonl: slow_round record of round "
                f"{r.get('round')!r} lacks {sorted(missing)} or the median "
                "it was held against")
            break
    if len(slow) > counters.get("serve/slow_rounds", 0):
        problems.append(
            f"metrics.jsonl: {len(slow)} slow_round record(s) but "
            f"serve/slow_rounds = {counters.get('serve/slow_rounds')!r} — "
            "every record bumps the counter")
    for r in records:
        if r.get("kind") == "startup" and _STARTUP_KEYS - set(r):
            problems.append(
                f"metrics.jsonl: startup record lacks "
                f"{sorted(_STARTUP_KEYS - set(r))}")
    return problems


def check_schema(run_dir: str) -> list[str]:
    """Schema violations across the run's artifacts ([] = clean)."""
    problems = []
    jsonl = os.path.join(run_dir, "metrics.jsonl")
    if not os.path.exists(jsonl):
        return [f"missing {jsonl}"]
    try:
        records = load_jsonl(jsonl)
    except ValueError as e:
        return [str(e)]
    for i, rec in enumerate(records):
        kind = rec.get("kind")
        if kind not in _KINDS:
            problems.append(f"metrics.jsonl:{i + 1}: unknown kind {kind!r}")
        elif kind == "step":
            missing = _STEP_KEYS - set(rec)
            if missing:
                problems.append(
                    f"metrics.jsonl:{i + 1}: step record missing "
                    f"{sorted(missing)}")
        elif kind == "serve":
            missing = _SERVE_KEYS - set(rec)
            if missing:
                problems.append(
                    f"metrics.jsonl:{i + 1}: serve record missing "
                    f"{sorted(missing)}")
            else:
                if rec["spec_accepted"] > rec["spec_proposed"]:
                    problems.append(
                        f"metrics.jsonl:{i + 1}: spec_accepted="
                        f"{rec['spec_accepted']} exceeds spec_proposed="
                        f"{rec['spec_proposed']} — the verify pass "
                        "accepted tokens the draft never proposed")
                if rec["prefill_chunks"] < 1:
                    problems.append(
                        f"metrics.jsonl:{i + 1}: prefill_chunks="
                        f"{rec['prefill_chunks']!r} — an admitted "
                        "request spans at least one prefill dispatch")
        elif kind == "reshard":
            missing = _RESHARD_KEYS - set(rec)
            if missing:
                problems.append(
                    f"metrics.jsonl:{i + 1}: reshard record missing "
                    f"{sorted(missing)}")
            elif rec["route"] == "compiled" \
                    and rec.get("peak_host_bytes"):
                problems.append(
                    f"metrics.jsonl:{i + 1}: compiled-route reshard "
                    f"claims peak_host_bytes="
                    f"{rec['peak_host_bytes']} — the fast path must "
                    "never stage through the host")
        elif kind == "fault":
            missing = _FAULT_KEYS - set(rec)
            if missing:
                problems.append(
                    f"metrics.jsonl:{i + 1}: fault record missing "
                    f"{sorted(missing)}")
            else:
                if rec["fault"] not in _FAULT_KINDS:
                    problems.append(
                        f"metrics.jsonl:{i + 1}: unknown fault kind "
                        f"{rec['fault']!r}")
                if rec["phase"] not in _FAULT_PHASES:
                    problems.append(
                        f"metrics.jsonl:{i + 1}: unknown fault phase "
                        f"{rec['phase']!r}")
        elif kind == "dispatch":
            missing = _DISPATCH_KEYS - set(rec)
            if missing:
                problems.append(
                    f"metrics.jsonl:{i + 1}: dispatch record missing "
                    f"{sorted(missing)}")
            else:
                if rec["reason"] not in _DISPATCH_REASONS:
                    problems.append(
                        f"metrics.jsonl:{i + 1}: unknown dispatch "
                        f"reason {rec['reason']!r} (have "
                        f"{list(_DISPATCH_REASONS)})")
                if rec["re_emitted"] != 0:
                    problems.append(
                        f"metrics.jsonl:{i + 1}: dispatch re_emitted="
                        f"{rec['re_emitted']!r} — the at-most-once "
                        "contract re-emitted tokens to a client "
                        "stream")
        elif kind == "handoff":
            missing = _HANDOFF_KEYS - set(rec)
            if missing:
                problems.append(
                    f"metrics.jsonl:{i + 1}: handoff record missing "
                    f"{sorted(missing)}")
            else:
                if rec["route"] not in _HANDOFF_ROUTES:
                    problems.append(
                        f"metrics.jsonl:{i + 1}: unknown handoff route "
                        f"{rec['route']!r} (have {list(_HANDOFF_ROUTES)})")
                if not rec["prefill_replica"] or not rec["decode_replica"]:
                    problems.append(
                        f"metrics.jsonl:{i + 1}: handoff without its "
                        "paired prefill/decode replica ids — the "
                        "transfer route cannot be audited")
                gather = rec.get("per_device_gather_elems")
                budget = rec.get("budget_elems")
                if gather is not None and budget and gather > budget:
                    problems.append(
                        f"metrics.jsonl:{i + 1}: handoff per-device "
                        f"gather {gather} exceeds its shard budget "
                        f"{budget} — a full-pool staging the ADT072 "
                        "contract forbids")
        elif kind == "scale":
            missing = _SCALE_KEYS - set(rec)
            if missing:
                problems.append(
                    f"metrics.jsonl:{i + 1}: scale record missing "
                    f"{sorted(missing)}")
            else:
                if rec["direction"] not in ("grow", "shrink"):
                    problems.append(
                        f"metrics.jsonl:{i + 1}: unknown scale "
                        f"direction {rec['direction']!r}")
                if rec["trigger"] not in _SCALE_TRIGGERS:
                    problems.append(
                        f"metrics.jsonl:{i + 1}: unknown scale trigger "
                        f"{rec['trigger']!r} (have "
                        f"{sorted(_SCALE_TRIGGERS)})")
                delta = rec["replicas_after"] - rec["replicas_before"]
                want = 1 if rec["direction"] == "grow" else -1
                if delta != want:
                    problems.append(
                        f"metrics.jsonl:{i + 1}: {rec['direction']} "
                        f"claims {rec['replicas_before']} -> "
                        f"{rec['replicas_after']} replicas — a scale "
                        "step moves the count by exactly one")
        elif kind == "drift":
            missing = _DRIFT_KEYS - set(rec)
            if missing:
                problems.append(
                    f"metrics.jsonl:{i + 1}: drift record missing "
                    f"{sorted(missing)}")
            else:
                if rec["direction"] not in ("over", "under"):
                    problems.append(
                        f"metrics.jsonl:{i + 1}: unknown drift "
                        f"direction {rec['direction']!r}")
                elif abs(rec["ratio"] - 1.0) <= rec["threshold"]:
                    problems.append(
                        f"metrics.jsonl:{i + 1}: drift record for "
                        f"{rec['term']!r} with ratio {rec['ratio']} "
                        f"INSIDE its ±{rec['threshold']} band — a "
                        "breach record that never breached")
        elif kind in ("slow_round", "startup"):
            pass            # held whole by _check_rounds, below
        elif "name" not in rec:
            problems.append(f"metrics.jsonl:{i + 1}: {kind} without name")
        elif kind == "histogram" and "count" not in rec:
            problems.append(f"metrics.jsonl:{i + 1}: histogram without count")

    # Every injected fault must reach a terminal outcome record
    # (recovered / degraded / escalated / teardown) for the same fault
    # kind and target — an injection with no outcome means the recovery
    # path silently never ran (or never recorded), which is exactly the
    # regression the chaos harness exists to catch.
    faults = [r for r in records if r.get("kind") == "fault"
              and _FAULT_KEYS <= set(r)]
    for rec in faults:
        if rec["phase"] != "injected":
            continue
        matched = any(
            o is not rec and o["fault"] == rec["fault"]
            and o["phase"] in _FAULT_TERMINAL
            and o["target"] == rec["target"]
            for o in faults)
        if not matched:
            problems.append(
                f"metrics.jsonl: injected fault "
                f"{rec['fault']}@{rec['target']} has no matching "
                f"recovery/degrade/escalation/teardown record — the "
                "recovery path never ran or never recorded")

    # A failover dispatch must pair with the fault/health record the
    # fleet emitted for the replica it failed AWAY from: a failover
    # with no recorded cause is a recovery nobody can audit (and a
    # telltale of a router re-homing healthy replicas' work).
    dispatches = [r for r in records if r.get("kind") == "dispatch"
                  and _DISPATCH_KEYS <= set(r)]
    fault_targets = {r.get("target") for r in faults}
    for rec in dispatches:
        if rec["reason"] != "failover":
            continue
        src = rec.get("from_replica")
        if src is None or src not in fault_targets:
            problems.append(
                f"metrics.jsonl: failover dispatch for "
                f"{rec.get('request')} names from_replica={src!r} with "
                "no paired fault/health record for that replica — an "
                "unaudited failover")
            continue
        # The PR-19 causal-chain gate, keyed on the distributed trace
        # id (absent on pre-tracing runs, which keep passing on the
        # pairing gate above alone): the failed-over trace must show a
        # PRIOR dispatch onto the replica it claims to flee — a
        # failover whose own trace never touched that replica is a
        # router re-homing work it never lost.
        tid = rec.get("trace_id")
        if tid is not None:
            on_src = any(o is not rec and o.get("trace_id") == tid
                         and o.get("replica") == src
                         for o in dispatches)
            if not on_src:
                problems.append(
                    f"metrics.jsonl: failover dispatch for trace "
                    f"{tid} claims from_replica={src!r} but the trace "
                    "has no dispatch record onto that replica — the "
                    "causal chain (dispatch → fault → failover) is "
                    "broken")

    trace = os.path.join(run_dir, "trace.json")
    trace_events: list = []
    trace_ok = False
    if os.path.exists(trace):
        try:
            with open(trace) as f:
                data = json.load(f)
            events = data["traceEvents"]
            for j, ev in enumerate(events):
                if not {"name", "ph", "ts"} <= set(ev):
                    problems.append(f"trace.json: event {j} malformed")
                    break
            else:
                trace_events = events
                trace_ok = True
        except (ValueError, KeyError, TypeError) as e:
            problems.append(f"trace.json: invalid chrome trace ({e})")

    # The PR-19 handoff causal gate, keyed the same trace-id way: a
    # ``kind="handoff"`` record tagged with a trace id claims "this
    # request prefilled on one pool and decoded on another" — the
    # stitched trace must actually contain BOTH halves (a prefill span
    # and a decode span tagged with the same id), or the KV transfer
    # moved a prefix no traced prefill produced / no traced decode
    # consumed.  Untagged (pre-tracing) handoffs keep passing.
    if trace_ok:
        tagged = {}
        for ev in trace_events:
            name = str(ev.get("name", ""))
            for t in _event_trace_ids(ev):
                got = tagged.setdefault(t, set())
                if "prefill" in name:
                    got.add("prefill")
                if "decode" in name:
                    got.add("decode")
        for rec in records:
            if rec.get("kind") != "handoff":
                continue
            tid = rec.get("trace_id")
            if tid is None:
                continue
            got = tagged.get(tid, set())
            missing = {"prefill", "decode"} - got
            if missing:
                problems.append(
                    f"metrics.jsonl: handoff record for trace {tid} "
                    f"has no {'/'.join(sorted(missing))} span tagged "
                    "with that trace id in trace.json — a KV transfer "
                    "outside its request's causal chain")

    # Any precision gauge must carry a legal wire width.
    gauges = {r.get("name"): r for r in records if r.get("kind") == "gauge"}
    for name, rec in gauges.items():
        if isinstance(name, str) and name.startswith("precision/") \
                and name.endswith("_bits") \
                and rec.get("value") not in _PRECISION_BITS.values():
            problems.append(
                f"metrics.jsonl: {name} = {rec.get('value')!r} is not a "
                f"wire width in {sorted(_PRECISION_BITS.values())}")
        # Fused-kernel election gauges: the name must be a registered
        # kernel and an elected gauge is always 1 (a lowering either
        # honored the election or emitted nothing).
        if isinstance(name, str) and name.startswith("kernel/") \
                and name.endswith("_elected"):
            kname = name[len("kernel/"):-len("_elected")]
            if name in _OBSERVED_ELECTIONS:
                engine_gauge, who, composed = _OBSERVED_ELECTIONS[name]
                if rec.get("value") not in (0, 1):
                    problems.append(
                        f"metrics.jsonl: {name} = {rec.get('value')!r} — "
                        f"1 (the fused kernel) or 0 ({composed})")
                if engine_gauge not in gauges:
                    problems.append(
                        f"metrics.jsonl: {name} without the "
                        f"{engine_gauge} gauge — {who}")
            elif kname not in _KERNEL_CHOICES:
                problems.append(
                    f"metrics.jsonl: {name} names an unregistered "
                    f"kernel (have {sorted(_KERNEL_CHOICES)})")
            elif rec.get("value") != 1:
                problems.append(
                    f"metrics.jsonl: {name} = {rec.get('value')!r} — an "
                    "elected-kernel gauge must be 1")

    # A paged serving run must carry the block-pool gauges: their
    # absence means the free-list accounting (the admission predicate's
    # ground truth) silently never ran.
    if any(r.get("kind") == "serve" and r.get("kv_layout") == "paged"
           for r in records):
        for gname in _KV_BLOCK_GAUGES:
            if gname not in gauges:
                problems.append(
                    f"metrics.jsonl: serve records declare "
                    f"kv_layout=\"paged\" but the {gname} gauge is "
                    "missing — the block-pool accounting never emitted")

    # The prefill work counters come together, and with them every
    # prefill dispatch span names its rows (runs from before the
    # counters carry neither and keep passing).
    counters = {r.get("name"): r for r in records
                if r.get("kind") == "counter"}
    rows_c, pos_c = (counters.get(n) for n in _PREFILL_COUNTERS)
    if (rows_c is None) != (pos_c is None):
        problems.append(
            f"metrics.jsonl: {_PREFILL_COUNTERS[0]} and "
            f"{_PREFILL_COUNTERS[1]} are advanced together — one is "
            "missing")
    elif rows_c is not None:
        if pos_c.get("value", 0) < rows_c.get("value", 0):
            problems.append(
                f"metrics.jsonl: {_PREFILL_COUNTERS[1]} = "
                f"{pos_c.get('value')!r} is under "
                f"{_PREFILL_COUNTERS[0]} = {rows_c.get('value')!r} — a "
                "row spans at least one position")
        rungs = {int(n[len(_RUNG_COUNTER):]): c.get("value", 0)
                 for n, c in counters.items()
                 if n.startswith(_RUNG_COUNTER)}
        if sum(rungs.values()) > rows_c.get("value", 0) \
                or sum(S * n for S, n in rungs.items()) \
                > pos_c.get("value", 0):
            problems.append(
                f"metrics.jsonl: the {_RUNG_COUNTER}<S> counters hold "
                f"{rungs!r}, more rows or positions than "
                f"{_PREFILL_COUNTERS[0]} = {rows_c.get('value')!r} and "
                f"{_PREFILL_COUNTERS[1]} = {pos_c.get('value')!r} — a "
                "row runs at one rung")
        bare = sum(1 for ev in trace_events
                   if ev.get("name") == "engine/prefill/dispatch"
                   and "rows" not in (ev.get("args") or {}))
        if bare:
            problems.append(
                f"trace.json: {bare} engine/prefill/dispatch span(s) "
                "without their `rows` argument in a run that counts "
                "prefill rows")

    # An engine sets its gauges once, where it is built.  One built before
    # this run's recorder was created (a `telemetry.reset()` since: a
    # benchmark's window) left them in the recorder that went, so the
    # rules that hold counters against those gauges hold the counters
    # alone.
    account = next((r for r in records if r.get("kind") == "startup"), {})
    engine_predates_run = 0 < account.get("engine_built_s", 0) \
        < account.get("run_started_s", 0)
    routing = [counters.get(n) for n in _ROUTING_COUNTERS]
    if any(c is not None for c in routing):
        held_g = gauges.get("engine/experts_held")
        if held_g is None and engine_predates_run:
            held_g = {"value": float("inf")}
        if any(c is None for c in routing) or held_g is None:
            problems.append(
                f"metrics.jsonl: {', '.join(_ROUTING_COUNTERS)} and the "
                "engine/experts_held gauge come together — one is missing")
        else:
            steps, routed, held, hit = (c.get("value", 0) for c in routing)
            if not hit <= held <= routed:
                problems.append(
                    f"metrics.jsonl: moe/experts_hit = {hit!r}, "
                    f"moe/rows_held = {held!r}, moe/rows_routed = "
                    f"{routed!r} — an expert that is hit holds a pair, "
                    "and a pair that is held was routed")
            if hit > steps * held_g.get("value", 0):
                problems.append(
                    f"metrics.jsonl: moe/experts_hit = {hit!r} is over "
                    f"moe/layer_steps = {steps!r} x engine/experts_held "
                    f"= {held_g.get('value')!r}")

    groups = counters.get(_GROUPS_COUNTER)
    if groups is not None:
        if any(c is None for c in routing):
            problems.append(
                f"metrics.jsonl: {_GROUPS_COUNTER} without "
                f"{', '.join(_ROUTING_COUNTERS)} — the groups a router "
                "keeps are counted with what it routed")
        else:
            kept, routed, held = (c.get("value", 0) for c in
                                  (groups, routing[1], routing[2]))
            if kept > routed or (held and not kept):
                problems.append(
                    f"metrics.jsonl: {_GROUPS_COUNTER} = {kept!r} beside "
                    f"moe/rows_routed = {routed!r} and moe/rows_held = "
                    f"{held!r} — a pair is held only through a group its "
                    "row kept, and a row that kept one was routed")
    both = [gauges.get(n) for n in _TWO_STATE_GAUGES]
    if any(g is not None for g in both[:3]) and not all(
            g is not None and g.get("value", 0) > 0 for g in both):
        problems.append(
            f"metrics.jsonl: {', '.join(_TWO_STATE_GAUGES)} come together "
            "and positive — an engine sets them where its cache manager "
            "holds latent rows beside recurrent state")
    if gauges.get(_STATE_GAUGES[0]) is not None and not all(
            (gauges.get(n) or {}).get("value", 0) > 0
            for n in _STATE_GAUGES):
        problems.append(
            f"metrics.jsonl: {', '.join(_STATE_GAUGES)} come together and "
            "positive — an engine whose stack keeps a recurrent state says "
            "how many rows a head holds, its bytes over all slots and a "
            "slot's")
    built = counters.get(_STATE_PROMPTS)
    if built is not None:
        rows = (counters.get(_PREFILL_COUNTERS[0]) or {}).get("value", 0)
        if not rows or built.get("value", 0) % rows \
                or built.get("value", 0) < rows:
            problems.append(
                f"metrics.jsonl: {_STATE_PROMPTS} = "
                f"{built.get('value')!r} beside {_PREFILL_COUNTERS[0]} = "
                f"{rows!r} — a prefill row builds one state in every "
                "linear layer: whole layers of the rows")
    blank = counters.get(_STATE_PROMPTS_BLANK)
    if blank is not None and not (
            0 <= blank.get("value", 0) <= (built or {}).get("value", -1)):
        problems.append(
            f"metrics.jsonl: {_STATE_PROMPTS_BLANK} = "
            f"{blank.get('value')!r} beside {_STATE_PROMPTS} = "
            f"{(built or {}).get('value')!r} — the states built from no "
            "state are some of the states built")

    # kernel/ssd_step_calls: a decode program traced the state-space
    # kernel — only where the engine's election said 1.
    if counters.get(_SSD_CALLS) is not None and (
            gauges.get("kernel/ssd_step_elected") or {}).get("value") != 1:
        problems.append(
            f"metrics.jsonl: {_SSD_CALLS} without kernel/ssd_step_elected "
            "= 1 — a program calls the kernel only where the engine's "
            "layout elected it")

    latent = counters.get(_LATENT_COUNTER)
    if latent is not None:
        held = [counters.get(_LATENT_BOUND[0]),
                *(gauges.get(n) for n in _LATENT_BOUND[1:])]
        if engine_predates_run and held[0] is not None:
            held = [r or {"value": float("inf")} for r in held]
        if any(r is None for r in held):
            problems.append(
                f"metrics.jsonl: {_LATENT_COUNTER}, "
                f"{', '.join(_LATENT_BOUND)} come together — one is "
                "missing")
        else:
            windows, lane, layers = (r.get("value", 0) for r in held)
            if latent.get("value", 0) > windows * lane * layers:
                problems.append(
                    f"metrics.jsonl: {_LATENT_COUNTER} = "
                    f"{latent.get('value')!r} is over steps x slots x "
                    f"blocks ({_LATENT_BOUND[0]} = {windows!r}) x max_len "
                    f"({_LATENT_BOUND[1]} = {lane!r}) x layers "
                    f"({_LATENT_BOUND[2]} = {layers!r})")

    fused = (counters.get(_ATTENTION_COUNTERS[0]) or {}).get("value", 0)
    if bool(fused) != ("kernel/flash_attention_elected" in gauges):
        problems.append(
            f"metrics.jsonl: {_ATTENTION_COUNTERS[0]} = {fused!r} and the "
            "kernel/flash_attention_elected gauge go together — a traced "
            "call that takes the fused kernels sets both")

    # A scale transition must come with the gauge for the trigger it
    # claims fired: the record says "queue depth crossed the line" —
    # without the autoscale/queue_depth gauge in the same run, the
    # signal behind the decision was never emitted and the transition
    # cannot be audited against it.
    for rec in records:
        if rec.get("kind") != "scale":
            continue
        gname = _SCALE_TRIGGERS.get(rec.get("trigger"))
        if gname is not None and gname not in gauges:
            problems.append(
                f"metrics.jsonl: scale record fired on "
                f"{rec['trigger']!r} but the {gname} gauge is missing "
                "— the trigger signal was never emitted")
            break

    manifest = os.path.join(run_dir, "manifest.json")
    spans_dropped = 0
    if os.path.exists(manifest):
        try:
            with open(manifest) as f:
                m = json.load(f)
            if m.get("kind") != "manifest" or "provenance" not in m:
                problems.append("manifest.json: kind/provenance missing")
            spans_dropped = (m.get("telemetry") or {}).get(
                "spans_dropped", 0)
            declared = (m.get("run") or {}).get("collective_precision")
            if isinstance(declared, dict):
                # A run annotated with a precision policy must carry the
                # per-boundary gauges the lowering emits — their absence
                # means the policy was silently dropped.
                for boundary, prec in declared.items():
                    if prec in (None, "fp32"):
                        continue
                    gname = f"precision/{boundary}_bits"
                    rec = gauges.get(gname)
                    if rec is None:
                        problems.append(
                            f"manifest run.collective_precision declares "
                            f"{boundary}={prec} but metrics.jsonl has no "
                            f"{gname} gauge — the lowering dropped the "
                            "policy")
                    elif rec.get("value") != _PRECISION_BITS.get(prec):
                        problems.append(
                            f"{gname} = {rec.get('value')!r} disagrees "
                            f"with the declared {boundary}={prec} "
                            f"({_PRECISION_BITS.get(prec)} bits)")
            declared_kernel = (m.get("run") or {}).get("kernel")
            if declared_kernel:
                # A run annotated with a fused-kernel election must
                # carry the kernel/<name>_elected gauge the lowering
                # (or serving engine) emits — absence means the
                # election was silently dropped between plan and
                # program.
                names = (declared_kernel if isinstance(
                    declared_kernel, (list, tuple))
                    else [k for k, v in declared_kernel.items() if v]
                    if isinstance(declared_kernel, dict)
                    else str(declared_kernel).split(","))
                for kname in names:
                    kname = str(kname).strip()
                    if not kname:
                        continue
                    gname = f"kernel/{kname}_elected"
                    rec = gauges.get(gname)
                    if rec is None:
                        problems.append(
                            f"manifest run.kernel declares {kname!r} "
                            f"but metrics.jsonl has no {gname} gauge — "
                            "the lowering dropped the election")
                    elif rec.get("value") != 1:
                        problems.append(
                            f"{gname} = {rec.get('value')!r} disagrees "
                            f"with the declared kernel election")
        except ValueError as e:
            problems.append(f"manifest.json: invalid ({e})")
    problems += _check_rounds(records, trace_events, spans_dropped)

    drift = os.path.join(run_dir, "drift.json")
    if os.path.exists(drift):
        try:
            with open(drift) as f:
                d = json.load(f)
            if d.get("kind") != "drift" or not isinstance(
                    d.get("ratios"), dict):
                problems.append("drift.json: kind/ratios missing")
            # Per-level comm terms (hierarchical network model) must
            # come paired: a cross-slice time term without its byte
            # term means the cost model or the report dropped half the
            # breakdown — the dcn_gbps proposal would fit garbage.
            pred = d.get("predicted") or {}
            if pred.get("comm_time_dcn_s") and not pred.get("dcn_bytes"):
                problems.append(
                    "drift.json: predicted.comm_time_dcn_s without "
                    "predicted.dcn_bytes — per-level comm terms out "
                    "of sync")
            # Expert dispatch/combine breakout comes paired the same
            # way, and an expert-parallel run (manifest run.moe with a
            # >1 expert axis) must carry it plus the comm/a2a_bytes
            # gauge — their absence means the cost model priced the
            # MoE plan with no a2a term at all.
            if pred.get("a2a_time_s") and not pred.get("a2a_bytes"):
                problems.append(
                    "drift.json: predicted.a2a_time_s without "
                    "predicted.a2a_bytes — a2a breakout terms out "
                    "of sync")
            moe_ann = None
            if os.path.exists(manifest):
                try:
                    with open(manifest) as f:
                        moe_ann = (json.load(f).get("run") or {}).get(
                            "moe")
                except ValueError:
                    pass
            if (isinstance(moe_ann, dict)
                    and int(moe_ann.get("expert_axis", 1) or 1) > 1):
                if not pred.get("a2a_bytes"):
                    problems.append(
                        "manifest run.moe declares an expert axis > 1 "
                        "but drift.json predicted.a2a_bytes is "
                        "missing — the dispatch/combine term was "
                        "never priced")
                elif "comm/a2a_bytes" not in gauges:
                    problems.append(
                        "manifest run.moe declares an expert axis > 1 "
                        "but metrics.jsonl has no comm/a2a_bytes "
                        "gauge — the a2a breakout was never emitted")
        except ValueError as e:
            problems.append(f"drift.json: invalid ({e})")
    return problems


def _fmt(v, nd=3) -> str:
    if v is None:
        return "—"
    if isinstance(v, float):
        return f"{v:.{nd}g}" if abs(v) < 1e4 else f"{v:.3e}"
    return str(v)


def _trace_sections(run_dir: str, records: list,
                    trace_filter=None) -> list:
    """The per-request trace timeline section: every trace id seen in
    the (possibly stitched) ``trace.json`` summarized with its span /
    record counts and the replicas (pids) it crossed; ``trace_filter``
    narrows to one request and expands it into the full ts-ordered
    timeline — the span tree with replica/pool attribution."""
    path = os.path.join(run_dir, "trace.json")
    if not os.path.exists(path):
        return []
    try:
        with open(path) as f:
            events = json.load(f)["traceEvents"]
    except (ValueError, KeyError, TypeError):
        return []
    by_trace: dict = {}
    for ev in events:
        for t in _event_trace_ids(ev):
            by_trace.setdefault(t, []).append(ev)
    if not by_trace:
        return []
    if trace_filter is not None and trace_filter not in by_trace:
        return ["## request traces", "",
                f"(trace {trace_filter!r} not found; run has "
                f"{len(by_trace)} traced request(s))", ""]
    lines = ["## request traces", "",
             "| trace | spans | records | pids | replicas |",
             "|---|---|---|---|---|"]
    wanted = [trace_filter] if trace_filter is not None \
        else sorted(by_trace)
    rec_by_trace: dict = {}
    for r in records:
        if r.get("trace_id"):
            rec_by_trace.setdefault(r["trace_id"], []).append(r)
    for t in wanted:
        evs = by_trace[t]
        spans = [e for e in evs if e.get("ph") == "X"
                 and not (e.get("args") or {}).get("folded")]
        insts = [e for e in evs if (e.get("args") or {}).get("folded")
                 or e.get("ph") == "i"]
        pids = sorted({e.get("pid") for e in evs})
        replicas = sorted(
            {str((e.get("args") or {}).get("replica"))
             for e in evs if (e.get("args") or {}).get("replica")})
        lines.append(
            f"| {t} | {len(spans)} | {len(insts)} "
            f"| {'/'.join(str(p) for p in pids)} "
            f"| {'/'.join(replicas) or '—'} |")
    lines.append("")
    if trace_filter is not None:
        lines += [f"### timeline — {trace_filter}", "",
                  "| ts (ms) | event | dur (ms) | pid | replica | "
                  "detail |",
                  "|---|---|---|---|---|---|"]
        evs = sorted(by_trace[trace_filter],
                     key=lambda e: float(e.get("ts", 0.0)))
        t0 = float(evs[0].get("ts", 0.0)) if evs else 0.0
        for ev in evs:
            args = ev.get("args") or {}
            detail = args.get("reason") or args.get("route") \
                or args.get("finish") or args.get("phase") or "—"
            dur = ev.get("dur")
            lines.append(
                f"| {_fmt((float(ev.get('ts', 0.0)) - t0) / 1e3)} "
                f"| {ev.get('name')} "
                f"| {_fmt(float(dur) / 1e3 if dur is not None else None)} "
                f"| {ev.get('pid')} "
                f"| {args.get('replica') or '—'} | {detail} |")
        lines.append("")
    return lines


def _rounds_section(run_dir: str, records: list) -> list:
    """The batcher's rounds and the process's start-up, where the run
    recorded them: the table of ``rounds_summary``, each slow round with
    its evidence, and ``startup_summary``."""
    events = []
    path = os.path.join(run_dir, "trace.json")
    if os.path.exists(path):
        try:
            with open(path) as f:
                events = json.load(f)["traceEvents"]
        except (ValueError, KeyError, TypeError):
            pass
    lines = []
    got = rounds_summary(events, records)
    if got is not None:
        lines += ["## Rounds", "",
                  "| rounds | round ms p50 | p95 | max | decode ms p50 | "
                  "prefill ms a row p50 | own ms p50 | rows admitted | "
                  "compile events | slow rounds | slow excess ms |",
                  "|---|---|---|---|---|---|---|---|---|---|---|",
                  "| " + " | ".join(_fmt(got[k], 4) for k in (
                      "rounds", "round_ms_p50", "round_ms_p95",
                      "round_ms_max", "decode_ms_p50",
                      "prefill_ms_a_row_p50", "own_ms_p50",
                      "rows_admitted", "compiles", "slow_rounds",
                      "slow_excess_ms")) + " |", ""]
        if got["slow"]:
            lines += ["| slow round | decode ms (median) | own ms (median) "
                      "| prefill ms | compiles | engine/* children ms |",
                      "|---|---|---|---|---|---|"]
            for r in got["slow"][:_SLOW_ROUNDS_SHOWN]:
                kids = ", ".join(f"{k} {_fmt(v, 4)}" for k, v in
                                 sorted(r.get("children_ms", {}).items()))
                lines.append(
                    f"| {r.get('round')} | {_fmt(r.get('decode_ms'), 4)} "
                    f"({_fmt(r.get('median_decode_ms'), 4)}) "
                    f"| {_fmt(r.get('own_ms'), 4)} "
                    f"({_fmt(r.get('median_own_ms'), 4)}) "
                    f"| {_fmt(r.get('prefill_ms'), 4)} "
                    f"| {r.get('compiles')} | {kids or '—'} |")
            if len(got["slow"]) > _SLOW_ROUNDS_SHOWN:
                lines.append(f"| … {len(got['slow']) - _SLOW_ROUNDS_SHOWN} "
                             "more in metrics.jsonl | | | | | |")
            lines.append("")
    before = startup_summary(records)
    if before is not None:
        lines += ["## Start-up (before this run's recorder was created)",
                  "", "| " + " | ".join(before) + " |",
                  "|" + "---|" * len(before),
                  "| " + " | ".join(_fmt(v, 4) for v in before.values())
                  + " |", ""]
    return lines


def render(run_dir: str, trace_filter=None) -> str:
    """The markdown report for one flushed run directory."""
    records = load_jsonl(os.path.join(run_dir, "metrics.jsonl"))
    steps = [r for r in records if r.get("kind") == "step"]
    serves = [r for r in records if r.get("kind") == "serve"]
    dispatches = [r for r in records if r.get("kind") == "dispatch"]
    reshards = [r for r in records if r.get("kind") == "reshard"]
    faults = [r for r in records if r.get("kind") == "fault"]
    handoffs = [r for r in records if r.get("kind") == "handoff"]
    scales = [r for r in records if r.get("kind") == "scale"]
    drifts = [r for r in records if r.get("kind") == "drift"]
    counters = [r for r in records if r.get("kind") == "counter"]
    gauges = [r for r in records if r.get("kind") == "gauge"]
    hists = [r for r in records if r.get("kind") == "histogram"]

    lines = [f"# telemetry report — {run_dir}", ""]

    manifest_path = os.path.join(run_dir, "manifest.json")
    if os.path.exists(manifest_path):
        with open(manifest_path) as f:
            manifest = json.load(f)
        prov = manifest.get("provenance", {})
        lines += ["## run", "",
                  f"- git: `{prov.get('git_sha')}`",
                  f"- jax {prov.get('jax')} / jaxlib {prov.get('jaxlib')}"
                  f" / python {prov.get('python')}"]
        run_ann = manifest.get("run", {})
        for k in sorted(run_ann):
            lines.append(f"- {k}: `{_fmt(run_ann[k])}`")
        lines.append("")

    lines += ["## steps", ""]
    if steps:
        # A fused-window record covers `steps` optimizer steps; its
        # per-step latency is duration/steps.
        per_step_ms = np.asarray([r["duration_ms"] / max(r.get("steps", 1), 1)
                                  for r in steps])
        n_steps = sum(r.get("steps", 1) for r in steps)
        # rate over FULL window durations (a fused record's examples
        # span its whole duration, not the per-step share)
        total_s = sum(r["duration_ms"] for r in steps) / 1e3
        examples = sum(r.get("examples", 0) for r in steps)
        rate = examples / total_s if total_s > 0 and examples else None
        lines += ["| records | steps | mean ms | p50 ms | p99 ms | "
                  "examples/sec |",
                  "|---|---|---|---|---|---|",
                  f"| {len(steps)} | {n_steps} "
                  f"| {_fmt(float(per_step_ms.mean()))} "
                  f"| {_fmt(float(np.percentile(per_step_ms, 50)))} "
                  f"| {_fmt(float(np.percentile(per_step_ms, 99)))} "
                  f"| {_fmt(rate)} |", ""]
    else:
        lines += ["(no per-step records)", ""]

    if serves:
        # A serving run: per-request TTFT + the fused-window-attributed
        # inter-token latencies (autodist_tpu/serving/batcher.py), with
        # the histogram instruments carrying the exact per-token
        # distributions when present.
        ttft = np.asarray([r["ttft_ms"] for r in serves], float)
        tokens = sum(int(r.get("tokens", 0)) for r in serves)
        itl = next((h for h in hists
                    if h["name"] == "serve/inter_token_ms"), None)
        rates = [r["tokens_per_sec"] for r in serves
                 if r.get("tokens_per_sec")]
        depth = next((g["value"] for g in gauges
                      if g["name"] == "serve/queue_depth"), None)
        layouts = sorted({r.get("kv_layout", "dense") for r in serves})
        lines += ["## serving", "",
                  "| requests | tokens | kv layout | ttft p50 ms | "
                  "ttft p99 ms | inter-token p50 ms | "
                  "inter-token p99 ms | tokens/s (per-request p50) | "
                  "queue depth |",
                  "|---|---|---|---|---|---|---|---|---|",
                  f"| {len(serves)} | {tokens} "
                  f"| {'/'.join(layouts)} "
                  f"| {_fmt(float(np.percentile(ttft, 50)))} "
                  f"| {_fmt(float(np.percentile(ttft, 99)))} "
                  f"| {_fmt(itl['p50'] if itl else None)} "
                  f"| {_fmt(itl['p99'] if itl else None)} "
                  f"| {_fmt(float(np.percentile(rates, 50)) if rates else None)} "
                  f"| {_fmt(depth)} |", ""]
        if "paged" in layouts:
            free = next((g["value"] for g in gauges
                         if g["name"] == "serve/kv_blocks_free"), None)
            used = next((g["value"] for g in gauges
                         if g["name"] == "serve/kv_blocks_used"), None)
            lines += [f"- kv block pool (final): {_fmt(used)} used / "
                      f"{_fmt(free)} free", ""]
        # The throughput ladder (chunked prefill / prefix caching /
        # speculative decoding): rendered whenever any request rode a
        # rung — the per-request fields are always recorded, so an
        # all-zero ladder simply stays silent.
        hit_blocks = sum(int(r.get("prefix_hit_blocks", 0))
                         for r in serves)
        proposed = sum(int(r.get("spec_proposed", 0)) for r in serves)
        accepted = sum(int(r.get("spec_accepted", 0)) for r in serves)
        chunked = [int(r.get("prefill_chunks", 1)) for r in serves
                   if int(r.get("prefill_chunks", 1)) > 1]
        if hit_blocks or proposed or chunked:
            acceptance = accepted / proposed if proposed else None
            lines += ["### throughput ladder", "",
                      "| prefix hit blocks | chunked prefills | "
                      "chunks p50 | spec proposed | spec accepted | "
                      "acceptance rate |",
                      "|---|---|---|---|---|---|",
                      f"| {hit_blocks} | {len(chunked)} "
                      f"| {_fmt(float(np.percentile(chunked, 50)) if chunked else None)} "
                      f"| {proposed} | {accepted} "
                      f"| {_fmt(acceptance)} |", ""]

    lines += _rounds_section(run_dir, records)

    if dispatches:
        # The fleet section: routing decisions by reason, the hedge
        # win rate, and each replica's final queue depth (the
        # fleet/<name>/queue_depth gauges the router emits per round).
        by_reason = {}
        for r in dispatches:
            by_reason[r.get("reason")] = by_reason.get(r.get("reason"),
                                                       0) + 1
        counter_vals = {r["name"]: r["value"] for r in counters}
        hedges = counter_vals.get("fleet/hedges", 0)
        hedge_wins = counter_vals.get("fleet/hedge_wins", 0)
        win_rate = hedge_wins / hedges if hedges else None
        lines += ["## fleet", "",
                  "| dispatches | route | failover | hedge | drain | "
                  "hedge win rate | replacements |",
                  "|---|---|---|---|---|---|---|",
                  f"| {len(dispatches)} "
                  f"| {by_reason.get('route', 0)} "
                  f"| {by_reason.get('failover', 0)} "
                  f"| {by_reason.get('hedge', 0)} "
                  f"| {by_reason.get('drain', 0)} "
                  f"| {_fmt(win_rate)} "
                  f"| {_fmt(counter_vals.get('fleet/replacements'))} |",
                  ""]
        depth = {g["name"]: g["value"] for g in gauges
                 if g["name"].startswith("fleet/")
                 and g["name"].endswith("/queue_depth")}
        if depth:
            lines += ["| replica | queue depth (final) |", "|---|---|"]
            for name in sorted(depth):
                replica = name[len("fleet/"):-len("/queue_depth")]
                lines.append(f"| {replica} | {_fmt(depth[name])} |")
            lines.append("")

    if handoffs:
        # The disaggregation section: one KV-prefix handoff per request
        # that crossed the prefill→decode boundary, summarized by route
        # plus the prefill→decode pairings — the same pairing --check
        # gates on.
        blocks = sum(int(r.get("blocks", 0)) for r in handoffs)
        moved = sum(int(r.get("bytes_moved", 0)) for r in handoffs)
        durs = np.asarray([r["duration_ms"] for r in handoffs
                           if r.get("duration_ms") is not None], float)
        routes = "/".join(sorted({str(r.get("route")) for r in handoffs}))
        lines += ["## disaggregated serving", "",
                  "| handoffs | route | blocks | MB moved | p50 ms | "
                  "p99 ms |",
                  "|---|---|---|---|---|---|",
                  f"| {len(handoffs)} | {routes} | {blocks} "
                  f"| {_fmt(moved / 1e6)} "
                  f"| {_fmt(float(np.percentile(durs, 50)) if len(durs) else None)} "
                  f"| {_fmt(float(np.percentile(durs, 99)) if len(durs) else None)} |",
                  ""]
        pairs = {}
        for r in handoffs:
            key = (r.get("prefill_replica"), r.get("decode_replica"))
            pairs[key] = pairs.get(key, 0) + 1
        lines += ["| prefill → decode | handoffs |", "|---|---|"]
        for (src, dst) in sorted(pairs):
            lines.append(f"| {src} → {dst} | {pairs[(src, dst)]} |")
        lines.append("")

    if scales:
        # The autoscaling section: every grow/shrink transition with
        # the trigger that fired it and the measured value against its
        # threshold, in record order.
        lines += ["## autoscaling", "",
                  "| direction | trigger | value | threshold | "
                  "replicas | replica |",
                  "|---|---|---|---|---|---|"]
        for r in scales:
            lines.append(
                f"| {r.get('direction')} | {r.get('trigger')} "
                f"| {_fmt(r.get('value'))} | {_fmt(r.get('threshold'))} "
                f"| {r.get('replicas_before')} → "
                f"{r.get('replicas_after')} "
                f"| {r.get('replica', '—')} |")
        lines.append("")
        final = {g["name"]: g["value"] for g in gauges
                 if g["name"] in ("autoscale/queue_depth",
                                  "autoscale/ttft_p99_ms")}
        if final:
            lines.append(
                f"- trigger gauges (final): queue depth "
                f"{_fmt(final.get('autoscale/queue_depth'))}, "
                f"ttft p99 {_fmt(final.get('autoscale/ttft_p99_ms'))} ms")
            lines.append("")

    if drifts:
        # The ONLINE drift monitor's breach records (edge-triggered:
        # one row per crossing, in either direction) — the live
        # sibling of the post-hoc drift.json section below.
        lines += ["## online drift breaches", "",
                  "| step | term | ratio | band | direction |",
                  "|---|---|---|---|---|"]
        for r in drifts:
            lines.append(
                f"| {r.get('step')} | {r.get('term')} "
                f"| {_fmt(r.get('ratio'))} "
                f"| ±{_fmt(r.get('threshold'))} "
                f"| {r.get('direction')} |")
        lines.append("")

    lines += _trace_sections(run_dir, records, trace_filter)

    if reshards:
        lines += ["## reshards", "",
                  "| route | leaves | MB moved | peak host MB | ms |",
                  "|---|---|---|---|---|"]
        for r in reshards:
            lines.append(
                f"| {r['route']} | {r['leaves']} "
                f"| {_fmt(r['bytes_moved'] / 1e6)} "
                f"| {_fmt(r['peak_host_bytes'] / 1e6)} "
                f"| {_fmt(r['duration_ms'])} |")
        lines.append("")

    if faults:
        # One row per injection, joined with its terminal outcome (the
        # same pairing --check gates on); standalone detections ride
        # the notes column of their injection when present.
        lines += ["## faults", "",
                  "| fault | target | phase(s) | outcome | step/t |",
                  "|---|---|---|---|---|"]
        injections = [r for r in faults if r.get("phase") == "injected"]
        for inj in injections:
            related = [r for r in faults if r is not inj
                       and r.get("fault") == inj.get("fault")
                       and r.get("target") == inj.get("target")]
            phases = " → ".join(["injected"]
                                + [r.get("phase", "?") for r in related])
            outcome = next((r.get("action") or r.get("phase")
                            for r in reversed(related)
                            if r.get("phase") in _FAULT_TERMINAL), "NONE")
            when = inj.get("step")
            when = f"step {when}" if when is not None \
                else f"t={_fmt(inj.get('t_s'))}s"
            lines.append(f"| {inj.get('fault')} | {inj.get('target')} "
                         f"| {phases} | {outcome} | {when} |")
        orphans = [r for r in faults if r.get("phase") != "injected"
                   and not any(i.get("fault") == r.get("fault")
                               and i.get("target") == r.get("target")
                               for i in injections)]
        for r in orphans:   # real (un-injected) faults the run survived
            lines.append(f"| {r.get('fault')} | {r.get('target')} "
                         f"| {r.get('phase')} | {r.get('action') or '—'} "
                         f"| step {_fmt(r.get('step'))} |")
        lines.append("")

    attention = {r["name"]: r["value"] for r in counters
                 if r["name"] in _ATTENTION_COUNTERS}
    if attention:
        fused, einsum = (attention.get(n, 0) for n in _ATTENTION_COUNTERS)
        lines += ["## training attention", "",
                  f"- traced attention calls: {_fmt(fused)} took the fused "
                  f"kernels (`kernel/flash_attention_elected`), "
                  f"{_fmt(einsum)} the einsum", ""]

    if counters or gauges:
        lines += ["## counters / gauges", "", "| name | value |", "|---|---|"]
        for r in counters + gauges:
            lines.append(f"| {r['name']} | {_fmt(r['value'])} |")
        lines.append("")
    if hists:
        lines += ["## histograms", "",
                  "| name | n | mean | p50 | p99 |", "|---|---|---|---|---|"]
        for r in hists:
            lines.append(f"| {r['name']} | {r['count']} | {_fmt(r['mean'])} "
                         f"| {_fmt(r['p50'])} | {_fmt(r['p99'])} |")
        lines.append("")

    drift_path = os.path.join(run_dir, "drift.json")
    if os.path.exists(drift_path):
        with open(drift_path) as f:
            drift = json.load(f)
        lines += ["## drift (measured / predicted)", "",
                  "| term | ratio |", "|---|---|"]
        for k, v in sorted(drift.get("ratios", {}).items()):
            lines.append(f"| {k} | {_fmt(v)} |")
        mfu = drift.get("measured", {}).get("mfu")
        if mfu is not None:
            lines.append(f"| mfu (measured) | {_fmt(mfu)} |")
        lines.append("")
        proposal = drift.get("proposal")
        if proposal:
            link = {k: v for k, v in proposal.items() if k != "note"}
            lines += [f"calibration proposal: `{json.dumps(link)}`",
                      f"({proposal.get('note')})", ""]
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("run_dir", help="directory a telemetry run flushed "
                                    "(contains metrics.jsonl)")
    ap.add_argument("--check", action="store_true",
                    help="validate the artifact schema; non-zero exit on "
                         "a break (CI smoke)")
    ap.add_argument("--trace", default=None, metavar="ID",
                    help="expand one request's distributed trace into "
                         "its full timeline (span tree with replica "
                         "attribution)")
    args = ap.parse_args(argv)
    if args.check:
        problems = check_schema(args.run_dir)
        if problems:
            for p in problems:
                print(f"SCHEMA: {p}", file=sys.stderr)
            return 2
        print(f"schema OK: {args.run_dir}")
        return 0
    try:
        print(render(args.run_dir, trace_filter=args.trace))
    except (OSError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
