"""A served model under a closed loop of callers.

As many callers as the traffic file says each send their next request
the moment the driver sees their reply; one thread drives
``ContinuousBatcher.step`` and plays every caller.  Set-up fills the loop
and runs it until every caller's first request has completed (which
compiles the one prefill and the one decode program and leaves the slots
in the staggered state a running service is in); the window then opens.

The benchmark takes its own clock.  The engine's two host entry points
that the batcher drives (``prefill`` and ``decode_window``) are wrapped
here, so the moment a request's first and last token reach the host is
read by this file, not taken from the program's ``Completion``.  A
request is *due* when the driver saw the reply before it.

``serve_tokens_per_s`` is the tokens that reached the host inside the
window over the window's length: ``EngineProbe.delivered`` read where the
window opens and after its last round.  Both ends lie between rounds and
tokens arrive only inside one, so whole rounds are counted over exactly
the time they took, whatever share of a request lies on either side.  The
tails, ``attempted``, ``failed`` and the reference's sample stay on the
requests that completed in the window.
"""
from __future__ import annotations

import gc
import itertools
import time

import numpy as np

from harness import device, loader, stats, traffic as traffic_gen, weights
from harness import window


class EngineProbe:
    """Stamps, on this file's clock, when each request's first and last
    token reached the host, and counts what each dispatch carried."""

    def __init__(self, engine):
        self.by_prompt: dict = {}     # prompt bytes -> rid
        self.asked: dict = {}         # rid -> output tokens asked
        self.prompt_len: dict = {}
        self.got: dict = {}           # rid -> tokens on the host so far
        self.first: dict = {}         # rid -> perf_counter of first token
        self.last: dict = {}
        self.slot_rid: dict = {}
        self.prefills: list = []      # (t, admitted, prompt tokens)
        self.decodes: list = []       # (t, active slots, live kv tokens)
        self._prefill, self._decode = engine.prefill, engine.decode_window
        self.steps = engine.decode_steps
        engine.prefill = self.prefill
        engine.decode_window = self.decode_window

    def expect(self, rid, prompt, asked):
        self.by_prompt[np.asarray(prompt, np.int32).tobytes()] = rid
        self.asked[rid], self.prompt_len[rid] = asked, len(prompt)

    def prefill(self, prompts, p_lens, admit, *args, **kwargs):
        with window.annotate("prefill"):
            toks = self._prefill(prompts, p_lens, admit, *args, **kwargs)
        now = time.perf_counter()
        slots = np.flatnonzero(np.asarray(admit))
        for s in slots:
            key = np.asarray(prompts[s, :p_lens[s]], np.int32).tobytes()
            rid = self.by_prompt.pop(key)
            self.slot_rid[int(s)] = rid
            self.first[rid], self.got[rid] = now, 1
        self.prefills.append((now, len(slots),
                              int(np.asarray(p_lens)[slots].sum())))
        return toks

    def decode_window(self, active, *args, **kwargs):
        slots = np.flatnonzero(np.asarray(active))
        live = sum(self.prompt_len[self.slot_rid[int(s)]]
                   + self.got[self.slot_rid[int(s)]] for s in slots)
        with window.annotate("decode_window"):
            w = self._decode(active, *args, **kwargs)
        now = time.perf_counter()
        for s in slots:
            rid = self.slot_rid[int(s)]
            self.got[rid] += int(w.counts[s])
            if rid not in self.last and self.got[rid] >= self.asked[rid]:
                self.last[rid] = now
        # a step reads on average (steps - 1) / 2 more rows than at entry
        self.decodes.append(
            (now, len(slots), live + len(slots) * (self.steps - 1) / 2.0))
        return w

    def delivered(self) -> int:
        """Tokens on the host so far, over every request, each capped at
        what it asked: a fused window's steps past a request's end are
        computed and thrown away, never delivered."""
        return sum(min(n, self.asked[rid]) for rid, n in self.got.items())


def serve(ctx, seed: int, seconds: float, say) -> dict:
    """Build the engine from ``seed``, fill the loop, run the window for
    ``seconds`` and free the engine again.  Returns what the window left
    on the host: its requests, stamps, counts and the seeded sample."""
    import jax

    from autodist_tpu import telemetry

    cfg, mix = ctx.config, ctx.traffic
    ref = loader.load_module("reference", ctx.cell["config"])
    builder = loader.load_module("builders", cfg["builder"])
    compiles = window.CompileCounter()
    gc_timer = window.GcTimer()

    # ---- set-up: weights, engine, a full loop, every first reply -----
    t = time.perf_counter()
    params = weights.seeded_fill(ref.param_shapes(cfg), seed,
                            cfg["initializer_range"])
    jax.block_until_ready(params)
    weights_s = time.perf_counter() - t
    t = time.perf_counter()
    engine, batcher = builder.build_serving(cfg, params)
    del params
    probe = EngineProbe(engine)
    say(f"[setup] weights_s={weights_s:.2f} "
        f"engine_s={time.perf_counter() - t:.2f}")
    stream = traffic_gen.RequestStream(mix, cfg["vocab_size"], seed)
    ids = itertools.count()
    due: dict = {}
    requests: dict = {}
    observed: dict = {}        # rid -> when the driver saw the reply
    seen = 0

    def submit(now):
        prompt, asked = stream.next()
        rid = f"r{next(ids)}"
        probe.expect(rid, prompt, asked)
        requests[rid] = prompt
        due[rid] = now
        batcher.submit(prompt, max_new_tokens=asked, rid=rid)

    def one_round():
        nonlocal seen
        with window.annotate("step"):
            batcher.step()
        now = time.perf_counter()
        with window.annotate("completions"):
            done = list(itertools.islice(batcher.completions, seen, None))
            seen += len(done)
            for rid in done:
                observed[rid] = now
        with window.annotate("submit"):
            for _ in done:
                submit(now)
        return now

    now = time.perf_counter()
    for _ in range(mix["callers"]):
        submit(now)
    first_wave = set(due)
    while not first_wave <= observed.keys():
        one_round()
    say(f"[setup] loop full and every caller's first reply in after "
        f"{len(observed)} replies, {len(probe.prefills)} prefill and "
        f"{len(probe.decodes)} decode dispatches")

    # ---- the window --------------------------------------------------
    telemetry.reset()
    n_pre, n_dec = len(probe.prefills), len(probe.decodes)
    at_open = probe.delivered()
    rounds = 0
    window.settle_heap()
    with window.profiled(ctx.out_dir, ctx.trace) as log_dir, \
            compiles.counting(), gc_timer.timing():
        setup_s = time.perf_counter() - ctx.t_start
        with window.annotate("window"):
            t0 = time.perf_counter()
            while True:
                now = one_round()
                rounds += 1
                if now - t0 >= seconds:
                    break
            elapsed = now - t0
    delivered = probe.delivered() - at_open
    t1 = t0 + elapsed
    out = {"setup_s": setup_s, "elapsed": elapsed, "log_dir": log_dir,
           "memory": device.memory_held(ctx.devices, say),
           "compile_events": compiles.events, "delivered": delivered,
           "prefills": probe.prefills[n_pre:],
           "decodes": probe.decodes[n_dec:]}

    finished = [(rid, batcher.completions[rid])
                for rid, at in observed.items() if t0 < at <= t1]
    good = [(rid, c) for rid, c in finished
            if c.finish_reason == "max_tokens"
            and len(c.tokens) == probe.asked[rid]]
    failed = len(finished) - len(good)
    out.update(
        finished=len(finished), failed=failed,
        tokens=sum(len(c.tokens) for _, c in good),
        ttft_ms=[(probe.first[rid] - due[rid]) * 1e3 for rid, _ in good],
        tpot_ms=[(probe.last[rid] - probe.first[rid]) * 1e3
                 / (len(c.tokens) - 1) for rid, c in good
                 if len(c.tokens) > 1],
        program_ttft_ms=[c.ttft_s * 1e3 for _, c in good],
        served=[(requests[rid], c.tokens) for rid, c in
                _sample(good, requests, mix["sample_requests"], seed)])
    out["counts"] = {
        "requests_completed": len(finished), "requests_failed": failed,
        "tokens_generated": out["tokens"],
        "tokens_delivered_in_window": delivered, "scheduler_rounds": rounds,
        "prefill_dispatches": len(out["prefills"]),
        "requests_admitted": sum(p[1] for p in out["prefills"]),
        "prompt_tokens_admitted": sum(p[2] for p in out["prefills"]),
        "decode_dispatches": len(out["decodes"]),
        "compilations_in_window": len(compiles.events),
    }
    say(f"[window] {len(finished)} requests completed ({failed} failed), "
        f"{out['tokens']} tokens of theirs, {delivered} tokens delivered "
        f"inside the window, {rounds} rounds, "
        f"{len(out['prefills'])} prefill and {len(out['decodes'])} decode "
        f"dispatches; compile events inside: {len(compiles.events)} "
        f"{sorted(set(compiles.events))}; {gc_timer}")
    # free the program before the reference runs
    engine.prefill, engine.decode_window = probe._prefill, probe._decode
    del engine, batcher, probe
    gc.collect()
    return out


def run(ctx, say) -> dict:
    cfg, mix = ctx.config, ctx.traffic
    ref = loader.load_module("reference", ctx.cell["config"])
    got = serve(ctx, ctx.seed,
                mix["trace_seconds"] if ctx.trace else ctx.seconds, say)
    n_comp = len(got["compile_events"])
    checks = [("compilations_in_window", n_comp, 0, n_comp == 0, ""),
              ("requests_failed", got["failed"], 0, got["failed"] == 0,
               "a request that did not end max_tokens at its asked length")]
    served = got["served"]
    t_ref = time.perf_counter()
    if served:
        checks.extend(ref.compare(ref.served_gaps(
            weights.seeded_fill(ref.param_shapes(cfg), ctx.seed,
                                cfg["initializer_range"]),
            served, cfg)))
    else:
        checks.append(("requests_sampled", 0, 1, False,
                       "the window completed no request to compare"))
    say(f"[check] reference ran over {len(served)} requests "
        f"({sum(len(t) for _, t in served)} served tokens, the longest "
        f"among them) in {time.perf_counter() - t_ref:.1f} s")
    for name, value, limit, ok, note in checks:
        say(f"[check] {name}: {value:.6g} (limit {limit:.6g}) -> "
            f"{'ok' if ok else 'FAILED'} {note}")

    result = {"correct": all(c[3] for c in checks),
              "attempted": got["finished"], "failed": got["failed"],
              "memory": got["memory"],
              "counts": got["counts"], "checks": checks}
    if ctx.rehearse:
        return result
    rate = got["delivered"] / got["elapsed"]
    say(f"[window] {got['elapsed']:.3f} s; {rate:.2f} tokens/s delivered "
        f"inside it ({got['tokens'] / got['elapsed']:.2f} by the tokens of "
        f"the requests completed in it)")
    result["end_to_end"] = {"serve_tokens_per_s": rate,
                            "setup_s": got["setup_s"]}
    ttft, tpot = got["ttft_ms"], got["tpot_ms"]
    if ttft and tpot:     # a window too short to complete a request has none
        say(f"[window] {len(ttft)} requests in the tails; ttft ms p50 "
            f"{stats.percentile(ttft, 50):.1f} p95 "
            f"{stats.percentile(ttft, 95):.1f} max {max(ttft):.1f}; tpot ms "
            f"p50 {stats.percentile(tpot, 50):.2f} p95 "
            f"{stats.percentile(tpot, 95):.2f}; the program's own "
            f"Completion.ttft_s p95 "
            f"{stats.percentile(got['program_ttft_ms'], 95):.1f} ms")
        result["end_to_end"].update(
            ttft_p95_ms=stats.percentile(ttft, 95),
            tpot_p95_ms=stats.percentile(tpot, 95))
    if ctx.trace:
        record = window.reduce_profile(got["log_dir"])
        record.update(
            cfg=cfg, traffic=mix, chips=ctx.chips, peaks=ctx.peaks,
            flops=loader.load_module("flops", ctx.cell["config"]),
            programs=cfg["trace_programs"],
            prefills=got["prefills"], decodes=got["decodes"])
        result["record"] = record
    return result


def readings(ctx, seed: int, control: str, say) -> dict:
    """What the limit is set from (``tools/readings.py``): a short window
    at the cell's own load on ``seed``, then the widest gap of the served
    tokens and, with ``control`` (a lower precision), of the tokens that
    precision puts first at the same positions."""
    ref = loader.load_module("reference", ctx.cell["config"])
    got = serve(ctx, seed, ctx.seconds, say)
    params = weights.seeded_fill(ref.param_shapes(ctx.config), seed,
                            ctx.config["initializer_range"])
    out = {"sound": ref.compare(ref.served_gaps(params, got["served"],
                                                ctx.config))}
    if control:
        out["control"] = ref.compare(ref.served_gaps(
            params, got["served"], ctx.config, control=control))
    return out


def _sample(good: list, requests: dict, n: int, seed: int) -> list:
    """``n`` of the requests the window finished, drawn from the seed,
    the longest (prompt + output) always among them."""
    if not good:
        return []
    size = lambda rc: len(requests[rc[0]]) + len(rc[1].tokens)
    longest = max(good, key=size)
    rest = [rc for rc in good if rc[0] != longest[0]]
    rng = weights.host_rng(seed, "sample")
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]
