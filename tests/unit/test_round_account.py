"""The batcher's round account and the process's start-up account.

A test double stands in for the engine (it opens the ``engine/*`` spans
the real one does and moves a fake clock instead of computing), so a
round's parts are exact: ``time.perf_counter`` is replaced for the
length of a test, and the recorder is re-created on the fake clock.
"""
import json
import os
import subprocess
import sys
import time
import types

import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.serving import batcher as batcher_mod
from autodist_tpu.serving.batcher import ContinuousBatcher
from autodist_tpu.telemetry import account, core

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "tools"))
import telemetry_report  # noqa: E402

TICK = 1e-6       # every reading of the fake clock moves it this far


class Clock:
    def __init__(self):
        self.now = 1000.0

    def __call__(self):
        self.now += TICK
        return self.now

    def pass_ms(self, ms):
        self.now += ms * 1e-3


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(time, "perf_counter", c)
    telemetry.reset()
    yield c
    monkeypatch.undo()
    telemetry.reset()


class Engine:
    """Four slots, four tokens a window; ``decode_ms`` / ``prefill_ms``
    are what the next call costs on the fake clock."""

    num_slots, max_len, prefill_len, decode_steps = 4, 4096, 16, 4
    free_blocks = 1 << 30

    def __init__(self, clock):
        self.clock = clock
        self.decode_ms, self.prefill_ms = 2.0, 1.0

    def blocks_needed(self, *a, **kw):
        return 0

    def reserve_slot(self, *a, **kw):
        return 0

    def release_slot(self, slot):
        pass

    def prefill(self, prompts, p_lens, admit, seeds=None):
        with telemetry.span("engine/prefill/dispatch", rows=int(admit.sum())):
            pass
        with telemetry.span("engine/prefill/fetch"):
            self.clock.pass_ms(self.prefill_ms)
        return np.ones((self.num_slots,), np.int32)

    def decode_window(self, active):
        with telemetry.span("engine/decode/stage"):
            pass
        with telemetry.span("engine/decode/dispatch"):
            pass
        with telemetry.span("engine/decode/fetch"):
            self.clock.pass_ms(self.decode_ms)
        counts = np.where(active, self.decode_steps, 0).astype(np.int32)
        z = np.zeros_like(counts)
        return types.SimpleNamespace(
            tokens=np.ones((self.decode_steps, self.num_slots), np.int32),
            counts=counts, spec_proposed=z, spec_accepted=z)


def serving(clock, requests=4, tokens=100000):
    engine = Engine(clock)
    b = ContinuousBatcher(engine)
    for _ in range(requests):
        b.submit([1, 2, 3], max_new_tokens=tokens)
    return engine, b


def steps():
    return [e for e in telemetry.get().chrome_trace()["traceEvents"]
            if e["name"] == "serve/step"]


def counter(name):
    return telemetry.counter(name).value


def slow_events():
    return [r for r in telemetry.get().step_records()
            if r["kind"] == "slow_round"]


# --------------------------------------------------------------------- #
# the round's account
# --------------------------------------------------------------------- #
def test_round_fields_add_up_to_the_span(clock):
    engine, b = serving(clock)
    for _ in range(5):
        b.step()
    spans = steps()
    assert [e["args"]["round"] for e in spans] == [1, 2, 3, 4, 5]
    assert counter("serve/rounds") == 5
    assert telemetry.histogram("serve/round_ms").count == 5
    first, later = spans[0]["args"], spans[1]["args"]
    assert (first["admitted"], first["active"]) == (4, 4)
    assert (later["admitted"], later["active"]) == (0, 4)
    assert first["prefill_ms"] == pytest.approx(1.0, abs=0.05)
    assert later["prefill_ms"] == 0.0
    for e in spans:
        a = e["args"]
        assert a["decode_ms"] == pytest.approx(2.0, abs=0.05)
        assert a["compiles"] == 0 and a["own_ms"] > 0
        parts = a["decode_ms"] + a["prefill_ms"] + a["own_ms"]
        assert parts <= e["dur"] * 1e-3
        assert parts == pytest.approx(e["dur"] * 1e-3, abs=0.05)


def test_round_ordinal_reaches_the_trace_annotation(clock, monkeypatch):
    seen = []
    real = core._trace_annotation

    def spy(name, args):
        if name == "serve/step":
            seen.append(dict(args))
        return real(name, args)

    monkeypatch.setattr(core, "_trace_annotation", spy)
    _, b = serving(clock)
    b.step()
    b.step()
    assert seen == [{"round": 1}, {"round": 2}]


def test_idle_round_is_counted_and_has_no_decode_part(clock):
    b = ContinuousBatcher(Engine(clock))
    b.step()
    (span,) = steps()
    assert span["args"]["active"] == 0 and span["args"]["decode_ms"] == 0.0
    assert counter("serve/rounds") == 1


def test_disabled_telemetry_serves_without_an_account(clock):
    telemetry.configure(enabled=False)
    _, b = serving(clock, tokens=8)
    done = b.run()
    assert len(done) == 4 and steps() == []
    assert telemetry.get().registry.snapshot() == []


# --------------------------------------------------------------------- #
# slow rounds
# --------------------------------------------------------------------- #
def test_slow_decode_is_flagged_with_its_children(clock):
    engine, b = serving(clock)
    for _ in range(12):
        b.step()
    assert counter("serve/slow_rounds") == 0 and not slow_events()
    engine.decode_ms = 10.0
    b.step()
    engine.decode_ms = 2.0
    b.step()
    assert counter("serve/slow_rounds") == 1
    (ev,) = slow_events()
    assert ev["round"] == 13 and ev["active"] == 4
    assert ev["median_decode_ms"] == pytest.approx(2.0, abs=0.05)
    assert ev["decode_excess_ms"] == pytest.approx(8.0, abs=0.1)
    assert "own_excess_ms" not in ev
    assert set(ev["children_ms"]) == {
        "engine/decode/stage", "engine/decode/dispatch",
        "engine/decode/fetch"}
    assert ev["children_ms"]["engine/decode/fetch"] == pytest.approx(
        10.0, abs=0.05)


def test_slow_distribute_is_flagged_by_own_ms(clock, monkeypatch):
    engine, b = serving(clock)
    for _ in range(12):
        b.step()
    check = b._check_terminal
    calls = []

    def slow_check(i):
        if not calls:
            clock.pass_ms(3.0)
        calls.append(i)
        return check(i)

    monkeypatch.setattr(b, "_check_terminal", slow_check)
    b.step()
    assert counter("serve/slow_rounds") == 1
    (ev,) = slow_events()
    assert ev["own_excess_ms"] == pytest.approx(3.0, abs=0.1)
    assert ev["own_ms"] > ev["median_own_ms"] + batcher_mod.SLOW_ROUND_OWN_MS
    assert "decode_excess_ms" not in ev
    assert ev["decode_ms"] == pytest.approx(2.0, abs=0.05)


def test_own_ms_must_exceed_its_median_by_a_millisecond(clock, monkeypatch):
    """Twice a tiny median is still not a slow round."""
    engine, b = serving(clock)
    for _ in range(12):
        b.step()
    check = b._check_terminal
    monkeypatch.setattr(
        b, "_check_terminal", lambda i: (clock.pass_ms(0.1), check(i))[1])
    b.step()       # +0.4 ms of own time: many times the median, under 1 ms
    assert counter("serve/slow_rounds") == 0


@pytest.mark.parametrize("decode_ms", [2.0, 20.0])
def test_uniform_pace_is_not_flagged(clock, decode_ms):
    engine, b = serving(clock)
    engine.decode_ms = decode_ms
    for _ in range(40):
        b.step()
    assert counter("serve/rounds") == 40
    assert counter("serve/slow_rounds") == 0 and not slow_events()


def test_nothing_is_judged_before_the_history_holds_enough(clock):
    engine, b = serving(clock)
    for k in range(batcher_mod.SLOW_ROUND_MIN_HISTORY):
        engine.decode_ms = 2.0 if k else 50.0     # a first round compiles
        b.step()
    assert counter("serve/slow_rounds") == 0


def test_prefill_part_is_not_judged(clock):
    engine, b = serving(clock, requests=3)
    for _ in range(12):
        b.step()
    engine.prefill_ms = 80.0
    b.submit([1, 2, 3], max_new_tokens=64)
    b.step()
    assert steps()[-1]["args"]["prefill_ms"] == pytest.approx(80.0, abs=0.05)
    assert counter("serve/slow_rounds") == 0


def test_history_is_the_batchers_and_outlives_a_reset(clock):
    engine, b = serving(clock)
    for _ in range(12):
        b.step()
    telemetry.reset()
    engine.decode_ms = 10.0
    b.step()
    assert counter("serve/rounds") == 1 and counter("serve/slow_rounds") == 1
    assert slow_events()[0]["round"] == 13


def test_slow_round_events_are_capped_and_the_counter_is_not(
        clock, monkeypatch):
    monkeypatch.setattr(batcher_mod, "MAX_SLOW_ROUND_EVENTS", 3)
    engine, b = serving(clock)
    for _ in range(12):
        b.step()
    for _ in range(5):
        engine.decode_ms = 10.0
        b.step()
        engine.decode_ms = 2.0
        b.step()
        b.step()
    assert counter("serve/slow_rounds") == 5
    assert [e["round"] for e in slow_events()] == [13, 16, 19]


def test_default_cap_is_256():
    assert batcher_mod.MAX_SLOW_ROUND_EVENTS == 256
    assert batcher_mod.SLOW_ROUND_HISTORY == 64
    assert batcher_mod.SLOW_ROUND_FACTOR == 1.25
    assert batcher_mod.SLOW_ROUND_OWN_MS == 1.0


# --------------------------------------------------------------------- #
# the report's Rounds table and its gates
# --------------------------------------------------------------------- #
@pytest.fixture
def flushed(clock, tmp_path):
    engine, b = serving(clock)
    for _ in range(12):
        b.step()
    engine.decode_ms = 10.0
    b.step()
    telemetry.flush(str(tmp_path))
    return tmp_path


def test_report_renders_rounds_and_passes_check(flushed):
    assert telemetry_report.check_schema(str(flushed)) == []
    text = telemetry_report.render(str(flushed))
    assert "## Rounds" in text and "## Start-up" in text
    assert "engine/decode/fetch" in text
    with open(flushed / "trace.json") as f:
        events = json.load(f)["traceEvents"]
    got = telemetry_report.rounds_summary(
        events, telemetry_report.load_jsonl(str(flushed / "metrics.jsonl")))
    assert got["rounds"] == 13 and got["slow_rounds"] == 1
    assert got["decode_ms_p50"] == pytest.approx(2.0, abs=0.05)
    assert got["prefill_ms_a_row_p50"] == pytest.approx(0.25, abs=0.02)
    assert got["slow_excess_ms"] == pytest.approx(8.0, abs=0.1)
    assert got["rows_admitted"] == 4 and got["compiles"] == 0
    assert telemetry_report.rounds_line(got).startswith("[rounds] rounds=13")


def _rewrite(path, edit):
    with open(path) as f:
        data = json.load(f)
    edit(data)
    with open(path, "w") as f:
        json.dump(data, f)


def _first_step(data):
    return next(e for e in data["traceEvents"] if e["name"] == "serve/step")


@pytest.mark.parametrize("edit, said", [
    (lambda d: d["traceEvents"].remove(_first_step(d)), "serve/rounds"),
    (lambda d: _first_step(d)["args"].update(own_ms=50.0), "exceeds"),
    (lambda d: [e["args"].update(decode_ms=0.0) for e in d["traceEvents"]
                if e["name"] == "serve/step"], "short"),
    (lambda d: _first_step(d)["args"].pop("decode_ms"), "comes whole"),
])
def test_check_fails_a_broken_round_account(flushed, edit, said):
    _rewrite(flushed / "trace.json", edit)
    problems = telemetry_report.check_schema(str(flushed))
    assert len(problems) == 1 and said in problems[0]


def test_check_fails_a_slow_round_without_its_evidence(flushed):
    path = flushed / "metrics.jsonl"
    records = telemetry_report.load_jsonl(str(path))
    for r in records:
        if r["kind"] == "slow_round":
            del r["children_ms"]
    path.write_text("".join(json.dumps(r) + "\n" for r in records))
    (problem,) = telemetry_report.check_schema(str(flushed))
    assert "children_ms" in problem


def test_report_without_rounds_has_no_rounds_table(tmp_path):
    telemetry.reset()
    telemetry.counter("x").inc()
    telemetry.flush(str(tmp_path))
    assert telemetry_report.check_schema(str(tmp_path)) == []
    assert "## Rounds" not in telemetry_report.render(str(tmp_path))
    telemetry.reset()


# --------------------------------------------------------------------- #
# the rings
# --------------------------------------------------------------------- #
def test_span_ring_keeps_the_newest(monkeypatch):
    monkeypatch.setattr(core, "MAX_SPANS", 5)
    tel = telemetry.reset()
    for k in range(8):
        with telemetry.span("s", k=k):
            pass
    held = [e["args"]["k"] for e in tel.chrome_trace()["traceEvents"]]
    assert held == [3, 4, 5, 6, 7]
    book = tel.manifest()["telemetry"]
    assert (book["spans"], book["spans_dropped"]) == (5, 3)
    telemetry.reset()


def test_step_record_ring_keeps_the_newest(monkeypatch):
    monkeypatch.setattr(core, "MAX_STEP_RECORDS", 4)
    tel = telemetry.reset()
    for k in range(3):
        assert telemetry.record_step(k, 0.001)
    for k in range(3):
        assert telemetry.record_event("serve", n=k)
    kept = tel.step_records()
    assert [r["kind"] for r in kept] == ["step", "serve", "serve", "serve"]
    assert kept[0]["step"] == 2
    book = tel.manifest()["telemetry"]
    assert (book["step_records"], book["step_records_dropped"]) == (4, 2)
    assert telemetry.histogram("step/duration_s").count == 3
    telemetry.reset()


def test_spans_since_walks_back_from_the_tail(clock):
    tel = telemetry.get()
    with telemetry.span("engine/old"):
        clock.pass_ms(1)
    t = time.perf_counter()
    with telemetry.span("serve/x"):
        with telemetry.span("engine/a"):
            clock.pass_ms(1)
        with telemetry.span("engine/b"):
            clock.pass_ms(1)
    assert [e["name"] for e in tel.spans_since(t, "engine/")] == [
        "engine/b", "engine/a"]
    assert [e["name"] for e in tel.spans_since(t)] == [
        "serve/x", "engine/b", "engine/a"]
    assert tel.spans_since(time.perf_counter()) == []


@pytest.mark.parametrize("count", [0, 1, 7])
def test_histogram_observes_a_count_at_once(count):
    telemetry.reset()
    h = telemetry.histogram("h")
    h.observe(2.0)
    h.observe(4.0, count=count)
    snap = h.snapshot()
    assert snap["count"] == 1 + count
    assert snap["sum"] == pytest.approx(2.0 + 4.0 * count)
    assert snap["max"] == (4.0 if count else 2.0)
    assert h.percentile(100) == (4.0 if count else 2.0)
    telemetry.reset()


def test_histogram_count_respects_the_sample_cap(monkeypatch):
    from autodist_tpu.telemetry import metrics

    monkeypatch.setattr(metrics, "HISTOGRAM_CAP", 4)
    h = metrics.Histogram("h")
    h.observe(1.0, count=3)
    h.observe(2.0, count=3)
    snap = h.snapshot()
    assert snap["count"] == 6 and snap["samples_dropped"] == 2


# --------------------------------------------------------------------- #
# the process's account
# --------------------------------------------------------------------- #
def test_startup_outlives_reset_and_sees_a_compile():
    import jax
    import jax.numpy as jnp

    from jax._src import monitoring

    x = jnp.arange(7.0)
    telemetry.reset()
    telemetry.watch_compiles()
    telemetry.watch_compiles()          # installs once
    assert monitoring.get_event_duration_listeners().count(
        account._on_duration) == 1
    before = telemetry.startup()
    events = account.compile_events()
    fn = jax.jit(lambda x: x * 3 + 1)
    fn(x).block_until_ready()
    after = telemetry.startup()
    fn(x).block_until_ready()           # compiled: nothing more
    assert telemetry.startup()["backend_s"] == after["backend_s"]
    assert after["trace_s"] > before["trace_s"]
    assert after["lower_s"] > before["lower_s"]
    assert after["backend_s"] > before["backend_s"]
    compiled = account.compile_events() - events
    run = {m["name"]: m for m in telemetry.get().registry.snapshot()}
    for key in ("trace_s", "lower_s", "backend_s"):
        assert run["compile/" + key]["sum"] == pytest.approx(
            after[key] - before[key])
    # one lowering and one backend compile; jax traces inner calls too
    assert run["compile/lower_s"]["count"] == 1
    assert run["compile/backend_s"]["count"] == 1
    assert compiled == 2 + run["compile/trace_s"]["count"] >= 3
    telemetry.reset()
    again = telemetry.startup()
    assert again["backend_s"] == after["backend_s"]
    assert again["since_import_s"] >= after["since_import_s"]
    assert again["import_wall_s"] == after["import_wall_s"]
    assert telemetry.get().registry.snapshot() == []
    rec = telemetry.get().startup_record()
    assert rec["kind"] == "startup" and 0 < rec["run_started_s"] \
        <= rec["since_import_s"]


def test_startup_summary_is_the_account_less_the_run(tmp_path):
    records = [
        {"kind": "startup", "run_started_s": 20.0, "since_import_s": 70.0,
         "trace_s": 5.0, "lower_s": 2.0, "backend_s": 9.0,
         "cache_retrieval_s": 3.0, "cache_hits": 4, "cache_misses": 1,
         "compile_events": 15, "engine_s": 1.5, "runner_s": 0.0,
         "engine_built_s": 9.0, "runner_built_s": 0.0},
        {"kind": "histogram", "name": "compile/trace_s", "sum": 1.0,
         "count": 2},
        {"kind": "histogram", "name": "compile/backend_s", "sum": 4.0,
         "count": 2},
        {"kind": "counter", "name": "compile/cache_misses", "value": 1},
    ]
    got = telemetry_report.startup_summary(records)
    assert got["programs_s"] == pytest.approx(4.0 + 2.0 + 5.0)
    assert got["cache_retrieval_s"] == 3.0 and got["cache_misses"] == 0
    assert got["import_to_run_s"] == 20.0 and got["engine_s"] == 1.5
    assert got["compile_events_in_run"] == 4
    assert telemetry_report.startup_summary(records[1:]) is None
    line = telemetry_report.startup_line(got, before_import_s=1.25)
    assert line.startswith("[startup] before_import_s=1.25 import_to_run_s=20")


def _routed_run(tmp_path, engine_built_s):
    """A flushed run whose routing and latent counters stand without the
    gauges an engine sets where it is built."""
    telemetry.reset()
    for name, n in [("moe/layer_steps", 8), ("moe/rows_routed", 96),
                    ("moe/rows_held", 12), ("moe/experts_hit", 9),
                    ("serve/latent_positions_read", 400),
                    ("serve/kv_blocks_resident", 16)]:
        telemetry.counter(name).inc(n)
    telemetry.flush(str(tmp_path))
    path = tmp_path / "metrics.jsonl"
    records = telemetry_report.load_jsonl(str(path))
    records[-1].update(engine_built_s=engine_built_s, run_started_s=20.0)
    path.write_text("".join(json.dumps(r) + "\n" for r in records))
    telemetry.reset()
    return telemetry_report.check_schema(str(tmp_path))


def test_check_lets_an_older_engines_gauges_go_with_the_reset(tmp_path):
    assert _routed_run(tmp_path, engine_built_s=5.0) == []


@pytest.mark.parametrize("built", [0.0, 25.0])
def test_check_wants_the_gauges_of_an_engine_built_in_the_run(tmp_path,
                                                               built):
    problems = _routed_run(tmp_path, engine_built_s=built)
    assert len(problems) == 2
    assert "engine/experts_held" in problems[0]
    assert "engine/latent_lane_rows" in problems[1]


def test_construction_seconds_nest_once():
    telemetry.reset()
    base = telemetry.startup()["engine_s"]
    outer = account.constructing("engine")
    time.sleep(0.01)
    inner = account.constructing("engine")
    time.sleep(0.02)
    account.constructed(inner)
    account.constructed(outer)
    after = telemetry.startup()
    assert 0.03 <= after["engine_s"] - base < 0.06
    assert 0 < after["engine_built_s"] <= after["since_import_s"]


_EXIT_SCRIPT = """
from autodist_tpu import telemetry
with telemetry.span("work"):
    telemetry.counter("n").inc()
"""


@pytest.mark.parametrize("with_dir", [True, False])
def test_flush_at_exit_only_where_the_variable_says(tmp_path, with_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "AUTODIST_TPU_TELEMETRY_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    out = tmp_path / "run"
    if with_dir:
        env["AUTODIST_TPU_TELEMETRY_DIR"] = str(out)
    done = subprocess.run([sys.executable, "-c", _EXIT_SCRIPT], env=env,
                          cwd=str(tmp_path), capture_output=True, text=True,
                          timeout=120)
    assert done.returncode == 0, done.stderr
    if not with_dir:
        assert os.listdir(tmp_path) == []
        return
    assert sorted(os.listdir(out)) == [
        "manifest.json", "metrics.jsonl", "summary.txt", "trace.json"]
    assert telemetry_report.check_schema(str(out)) == []
    kinds = [r["kind"] for r in
             telemetry_report.load_jsonl(str(out / "metrics.jsonl"))]
    assert kinds == ["counter", "startup"]
