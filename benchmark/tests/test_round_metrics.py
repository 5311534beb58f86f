"""The readers of the program's round account and start-up account:
``round_ms_p50``, ``round_slow_pct`` and ``setup_programs_s`` on
hand-made instruments, and nothing to read from a program that keeps
none (the parent of the PR that brought them)."""
import pytest

from autodist_tpu import telemetry
from autodist_tpu.telemetry import account
from harness import loader

NEW = ("round_ms_p50", "round_slow_pct", "setup_programs_s")
SERVING = ["gpt2-large-postln.closed-loop",
           "ouro-2.6b.closed-loop-decode-heavy",
           "qwen3-next-80b-a3b.closed-loop-32-decode-heavy",
           "deepseek-v2-lite.closed-loop-64-long-decode"]


@pytest.fixture(autouse=True)
def fresh():
    telemetry.reset()
    yield
    telemetry.reset()


def read(name, rec=None):
    return loader.load_module("metrics", name).read(rec or {"window_s": 4.0})


def test_round_ms_p50_is_the_histograms_median(monkeypatch):
    base = loader.load_module("metrics", "round_ms_p50")
    said = []
    monkeypatch.setattr(base, "report", said.append)
    for ms in (60.0, 62.0, 61.0, 90.0, 59.0):
        telemetry.histogram("serve/round_ms").observe(ms)
    rec = {"window_s": 4.0}
    assert base.read(rec) == 61.0
    assert said == [rec]


def test_a_failing_report_does_not_lose_the_metric(monkeypatch, capsys):
    base = loader.load_module("metrics", "round_ms_p50")

    def broken(rec):
        raise RuntimeError("no xplane here")

    monkeypatch.setattr(base, "report", broken)
    telemetry.histogram("serve/round_ms").observe(7.0)
    assert base.read({"window_s": 1.0}) == 7.0
    assert "no xplane here" in capsys.readouterr().err


def test_round_slow_pct_sums_the_excess_over_the_slice():
    telemetry.counter("serve/rounds").inc(64)
    telemetry.counter("serve/slow_rounds").inc(2)
    telemetry.record_event("slow_round", round=7, decode_excess_ms=30.0,
                           median_decode_ms=50.0)
    telemetry.record_event("slow_round", round=9, own_excess_ms=6.0,
                           decode_excess_ms=4.0, median_own_ms=1.0)
    telemetry.record_event("serve", request="r1")
    assert read("round_slow_pct") == pytest.approx(100 * 0.040 / 4.0)


def test_round_slow_pct_is_zero_in_a_sound_slice():
    telemetry.counter("serve/rounds").inc(64)
    assert read("round_slow_pct") == 0.0


def test_setup_programs_s_is_the_account_less_the_window(monkeypatch):
    monkeypatch.setitem(account._account, "trace_s", 6.0)
    monkeypatch.setitem(account._account, "lower_s", 2.0)
    monkeypatch.setitem(account._account, "backend_s", 11.0)
    monkeypatch.setitem(account._account, "cache_retrieval_s", 4.0)
    # the reference compiled after the window: in the account and in the
    # run's own instruments alike
    telemetry.histogram("compile/trace_s").observe(1.5)
    telemetry.histogram("compile/backend_s").observe(3.0)
    telemetry.histogram("compile/cache_retrieval_s").observe(1.0)
    assert read("setup_programs_s") == pytest.approx(19.0 - 4.5)


@pytest.mark.parametrize("name", ["round_ms_p50", "round_slow_pct"])
def test_a_program_without_the_round_account_reads_nothing(name):
    telemetry.counter("serve/tokens").inc(7)      # other instruments do not
    telemetry.histogram("serve/ttft_ms").observe(20.0)
    assert read(name) is None


def test_a_program_without_the_startup_account_reads_nothing(monkeypatch):
    monkeypatch.delattr(telemetry, "startup")
    assert read("setup_programs_s") is None


def test_the_report_tool_of_an_older_tree_is_passed_over(monkeypatch,
                                                        tmp_path):
    base = loader.load_module("metrics", "round_ms_p50")
    (tmp_path / "tools").mkdir()
    (tmp_path / "tools" / "telemetry_report.py").write_text("x = 1\n")
    monkeypatch.setattr(loader, "ROOT", str(tmp_path))
    assert base.report_tool() is None
    base.report({})                               # prints nothing, raises not


def test_the_report_tool_of_this_tree_is_found():
    tool = loader.load_module("metrics", "round_ms_p50").report_tool()
    assert hasattr(tool, "rounds_line") and hasattr(tool, "startup_line")


@pytest.mark.parametrize("name", NEW)
def test_the_metrics_are_declared_for_the_four_serving_cells(name):
    spec = loader.benchmark_spec()
    entry = next(m for m in spec["per_layer"] if m["name"] == name)
    assert entry["workloads"] == SERVING
    assert spec["per_layer"][-len(NEW):][NEW.index(name)] is entry
    assert entry["layer"] == ("serving engine" if name == "setup_programs_s"
                              else "batcher")
    assert entry["moves"] == ("setup_s" if name == "setup_programs_s"
                              else "serve_tokens_per_s")
    moved = next(m for m in spec["end_to_end"] if m["name"] == entry["moves"])
    for cell in entry["workloads"]:
        assert entry in loader.metrics_of(spec, "per_layer", cell)
        assert cell in moved.get("workloads", SERVING)
