"""``harness/scoped_ops.py`` against a trace small enough to reduce by
hand: own time by an inner scope or a kernel's name inside the runs of
one program, and a roofline share from a counter and a bytes function."""
import pytest

from harness import program_trace as pt, scoped_ops
from harness.trace_reduce import Device, Event, Trace

MS = 1e6      # trace times are nanoseconds


def hand_trace() -> Trace:
    """One device.  jit_decode(7) runs 10-60 and 70-90, jit_prefill(9)
    62-68.  In the first decode run: a while 10-60 holding the routing
    10-14 (moe/moe_experts), the grouped matmul 14-24 (no path at all),
    the shared expert 24-28 (moe), the state's sums 30-36 and its write
    36-44 (linear_attention/state_update), the mixer's projection 44-50
    (linear_attention); the while keeps 28-30 and 50-60.  In the second:
    the grouped matmul 70-78 and attention 78-90.  The prefill's grouped
    matmul 62-68 is no decode run's."""
    e = lambda n, a, b, c="": Event(n, a * MS, b * MS, c)
    p = "jit(decode)/while/body/closed_call/"
    ops = [e("while.1", 10, 60),
           e("fusion.1", 10, 14, p + "moe/moe_experts/sort:"),
           e("ragged-dot-none bf16[320,1024]", 14, 24, "ragged-dot-none"),
           e("fusion.2", 24, 28, p + "moe/dot_general:"),
           e("fusion.3", 30, 36,
             p + "linear_attention/state_update/reduce_sum:"),
           e("fusion.4", 36, 44,
             p + "linear_attention/state_update/dynamic_update_slice:"),
           e("fusion.5", 44, 50, p + "linear_attention/dot_general:"),
           e("ragged-dot-none bf16[10240,1024]", 62, 68, "ragged-dot-none"),
           e("ragged-dot-none bf16[320,1024]", 70, 78, "ragged-dot-none"),
           e("fusion.6", 78, 90, p + "attention/dot_general:")]
    modules = [e("jit_decode(7)", 10, 60), e("jit_prefill(9)", 62, 68),
               e("jit_decode(7)", 70, 90)]
    return Trace({"/device:TPU:0": Device(ops, modules)}, [])


@pytest.fixture
def rec(monkeypatch):
    monkeypatch.setattr(pt, "xplane_path", lambda rec: "hand")
    monkeypatch.setattr(pt, "_read_cached", lambda path: hand_trace())
    monkeypatch.setattr(pt, "vocabulary", lambda: (
        "attention", "linear_attention", "state_update", "moe",
        "moe_experts"))
    return {"lo": 0.0, "hi": 100 * MS, "programs": {"decode": "^jit_decode"},
            "decodes": [(0, 32, 0)] * 4, "peaks": {"hbm_bytes_per_s": 1e9}}


@pytest.mark.parametrize("scope,kernel,want", [
    ("moe", "ragged-dot", 4 + 10 + 4 + 8),
    ("moe", "", 4 + 4),
    ("moe_experts", "ragged-dot", 4 + 10 + 8),
    ("linear_attention", "", 6 + 8 + 6),
    ("state_update", "", 6 + 8),
    ("attention", "", 12),
])
def test_own_seconds_by_any_scope_of_the_path(rec, scope, kernel, want):
    worn, total, runs = scoped_ops.own_seconds(rec, "^jit_decode", scope,
                                               kernel)
    assert runs == 2
    assert total == pytest.approx(0.070)       # 50 ms + 20 ms of runs
    assert worn == pytest.approx(want * 1e-3)


def test_nothing_to_read_without_the_scope_or_a_run(rec, monkeypatch):
    assert scoped_ops.own_seconds(rec, "^jit_verify", "moe") is None
    monkeypatch.setattr(pt, "vocabulary", lambda: ("attention", "mlp"))
    assert scoped_ops.own_seconds(rec, "^jit_decode", "moe") is None
    monkeypatch.setattr(pt, "vocabulary", lambda: None)
    assert scoped_ops.own_seconds(rec, "^jit_decode", "moe") is None


def test_roofline_share_from_a_counter(rec, monkeypatch):
    monkeypatch.setattr(scoped_ops, "counter", lambda name: 8.0)
    # 2 of the window's 4 dispatches lie in the slice: 4 of the 8 rows,
    # 1 MB each at 1 GB/s = 4 ms least, against 14 ms of state_update
    got = scoped_ops.roofline_pct(rec, "state_update", "",
                                  "engine/state_rows", lambda n: n * 1e6)
    assert got == pytest.approx(100 * 4 / 14)
    monkeypatch.setattr(scoped_ops, "counter", lambda name: None)
    assert scoped_ops.roofline_pct(rec, "state_update", "",
                                   "engine/state_rows", lambda n: n) is None


def test_the_cells_readers_read_the_record(rec, monkeypatch):
    from harness import loader

    monkeypatch.setattr(scoped_ops, "counter", lambda name: 8.0)
    rec["flops"] = loader.load_module("flops", "qwen3-next-80b-a3b")
    spec = loader.benchmark_spec()
    rec["cfg"] = loader.config_of(spec, {
        "name": "x", "config": "qwen3-next-80b-a3b"})
    read = lambda name: loader.load_module("metrics", name).read(rec)
    assert read("decode_moe_pct") == pytest.approx(100 * 26 / 70)
    assert read("decode_linear_attention_pct") == pytest.approx(
        100 * 20 / 70)
    # 4 experts x 6,291,456 B at 1 GB/s against 22 ms
    assert read("decode_experts_roofline_pct") == pytest.approx(
        100 * 4 * 6_291_456e-9 / 0.022)
    # 4 rows x 2 x 2,097,152 B against 14 ms
    assert read("decode_state_update_roofline_pct") == pytest.approx(
        100 * 4 * 2 * 2_097_152e-9 / 0.014)
