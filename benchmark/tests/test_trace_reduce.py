"""``harness/trace_reduce.py`` against a trace small enough to reduce by
hand, and against a slice recorded on the chip."""
import os

import pytest

from harness import trace_reduce as tr
from harness.trace_reduce import Device, Event, Trace

MS = 1e6      # trace times are nanoseconds


def hand_trace() -> Trace:
    """Two devices, one window of 100 ms.

    dev0: a ``while`` from 10 to 60 holding fusion.1 (10-30), an
    all-reduce (30-45) and fusion.2 (50-60); later fusion.3 (70-80).
    dev1: fusion.1 (10-30), an all-reduce that jax named ``psum.3``
    (30-40), a copy (40-50), fusion.3 (70-90).
    Programs: step(1) 10-60 and step(2) 70-80/90 on both.
    Host: bench/window 0-100, bench/run_steps 0-5, bench/wait 5-100,
    and inside it bench/fetch 60-66.
    """
    e = lambda n, a, b: Event(n, a * MS, b * MS)
    dev0 = Device(
        ops=[e("while.7", 10, 60), e("fusion.1 f32[8]", 10, 30),
             e("all-reduce.3 f32[8]", 30, 45), e("fusion.2 f32[8]", 50, 60),
             e("fusion.3 f32[8]", 70, 80)],
        modules=[e("jit_step(1)", 10, 60), e("jit_step(2)", 70, 80)])
    dev1 = Device(
        ops=[e("fusion.1 f32[8]", 10, 30),
             Event("psum.3 f32[8]", 30 * MS, 40 * MS, "all-reduce"),
             e("copy.9 f32[8]", 40, 50), e("fusion.3 f32[8]", 70, 90)],
        modules=[e("jit_step(1)", 10, 50), e("jit_step(2)", 70, 90)])
    host = [e("bench/window", 0, 100), e("bench/run_steps", 0, 5),
            e("bench/wait", 5, 100), e("bench/fetch", 60, 66)]
    return Trace({"/device:TPU:0": dev0, "/device:TPU:1": dev1}, host)


def test_window_and_busy_union():
    t = hand_trace()
    lo, hi = tr.annotated_window(t)
    assert (lo, hi) == (0, 100 * MS)
    # dev0 busy 10-60 and 70-80 = 60 ms; dev1 10-50 and 70-90 = 60 ms
    assert tr.busy_seconds(t, lo, hi) == pytest.approx(0.060)
    # clipped to 20-75: dev0 40+5, dev1 30+5
    assert tr.busy_seconds(t, 20 * MS, 75 * MS) == pytest.approx(0.040)


def test_op_time_is_own_time():
    t = hand_trace()
    got = tr.op_seconds_by_name(t, 0, 100 * MS)
    # the while keeps only what its children leave: 45-50 on dev0
    assert got["while.7"] == pytest.approx(0.005 / 2)
    assert got["fusion.1 f32[8]"] == pytest.approx(0.020)
    assert got["all-reduce.3 f32[8]"] == pytest.approx(0.015 / 2)
    assert got["psum.3 f32[8]"] == pytest.approx(0.010 / 2)
    assert got["fusion.3 f32[8]"] == pytest.approx((0.010 + 0.020) / 2)
    assert tr.top(got, 2)[0][0] == "fusion.1 f32[8]"


def test_exposed_collective():
    t = hand_trace()
    # dev0: the whole 15 ms (nothing else runs; the while is its parent,
    # whose own time lies elsewhere); dev1: the 10 ms of the op whose
    # opcode, not its name, says all-reduce
    assert tr.exposed_collective_seconds(t, 0, 100 * MS) == \
        pytest.approx((0.015 + 0.010) / 2)


def test_idle_charged_to_innermost_host_span():
    t = hand_trace()
    got = tr.idle_seconds_by_host_span(t, 0, 100 * MS)
    # dev0 idle: 0-10, 60-70, 80-100; dev1: 0-10, 50-70, 90-100
    assert got["bench/run_steps"] == pytest.approx(0.005)
    assert got["bench/fetch"] == pytest.approx(0.006)
    assert got["bench/wait"] == pytest.approx(
        ((5 + 4 + 20) + (5 + 14 + 10)) / 2 * 1e-3)
    assert "host:unannotated" not in got
    assert sum(got.values()) == pytest.approx(0.040)


def test_runs_and_gaps_between_them():
    t = hand_trace()
    assert tr.runs_window(t, "^jit_step", 0, 100 * MS) == \
        (10 * MS, 90 * MS, 2)
    assert sorted(tr.gaps_between_runs_seconds(
        t, "^jit_step", 0, 100 * MS)) == pytest.approx([0.010, 0.020])
    # a run that is cut by the slice does not count
    assert tr.runs_window(t, "^jit_step", 20 * MS, 100 * MS)[2] == 1
    assert tr.runs_window(t, "^jit_other", 0, 100 * MS) is None


def test_short_name():
    line = ("%fusion.16 = (u32[1]{0:T(128)}, u32[1]{0:T(128)}) "
            "fusion(u32[2]{0:T(128)} %key.1), kind=kLoop")
    assert tr.short_name(line) == "fusion.16 u32[1]"
    assert tr.opcode(line) == "fusion"
    assert tr.short_name("all-reduce-start.3") == "all-reduce-start.3"
    psum = ("%psum.34 = f32[109514298]{0:T(1024)} all-reduce(f32[109514298]"
            "{0:T(1024)} %concatenate.25), channel_id=2")
    assert tr.short_name(psum) == "psum.34 f32[109514298]"
    assert tr.is_collective(Event(tr.short_name(psum), 0, 1,
                                  tr.opcode(psum)))
    assert not tr.is_collective(Event("fusion.1", 0, 1, "fusion"))


RECORDED = os.path.join(os.path.dirname(__file__), "..", "testdata")


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(RECORDED) if f.endswith(".trace.json")))
def test_recorded_slice(name):
    """A slice recorded on the v5e reduces to the numbers written beside
    it when it was cut (``<name>.expected.json``)."""
    import json

    t = tr.load_json(os.path.join(RECORDED, name))
    with open(os.path.join(RECORDED, name.replace(".trace.", ".expected."))) \
            as f:
        want = json.load(f)
    lo, hi = tr.annotated_window(t)
    assert tr.busy_seconds(t, lo, hi) == pytest.approx(want["busy_s"])
    assert (hi - lo) * tr.NS == pytest.approx(want["window_s"])
    assert tr.exposed_collective_seconds(t, lo, hi) == \
        pytest.approx(want["exposed_collective_s"])
    assert tr.runs_window(t, want["program"], lo, hi)[2] == want["runs"]
    top = tr.top(tr.op_seconds_by_name(t, lo, hi), 3)
    assert [n for n, _ in top] == want["top3"]
    idle = tr.idle_seconds_by_host_span(t, lo, hi)
    assert sum(idle.values()) + want["busy_s"] == pytest.approx(
        want["window_s"])
