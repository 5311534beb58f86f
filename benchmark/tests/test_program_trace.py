"""``harness/program_trace.py``: the program's spans and scopes read out
of a trace, against a trace small enough to reduce by hand, a hand-made
xplane, and two slices recorded on the chip at rehearsal size
(``testdata/program/serve``, ``train``; written by
``tools/record_program_trace.py``, and kept in a directory of their own:
``test_trace_reduce.py`` takes every ``testdata/*.trace.json`` for a
slice with the runners' annotations)."""
import json
import os

import pytest

from harness import program_trace as pt, trace_reduce as tr
from harness.trace_reduce import Device, Event, Trace

MS = 1e6      # trace times are nanoseconds
VOCAB = ("embed", "attention", "mlp", "kv_write", "lm_head", "grad_sync",
         "optimizer")
DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "program")


def hand_trace() -> Trace:
    """One device, one slice of 100 ms.

    Programs: jit_decode(7) 10-60 and 70-90, jit_prefill(9) 62-68.
    jit_decode(7) 10-60: a ``while`` 10-60 holding attention 10-30, a
    cache write 30-34, a copy without a name 34-44 and the mlp 50-60
    (the while keeps 44-50).  70-90: attention 70-80, lm_head 80-90.
    jit_prefill(9) 62-68: attention, which the decode shares leave out.
    Host: serve/step 0-95 holding serve/decode 5-92, which holds
    engine/decode/dispatch 5-12 and engine/decode/fetch 12-92; nothing
    covers 95-100.
    Idle: 0-10, 60-62, 68-70, 90-100.
    """
    e = lambda n, a, b, c="": Event(n, a * MS, b * MS, c)
    p = "jit(decode)/while/body/closed_call/"
    ops = [e("while.1", 10, 60),
           e("fusion.1", 10, 30, p + "attention/dot_general:"),
           e("dynamic-update-slice.2", 30, 34,
             p + "kv_write/dynamic_update_slice:"),
           e("copy.3", 34, 44),
           e("fusion.4", 50, 60, p + "mlp/dot_general:"),
           e("fusion.9", 62, 68, "jit(prefill)/attention/dot_general:"),
           e("fusion.1", 70, 80, p + "attention/dot_general:"),
           e("fusion.5", 80, 90, p + "lm_head/argmax:")]
    modules = [e("jit_decode(7)", 10, 60), e("jit_prefill(9)", 62, 68),
               e("jit_decode(7)", 70, 90)]
    host = [e("serve/step", 0, 95), e("serve/decode", 5, 92),
            e("engine/decode/dispatch", 5, 12),
            e("engine/decode/fetch", 12, 92)]
    return Trace({"/device:TPU:0": Device(ops, modules)}, host)


def test_idle_is_charged_to_the_innermost_program_span():
    t = hand_trace()
    got = pt.idle_by_span(t, 0, 100 * MS)
    # 0-5 and 92-95 are the step's own; 5-10 dispatch; 60-62, 68-70 and
    # 90-92 inside the fetch; 95-100 outside every span
    assert got == pytest.approx({
        "serve/step": 0.008, "engine/decode/dispatch": 0.005,
        "engine/decode/fetch": 0.006, pt.UNANNOTATED: 0.005})
    assert got == pytest.approx(tr.idle_seconds_by_host_span(t, 0, 100 * MS))
    # and it adds up to the slice's idle, also on a slice cut mid-span
    for lo, hi in ((0, 100), (8, 61), (33, 97)):
        idle = (hi - lo) * 1e-3 - tr.busy_seconds(t, lo * MS, hi * MS)
        assert sum(pt.idle_by_span(t, lo * MS, hi * MS).values()) \
            == pytest.approx(idle)
    shares = pt.idle_shares(got, 0.100)
    assert shares == pytest.approx({"serve/*": 8.0, "engine/*": 11.0,
                                    "runner/*": 0.0, pt.UNANNOTATED: 5.0})


def test_span_table_counts_the_spans_inside_the_slice():
    t = hand_trace()
    table = pt.span_table(t, 0, 100 * MS)
    assert table["spans"]["engine/decode/fetch"] == (1, pytest.approx(80.0))
    assert list(table["spans"]) == sorted(table["spans"])
    # no program span wholly inside: nothing to read
    assert pt.span_table(t, 96 * MS, 100 * MS) is None


@pytest.mark.parametrize("op_name, want", [
    ("jit(decode)/while/body/closed_call/attention/dot_general:",
     ("attention", "fwd")),
    ("jit(step)/jvp(BertModel)/encoder/layer_0/attention/qkv/dot_general:",
     ("attention", "fwd")),
    ("jit(step)/transpose(jvp(BertModel))/encoder/layer_3/mlp/wi/transpose:",
     ("mlp", "bwd")),
    # a scope straight under a transform is wrapped by it
    ("jit(loss)/transpose(jvp(lm_head))/dot_general:", ("lm_head", "bwd")),
    ("jit(loss)/jvp(lm_head)/reduce_max:", ("lm_head", "fwd")),
    # the first name of the vocabulary wins
    ("jit(step)/jvp(M)/lm_head/token_embed/dot_general:",
     ("lm_head", "fwd")),
    ("jit(step)/jvp(M)/embed/token_embed/take:", ("embed", "fwd")),
    # a component that merely contains a scope's name is not the scope
    ("jit(step)/jvp(M)/token_embed/take:", (pt.UNSCOPED, "fwd")),
    ("jit(step)/jvp(M)/encoder/layer_0/ln_attention/mul:",
     (pt.UNSCOPED, "fwd")),
    ("jit(decode)/while/body/dynamic_update_slice:", (pt.UNSCOPED, "fwd")),
    ("", (pt.UNSCOPED, "fwd")),
])
def test_scope_of_an_op_name(op_name, want):
    assert pt.scope_of(op_name, VOCAB) == want


def test_scope_shares_of_one_programs_runs_add_up_to_100():
    t = hand_trace()
    table = pt.scope_table(t, "^jit_decode", 0, 100 * MS, VOCAB)
    assert table["runs"] == 2
    assert table["device_s"] == pytest.approx(0.070)      # 50 + 20 ms
    pct = {s: v["fwd"] for s, v in table["pct"].items()}
    # attention 20 + 10, kv_write 4, copy 10 + the while's own 6, mlp 10,
    # lm_head 10, of 70; the prefill's attention is another program's
    assert pct == pytest.approx({
        "attention": 300 / 7, "kv_write": 40 / 7, "mlp": 100 / 7,
        "lm_head": 100 / 7, pt.UNSCOPED: 160 / 7, "embed": 0,
        "grad_sync": 0, "optimizer": 0})
    assert sum(pct.values()) == pytest.approx(100.0)
    assert all(v["bwd"] == 0 for v in table["pct"].values())
    assert [n for n, _ in table["unscoped_ops"]] == ["copy.3", "while.1"]
    # a slice that holds no whole run of the program: nothing to read
    assert pt.scope_table(t, "^jit_decode", 20 * MS, 65 * MS, VOCAB) is None


def unscoped(t: Trace) -> Trace:
    """The trace as an executable compiled before the scopes existed
    gives it: the same ops, and of the vocabulary only the names the
    flax modules give by themselves."""
    flax_only = tuple(s for s in VOCAB if s in pt.FLAX_NAMED)
    strip = lambda c: "/".join(
        p for p in c.split("/")
        if pt.scope_of(p, VOCAB)[0] == pt.scope_of(p, flax_only)[0])
    return Trace({n: Device([Event(e.name, e.start, e.end,
                                   strip(e.category)) for e in d.ops],
                            d.modules) for n, d in t.devices.items()},
                 t.host)


def test_a_trace_without_any_scope_is_an_error_that_names_the_cache():
    with pytest.raises(RuntimeError, match="compilation cache"):
        pt.scope_table(unscoped(hand_trace()), "^jit_decode", 0, 100 * MS,
                       VOCAB)


# --------------------------------------------------------------------- #
# the op names, from the wire
# --------------------------------------------------------------------- #
def _varint(n: int) -> bytes:
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _xspace() -> bytes:
    """Two planes as the TPU runtime writes them: stat metadata 1 =
    ``tf_op``, 2 = ``program_id``, 3 = ``flops``, 4 = a name some stat
    refers to; event metadata for two programs that share an HLO line."""
    stat = lambda mid, **v: _field(5, _field(1, mid) + b"".join(
        _field({"u64": 3, "i64": 4, "text": 5, "ref": 7}[k], x)
        for k, x in v.items()))
    meta = lambda mid, name, *stats: _field(4, _field(1, mid) + _field(
        2, _field(1, mid) + _field(2, name) + b"".join(stats)))
    names = b"".join(_field(5, _field(1, i) + _field(2, _field(1, i)
                                                     + _field(2, n)))
                     for i, n in ((1, "tf_op"), (2, "program_id"),
                                  (3, "flops"),
                                  (4, "jit(f)/mlp/dot_general:")))
    line = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    device = _field(2, "/device:TPU:0") + names \
        + meta(10, line, stat(3, u64=99), stat(2, u64=7),
               stat(1, text="jit(f)/attention/dot_general:")) \
        + meta(11, line, stat(2, u64=(1 << 64) - 5), stat(1, ref=4)) \
        + meta(12, "%copy.2 = f32[8]{0} copy(f32[8]{0} %x)",
               stat(2, u64=7))
    host = _field(2, "/host:CPU") + names \
        + meta(10, "serve/step", stat(1, text="not a device plane"))
    return _field(1, device) + _field(1, host) + _field(3, "a hostname")


def test_op_names_from_the_planes_event_metadata():
    line = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    got = pt.op_names(_xspace())
    assert list(got) == ["/device:TPU:0"]
    table = got["/device:TPU:0"]
    assert table[("7", line)] == "jit(f)/attention/dot_general:"
    # a program id past 2^63 is printed signed in a run's name; a stat
    # may refer to a name instead of holding one
    assert table[("-5", line)] == "jit(f)/mlp/dot_general:"
    # an op without the stat is left out: it has no name to give
    assert not any("copy.2" in k[1] for k in table)


# --------------------------------------------------------------------- #
# the recorded slices
# --------------------------------------------------------------------- #
def recorded(name: str):
    trace = tr.load_json(os.path.join(DATA, name + ".trace.json"))
    with open(os.path.join(DATA, name + ".expected.json")) as f:
        return trace, json.load(f)


@pytest.mark.parametrize("name", ["serve", "train"])
def test_recorded_idle_by_program_span_adds_up_to_the_slices_idle(name):
    trace, want = recorded(name)
    lo, hi = want["lo"], want["hi"]
    got = pt.idle_by_span(trace, lo, hi)
    assert got == pytest.approx(want["idle_by_span"])
    assert got == pytest.approx(tr.idle_seconds_by_host_span(trace, lo, hi))
    busy = tr.busy_seconds(trace, lo, hi)
    assert busy == pytest.approx(want["busy_s"])
    assert sum(got.values()) == pytest.approx((hi - lo) * tr.NS - busy)
    shares = pt.idle_shares(got, (hi - lo) * tr.NS)
    assert sum(shares.values()) == pytest.approx(
        100.0 * (1 - busy / ((hi - lo) * tr.NS)))


def test_recorded_serving_spans_and_decode_scopes():
    trace, want = recorded("serve")
    lo, hi = want["lo"], want["hi"]
    table = pt.span_table(trace, lo, hi)
    rounds = table["spans"]["serve/step"][0]
    assert rounds >= 3
    for name in ("serve/evict", "serve/decode", "engine/decode/stage",
                 "engine/decode/dispatch", "engine/decode/fetch"):
        assert table["spans"][name][0] == rounds
    # the chip waits inside the engine's calls, as in the cell
    shares = pt.idle_shares(table["idle"], (hi - lo) * tr.NS)
    assert shares["engine/*"] > shares["serve/*"] > 0
    scopes = pt.scope_table(trace, want["program"], lo, hi, VOCAB)
    assert scopes["runs"] == want["runs"] == rounds
    pct = scopes["pct"]
    assert sum(v["fwd"] + v["bwd"] for v in pct.values()) \
        == pytest.approx(100.0)
    assert all(v["bwd"] == 0 for v in pct.values())       # no backward
    for scope in ("embed", "attention", "mlp", "kv_write", "lm_head",
                  pt.UNSCOPED):
        assert pct[scope]["fwd"] > 0, scope
    assert pct["grad_sync"]["fwd"] == pct["optimizer"]["fwd"] == 0
    total = scopes["device_s"]
    for scope, direction, seconds in want["by_scope"]:
        assert pct[scope][direction] == pytest.approx(
            100.0 * seconds / total)


def test_recorded_training_scopes_forward_and_backward_apart():
    trace, want = recorded("train")
    lo, hi = want["lo"], want["hi"]
    table = pt.span_table(trace, lo, hi)
    windows = table["spans"]["runner/run_steps"][0]
    assert table["spans"]["runner/place"][0] == windows \
        == table["spans"]["runner/dispatch"][0]
    assert table["spans"]["runner/place"][1] > 0          # feed_ms.train
    scopes = pt.scope_table(trace, want["program"], lo, hi, VOCAB)
    assert scopes["runs"] == want["runs"]
    pct = scopes["pct"]
    assert sum(v["fwd"] + v["bwd"] for v in pct.values()) \
        == pytest.approx(100.0)
    for scope in ("attention", "mlp", "lm_head"):
        assert pct[scope]["fwd"] > 0 and pct[scope]["bwd"] > 0, scope
    # the update has no backward pass; one chip exchanges nothing
    assert pct["optimizer"]["fwd"] > 0 and pct["optimizer"]["bwd"] == 0
    assert pct["grad_sync"] == {"fwd": 0.0, "bwd": 0.0}
    assert pct["kv_write"] == {"fwd": 0.0, "bwd": 0.0}


@pytest.mark.parametrize("name", ["serve", "train"])
def test_recorded_trace_stripped_of_its_scopes_raises(name):
    trace, want = recorded(name)
    with pytest.raises(RuntimeError, match="compiled before the scopes"):
        pt.scope_table(unscoped(trace), want["program"], want["lo"],
                       want["hi"], VOCAB)
