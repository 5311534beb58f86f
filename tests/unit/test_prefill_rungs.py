"""A prompt runs at the rung its length fits, and an engine makes its
programs together (ISSUE 42).

Five block families at toy width with ``prefill_len`` 512, so that the
rungs ``(256, 512)`` exist: a prompt served at its short rung and the
same prompt forced through the top rung give the same first token, the
same cache rows below ``p_len``, the same recurrent state and, in the
decode window behind them, the same tokens and expert tallies — and leave
every other slot bit for bit.  Then the preparation: after an engine's
first dispatch every rung and the decode program are executables, and no
later row of any length raises a compile event.
"""
import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import telemetry
from autodist_tpu.models import pipeline_lm as lm
from autodist_tpu.models.transformer import TransformerConfig
from autodist_tpu.serving import ServingEngine
from autodist_tpu.serving.engine import MIN_PREFILL_RUNG, prefill_rungs
from tests.unit import test_looped_block as looped
from tests.unit.test_hybrid_block import _bench, _fill
from tests.unit.test_serving import slot_lane

TOP = 512                   # prefill_len: rungs (256, 512)
MAX_LEN = TOP + 16
SLOTS, SLOT = 3, 1          # the slot the prompts under test are admitted to
# Two programs of different lengths differ by the order in which float32
# sums run over keys that are all but ``p_len`` exact zeros (softmax over
# 256 or 512 positions, the delta rule's trailing padded chunks): measured
# here at most 7e-7 (dense), 2.6e-6 (looped) and 6.1e-6 (linear + routed)
# on rows and states of size ~1.
TOL = 5e-5


def _dense_cfg(max_len=MAX_LEN):
    return TransformerConfig(
        vocab_size=67, hidden_size=16, num_layers=2, num_heads=2,
        mlp_dim=32, max_len=max_len, dtype=jnp.float32,
        dropout_rate=0.0, attention_dropout_rate=0.0)


def _dense_params(cfg):
    return lm.make_pipeline_lm_trainable(
        cfg, optax.sgd(0.1), jax.random.PRNGKey(0)).params


def _routed(name, builder):
    """A benchmark configuration at its rehearsal size, as its builder
    turns it into the program's, with seeded weights."""
    bench = _bench()
    rc = bench.sized(bench.config_of(bench.benchmark_spec(),
                                     {"name": name, "config": name}), True)
    cfg = bench.load_module("builders", builder).transformer_config(rc)
    params = _fill(bench.load_module("reference", name).param_shapes(rc))
    # rotary positions: no table bounds the lane, the rehearsal's 64 aside
    return dataclasses.replace(cfg, max_len=max(cfg.max_len, MAX_LEN)), params


def _model(family):
    """``(cfg, params, engine_kw)`` of one block family at toy width."""
    if family in ("dense-postln", "dense-paged-prefix"):
        cfg = _dense_cfg()
        kw = ({"kv_layout": "paged", "kv_block_len": 64,
               "prefix_caching": True} if "paged" in family else {})
        return cfg, _dense_params(cfg), kw
    if family == "looped":
        rc = dict(looped._ref_cfg(), max_position_embeddings=MAX_LEN)
        ref = _bench().load_module("reference", "ouro-2.6b")
        return looped._cfg(rc), looped._params(ref, rc), {}
    name, builder = {
        "linear-routed": ("qwen3-next-80b-a3b", "hybrid_moe_lm_serving"),
        "latent-routed": ("deepseek-v2-lite", "latent_moe_lm_serving"),
        "kda-latent": ("ling-3.0-flash", "hybrid_latent_moe_lm_serving"),
        "retention": ("brumby-14b-base", "retention_lm_serving"),
        "ssd": ("granite-4.0-h-micro", "hybrid_ssd_lm_serving"),
    }[family]
    return (*_routed(name, builder), {})


FAMILIES = ["dense-postln", "looped", "linear-routed", "latent-routed",
            "kda-latent", "retention", "ssd", "dense-paged-prefix"]
RESIDENT = {0: 300, 2: 5}       # slot -> its resident prompt's length


def _prompt(cfg, n, seed):
    return np.random.default_rng(seed).integers(
        1, cfg.vocab_size, n).astype(np.int32)


def _admit(eng, prompts: dict, budget=8, padding=0):
    """Admit ``prompts`` (slot -> tokens) through one ``prefill`` call,
    the rows padded with ``padding``; returns the tokens ``[B]``."""
    rows = np.full((eng.num_slots, eng.prefill_len), padding, np.int32)
    p_lens = np.zeros((eng.num_slots,), np.int64)
    admit = np.zeros((eng.num_slots,), bool)
    for slot, prompt in prompts.items():
        eng.release_slot(slot)                  # no-ops for a dense cache
        eng.reserve_slot(slot, len(prompt), budget, prompt=prompt)
        rows[slot, :len(prompt)] = prompt
        p_lens[slot], admit[slot] = len(prompt), True
    return eng.prefill(rows, p_lens, admit)


@pytest.fixture(scope="module", params=FAMILIES)
def pair(request):
    """Two engines of one family with requests resident in slots 0 and 2
    and a decode window behind them: ``rung`` picks a row's rung, ``top``
    has been left its top rung alone, so every row runs at ``[1, 512]``
    as before the rungs."""
    cfg, params, kw = _model(request.param)
    made = {}
    for name in ("rung", "top"):
        eng = ServingEngine(cfg, params, num_slots=SLOTS, max_len=MAX_LEN,
                            prefill_len=TOP, decode_steps=2, **kw)
        assert eng.prefill_rungs == (256, TOP)
        if name == "top":
            eng.prefill_rungs = (TOP,)
        _admit(eng, {s: _prompt(cfg, n, 100 + s)
                     for s, n in RESIDENT.items()})
        eng.decode(np.array([s in RESIDENT for s in range(SLOTS)]))
        made[name] = eng
    return cfg, made


def _held(eng, slots):
    """What ``slots`` hold, as numpy: their lanes of both cache arrays
    (through the block table, paged), their rows of the recurrent state,
    their lengths and tokens."""
    c = eng.cache
    return ([slot_lane(eng, a, s) for a in (c.k, c.v) for s in slots]
            + [np.asarray(a)[:, list(slots)] for a in eng._state_args()]
            + [eng.lengths[list(slots)], np.asarray(eng._tok)[list(slots)]])


def _counters():
    return {m["name"]: m["value"]
            for m in telemetry.get().registry.snapshot()
            if m["kind"] == "counter"}


@pytest.mark.parametrize("p_len", [1, 100, 256, 257, 400])
def test_a_short_rung_serves_what_the_top_rung_serves(pair, p_len):
    cfg, engines = pair
    rung, top = engines["rung"], engines["top"]
    prompt = _prompt(cfg, p_len, p_len)
    if rung.prefix_caching and p_len == 100:
        # the resident of slot 0 shares its first block: a prefix hit, so
        # the rung's program skips a block it must not write
        prompt = np.concatenate([_prompt(cfg, RESIDENT[0], 100)[:64],
                                 prompt[64:]])
    others = sorted(RESIDENT)
    got = {}
    for name, eng in engines.items():
        before = _held(eng, others)
        telemetry.reset()
        toks = _admit(eng, {SLOT: prompt})
        counted = _counters()
        after = _held(eng, others)
        # every other slot: bit for bit
        for a, b in zip(before, after):
            assert a.tobytes() == b.tobytes()
        want = TOP if name == "top" or p_len > 256 else 256
        assert counted["engine/prefill_positions"] == want
        assert counted[f"engine/prefill_rung_rows/{want}"] == 1
        assert eng.lengths[SLOT] == p_len
        c = eng.cache
        got[name] = (int(toks[SLOT]),
                     [slot_lane(eng, a, SLOT)[:, :, :p_len]
                      for a in (c.k, c.v)],
                     [np.asarray(a)[:, SLOT] for a in eng._state_args()])
    # the rung's padding reaches nothing: other padding, the same bits
    again = _admit(rung, {SLOT: prompt}, padding=7)
    c = rung.cache
    assert int(again[SLOT]) == got["rung"][0]
    for a, b in zip(got["rung"][1] + got["rung"][2],
                    [slot_lane(rung, a, SLOT)[:, :, :p_len]
                     for a in (c.k, c.v)]
                    + [np.asarray(a)[:, SLOT] for a in rung._state_args()]):
        assert a.tobytes() == b.tobytes()
    # the short rung against the top rung: the same token, rows and state
    assert got["rung"][0] == got["top"][0]
    for a, b in zip(got["rung"][1] + got["rung"][2],
                    got["top"][1] + got["top"][2]):
        np.testing.assert_allclose(a, b, atol=TOL, rtol=0)
    # and behind them the same decode window: tokens and expert tallies
    active = np.arange(SLOTS) == SLOT
    windows = {}
    for name, eng in engines.items():
        telemetry.reset()
        toks = eng.decode(active)[:, SLOT]
        windows[name] = (toks.tolist(), {
            k: v for k, v in _counters().items() if k.startswith("moe/")})
    telemetry.reset()
    assert windows["rung"] == windows["top"]
    if cfg.block.moe is not None:
        assert windows["rung"][1]["moe/rows_routed"] > 0


@pytest.mark.parametrize("prefill_len,want", [
    (16, (16,)), (32, (32,)), (256, (256,)), (511, (511,)),
    (512, (256, 512)), (1024, (256, 512, 1024)), (600, (300, 600)),
    (1000, (500, 1000)), (2048, (256, 512, 1024, 2048))])
def test_the_rungs_follow_from_prefill_len_alone(prefill_len, want):
    assert prefill_rungs(prefill_len) == want
    assert min(want) >= min(MIN_PREFILL_RUNG, prefill_len)


# --------------------------------------------------------------------- #
# which rung a length picks, and what the counters say of it
# --------------------------------------------------------------------- #
class CompileEvents:
    """Every compile event jax reports while ``counting`` (tracing,
    lowering, backend compilation): the benchmark's ``CompileCounter``,
    with none left out — an executable's call looks no trace up."""

    def __init__(self):
        import jax.monitoring

        self.events = None
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if self.events is not None and "/jax/core/compile/" in name:
            self.events.append(name)

    @contextlib.contextmanager
    def counting(self):
        self.events = seen = []
        try:
            yield seen
        finally:
            self.events = None


@pytest.fixture(scope="module")
def compile_events():
    return CompileEvents()


@pytest.fixture(scope="module")
def dense():
    cfg = _dense_cfg(max_len=1024 + 16)
    return cfg, _dense_params(cfg)


def _engine(dense, prefill_len=1024, **kw):
    cfg, params = dense
    return ServingEngine(cfg, params, num_slots=SLOTS,
                         max_len=prefill_len + 16, prefill_len=prefill_len,
                         decode_steps=2, **kw)


def test_each_length_picks_the_rung_expected(dense):
    """256, 257, 512, 513 and ``prefill_len`` tokens, one row a call and
    then three rows in one call; the counter a rung and
    ``engine/prefill_positions`` add up to the rows times their rungs,
    and every dispatch span says its positions."""
    cfg, _ = dense
    eng = _engine(dense)
    assert eng.prefill_rungs == (256, 512, 1024)
    assert eng.max_prompt_tokens == eng.prefill_len == 1024
    telemetry.reset()
    want = {1: 256, 256: 256, 257: 512, 512: 512, 513: 1024, 1024: 1024}
    for n in want:
        _admit(eng, {SLOT: _prompt(cfg, n, n)})
        assert eng.lengths[SLOT] == n
    _admit(eng, {0: _prompt(cfg, 40, 1), 1: _prompt(cfg, 700, 2),
                 2: _prompt(cfg, 300, 3)})
    counted = _counters()
    spans = [e["args"] for e in
             telemetry.get().chrome_trace()["traceEvents"]
             if e["name"] == "engine/prefill/dispatch"]
    telemetry.reset()
    assert [s["positions"] for s in spans] == [*want.values(),
                                               256 + 1024 + 512]
    assert [s["rows"] for s in spans] == [1] * len(want) + [3]
    by_rung = {S: counted[f"engine/prefill_rung_rows/{S}"]
               for S in eng.prefill_rungs}
    assert by_rung == {256: 3, 512: 3, 1024: 3}
    assert sum(by_rung.values()) == counted["engine/prefill_rows"] == 9
    assert sum(S * n for S, n in by_rung.items()) \
        == counted["engine/prefill_positions"]


def test_a_row_at_its_rung_gives_the_sequential_reference(dense):
    """The token a rung's program emits is the full-recompute
    reference's, at every rung of a ladder of three."""
    cfg, params = dense
    eng = _engine(dense)
    for n in (200, 300, 600):
        prompt = _prompt(cfg, n, n)
        toks = _admit(eng, {SLOT: prompt})
        logits = lm.sequential_logits(cfg, params, jnp.asarray(prompt)[None])
        assert int(toks[SLOT]) == int(jnp.argmax(logits[0, -1]))


# --------------------------------------------------------------------- #
# the preparation: every program made at the first dispatch
# --------------------------------------------------------------------- #
def _serve_every_rung(eng, cfg):
    for n in (300, 1000, 20, 512):
        _admit(eng, {SLOT: _prompt(cfg, n, n)})
    eng.decode(np.arange(SLOTS) == SLOT)


@pytest.mark.parametrize("first", ["short-prompt", "warm_prefill", "decode"])
@pytest.mark.parametrize("kw", [{}, {"tensor_parallel": 2}],
                         ids=["tp1", "tp2"])
def test_the_first_dispatch_makes_every_program(dense, compile_events, kw,
                                                first):
    """Whichever call comes first — a fill that admits no prompt over 256
    tokens, the warm-up, a decode window — every rung and the decode
    program are executables after it, and no later row of any length,
    nor the decode window, traces, lowers or compiles."""
    cfg, _ = dense
    eng = _engine(dense, **kw)
    assert eng._compiled is None
    with compile_events.counting() as seen:
        if first == "short-prompt":
            _admit(eng, {0: _prompt(cfg, 7, 0), 2: _prompt(cfg, 256, 2)})
        elif first == "warm_prefill":
            eng.warm_prefill()
        else:
            eng.decode(np.zeros((SLOTS,), bool))
    assert any("backend_compile" in e for e in seen)
    assert set(eng._compiled) == {"decode", 256, 512, 1024}
    with compile_events.counting() as seen:
        _serve_every_rung(eng, cfg)
    assert seen == []


def test_an_engine_with_one_rung_is_the_engine_it_was(dense, compile_events):
    """``prefill_len`` under two rungs' worth: one prefill program, the
    top rung's, counted as before."""
    cfg, _ = dense
    eng = _engine(dense, prefill_len=32)
    assert eng.prefill_rungs == (32,)
    assert eng._rung_jits[32] is eng._prefill_jit
    telemetry.reset()
    _admit(eng, {0: _prompt(cfg, 3, 0), 2: _prompt(cfg, 32, 2)})
    counted = _counters()
    telemetry.reset()
    assert set(eng._compiled) == {"decode", 32}
    assert counted["engine/prefill_rows"] == 2
    assert counted["engine/prefill_positions"] == 2 * 32
    assert counted["engine/prefill_rung_rows/32"] == 2
    with compile_events.counting() as seen:
        _admit(eng, {1: _prompt(cfg, 17, 1)})
        eng.decode(np.ones((SLOTS,), bool))
    assert seen == []


def test_a_speculative_draft_gets_rungs_by_the_same_code(dense,
                                                         compile_events):
    """The nested draft engine and the verify program: the draft has the
    target's rungs, each engine prepares its own programs at its first
    dispatch, and a window of the speculative path compiles nothing
    after it."""
    cfg, params = dense
    eng = _engine(dense, prefill_len=TOP, kv_layout="paged", kv_block_len=64,
                  speculative=2, draft_cfg=cfg, draft_params=params)
    assert eng.draft.prefill_rungs == eng.prefill_rungs == (256, TOP)
    _admit(eng, {SLOT: _prompt(cfg, 9, 9)})
    assert set(eng._compiled) == {"decode", "verify", 256, TOP}
    assert set(eng.draft._compiled) == {"decode", 256, TOP}
    active = np.arange(SLOTS) == SLOT
    eng.decode_window(active)       # the draft's lazy K=1 program aside
    with compile_events.counting() as seen:
        _admit(eng, {0: _prompt(cfg, 400, 4)})
        w = eng.decode_window(np.arange(SLOTS) == 0)
    assert [e for e in seen if "backend_compile" in e] == []
    assert w.counts[0] >= 1


def test_a_chunked_engine_has_no_rungs_and_is_prepared_alike(
        dense, compile_events):
    cfg, _ = dense
    eng = _engine(dense, prefill_len=TOP, kv_layout="paged", kv_block_len=64,
                  prefill_chunk=128)
    assert eng.prefill_rungs == ()
    eng.warm_prefill()
    assert set(eng._compiled) == {"decode", "prefill"}
    with compile_events.counting() as seen:
        _admit(eng, {SLOT: _prompt(cfg, 300, 3)})
        eng.decode(np.arange(SLOTS) == SLOT)
    assert [e for e in seen if "backend_compile" in e] == []
    assert eng.lengths[SLOT] == 300 + 2


def test_the_top_rung_keeps_its_name_and_its_hooks(dense):
    """``_prefill_jit``, ``compiled_prefill_text`` and
    ``_blank_prefill_args`` keep meaning the top rung; every rung's
    program is named ``prefill`` (``^jit_prefill`` is what the
    benchmark's per-layer metrics find a program by)."""
    eng = _engine(dense, prefill_len=TOP)
    assert eng._blank_prefill_args()[3].shape == (1, TOP)
    assert eng._blank_prefill_args(length=256)[3].shape == (1, 256)
    assert "jit_prefill" in eng.compiled_prefill_text()
    c = eng.cache
    for S, jitted in eng._rung_jits.items():
        text = jitted.lower(eng.params, c.k, c.v, c.lengths, eng._tok,
                            *eng._blank_prefill_args(length=S)).as_text()
        assert "module @jit_prefill" in text
        assert f"tensor<1x{S}xi32>" in text
