"""The cache manager's one decision (``kv_cache.layout_for``), the one
election a decode-attention kernel (``flash_decode.dense_decode_elected``
beside ``latent_decode_elected``), what each layout serves of the
features that ride the block table, and the one walker of the stack
(``ServingEngine._run_layers``) — each held to what the engine's
constructor and its two walkers did before PR 47, on the benchmark's
configurations at their rehearsal size.
"""
import dataclasses
import hashlib
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest

from autodist_tpu.kernel.pallas import flash_decode
from autodist_tpu.models import pipeline_lm as lm
from autodist_tpu.models.transformer import BlockSpec, TransformerConfig
from autodist_tpu.serving import ServingEngine, kv_cache

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def bench():
    path = os.path.join(ROOT, "benchmark", "harness", "loader.py")
    spec = importlib.util.spec_from_file_location("layout_test_loader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg_of(bench):
    """A cell's configuration at its rehearsal size, with room for 256
    positions."""
    made = {}

    def of(name):
        if name not in made:
            rc = bench.sized(bench.config_of(
                bench.benchmark_spec(), {"name": name, "config": name}), True)
            made[name] = dataclasses.replace(
                bench.load_module("builders", rc["builder"])
                .transformer_config(rc), max_len=256)
        return made[name]

    return of


def _zeros(shapes):
    return {name: _zeros(v) if isinstance(v, dict)
            else jnp.zeros(v, jnp.float32) for name, v in shapes.items()}


# --------------------------------------------------------------------- #
# one election a kernel, beside the kernel
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("word,backend,max_len,head_dim,block", [
    # left open: a TPU, a cache the kernel reads in place, a long lane —
    # the rows of fused_decode_block's docstring
    (None, "tpu", 1024, 64, 128),       # narrow heads, [d, block] tiles
    (None, "tpu", 1024, 96, 128),
    (None, "tpu", 1024, 128, 128),      # whole lanes, [block, d] tiles
    (None, "tpu", 2560, 256, 256),      # wide heads walk blocks of 256
    (None, "tpu", 2688, 256, 128),      # ... where 256 divides the lane
    (None, "tpu", 1024, 192, None),     # heads of 1.5 lanes: a copy
    (None, "tpu", 1000, 64, None),      # no block divides the lane
    (None, "tpu", 256, 64, 128),        # MIN_FUSED_DECODE_LEN: from here
    (None, "tpu", 128, 64, None),       # ... and not under it
    (None, "cpu", 1024, 64, None),      # never off the TPU
    (False, "tpu", 1024, 64, None),     # forbidden
    (True, "cpu", 1024, 64, 128),       # forced: the interpreter
    (True, "cpu", 128, 64, 128),        # ... whatever the lane's length
    (True, "tpu", 1024, 192, 128),      # ... on a copy where it must
    (True, "cpu", 96, 64, 96),          # ... a short lane as one block
    (True, "cpu", 48, 8, 48),
    (True, "cpu", 200, 8, 8),           # ... or any block that divides it
])
def test_dense_decode_election(word, backend, max_len, head_dim, block):
    assert flash_decode.dense_decode_elected(
        word, max_len, head_dim, backend) == block
    if block and flash_decode.fused_decode_block(max_len, head_dim) is None:
        assert word is True and max_len % block == 0    # forced on a copy


def test_the_election_reads_the_backend_where_none_is_given(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert flash_decode.dense_decode_elected(None, 1024, 64) == 128
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert flash_decode.dense_decode_elected(None, 1024, 64) is None


# --------------------------------------------------------------------- #
# one decision: the class, its dims, the block and the kernel words, as
# the constructor built them at PR 46 (2 slots of 256 positions)
# --------------------------------------------------------------------- #
CELLS = {
    # class, dims, the block a TPU (or the word) elects
    "bert-base-mlm": ("DenseLayout", (2, 2, 2, 32, 256), 128),
    "gpt2-large-postln": ("DenseLayout", (2, 2, 2, 32, 256), 128),
    "ouro-2.6b": ("DenseLayout", (48, 2, 2, 32, 256), 128),
    "qwen3-next-80b-a3b": ("DenseLayout", (2, 2, 2, 32, 256), 128),
    "deepseek-v2-lite": ("LatentLayout", (5, 2, 1, 40, 256), 256),
    "ling-3.0-flash": ("LatentLayout", (2, 2, 1, 40, 256), 256),
    # no caching layer: nothing to elect, and a forced word stays a word
    "brumby-14b-base": ("DenseLayout", (0, 2, 2, 16, 256), None),
}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_layout_for_decides_what_the_constructor_did(monkeypatch, cfg_of,
                                                     name):
    cls, dims, block = CELLS[name]
    cfg = cfg_of(name)
    for backend, word, elected in (("tpu", None, True), ("cpu", None, False),
                                   ("cpu", True, True),
                                   ("tpu", False, False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        words = {"delta_step": False,
                 **({} if word is None else {"flash_decode": word})}
        layout = kv_cache.layout_for(cfg, words, num_slots=2, max_len=256)
        assert type(layout).__name__ == cls and layout.dims == dims
        assert layout.fused_block == (block if elected else None)
        assert layout.kernel == {
            "delta_step": False,
            **({"flash_decode": True} if word or (elected and block)
               else {})}
        assert layout.arrays == (1 if cls == "LatentLayout" else 2)
        assert (layout.recurrent is not None) == (
            cfg.block.linear is not None)
    if name != "bert-base-mlm":
        # and the engine's is that layout: the words become the layout's
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        eng = ServingEngine(cfg, _zeros(lm.param_shapes(cfg)), num_slots=2,
                            max_len=256, prefill_len=16)
        assert type(eng.kv).__name__ == cls and eng.kv.dims == dims
        assert eng.kernel is eng.kv.kernel
        assert eng.kernel == ({"flash_decode": True} if block else {})
        assert eng.cache_layers == dims[0]


def test_layout_for_pages_a_cache_of_keys_and_values_a_head(cfg_of):
    cfg = cfg_of("gpt2-large-postln")
    kw = dict(num_slots=2, max_len=256, kv_layout="paged", kv_block_len=16)
    layout = kv_cache.layout_for(cfg, {"flash_decode": False},
                                 kv_num_blocks=32, prefix_caching=True, **kw)
    assert isinstance(layout, kv_cache.PagedLayout)
    assert layout.dims == (2, 2, 2, 32, 256) and layout.kernel == {}
    assert layout.prefix_caching and layout.accounting() == (32, 0, 32)
    assert kv_cache.layout_for(cfg, {"flash_decode": True}, kv_num_blocks=32,
                               **kw).kernel == {"flash_decode": True}
    with pytest.raises(ValueError, match="cannot hold even one"):
        kv_cache.layout_for(cfg, {}, kv_num_blocks=15, **kw)


# --------------------------------------------------------------------- #
# what each format serves: the one matrix (docs/usage/serving.md shows it)
# --------------------------------------------------------------------- #
def _grouped(cfg):
    return dataclasses.replace(cfg, block=dataclasses.replace(
        cfg.block, kv_heads=1))


FORMATS = {
    # the format, the cell (or what is made of one) that has it, the
    # features it serves, and the words of its refusals
    "paged keys and values a head": (
        "gpt2-large-postln", "paged", sorted(kv_cache.FEATURES), None),
    "lanes of keys and values a head": (
        "gpt2-large-postln", "dense", ["speculative"], "requires"),
    "lanes under grouped query heads": (
        _grouped, "dense", [], "grouped-query"),
    "lanes beside a recurrent state": (
        "qwen3-next-80b-a3b", "dense", [], "recurrent state"),
    "a recurrent state alone": (
        "brumby-14b-base", "dense", [], "recurrent state"),
    "lanes of latent rows": (
        "deepseek-v2-lite", "dense", [], "latent KV row"),
    "latent rows beside a recurrent state": (
        "ling-3.0-flash", "dense", [], "recurrent state"),
}
KNOBS = {"prefill_chunk": 16, "speculative": 2, "prefix_caching": True}


@pytest.mark.parametrize("fmt", sorted(FORMATS))
def test_what_a_format_serves_and_how_it_refuses_the_rest(cfg_of, fmt):
    cell, kv_layout, serves, says = FORMATS[fmt]
    cfg = _grouped(cfg_of("gpt2-large-postln")) if callable(cell) \
        else cfg_of(cell)
    kw = dict(num_slots=2, max_len=256, kv_layout=kv_layout,
              kv_block_len=16, kv_num_blocks=32)
    layout = kv_cache.layout_for(cfg, {}, **kw)
    assert sorted(layout.serves) == serves
    assert layout.serves <= set(kv_cache.FEATURES)
    for feature in kv_cache.FEATURES:
        knob = {feature: KNOBS[feature]} if feature in KNOBS else {}
        if feature in serves:
            kv_cache.layout_for(cfg, {}, **kw, **knob)
            continue
        # the layout's words, whoever asks: the engine's knob ...
        assert re.search(says, layout.refusal(feature, "who"))
        if knob:
            with pytest.raises(ValueError, match=says) as e:
                kv_cache.layout_for(cfg, {}, **kw, **knob)
            assert str(e.value).startswith(
                f"{feature}: {kv_cache.FEATURES[feature][0]} ")
        # ... or the handoff's pool
        else:
            from autodist_tpu.serving.disagg import check_handoff_block

            class Engine:
                kv = layout

            with pytest.raises(ValueError, match=says) as e:
                check_handoff_block(Engine, "pool-0")
            assert str(e.value).startswith(
                "pool-0: the disaggregated handoff ")
    if kv_layout == "dense" and says != "requires":
        # the block's cache cannot be paged, and says why in those words
        with pytest.raises(ValueError, match=f"paged KV .*{says}"):
            kv_cache.layout_for(cfg, {}, **dict(kw, kv_layout="paged"))
        # a knob asked for beside it is named first, as it was
        with pytest.raises(ValueError, match="^prefill_chunk: chunked"):
            kv_cache.layout_for(cfg, {}, **dict(kw, kv_layout="paged"),
                                prefill_chunk=16)


def test_a_stack_of_two_kinds_of_state_names_both(cfg_of):
    layout = kv_cache.layout_for(cfg_of("ling-3.0-flash"), {}, num_slots=2,
                                 max_len=256)
    said = layout.refusal("handoff", "pool-0")
    assert "recurrent state" in said and "latent KV row" in said
    assert "delta-rule" in said


def test_the_docs_table_is_the_layouts(cfg_of):
    """``docs/usage/serving.md``'s format x feature table, row for row
    what the layouts say they serve."""
    with open(os.path.join(ROOT, "docs", "usage", "serving.md")) as f:
        text = f.read()
    rows = {}
    for line in text.splitlines():
        cells = [c.strip() for c in line.strip().strip("|").split("|")]
        if cells[0] in FORMATS:
            rows[cells[0]] = cells[1:]
    head = re.search(r"^\| the cache holds \|(.*)\|$", text, re.M)
    features = [c.strip(" `") for c in head.group(1).split("|")]
    assert features[:4] == list(kv_cache.FEATURES)
    assert sorted(rows) == sorted(FORMATS)
    for fmt, (_, _, serves, _) in FORMATS.items():
        assert [f for f, c in zip(features, rows[fmt]) if c == "yes"] \
            == [f for f in kv_cache.FEATURES if f in serves], fmt


# --------------------------------------------------------------------- #
# one walker: the programs of the engine's two walkers at PR 46
# --------------------------------------------------------------------- #
# The equations of each program's trace, sub-jaxprs included — how many,
# and a hash of their primitives in order — read on the parent commit
# (ce38f11) with the two walkers, at 2 slots of 32 positions, a prompt
# bucket of 16 and 2 decode steps.  A PR that means to change one of
# these programs reads it anew.
WALKED = {
    # a default stack
    "gpt2-large-postln": {"decode": (390, "a578bfdd2ede3594"),
                          "prefill": (341, "ab48391c47efd5f0")},
    # a looped one
    "ouro-2.6b": {"decode": (2346, "5c914ab0b3e47b05"),
                  "prefill": (1812, "98762f5104e13ff9")},
    # mixed (linear and full layers) and routed
    "qwen3-next-80b-a3b": {"decode": (2350, "1b329c735b015952"),
                           "prefill": (3934, "01df12a41e913566")},
    # routed, of one kind of layer (latent)
    "deepseek-v2-lite": {"decode": (1164, "bf257aac29798f9e"),
                         "prefill": (1118, "f8c33af87e2898b5")},
    # mixed (linear and latent layers) and routed
    "ling-3.0-flash": {"decode": (3509, "3419ec48c9858529"),
                       "prefill": (8181, "9240ddb86c992b8e")},
    # linear layers alone
    "brumby-14b-base": {"decode": (1108, "3b74dc08f425dc88"),
                        "prefill": (1153, "0bc831b24fbd328d")},
}


def _primitives(jaxpr, out):
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, out)
    return out


def _pin(traced):
    names = _primitives(traced.jaxpr.jaxpr, [])
    return (len(names),
            hashlib.sha256(" ".join(names).encode()).hexdigest()[:16])


@pytest.mark.parametrize("name", sorted(WALKED))
def test_the_one_walker_traces_the_programs_of_the_two(cfg_of, name):
    cfg = cfg_of(name)
    # (the pins were read with weights drawn at 0.11: a trace has no
    # values in it)
    eng = ServingEngine(cfg, _zeros(lm.param_shapes(cfg)), num_slots=2,
                        max_len=32, prefill_len=16, decode_steps=2)
    c = eng.cache
    head = (eng.params, c.k, c.v, c.lengths, eng._tok)
    got = {"decode": _pin(eng._decode_jit.trace(
               *head, eng.kv.table_arg(c), jnp.asarray(eng._sample_seeds),
               jnp.ones((2,), bool), *eng._state_args())),
           "prefill": _pin(eng._prefill_jit.trace(
               *head, *eng._blank_prefill_args()))}
    assert got == WALKED[name]


def test_the_walker_keeps_the_six_positions_a_planted_fault_wraps(
        monkeypatch):
    """``benchmark/tests/test_ouro.py`` wraps ``_run_layers(self, shared,
    stages, x, kc, vc, layer_fn)`` and hands back what it returns: a
    stack without a linear layer is walked with those six alone."""
    import inspect

    names = list(inspect.signature(ServingEngine._run_layers).parameters)
    assert names == ["self", "shared", "stages", "x", "kc", "vc",
                     "layer_fn", "state", "linear_fn"]
    seen = []
    real = ServingEngine._run_layers

    def six(self, shared, stages, x, kc, vc, layer_fn):
        seen.append(layer_fn)
        return real(self, shared, stages, x, kc, vc, layer_fn)

    cfg = TransformerConfig(vocab_size=32, hidden_size=16, num_layers=2,
                            num_heads=2, mlp_dim=32, max_len=16,
                            dtype=jnp.float32, dropout_rate=0.0,
                            attention_dropout_rate=0.0, block=BlockSpec())
    eng = ServingEngine(cfg, _zeros(lm.param_shapes(cfg)), num_slots=2,
                        max_len=16, prefill_len=8, decode_steps=2)
    monkeypatch.setattr(ServingEngine, "_run_layers", six)
    c = eng.cache
    head = (eng.params, c.k, c.v, c.lengths, eng._tok)
    eng._decode_jit.trace(
        *head, eng.kv.table_arg(c), jnp.asarray(eng._sample_seeds),
        jnp.ones((2,), bool))
    eng._prefill_jit.trace(*head, *eng._blank_prefill_args())
    assert len(seen) == 2
