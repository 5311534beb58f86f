"""The program's one span system and its device scopes (ISSUE 24).

* a ``telemetry.span`` is a ``jax.profiler.TraceAnnotation`` of the same
  name: it lands in a running profiler session's host plane, and the
  disabled path writes nothing;
* the serving batcher, the engine and the runner record the spans of
  ``docs/usage/observability.md`` with the right parents;
* the decode, prefill and training programs wear the scopes of
  ``telemetry.SCOPES`` where the table says;
* the compile cache's key covers the scopes: an unscoped executable never
  comes back for a scoped function.
"""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import telemetry
from autodist_tpu.models.pipeline_lm import make_pipeline_lm_trainable
from autodist_tpu.models.transformer import TransformerConfig
from autodist_tpu.serving import ContinuousBatcher, ServingEngine
from autodist_tpu.utils import compile_cache


@pytest.fixture(autouse=True)
def fresh_telemetry():
    telemetry.reset()
    yield
    telemetry.reset()


@pytest.fixture(scope="module")
def cfg():
    return TransformerConfig(
        vocab_size=33, hidden_size=16, num_layers=2, num_heads=2,
        mlp_dim=32, max_len=24, dtype=jnp.float32, dropout_rate=0.0,
        attention_dropout_rate=0.0)


@pytest.fixture(scope="module")
def params(cfg):
    return make_pipeline_lm_trainable(
        cfg, optax.sgd(0.1), jax.random.PRNGKey(0)).params


def make_engine(cfg, params, **kw):
    return ServingEngine(cfg, params, num_slots=2, max_len=cfg.max_len,
                         prefill_len=8, decode_steps=3, **kw)


# --------------------------------------------------------------------- #
# one clock
# --------------------------------------------------------------------- #
def _host_events(log_dir, prefix):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(prefix)]


def _traced_spans(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with telemetry.span("probe/outer", k=3, rids=["a", "b"]):
            with telemetry.span("probe/inner"):
                pass
    return _host_events(tmp_path, "probe/")


def test_span_lands_in_the_profilers_host_plane(tmp_path):
    events = _traced_spans(tmp_path)
    assert [n for n, _ in events] == ["probe/outer", "probe/inner"]
    # scalar args ride along as the event's stats; a list does not
    assert events[0][1] == {"k": 3}
    # the in-memory chrome trace is unchanged, plus the parent's name
    outer, inner = sorted(telemetry.get().chrome_trace()["traceEvents"],
                          key=lambda e: e["name"], reverse=True)
    assert outer["args"] == {"k": 3, "rids": ["a", "b"]}
    assert inner["args"] == {"depth": 1, "parent": "probe/outer"}


def test_disabled_telemetry_writes_no_annotation(tmp_path, monkeypatch):
    monkeypatch.setenv("AUTODIST_TPU_TELEMETRY", "0")
    telemetry.reset()
    assert telemetry.span("probe/outer") is telemetry.NULL_SPAN
    assert _traced_spans(tmp_path) == []


# --------------------------------------------------------------------- #
# spans where the host work happens
# --------------------------------------------------------------------- #
def _parents():
    """``{span name: set of parents it was recorded under}``"""
    out: dict = {}
    for e in telemetry.get().chrome_trace()["traceEvents"]:
        out.setdefault(e["name"], set()).add(
            e.get("args", {}).get("parent"))
    return out


def test_one_batcher_step_records_the_span_tree(cfg, params):
    batcher = ContinuousBatcher(make_engine(cfg, params))
    batcher.submit(np.arange(1, 6), max_new_tokens=8)
    telemetry.reset()
    batcher.step()
    assert _parents() == {
        "serve/step": {None},
        "serve/evict": {"serve/step"},
        "serve/admit": {"serve/step"},
        "serve/prefill": {"serve/admit"},
        "engine/prefill/stage": {"serve/prefill"},
        "engine/prefill/dispatch": {"serve/prefill"},
        # a fresh engine's first dispatch makes all of its programs
        "engine/prepare": {"engine/prefill/dispatch"},
        "engine/prefill/register": {"serve/prefill"},
        "engine/prefill/fetch": {"serve/prefill"},
        "serve/decode": {"serve/step"},
        "engine/decode/stage": {"serve/decode"},
        "engine/decode/dispatch": {"serve/decode"},
        "engine/decode/fetch": {"serve/decode"},
        # tokens handed to slots after the prefill and after the decode
        "serve/distribute": {"serve/admit", "serve/step"},
    }


def test_run_steps_records_place_and_dispatch_as_children():
    import autodist_tpu as adt
    from autodist_tpu.capture import Trainable
    from autodist_tpu.resource import ResourceSpec

    def loss(p, extra, batch, rng):
        l = jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2)
        return l, extra, {"loss": l}

    runner = adt.AutoDist(ResourceSpec({}), adt.AllReduce()).build(
        Trainable(loss, {"w": jnp.zeros((4, 1))}, optax.sgd(0.1)))
    window = adt.stack_steps(
        [{"x": np.ones((8, 4), np.float32), "y": np.ones((8, 1), np.float32)}
         for _ in range(2)])
    telemetry.reset()
    runner.run_steps(window)
    assert _parents() == {"runner/run_steps": {None},
                          "runner/place": {"runner/run_steps"},
                          "runner/dispatch": {"runner/run_steps"}}
    runner.close()


# --------------------------------------------------------------------- #
# scopes on the device ops
# --------------------------------------------------------------------- #
def _hlo(jitted, *args) -> list:
    """The lowered program's instructions, one per line, each with its
    ``op_name`` metadata."""
    from jax._src.lib import xla_client

    opts = xla_client._xla.HloPrintOptions()
    opts.print_metadata = True
    return jitted.lower(*args).compiler_ir(dialect="hlo") \
        .get_hlo_module().to_string(opts).splitlines()


def _scopes_of(lines, opcode, shape=None) -> list:
    """The scope path of every ``opcode`` instruction (of result
    ``shape``, when given) in the lowered text."""
    out = []
    for line in lines:
        m = re.search(r"= (\S+) %s\(" % re.escape(opcode), line)
        if m and (shape is None or m.group(1).startswith(shape)):
            name = re.search(r'op_name="([^"]*)"', line)
            out.append(name.group(1) if name else "")
    return out


def _decode_args(engine):
    c = engine.cache
    return (engine.params, c.k, c.v, c.lengths, engine._tok,
            engine.kv.table_arg(c), jnp.asarray(engine._sample_seeds),
            jnp.ones((engine.num_slots,), bool))


def _cache_shape(engine) -> str:
    return "f32[" + ",".join(map(str, engine.cache.k.shape)) + "]"


def _in(scope: str, path: str) -> bool:
    """``scope`` is one of the components of an op's name path."""
    return scope in path.split("/")


def _check_serving_scopes(lines, cfg, engine, rows=None):
    writes = _scopes_of(lines, "dynamic-update-slice", _cache_shape(engine))
    # per layer: keys and values, one write per row (decode: per slot)
    assert len(writes) == cfg.num_layers * 2 * (rows or engine.num_slots)
    assert all(_in("kv_write", w) and not _in("attention", w)
               for w in writes)
    dots = _scopes_of(lines, "dot")
    assert sum(_in("attention", d) for d in dots) == 4 * cfg.num_layers
    assert sum(_in("mlp", d) for d in dots) == 2 * cfg.num_layers
    assert sum(_in("lm_head", d) for d in dots) == 1
    # every matmul of the program belongs to a scope of the vocabulary
    assert all(any(_in(s, d) for s in telemetry.SCOPES) for d in dots)
    assert any(_in("embed", g) for g in _scopes_of(lines, "gather"))


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_decode_program_wears_the_scopes(cfg, params, layout):
    engine = make_engine(cfg, params, **(
        {"kv_layout": "paged", "kv_block_len": 4} if layout == "paged"
        else {}))
    _check_serving_scopes(_hlo(engine._decode_jit, *_decode_args(engine)),
                          cfg, engine)


def test_prefill_program_wears_the_scopes(cfg, params):
    engine = make_engine(cfg, params)
    c = engine.cache
    one = jnp.ones((1,), jnp.int32)
    _check_serving_scopes(
        _hlo(engine._prefill_jit, engine.params, c.k, c.v, c.lengths,
             engine._tok, jnp.int32(0), jnp.zeros((1, 1), jnp.int32), one,
             jnp.zeros((1, engine.prefill_len), jnp.int32), one),
        cfg, engine, rows=1)


def test_training_step_wears_the_scopes():
    import autodist_tpu as adt
    from autodist_tpu.models import bert
    from autodist_tpu.resource import ResourceSpec

    tcfg = TransformerConfig(
        vocab_size=64, hidden_size=16, num_layers=1, num_heads=2,
        mlp_dim=32, max_len=16, dtype=jnp.float32, dropout_rate=0.0,
        attention_dropout_rate=0.0)
    rng = jax.random.PRNGKey(0)
    trainable = bert.make_mlm_trainable(tcfg, optax.adamw(1e-3), rng,
                                        batch_size=8, seq_len=16,
                                        num_masked=4)
    batch = bert.synthetic_mlm_batch(rng, 8, 16, 4, tcfg.vocab_size)

    def lowered(builder):
        runner = adt.AutoDist(ResourceSpec({}), builder).build(trainable)
        lines = _hlo(runner.lowered.step_fn, runner.state,
                     runner._place_batch(batch), rng)
        runner.close()
        return lines

    # PS: reduce-scatter, an update on the local shard, all-gather
    lines = lowered(adt.PS())
    paths = [m.group(1) for m in
             (re.search(r'op_name="([^"]*)"', l) for l in lines) if m]

    def has(*parts):
        return any(all(p in path for p in parts) for path in paths)

    # forward scopes by the flax modules' own names, and by the program's
    assert has("/attention/qkv/") and has("/mlp/wi/")
    assert has("/embed/token_embed/") and has("/lm_head/mlm_dense/")
    assert has("lm_head", "reduce_max")             # the loss's logsumexp
    # the backward pass carries the same scope behind transpose(jvp(..))
    assert has("transpose(jvp(", "/attention/qkv/")
    # the exchange, the update (every sqrt outside the model is Adam's)
    # and the gather back to storage
    scatter = _scopes_of(lines, "reduce-scatter")
    assert scatter and all(_in("grad_sync", s) for s in scatter)
    gather = _scopes_of(lines, "all-gather")
    assert gather and all(_in("optimizer", g) for g in gather)
    roots = [r for r in _scopes_of(lines, "sqrt") if "jvp(" not in r]
    assert roots and all(_in("optimizer", r) for r in roots)
    # AllReduce: the bucket's all-reduce is the exchange
    reduce = [r for r in _scopes_of(lowered(adt.AllReduce()), "all-reduce")
              if "jvp(" not in r]
    assert any(_in("grad_sync", r) for r in reduce)
    assert not any(_in("optimizer", r) for r in reduce)


def test_scope_rejects_a_name_outside_the_vocabulary():
    with pytest.raises(ValueError, match="not a scope"):
        telemetry.scope("atention")


# --------------------------------------------------------------------- #
# the cache must not hide the scopes
# --------------------------------------------------------------------- #
@pytest.fixture
def shared_cache(tmp_path, monkeypatch):
    """A persistent compilation cache of this test's own, keyed as
    ``enable_compile_cache`` keys it, every jax setting put back after."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir",
             "jax_compilation_cache_include_metadata_in_key",
             "jax_traceback_in_locations_limit",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        cc.reset_cache()
        yield tmp_path
    finally:
        for n, v in before.items():
            jax.config.update(n, v)
        cc.reset_cache()


def _compiled_text(scoped: bool) -> str:
    def f(x):
        if scoped:
            with telemetry.scope("attention"):
                return jnp.tanh(x @ x)
        return jnp.tanh(x @ x)

    return jax.jit(f).lower(jnp.ones((8, 8))).compile().as_text()


def test_shared_cache_does_not_strip_the_scope(shared_cache):
    assert "attention" not in _compiled_text(scoped=False)
    entries = set(shared_cache.iterdir())
    assert entries                  # the unscoped executable was cached
    assert "attention/" in _compiled_text(scoped=True)
    # a miss: the scoped function made an entry of its own
    assert set(shared_cache.iterdir()) > entries


def test_without_metadata_in_the_key_the_cache_strips_the_scope(shared_cache):
    """The hazard itself, and the proof that a scope changes no compiled
    program: with jax's default key the scoped function is a cache hit
    for the unscoped executable."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    _compiled_text(scoped=False)
    entries = set(shared_cache.iterdir())
    assert "attention" not in _compiled_text(scoped=True)
    assert set(shared_cache.iterdir()) == entries
