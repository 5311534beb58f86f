"""The grouped-matmul kernel (``kernel/pallas/grouped_matmul.py``): a
decode step's sorted (row, expert) pairs through the held experts that
have rows, gate/up, SiLU and down in one call — under the Pallas
interpreter against a dense sum over every expert (float32, ``h``
rounded where the program rounds it) and against the two ``ragged_dot``
it replaces, which stay the CPU path and a prefill's; the election
(``parallel.moe.routed_experts``, the kernel slot's ``grouped_matmul``);
and the engine's gauges.  What Mosaic makes of the kernel at the
benchmark's shapes is ``tests/unit/test_tpu_compile.py``'s.
"""
import importlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.kernel.pallas import grouped_matmul as gm
from autodist_tpu.models import pipeline_lm as lm
from autodist_tpu.parallel import moe

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
F32, BF16 = jnp.float32, jnp.bfloat16


def _inputs(R, H, E, M, held, first=0, seed=0):
    """bf16 rows, a float32 router over all ``E`` and experts ``first ..
    first + held`` as a device holds them."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (R, H), F32).astype(BF16),
            jax.random.normal(ks[1], (H, E), F32) * 0.3,
            (jax.random.normal(ks[2], (held, H, 2 * M), F32) * 0.1)
            .astype(BF16),
            (jax.random.normal(ks[3], (held, M, H), F32) * 0.1).astype(BF16))


def _dense_routed(x, router, wi, wo, top_k, first=0, valid=None,
                  renormalise=True):
    """Every held expert over every row in float32 (``h`` rounded to the
    rows' type, as the program rounds it), weighted by the router's
    top-k weight (0 outside it, 0 for a row that is nobody's)."""
    w, e = moe.route_top_k(x, router, top_k, renormalise)[::-1]
    full = jnp.zeros((len(x), router.shape[1]), F32) \
        .at[jnp.arange(len(x))[:, None], e].set(w)
    full = full[:, first:first + wi.shape[0]]
    if valid is not None:
        full = full * valid[:, None]
    M = wo.shape[1]
    hi = jax.lax.Precision.HIGHEST
    h = jnp.einsum("rh,ehm->erm", x.astype(F32), wi.astype(F32),
                   precision=hi)
    h = (jax.nn.silu(h[..., :M]) * h[..., M:]).astype(x.dtype).astype(F32)
    y = jnp.einsum("erm,emh->erh", h, wo.astype(F32), precision=hi)
    return jnp.einsum("re,erh->rh", full, y, precision=hi)


def _biased(x, router, onto):
    """Every row's first choices are ``onto``: one input at 40 and the
    router's row for it 1 on those experts."""
    x = x.at[:, 0].set(40.0)
    return x, router.at[0].set(0.0).at[0, jnp.asarray(onto)].set(1.0)


# rows, hidden, experts, width, held, top_k, then what the case bends
CASES = {
    # the two cells' routing at small widths: 64 x 6 of 64, 8 held, and
    # 32 x 10 of 512, 64 held (most experts without a row)
    "deepseek-v2-lite-scaled": dict(R=64, H=256, E=64, M=128, held=8, k=6),
    "qwen3-next-scaled": dict(R=32, H=128, E=512, M=128, held=64, k=10),
    "an-expert-without-a-row": dict(R=8, H=128, E=4, M=128, held=4, k=1),
    # 40 rows on one expert: a group of two windows, and a third where it
    # starts off a window's edge
    "every-row-on-one-expert": dict(R=40, H=128, E=8, M=128, held=8, k=2,
                                    onto=[5]),
    "a-long-group-off-the-edge": dict(R=45, H=128, E=8, M=128, held=8, k=2,
                                      onto=[2, 5]),
    "rows-that-are-nobodys": dict(R=24, H=128, E=8, M=128, held=8, k=3,
                                  nobody=3),
    "no-choice-is-held": dict(R=16, H=128, E=16, M=128, held=4, k=2,
                              first=12, onto=[0, 1]),
    "first-expert-above-0": dict(R=24, H=128, E=16, M=128, held=4, k=4,
                                 first=8),
    "renormalise-off": dict(R=24, H=128, E=16, M=256, held=16, k=4,
                            renormalise=False),
    "pairs-no-multiple-of-16": dict(R=7, H=128, E=8, M=128, held=8, k=3),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_agrees_with_the_dense_sum_and_the_composed_path(case):
    c = dict(CASES[case])
    k, first = c["k"], c.get("first", 0)
    x, router, wi, wo = _inputs(c["R"], c["H"], c["E"], c["M"], c["held"],
                                first)
    if "onto" in c:
        x, router = _biased(x, router, c["onto"])
    valid = None
    if "nobody" in c:
        valid = jnp.arange(c["R"]) % c["nobody"] != 0
    kw = dict(top_k=k, first_expert=first, valid=valid,
              renormalise=c.get("renormalise", True))
    composed, want_stats = moe.routed_experts(x, router, wi, wo,
                                              kernel=False, **kw)
    got, stats = jax.jit(lambda *a: moe.routed_experts(
        *a, kernel=True, **kw))(x, router, wi, wo)
    assert got.dtype == F32 and got.shape == x.shape
    assert np.array_equal(stats, want_stats)        # bit for bit
    dense = _dense_routed(x, router, wi, wo, k, first, valid,
                          kw["renormalise"])
    # bf16 products accumulated in float32 either way: what is left is
    # the order of the sums and an h that rounds the other way
    scale = float(jnp.abs(dense).max()) or 1.0
    np.testing.assert_allclose(got, composed, atol=4e-3 * scale)
    np.testing.assert_allclose(got, dense, atol=4e-3 * scale)
    if case == "no-choice-is-held":
        assert int(stats[0]) == 0 and not np.asarray(got).any()
    if case == "an-expert-without-a-row":
        assert int(stats[1]) < c["held"]
    if "onto" in c and first == 0:
        assert int(stats[0]) == c["R"] * k
    if valid is not None:
        assert not np.asarray(got)[~np.asarray(valid)].any()


@pytest.mark.parametrize("sizes", [
    [5, 0, 20, 3], [0, 70, 0, 0], [0, 0, 0, 0], [16, 16, 16, 16],
    [1, 1, 1, 93], [17, 15, 33, 31],
], ids=lambda s: "-".join(map(str, s)))
@pytest.mark.parametrize("slab_rows,in_flight", [(128, 3), (256, 2)])
def test_the_products_alone(sizes, slab_rows, in_flight):
    """``grouped_matmul`` against ``ragged_products`` over given groups,
    at slabs that split ``wi`` and ``wo`` and slabs that do not: rows
    past the groups come back zero."""
    P, H, M, E = 96, 256, 256, 4
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(ks[0], (P, H), F32).astype(BF16)
    wi = (jax.random.normal(ks[1], (E, H, 2 * M), F32) * 0.1).astype(BF16)
    wo = (jax.random.normal(ks[2], (E, M, H), F32) * 0.1).astype(BF16)
    sizes = jnp.asarray(sizes, jnp.int32)
    got = gm.grouped_matmul(x, wi, wo, sizes, in_flight=in_flight,
                            slab_bytes=slab_rows * 2 * M * 2,
                            interpret=True)
    n = int(sizes.sum())
    want = moe.ragged_products(x, wi, wo, sizes)[:n]
    np.testing.assert_allclose(got[:n], want, atol=2e-2)
    assert not np.asarray(got[n:]).any()


def test_one_lowering_serves_every_layer():
    x, _, wi, wo = _inputs(32, 128, 4, 128, 4)
    sizes = jnp.asarray([3, 0, 9, 4], jnp.int32)

    def three(x, wi, wo):
        for _ in range(3):
            x = gm.grouped_matmul(x, wi, wo, sizes,
                                  interpret=True).astype(BF16)
        return x

    text = jax.jit(three).lower(x, wi, wo).as_text()
    assert text.count("func.func private @grouped_matmul_layer") == 1
    assert len(re.findall(r"call @grouped_matmul_layer\(", text)) == 3


def test_kernel_refuses_what_it_cannot_run():
    x, _, wi, wo = _inputs(32, 128, 4, 128, 4)
    sizes = jnp.zeros((4,), jnp.int32)
    with pytest.raises(ValueError, match="ragged_dot serves it"):
        gm.grouped_matmul(x.astype(F32), wi, wo, sizes)
    with pytest.raises(ValueError, match="ragged_dot serves it"):
        gm.grouped_matmul(x, wi.astype(F32), wo, sizes)
    with pytest.raises(ValueError, match="ragged_dot serves it"):
        gm.grouped_matmul(jnp.zeros((gm.MAX_GROUPED_PAIRS + 16, 128), BF16),
                          wi, wo, sizes)


# the election's truth table: the slot's word, the backend, the type, the
# widths, and the pairs of a decode step (384, 320) and of a prefill
# row's bound and whole (1,600, 6,144)
@pytest.mark.parametrize("word,backend,pairs,hidden,width,dtype,elected", [
    (None, "tpu", 384, 2048, 1408, BF16, True),
    (None, "tpu", 320, 2048, 512, BF16, True),
    (None, "tpu", 1600, 2048, 1408, BF16, False),
    (None, "tpu", 6144, 2048, 1408, BF16, False),
    (None, "tpu", 10240, 2048, 512, BF16, False),
    (None, "cpu", 384, 2048, 1408, BF16, False),
    (None, "gpu", 384, 2048, 1408, BF16, False),
    (True, "cpu", 384, 2048, 1408, BF16, True),     # the interpreter
    (True, "cpu", 6144, 2048, 1408, BF16, False),   # a word moves no bound
    (False, "tpu", 384, 2048, 1408, BF16, False),   # forbidden
    (None, "tpu", 384, 2048, 1408, F32, False),     # never cast
    (True, "tpu", 384, 2048, 1408, F32, False),
    (None, "tpu", 384, 2000, 1408, BF16, False),    # hidden not in lanes
    (None, "tpu", 384, 2048, 1400, BF16, False),    # width not in lanes
    (None, "tpu", 384, 2048, 704, BF16, False),     # 2M is, M is not
    (None, "tpu", 0, 2048, 1408, BF16, False),
])
def test_election(word, backend, pairs, hidden, width, dtype, elected):
    assert gm.grouped_matmul_elected(word, pairs, hidden, width, dtype,
                                     backend) == elected


def _calls():
    return {m["name"]: m["value"]
            for m in telemetry.get().registry.snapshot()
            if m["kind"] == "counter"}.get("kernel/grouped_matmul_calls", 0)


@pytest.mark.parametrize("backend,word,rows,fused", [
    ("cpu", None, 8, False), ("tpu", None, 8, True),
    ("tpu", False, 8, False), ("cpu", True, 8, True),
    ("tpu", None, 1024, False),     # a prefill row's pairs
])
def test_the_call_observes_the_backend_and_the_rows(monkeypatch, backend,
                                                    word, rows, fused):
    """``routed_experts`` elects by itself: the traced program holds the
    kernel or two ``ragged_dot`` (behind the ``lax.cond`` on the bound,
    which the kernel's program does not build), and every traced call
    that took the kernel is counted."""
    x, router, wi, wo = _inputs(rows, 128, 64, 128, 4)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(gm, "default_interpret", lambda: True)
    telemetry.reset()
    try:
        text = str(jax.make_jaxpr(lambda *a: moe.routed_experts(
            *a, top_k=2, kernel=word))(x, router, wi, wo))
        calls = _calls()
    finally:
        telemetry.reset()
    assert ("grouped_matmul_layer" in text) == fused
    # two products, in both branches of the cond where there is a bound
    assert len(re.findall(r"= ragged_dot", text)) == (
        0 if fused else 4 if rows == 1024 else 2)
    assert calls == int(fused)


def _routed_lm(dtype=BF16, moe_spec=True):
    from autodist_tpu.models.transformer import (BlockSpec, RoutedFFNSpec,
                                                 TransformerConfig)

    cfg = TransformerConfig(
        vocab_size=61, hidden_size=128, num_layers=2, num_heads=2,
        mlp_dim=128, max_len=32, dtype=dtype, dropout_rate=0.0,
        attention_dropout_rate=0.0,
        block=BlockSpec(
            norm="rmsnorm", norm_placement="pre", positions="rope",
            ffn="swiglu", bias=False, tied_head=False,
            moe=RoutedFFNSpec(8, 2, 128, experts_held=4, shared_width=128)
            if moe_spec else None))
    leaves, tree = jax.tree.flatten(
        lm.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.PRNGKey(0), len(leaves))
    params = tree.unflatten(
        [0.2 * jax.random.normal(k, s, dtype) for k, s in zip(keys, leaves)])
    return cfg, params


def _gauges():
    return {m["name"]: m["value"]
            for m in telemetry.get().registry.snapshot()
            if m["kind"] == "gauge"}


@pytest.mark.parametrize("backend,kernel,dtype,elected", [
    ("cpu", None, BF16, 0), ("tpu", None, BF16, 1),
    ("tpu", {"grouped_matmul": False}, BF16, 0),
    ("cpu", {"grouped_matmul": True}, BF16, 1),
    ("tpu", None, F32, 0),
    ("tpu", ("quant_ring",), BF16, 1),      # no word on grouped_matmul
])
def test_engine_says_what_a_decode_step_elects(monkeypatch, backend, kernel,
                                               dtype, elected):
    """``kernel/grouped_matmul_elected`` is 1 or 0 whenever the block
    routes, and the engine's decode program is what it says."""
    from autodist_tpu.serving import ServingEngine

    cfg, params = _routed_lm(dtype)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(gm, "default_interpret", lambda: True)
    telemetry.reset()
    try:
        # flash_decode is not this test's
        kernel = dict.fromkeys(kernel, True) if isinstance(kernel, tuple) \
            else dict(kernel or {})
        eng = ServingEngine(cfg, params, num_slots=2, max_len=32,
                            prefill_len=8, decode_steps=2,
                            kernel=dict(kernel, flash_decode=False))
        gauges = _gauges()
        args = (eng.params, eng.cache.k, eng.cache.v,
                jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
                jnp.zeros((2, 1), jnp.int32), jnp.zeros((2,), jnp.int32),
                jnp.ones((2,), bool))
        text = str(jax.make_jaxpr(eng._decode_jit)(*args))
        calls = _calls()
    finally:
        telemetry.reset()
    assert gauges["kernel/grouped_matmul_elected"] == elected
    assert ("grouped_matmul_layer" in text) == bool(elected)
    assert ("ragged_dot" in text) != bool(elected)
    assert calls == (cfg.num_layers if elected else 0)


def test_experts_held_in_another_type_are_never_cast(monkeypatch):
    """float32 experts under bf16 activations: the call declines the
    kernel (the composed products cast them, as before), and the engine's
    gauge says so."""
    from autodist_tpu.serving import ServingEngine

    cfg, params = _routed_lm()
    params["stages"]["moe"]["experts"] = jax.tree.map(
        lambda w: w.astype(F32), params["stages"]["moe"]["experts"])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    telemetry.reset()
    try:
        ServingEngine(cfg, params, num_slots=2, max_len=32, prefill_len=8,
                      kernel={"flash_decode": False})
        assert _gauges()["kernel/grouped_matmul_elected"] == 0
        chunk = lm.layer_chunk(cfg, params["stages"], 1)
        text = str(jax.make_jaxpr(lambda h: lm.routed_ffn(
            cfg, chunk["moe"], h))(jnp.ones((2, 1, 128), BF16)))
        assert "ragged_dot" in text and "grouped_matmul_layer" not in text
    finally:
        telemetry.reset()


def test_an_engine_that_routes_nothing_says_nothing():
    from autodist_tpu.serving import ServingEngine

    cfg, params = _routed_lm(moe_spec=False)
    telemetry.reset()
    try:
        ServingEngine(cfg, params, num_slots=2, max_len=32, prefill_len=8,
                      kernel={"grouped_matmul": True, "flash_decode": False})
        assert "kernel/grouped_matmul_elected" not in _gauges()
        assert _calls() == 0
    finally:
        telemetry.reset()


def test_engine_decode_with_the_kernel_serves_the_composed_logits():
    """Prefill, then a fused decode window with the routed layers through
    the kernel (forced: the interpreter) against the composed window, on
    the same cache: the same tokens."""
    from autodist_tpu import serving
    from autodist_tpu.serving import ServingEngine

    cfg, params = _routed_lm()
    r = np.random.default_rng(3)
    prompts = [r.integers(0, 61, n).astype(np.int32) for n in (5, 8, 3)]
    served = {}
    for word in (False, True):
        eng = ServingEngine(cfg, params, num_slots=2, max_len=32,
                            prefill_len=8, decode_steps=4,
                            kernel={"grouped_matmul": word,
                                    "flash_decode": False})
        batcher = serving.ContinuousBatcher(eng)
        for i, p in enumerate(prompts):
            batcher.submit(p, max_new_tokens=6, rid=f"r{i}")
        batcher.run()
        served[word] = [np.asarray(batcher.completions[f"r{i}"].tokens)
                        for i in range(len(prompts))]
    for composed, fused in zip(served[False], served[True]):
        assert np.array_equal(composed, fused)


def test_the_kernel_slot_knows_the_name():
    from autodist_tpu.kernel.pallas import (KERNEL_CHOICES, OBSERVED_KERNELS,
                                            kernel_marker)
    from autodist_tpu.strategy.ir import normalize_kernel

    assert "grouped_matmul" in KERNEL_CHOICES
    assert "grouped_matmul" in OBSERVED_KERNELS
    assert kernel_marker("grouped_matmul") == "adtk_grouped_matmul"
    # an observed kernel keeps the word that forbids it
    assert normalize_kernel({"grouped_matmul": False}) == \
        {"grouped_matmul": False}
    assert normalize_kernel("grouped_matmul") == {"grouped_matmul": True}
    assert normalize_kernel({"grouped_matmul": None}) == {}


def test_the_call_wears_the_scopes_the_roofline_reads():
    """``moe/moe_experts/adtk_grouped_matmul``: the benchmark's
    ``decode_experts_roofline_pct`` and ``decode_moe_pct`` find the
    kernel, as the sort and the gather around it, by the ``moe_experts``
    component (the compiler's ``ragged-dot`` had no path at all)."""
    cfg, params = _routed_lm()
    chunk = lm.layer_chunk(cfg, params["stages"], 0)
    h = jnp.ones((2, 1, cfg.hidden_size), BF16)
    names = set(re.findall(r'loc\("([^"]*)"', jax.jit(
        lambda h: lm.routed_ffn(cfg, chunk["moe"], h, kernel=True)[0])
        .lower(h).as_text(debug_info=True)))
    worn = [n for n in names if "adtk_grouped_matmul" in n]
    assert worn and all("moe/moe_experts/adtk_grouped_matmul" in n
                        for n in worn)
    for op in ("jit(argsort)", "gather"):   # the sort, the pairs' rows
        assert any(n.endswith(f"moe/moe_experts/{op}") for n in names), op


@pytest.mark.parametrize("records,says", [
    ([("engine/experts_held", 8), ("kernel/grouped_matmul_elected", 1)],
     None),
    ([("engine/experts_held", 8), ("kernel/grouped_matmul_elected", 0)],
     None),
    ([("engine/experts_held", 8), ("kernel/grouped_matmul_elected", 2)],
     "1 (the fused kernel) or 0"),
    ([("kernel/grouped_matmul_elected", 1)], "routes"),
])
def test_report_check_knows_the_gauge(tmp_path, records, says):
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    try:
        report = importlib.import_module("telemetry_report")
    finally:
        sys.path.pop(0)
    with open(os.path.join(tmp_path, "metrics.jsonl"), "w") as f:
        f.write("\n".join(json.dumps(
            {"kind": "gauge", "name": n, "value": v}) for n, v in records)
            + "\n")
    problems = report.check_schema(str(tmp_path))
    if says is None:
        assert problems == []
    else:
        assert len(problems) == 1 and says in problems[0]
