"""Training attention's fused path (PR 31): the one-pass kernels against
``dot_product_attention``, and the election that takes them.

All of it runs the Pallas interpreter on the CPU; on a TPU the same
calls compile through Mosaic (``chip_smoke.py`` and the benchmark's
training cell run them there).
"""
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from autodist_tpu.kernel.pallas import kernel_marker
from autodist_tpu.models import bert
from autodist_tpu.models.transformer import (TransformerConfig, attend,
                                             dot_product_attention)
from autodist_tpu.parallel.tensor import kernel_scope

# ``autodist_tpu.ops`` exports the function under the module's name
fa = importlib.import_module("autodist_tpu.ops.flash_attention")

MARKER = kernel_marker("flash_attention")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _projection(shape, dtype, seed=0):
    b, l, h, d = shape
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randn(b, l, 3 * h * d) * 0.5, dtype),
            jnp.asarray(r.randn(b, l, h * d), jnp.float32))


def _views(qkv, h, d):
    b, l, _ = qkv.shape
    return jnp.moveaxis(qkv.reshape(b, l, 3, h, d), 2, 0)


# the cell's rehearsal shape (8 x 64, two heads of 32) and a real row
@pytest.mark.parametrize("entry", ["views", "packed"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("shape", [(8, 64, 2, 32), (2, 512, 12, 64)],
                         ids=["rehearsal", "2x512x12x64"])
def test_fused_path_matches_composed(shape, dtype, entry):
    """Forward and the three gradients of the path ``attend`` elects
    (``flash_attention`` on views, ``flash_attention_packed`` on the
    projection) against float32 ``dot_product_attention``."""
    b, l, h, d = shape
    qkv, cot = _projection(shape, dtype)

    def fused(qkv):
        if entry == "packed":
            return fa.flash_attention_packed(qkv, h)
        return fa.flash_attention(*_views(qkv, h, d)).reshape(b, l, h * d)

    def composed(qkv):
        q, k, v = _views(qkv.astype(jnp.float32), h, d)
        return dot_product_attention(q, k, v, None,
                                     dtype=jnp.float32).reshape(b, l, h * d)

    def loss(attn):
        return lambda qkv: jnp.sum(attn(qkv).astype(jnp.float32) * cot)

    out, ref = fused(qkv), composed(qkv)
    assert out.dtype == dtype and out.shape == (b, l, h * d)
    grad, grad_ref = jax.grad(loss(fused))(qkv), jax.grad(loss(composed))(qkv)
    assert grad.dtype == dtype
    # bf16: one rounding of the probabilities and one of each output
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=tol, rtol=tol)
    for name, got, want in zip(
            "qkv", np.split(np.asarray(grad, np.float32), 3, axis=-1),
            np.split(np.asarray(grad_ref), 3, axis=-1)):
        np.testing.assert_allclose(
            got, want, atol=tol * np.abs(want).max(), rtol=tol,
            err_msg=f"d{name}")


@pytest.mark.parametrize("tiles_per_step", [1, 2, 3, None])
def test_lane_tiles_per_step_agree(tiles_per_step):
    """Every grouping of a row's lane tiles into grid steps is the same
    function (the groups share the row's ``[L, heads]`` lse block)."""
    qkv, cot = _projection((2, 128, 12, 64), jnp.float32)

    def loss(t):
        return lambda qkv: jnp.sum(fa.flash_attention_packed(
            qkv, 12, tiles_per_step=t) * cot)

    want, want_grad = jax.value_and_grad(loss(6))(qkv)
    got, got_grad = jax.value_and_grad(loss(tiles_per_step))(qkv)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got_grad, want_grad, atol=1e-6, rtol=1e-5)


def test_flash_attention_keeps_blockwise_where_it_must(monkeypatch):
    """Given blocks, a causal mask or a length the one-pass kernels do
    not take, ``flash_attention`` runs the blockwise kernels as before."""
    q, k, v = (jnp.ones((1, 16, 2, 8), jnp.float32),) * 3
    one_pass = []
    monkeypatch.setattr(
        fa, "flash_attention_one_pass",
        lambda q, k, v, **kw: one_pass.append(kw) or q)

    def calls(**kw):
        del one_pass[:]
        fa.flash_attention(q, k, v, **kw)
        return bool(one_pass)

    assert calls()
    assert not calls(causal=True)
    assert not calls(block_q=8, block_k=8)
    assert not fa.one_pass_fits(fa.MAX_ONE_PASS_LEN + 8)
    assert not fa.one_pass_fits(12)
    monkeypatch.undo()
    with pytest.raises(ValueError, match="one-pass kernels take lengths"):
        fa.flash_attention_one_pass(*(jnp.ones((1, 12, 2, 8)),) * 3)


# --------------------------------------------------------------------- #
# the election
# --------------------------------------------------------------------- #
CELL = dict(shape=(2, 512, 12, 64), dtype=jnp.bfloat16, mask=None,
            dropout=False, tpu=True, word=None)
ELECTION = {
    # what the cell runs, on a TPU
    "cell": (dict(), True),
    # what the call observes about its operands
    "padding-mask": (dict(mask="padding"), False),
    "causal-triangle": (dict(mask="causal"), False),
    "dropout-rng": (dict(dropout=True), False),
    "float16": (dict(dtype=jnp.float16), False),
    "float32": (dict(dtype=jnp.float32), True),
    "short": (dict(shape=(2, fa.MIN_FUSED_LEN - 64, 12, 64)), False),
    "long": (dict(shape=(1, fa.MAX_FUSED_LEN + 128, 12, 64)), False),
    "ragged-length": (dict(shape=(2, 520, 12, 64)), False),
    "head-width-32": (dict(shape=(2, 512, 12, 32)), False),
    "head-width-128": (dict(shape=(2, 256, 8, 128)), True),
    # the backend
    "cpu": (dict(tpu=False), False),
    # the kernel slot's word
    "forbidden": (dict(word=False), False),
    "forced-on-cpu": (dict(tpu=False, word=True), True),
    "forced-short": (dict(shape=(2, 64, 2, 32), tpu=False, word=True), True),
    "forced-but-masked": (dict(mask="causal", word=True), False),
    "forced-float16": (dict(dtype=jnp.float16, word=True), False),
}


def _lowered_text(fn, *args):
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("name", sorted(ELECTION))
def test_election(name, monkeypatch):
    """Which path ``attend`` takes, read from the lowered program's
    ``adtk_flash_attention`` marker."""
    case = {**CELL, **ELECTION[name][0]}
    monkeypatch.setattr(fa, "_backend_is_tpu", lambda: case["tpu"])
    monkeypatch.setattr(jax, "device_count", lambda: 1)  # whole operands
    b, l, h, d = case["shape"]
    cfg = TransformerConfig(hidden_size=h * d, num_heads=h,
                            dtype=case["dtype"])
    mask = {None: None,
            "padding": jnp.ones((b, 1, 1, l), bool),
            "causal": jnp.tril(jnp.ones((l, l), bool))[None, None]}[
                case["mask"]]
    x = jax.ShapeDtypeStruct(case["shape"], case["dtype"])

    def layer(q, k, v):
        with kernel_scope({} if case["word"] is None
                          else {"flash_attention": case["word"]}):
            return attend(cfg, q, k, v, mask, dropout_rate=0.1,
                          dropout_rng=(jax.random.PRNGKey(0)
                                       if case["dropout"] else None))

    assert (MARKER in _lowered_text(layer, x, x, x)) == ELECTION[name][1]


def test_attention_fn_still_wins(monkeypatch):
    monkeypatch.setattr(fa, "_backend_is_tpu", lambda: True)
    seen = []
    cfg = TransformerConfig(
        hidden_size=768, num_heads=12,
        attention_fn=lambda q, k, v, mask, rng: seen.append(q.shape) or q)
    x = jnp.zeros((2, 512, 12, 64), jnp.bfloat16)
    assert attend(cfg, x, x, x, None) is x and seen == [x.shape]


@pytest.mark.parametrize("where", ["shard_map", "partly-manual", "jit"])
def test_election_sees_whole_operands(where, monkeypatch):
    """On several devices the kernels run only inside a ``shard_map``
    over every mesh axis: under ``jit`` alone the operands may be
    GSPMD-sharded, and XLA cannot partition a bare ``pallas_call``."""
    monkeypatch.setattr(fa, "_backend_is_tpu", lambda: True)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))
    cfg = TransformerConfig(hidden_size=768, num_heads=12)
    x = jax.ShapeDtypeStruct((4, 512, 12, 64), jnp.bfloat16)

    def layer(q, k, v):
        return attend(cfg, q, k, v, None)

    fn = {"jit": layer,
          "shard_map": jax.shard_map(
              layer, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
              check_vma=False),
          "partly-manual": jax.shard_map(
              layer, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
              axis_names={"data"}, check_vma=False)}[where]
    assert (MARKER in _lowered_text(fn, x, x, x)) == (where == "shard_map")


def test_default_device_names_the_platform(monkeypatch):
    """A program traced under ``jax.default_device(cpu)`` on a TPU host
    (the benchmark's flax init) is a CPU program."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert fa._backend_is_tpu()
    with jax.default_device(jax.devices("cpu")[0]):
        assert not fa._backend_is_tpu()


# --------------------------------------------------------------------- #
# through the lowerings
# --------------------------------------------------------------------- #
def _mlm(kernel, builder, devices):
    from autodist_tpu import AutoDist
    from autodist_tpu.resource import ResourceSpec

    cfg = TransformerConfig(
        vocab_size=1024, hidden_size=64, num_layers=2, num_heads=2,
        mlp_dim=128, max_len=64, dropout_rate=0.0,
        attention_dropout_rate=0.0)
    trainable = bert.make_mlm_trainable(
        cfg, optax.adamw(1e-3), jax.random.PRNGKey(0), batch_size=2,
        seq_len=64, num_masked=8, with_input_mask=False)
    ad = AutoDist(ResourceSpec({"topology": {"num_devices": devices}}),
                  builder)
    strategy = ad.build_or_load_strategy(trainable)
    strategy.graph_config.kernel = kernel
    # the word survives the chief-to-worker handoff
    strategy = type(strategy).from_json(strategy.to_json())
    assert strategy.graph_config.kernel == kernel
    batch = bert.synthetic_mlm_batch(0, 8, 64, 8, 1024)
    batch.pop("input_mask")
    return ad.build(trainable, strategy), batch


@pytest.mark.parametrize("lowering,kernel,fused", [
    ("collective", {"flash_attention": True}, True),
    ("collective", {"flash_attention": False}, False),
    ("collective", {}, False),              # the CPU elects nothing
    ("gspmd-4", {"flash_attention": True}, False),
    ("gspmd-1", {"flash_attention": True}, True),
])
def test_kernel_slot_reaches_the_model(lowering, kernel, fused):
    """The Strategy IR's kernel slot forces or forbids the election in
    the program a lowering traces; on four devices the GSPMD lowering
    keeps the einsum whatever the word."""
    import autodist_tpu
    from autodist_tpu import telemetry

    builder, devices = {
        "collective": (autodist_tpu.AllReduce(chunk_size=256), 4),
        "gspmd-4": (autodist_tpu.Sharded(), 4),
        "gspmd-1": (autodist_tpu.Sharded(), 1)}[lowering]
    runner, batch = _mlm(kernel, builder, devices)
    before = {k: telemetry.counter(f"kernel/{k}_attention_calls").value
              for k in ("flash", "einsum")}
    if lowering == "gspmd-1":
        # one device of this process's eight: the operands are whole,
        # which a one-device process observes by its device count
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax, "device_count", lambda: 1)
            loss = runner.step(batch)["loss"]
    else:
        loss = runner.step(batch)["loss"]
    assert np.isfinite(loss)
    calls = {k: telemetry.counter(f"kernel/{k}_attention_calls").value
             - before[k] for k in before}
    assert calls == ({"flash": 2, "einsum": 0} if fused
                     else {"flash": 0, "einsum": 2})
    if fused:
        assert telemetry.gauge("kernel/flash_attention_elected").value == 1


def test_rehearsal_window_wears_the_marker_forward_and_backward():
    """``attention_device_pct.train`` reads the ops under ``attention``:
    in the cell's rehearsal ``jit_scanned`` program the kernels wear it,
    with the marker, in the forward pass and in ``transpose(jvp(...))``."""
    import autodist_tpu
    from autodist_tpu.runner import stack_steps

    runner, batch = _mlm({"flash_attention": True},
                         autodist_tpu.AllReduce(chunk_size=256), 1)
    losses = runner.run_steps(stack_steps([batch, batch]))["loss"]
    assert np.all(np.isfinite(losses))
    args = (runner.state, runner.place_steps(stack_steps([batch, batch])),
            jax.random.split(jax.random.PRNGKey(0), 2))
    text = runner._scanned_fn.lower(*args).compile().as_text()
    names = [line.split('op_name="')[1].split('"')[0]
             for line in text.splitlines()
             if MARKER in line and 'op_name="' in line]
    assert names and all("/attention/" in n for n in names)
    forward = [n for n in names if "transpose(jvp(" not in n]
    backward = [n for n in names if "transpose(jvp(" in n]
    assert forward and backward
    assert "[8,2,64,64]" not in text      # no score-shaped array is left


def test_einsum_path_is_the_parents_program():
    """Where nothing is elected (here: the CPU) ``SelfAttention`` and
    ``_tp_encoder_layer`` trace what they traced before ``attend``."""
    from autodist_tpu.models.transformer import SelfAttention

    cfg = TransformerConfig(hidden_size=64, num_heads=2, dtype=jnp.bfloat16,
                            dropout_rate=0.0, attention_dropout_rate=0.0)
    layer = SelfAttention(cfg)
    x = jnp.ones((2, 16, 64), jnp.bfloat16)
    params = layer.init(jax.random.PRNGKey(0), x, None, True)
    text = str(jax.make_jaxpr(
        lambda p, x: layer.apply(p, x, None, True))(params, x))
    assert "pallas_call" not in text and "bhqk" not in text
    assert text.count("dot_general") == 4    # qkv, scores, values, out


@pytest.mark.parametrize("kernel,want", [
    ({"flash_attention": False}, {"flash_attention": False}),
    ({"flash_attention": True}, {"flash_attention": True}),
    ({"flash_attention": False, "quant_ring": False}, {
        "flash_attention": False}),
    ({"flash_attention": None}, {}),
    ("flash_attention", {"flash_attention": True}),
])
def test_kernel_slot_keeps_the_forbidding_word(kernel, want):
    from autodist_tpu.strategy.ir import GraphConfig, normalize_kernel

    assert normalize_kernel(kernel) == want
    assert GraphConfig.from_dict({"kernel": kernel}).kernel == want


def test_crossover_tool_cell_mode(capsys, monkeypatch):
    """``tools/flash_crossover.py --cell`` prints one record a variant
    and a summary a length, and writes nothing."""
    import json

    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    try:
        tool = importlib.import_module("flash_crossover")
    finally:
        sys.path.pop(0)
    monkeypatch.setattr(sys, "argv", [
        "flash_crossover.py", "--cell", "--tokens", "128", "--seqs", "64",
        "--heads", "4", "--head-dim", "64", "--steps", "1", "--blocks",
        "64"])
    tool.main()
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    variants = {l["variant"] for l in lines if "variant" in l}
    assert {"composed", "blockwise_64", "one_pass_views_1",
            "one_pass_packed_2"} <= variants
    assert lines[-1]["summary"].startswith("seq 64: ")


def test_report_renders_and_gates_the_election(tmp_path):
    import json

    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    try:
        report = importlib.import_module("telemetry_report")
    finally:
        sys.path.pop(0)
    calls = {"kind": "counter", "name": "kernel/flash_attention_calls",
             "value": 12}
    einsum = {"kind": "counter", "name": "kernel/einsum_attention_calls",
              "value": 0}
    gauge = {"kind": "gauge", "name": "kernel/flash_attention_elected",
             "value": 1}

    def write(records):
        with open(os.path.join(tmp_path, "metrics.jsonl"), "w") as f:
            f.write("\n".join(json.dumps(r) for r in records) + "\n")
        return report.check_schema(str(tmp_path))

    assert write([calls, einsum, gauge]) == []
    assert "12 took the fused kernels" in report.render(str(tmp_path))
    assert any("go together" in p for p in write([calls, einsum]))
    assert any("go together" in p for p in write([einsum, gauge]))
    assert write([einsum]) == []
