"""Resource model tests (≙ reference ``test_resource_spec.py`` /
``test_device_spec.py``: YAML parsing, defaults, validation)."""
import jax
import pytest

from autodist_tpu import ResourceSpec
from autodist_tpu import const


def test_default_spec_uses_all_devices():
    rs = ResourceSpec({})
    assert rs.num_devices() == 8
    assert rs.resolved_mesh_shape() == {const.DATA_AXIS: 8}
    mesh = rs.make_mesh()
    assert mesh.shape[const.DATA_AXIS] == 8


def test_explicit_mesh_shape():
    rs = ResourceSpec({"mesh": {"data": 4, "model": 2}})
    assert rs.resolved_mesh_shape() == {"data": 4, "model": 2}
    mesh = rs.make_mesh()
    assert mesh.shape == {"data": 4, "model": 2}


def test_wildcard_axis():
    rs = ResourceSpec({"mesh": {"data": -1, "model": 2}})
    assert rs.resolved_mesh_shape() == {"data": 4, "model": 2}


def test_num_devices_subset():
    rs = ResourceSpec({"topology": {"num_devices": 4}})
    assert rs.num_devices() == 4
    assert rs.resolved_mesh_shape() == {"data": 4}


def test_mismatched_mesh_raises():
    with pytest.raises(ValueError):
        ResourceSpec({"mesh": {"data": 3}}).resolved_mesh_shape()


def test_unknown_axis_raises():
    with pytest.raises(ValueError):
        ResourceSpec({"mesh": {"bogus": 8}})


def test_too_many_devices_raises():
    with pytest.raises(ValueError):
        ResourceSpec({"topology": {"num_devices": 64}}).devices()


def test_device_order_deterministic():
    # ≙ reference sorted node list (cluster.py:78-81)
    a = [d.id for d in ResourceSpec({}).devices()]
    b = [d.id for d in ResourceSpec({}).devices()]
    assert a == b == sorted(a)


def test_yaml_roundtrip(tmp_path):
    p = tmp_path / "spec.yml"
    p.write_text("topology:\n  platform: cpu\nmesh:\n  data: 8\n")
    rs = ResourceSpec(str(p))
    assert rs.platform == "cpu"
    assert rs.resolved_mesh_shape() == {"data": 8}


def test_chip_spec_lookup():
    rs = ResourceSpec({"topology": {"generation": "v5e"}})
    assert rs.chip.name == "v5e"
    assert rs.chip.peak_bf16_tflops > 0


def test_named_platform_that_is_absent_raises():
    """A spec that says ``platform: tpu`` gets TPU devices or an error
    naming both what was asked and what jax has — never the CPUs."""
    import pytest

    rs = ResourceSpec({"topology": {"platform": "tpu"}})
    with pytest.raises(RuntimeError, match="'tpu'.*'cpu'"):
        rs.devices()
    with pytest.raises(RuntimeError, match="'tpu'"):
        rs.chip
    # auto keeps jax's default backend; cpu keeps the simulated mesh
    assert len(ResourceSpec({}).devices()) == 8
    assert ResourceSpec({"topology": {"platform": "cpu"}}).chip.name == "cpu"


def test_unknown_device_kind_or_generation_raises(monkeypatch):
    """An unrecognised TPU is an error naming the string seen — not a
    v5e, and not the simulated mesh's made-up ``cpu`` entry."""
    import pytest

    from autodist_tpu import resource

    class Device:
        platform = "tpu"
        device_kind = "TPU v9 mega"

    with pytest.raises(ValueError, match="TPU v9 mega"):
        resource._detect_generation(Device())
    Device.device_kind = "TPU v5 lite"       # what the v5e reports
    assert resource._detect_generation(Device()) == "v5e"
    with pytest.raises(ValueError, match="v9"):
        ResourceSpec({"topology": {"generation": "v9"}}).chip
    monkeypatch.setenv("AUTODIST_TPU_GENERATION", "v9")
    with pytest.raises(ValueError, match="AUTODIST_TPU_GENERATION"):
        ResourceSpec({}).chip


def test_reference_style_nodes_spec_rejected():
    """Deliberate exclusion (docs/usage/migration.md): reference SSH GPU
    inventories are not a TPU topology; heterogeneous ones name the
    exclusion explicitly."""
    import pytest
    from autodist_tpu.resource import ResourceSpec

    hetero = {"nodes": [{"address": "a", "gpus": [0, 1]},
                        {"address": "b", "gpus": [0]}]}
    with pytest.raises(ValueError, match="heterogeneous replica sets"):
        ResourceSpec(hetero)

    homo = {"nodes": [{"address": "a", "gpus": [0, 1]},
                      {"address": "b", "gpus": [0, 1]}]}
    with pytest.raises(ValueError, match="not a TPU topology"):
        ResourceSpec(homo)


def test_local_proxy_variable_warns_at_lowering(caplog):
    """A no-op knob the user explicitly set must say so (reference
    ProxyVariable has no TPU analog: params re-gather every step)."""
    import logging as _logging

    import jax
    import jax.numpy as jnp
    import optax

    from autodist_tpu import AutoDist, PS, Trainable

    t = Trainable.from_loss_fn(
        lambda p, b: jnp.mean((b["x"] @ p["w"]) ** 2),
        {"w": jnp.ones((4, 2))}, optax.sgd(0.1))
    ad = AutoDist({"topology": {"platform": "cpu", "num_devices": 8}},
                  PS(local_proxy_variable=True))
    from autodist_tpu.utils.logging import get_logger
    logger = get_logger()  # propagate=False: attach the capture handler
    logger.addHandler(caplog.handler)
    try:
        ad.build(t)
    finally:
        logger.removeHandler(caplog.handler)
    assert any("local_proxy_variable" in r.message for r in caplog.records)
