"""Resource model: TPU topology spec → ``jax.sharding.Mesh``.

TPU-native counterpart of the reference's resource layer
(``autodist/resource_spec.py:45-331`` — YAML of SSH-reachable GPU nodes —
and ``autodist/kernel/device/resolver.py:38-67`` — device-string
resolution).  Here the resource spec describes a TPU pod slice (or a
simulated CPU mesh for tests) and resolves to a named device mesh; the
"device resolution" step of the reference's StrategyCompiler becomes mesh
construction with a deterministic device order.

Spec format (dict or YAML file)::

    topology:
      platform: tpu          # auto (jax's default backend) | tpu | cpu
                             # (simulated mesh for tests); a named
                             # platform that is absent is an error
      generation: v5e        # selects hardware constants; default: from
                             # the device_kind jax reports
      num_devices: 8         # optional; default = all visible devices
    mesh:                    # optional; default {'data': num_devices}
      data: 4
      model: 2
    multihost:               # optional (single-host if absent)
      coordinator: 10.0.0.2:8476
      num_processes: 4
      process_id: 0          # usually from env on each host

The reference forbade multi-node loopback and filled in bandwidth defaults
(``resource_spec.py:186-215``); here the analogous validation is
mesh-shape-vs-device-count and axis-name checks, plus per-generation
hardware constants used by cost-model-driven strategy builders.
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Any, Mapping, Optional, Sequence

import numpy as np

from autodist_tpu import const
from autodist_tpu.utils import logging

try:
    import yaml
except ImportError:  # pragma: no cover
    yaml = None


@dataclasses.dataclass(frozen=True)
class LinkSpec:
    """One level of the hierarchical network model: a named link class
    with its effective per-device bandwidth and per-collective launch
    latency.  Two levels exist on a TPU pod — ``ici`` within a slice
    and ``dcn`` across slices (the data-center network joining slices,
    orders of magnitude slower per device) — and the cost model prices
    each collective per level it crosses (the two-level reduction shape
    of arxiv 2110.10548).  Calibration (``calibration.json`` ``"link"``
    section: ``ici_gbps`` / ``dcn_gbps`` / ``dcn_alpha_s`` / ...)
    overrides these chip-table defaults the same way for both levels."""

    level: str                   # "ici" | "dcn"
    gbps: float                  # effective GB/s per device at this level
    alpha_s: float               # per-collective launch latency (seconds)


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Per-generation hardware constants (analog of the reference's
    ``network_bandwidth`` field, ``resource_spec.py:209-215``, generalized
    to what a TPU cost model needs)."""

    name: str
    peak_bf16_tflops: float      # per chip
    hbm_gb: float
    hbm_gbps: float              # memory bandwidth
    ici_gbps: float              # per-link interconnect bandwidth
    mxu_tile: int = 128
    # Cross-slice (DCN) level: per-device share of the slice's
    # data-center uplink, and the (much larger) cross-slice collective
    # launch latency.  Like ici_gbps these are *relative-rank* figures,
    # not datasheet truth; a measured "link" dcn_* calibration section
    # replaces them.
    dcn_gbps: float = 5.0
    dcn_alpha_s: float = 1e-4

    def link_levels(self) -> dict[str, LinkSpec]:
        """The hierarchical network model: level name → LinkSpec."""
        return {
            "ici": LinkSpec("ici", self.ici_gbps, 5e-6),
            "dcn": LinkSpec("dcn", self.dcn_gbps, self.dcn_alpha_s),
        }


# Public figures; used only for relative cost decisions and MFU math.
# The "cpu" entry prices the simulated mesh the cost-model tests run on;
# it describes no real device.
CHIP_SPECS = {
    "v4": ChipSpec("v4", peak_bf16_tflops=275.0, hbm_gb=32, hbm_gbps=1228, ici_gbps=50, dcn_gbps=6.25),
    "v5e": ChipSpec("v5e", peak_bf16_tflops=197.0, hbm_gb=16, hbm_gbps=819, ici_gbps=50, dcn_gbps=6.25),
    "v5p": ChipSpec("v5p", peak_bf16_tflops=459.0, hbm_gb=95, hbm_gbps=2765, ici_gbps=100, dcn_gbps=12.5),
    "v6e": ChipSpec("v6e", peak_bf16_tflops=918.0, hbm_gb=32, hbm_gbps=1640, ici_gbps=100, dcn_gbps=12.5),
    "cpu": ChipSpec("cpu", peak_bf16_tflops=1.0, hbm_gb=8, hbm_gbps=50, ici_gbps=10, dcn_gbps=1.0),
}


# ``device_kind`` as jax reports it -> generation.  "TPU v5 lite" is what
# a v5e reports (chip run, PR 21: jax 0.9.0 / libtpu 0.0.34); the other
# spellings are the ones libtpu is documented to use and have not been
# seen on hardware here.  A kind that is not listed is an error, not a
# default: pricing an unknown chip as a v5e hides the device.
DEVICE_KIND_GENERATION = {
    "tpu v4": "v4",
    "tpu v5 lite": "v5e", "tpu v5e": "v5e",
    "tpu v5": "v5p", "tpu v5p": "v5p",
    "tpu v6 lite": "v6e", "tpu v6e": "v6e",
}


def on_accelerator() -> bool:
    """Whether jax's default backend is an accelerator — the one rule
    the benchmark entry points size themselves by.  A CPU backend is
    acceptable only when the caller pinned it (``JAX_PLATFORMS=cpu``:
    the toy-size dry run); reached any other way it means the chip did
    not come up, and that is an error rather than a smaller model."""
    import jax

    backend = jax.default_backend()
    if backend != "cpu":
        return True
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return False
    raise RuntimeError(
        "no accelerator: jax.default_backend() is 'cpu' and JAX_PLATFORMS "
        "does not ask for it; set JAX_PLATFORMS=cpu for the toy-size dry "
        "run")


def factor_3d(num_devices: int, *, pipe: int = 1, model: int = 1,
              data: Optional[int] = None) -> dict[str, int]:
    """Factor a device count into the canonical 3D ``(data, pipe, model)``
    mesh shape — the dp×pp×tp composition.

    ``data`` defaults to whatever is left after the pipeline and tensor
    degrees (``num_devices // (pipe·model)``); passing it explicitly
    turns the residual check into a full ``dp·pp·tp == num_devices``
    validation.  Axis order is data-outermost / model-innermost so the
    reshape-constructed mesh places each tensor-parallel group on
    adjacent device ids (the highest-volume collectives — the per-block
    activation all-reduces — ride the shortest links; pipe's one-hop
    ppermute and data's per-step grad sync tolerate longer paths).

    Size-1 axes other than ``pipe`` are dropped so downstream code sees
    the same mesh shapes users write by hand (``{'pipe': 4}``, not
    ``{'data': 1, 'pipe': 4, 'model': 1}``).
    """
    if pipe < 1 or model < 1:
        raise ValueError(f"pipe ({pipe}) and model ({model}) must be >= 1")
    if num_devices % (pipe * model):
        raise ValueError(
            f"cannot factor {num_devices} devices into pipe={pipe} x "
            f"model={model} (times an integer data degree)")
    inferred = num_devices // (pipe * model)
    if data is None:
        data = inferred
    elif data * pipe * model != num_devices:
        raise ValueError(
            f"dp x pp x tp = {data} x {pipe} x {model} = "
            f"{data * pipe * model} != {num_devices} devices")
    shape: dict[str, int] = {}
    if data > 1:
        shape[const.DATA_AXIS] = data
    shape[const.PIPE_AXIS] = pipe
    if model > 1:
        shape[const.MODEL_AXIS] = model
    return shape


class ResourceSpec:
    """Parses and validates a topology spec; factory for the device mesh."""

    def __init__(self, spec: Optional[Mapping[str, Any] | str] = None):
        if isinstance(spec, str):
            if yaml is None:
                raise RuntimeError("pyyaml unavailable; pass a dict spec")
            with open(spec) as f:
                spec = yaml.safe_load(f)
        spec = dict(spec or {})
        if "nodes" in spec:
            # Reference-style SSH GPU inventories (resource_spec.py:160-215)
            # do not describe a TPU topology; silently ignoring the key
            # would train on a different cluster than the user declared.
            # Heterogeneous replica sets in particular (the reference's
            # r4.yml 2-GPU + 1-GPU workers with weighted-average gradient
            # semantics, cases/c0.py:88-138) are deliberately out of scope:
            # TPU pod slices are homogeneous by construction.
            counts = {len(n.get("gpus", n.get("devices", [])) or [])
                      for n in spec["nodes"] if isinstance(n, dict)}
            if len(counts) > 1:
                raise ValueError(
                    "heterogeneous replica sets (nodes with differing "
                    f"device counts {sorted(counts)}) are out of scope on "
                    "homogeneous TPU meshes — see docs/usage/migration.md "
                    "'Deliberate exclusions'")
            raise ValueError(
                "reference-style 'nodes' inventories are not a TPU "
                "topology; declare topology.num_devices (+ multihost for "
                "multi-process jobs) — see docs/usage/migration.md")
        topo = dict(spec.get("topology") or {})
        self.platform: str = topo.get("platform", "auto")
        self.generation: str = topo.get("generation", "auto")
        self._requested_devices: Optional[int] = topo.get("num_devices")
        # Multi-slice pods: the outer replica axis rides DCN.
        self.num_slices: int = int(topo.get("num_slices", 1))
        self.mesh_shape: dict[str, int] = dict(spec.get("mesh") or {})
        mh = dict(spec.get("multihost") or {})
        self.coordinator: str = mh.get(
            "coordinator", const.ENV.AUTODIST_TPU_COORDINATOR.val)
        self.num_processes: int = int(
            mh.get("num_processes", const.ENV.AUTODIST_TPU_NUM_PROCESSES.val))
        self.process_id: int = int(
            mh.get("process_id", const.ENV.AUTODIST_TPU_PROCESS_ID.val))
        for ax in self.mesh_shape:
            if ax not in const.ALL_AXES:
                raise ValueError(
                    f"unknown mesh axis {ax!r}; valid axes: {const.ALL_AXES}")

    # ------------------------------------------------------------------ #
    @property
    def is_multihost(self) -> bool:
        return self.num_processes > 1

    @property
    def chip(self) -> ChipSpec:
        gen = self.generation
        if gen == "auto":
            gen = "cpu" if self.platform == "cpu" else _detect_generation(
                self._platform_devices()[0])
        if gen not in CHIP_SPECS:
            raise ValueError(
                f"unknown chip generation {gen!r}; known: "
                f"{sorted(CHIP_SPECS)}")
        return CHIP_SPECS[gen]

    def _platform_devices(self) -> list:
        """The devices of ``topology.platform``: jax's default backend
        for ``auto``, otherwise that platform's or an error — a spec
        that names a platform never runs on another."""
        import jax
        if self.platform == "auto":
            return list(jax.devices())
        try:
            return list(jax.devices(self.platform))
        except RuntimeError as e:
            raise RuntimeError(
                f"topology.platform is {self.platform!r} but jax has no "
                f"such backend here (default backend: "
                f"{jax.default_backend()!r}): {e}") from e

    def devices(self) -> Sequence[Any]:
        """Deterministically ordered global device list (counterpart of the
        reference's sorted node list for cross-worker determinism,
        ``cluster.py:78-81``).  Touching the live device list in a
        multihost job requires the distributed backend, so this
        bootstraps first (idempotent)."""
        self.bootstrap()
        devs = self._platform_devices()
        devs.sort(key=lambda d: d.id)
        if self._requested_devices is not None:
            if self._requested_devices > len(devs):
                raise ValueError(
                    f"requested {self._requested_devices} devices, "
                    f"only {len(devs)} visible")
            devs = devs[: self._requested_devices]
        return devs

    def num_devices(self) -> int:
        """Declared device count when the spec gives one — strategy
        building must work *before* the backend is initialized (the chief
        plans, then launches workers, then bootstraps; ≙ the reference
        building strategies from the YAML inventory alone,
        ``resource_spec.py:45-78``).  Falls back to the live device list."""
        if self._requested_devices is not None:
            return self._requested_devices
        if self.is_multihost and not getattr(self, "_bootstrapped", False):
            # Counting live devices here would join (and block on) the
            # jax.distributed job mid-planning — before workers may even
            # be launched.  Demand an explicit inventory instead.
            raise ValueError(
                "multihost planning needs an explicit device inventory: "
                "declare topology.num_devices (the global count), or "
                "bootstrap() first")
        return len(self.devices())

    def resolved_mesh_shape(self) -> dict[str, int]:
        """Mesh shape with defaults filled: unspecified → pure data axis
        (split as ``dcn × data`` when the topology declares slices)."""
        n = self.num_devices()
        shape = dict(self.mesh_shape)
        if not shape:
            if self.num_slices > 1:
                if n % self.num_slices:
                    raise ValueError(
                        f"{n} devices do not divide into "
                        f"{self.num_slices} slices")
                shape = {const.DCN_AXIS: self.num_slices,
                         const.DATA_AXIS: n // self.num_slices}
            else:
                shape = {const.DATA_AXIS: n}
        known = math.prod(v for v in shape.values() if v != -1)
        wildcards = [k for k, v in shape.items() if v == -1]
        if wildcards:
            if len(wildcards) > 1:
                raise ValueError("at most one mesh axis may be -1")
            if n % known:
                raise ValueError(
                    f"cannot infer axis {wildcards[0]!r}: {n} % {known} != 0")
            shape[wildcards[0]] = n // known
        if math.prod(shape.values()) != n:
            raise ValueError(
                f"mesh shape {shape} does not match {n} devices")
        return shape

    def with_mesh(self, mesh_shape: Mapping[str, int]) -> "ResourceSpec":
        """A copy of this spec with a different mesh factorization of
        the *same* topology — how the topology-aware search
        (:mod:`autodist_tpu.simulator.search`) enumerates candidate
        ``(dcn, data, pipe, model, ...)`` factorizations without
        re-parsing or re-bootstrapping anything.  Shares platform,
        generation, device inventory, slice count, and multihost state
        with the original."""
        import copy

        for ax in mesh_shape:
            if ax not in const.ALL_AXES:
                raise ValueError(
                    f"unknown mesh axis {ax!r}; valid axes: "
                    f"{const.ALL_AXES}")
        clone = copy.copy(self)
        clone.mesh_shape = dict(mesh_shape)
        return clone

    def link_levels(self) -> dict[str, LinkSpec]:
        """This topology's hierarchical network model (chip-table
        defaults; the cost model overlays calibrated ``"link"``
        constants on top)."""
        return self.chip.link_levels()

    def three_d(self) -> tuple[int, int, int]:
        """The resolved ``(dp, pp, tp)`` degrees of this topology.

        ``dp`` folds the cross-slice DCN axis in (both are data
        parallelism), ``pp`` is the pipe axis, ``tp`` the model axis;
        a topology whose mesh carries any *other* non-trivial axis
        (seq/expert) is not a 3D composition and is rejected so callers
        can't mis-price it as one.
        """
        shape = self.resolved_mesh_shape()
        extra = {a: s for a, s in shape.items()
                 if s > 1 and a not in (const.DATA_AXIS, const.DCN_AXIS,
                                        const.PIPE_AXIS, const.MODEL_AXIS)}
        if extra:
            raise ValueError(
                f"not a (data, pipe, model) factorization: mesh also "
                f"carries {extra}")
        dp = shape.get(const.DATA_AXIS, 1) * shape.get(const.DCN_AXIS, 1)
        return dp, shape.get(const.PIPE_AXIS, 1), \
            shape.get(const.MODEL_AXIS, 1)

    def make_mesh(self):
        """Build the named device mesh (the resolution step ≙ reference
        ``DeviceResolver.resolve_to_device_str``, ``resolver.py:47-67``).

        With a ``dcn`` axis on real multi-slice hardware the mesh comes
        from ``mesh_utils.create_hybrid_device_mesh`` so the dcn axis
        provably falls on slice boundaries (a naive reshape could put the
        high-volume data-axis collectives on the slow DCN links).
        Devices that are one slice — simulated/CPU devices, which carry
        no slice topology, and a single host's chips, which all report
        the same ``slice_index`` — keep the deterministic reshape: the
        ``dcn`` axis is then a logical split whose collectives ride ICI
        (the hybrid builder refuses a slice count the hardware lacks)."""
        import jax
        shape = self.resolved_mesh_shape()
        devs = self.devices()
        slices = {getattr(d, "slice_index", None) for d in devs}
        if const.DCN_AXIS in shape and len(slices) > 1:
            from jax.experimental import mesh_utils
            axes = list(shape.keys())
            per_slice = [1 if a == const.DCN_AXIS else shape[a]
                         for a in axes]
            across = [shape[a] if a == const.DCN_AXIS else 1 for a in axes]
            arr = mesh_utils.create_hybrid_device_mesh(
                per_slice, across, devices=list(devs))
            return jax.sharding.Mesh(arr, tuple(axes))
        arr = np.array(devs).reshape(tuple(shape.values()))
        return jax.sharding.Mesh(arr, tuple(shape.keys()))

    def bootstrap(self):
        """Multi-host initialization (counterpart of the reference's
        cluster start, ``cluster.py:160-210``): connect this process to
        the coordination service before any mesh use.  Idempotent, and
        lazy — callers that never touch a global mesh (e.g. the async-PS
        runner, which trains on a process-local mesh) never join a
        ``jax.distributed`` job."""
        if getattr(self, "_bootstrapped", False):
            return
        # Opt-in XLA async-collective/latency-hiding flags must land in
        # XLA_FLAGS before the first backend touch (the client reads them
        # once); bootstrap is the last frame that runs before it.
        from autodist_tpu.kernel.lowering import apply_latency_hiding_flags
        apply_latency_hiding_flags(platform=self.platform)
        if self.is_multihost:
            import jax
            logging.info(
                "jax.distributed.initialize(%s, %d, %d)",
                self.coordinator, self.num_processes, self.process_id)
            jax.distributed.initialize(
                coordinator_address=self.coordinator,
                num_processes=self.num_processes,
                process_id=self.process_id,
            )
        # Latch only after success so a transient failure (coordinator not
        # up yet) can be retried instead of silently running single-host.
        self._bootstrapped = True

    def to_dict(self) -> dict:
        return {
            "topology": {
                "platform": self.platform,
                "generation": self.generation,
                "num_devices": self._requested_devices,
            },
            "mesh": dict(self.mesh_shape),
            "multihost": {
                "coordinator": self.coordinator,
                "num_processes": self.num_processes,
                "process_id": self.process_id,
            },
        }


def _detect_generation(device) -> str:
    """Generation of ``device``: the ``AUTODIST_TPU_GENERATION``
    override, else the simulated-mesh ``"cpu"`` entry for a CPU device,
    else :data:`DEVICE_KIND_GENERATION` — raising on a ``device_kind``
    it does not list."""
    env_gen = const.ENV.AUTODIST_TPU_GENERATION.val
    if env_gen:
        if env_gen not in CHIP_SPECS:
            raise ValueError(
                f"AUTODIST_TPU_GENERATION={env_gen!r} is not a known chip "
                f"generation; known: {sorted(CHIP_SPECS)}")
        return env_gen
    if device.platform == "cpu":
        return "cpu"
    gen = DEVICE_KIND_GENERATION.get(device.device_kind.lower())
    if gen is None:
        raise ValueError(
            f"unrecognised device_kind {device.device_kind!r} (platform "
            f"{device.platform!r}); known kinds: "
            f"{sorted(DEVICE_KIND_GENERATION)} — set topology.generation "
            "or AUTODIST_TPU_GENERATION to one of "
            f"{sorted(CHIP_SPECS)}")
    return gen
