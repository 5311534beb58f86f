"""Constants and environment-variable config plane.

TPU-native counterpart of the reference's ``autodist/const.py`` (env flags +
name-scope constants, reference ``const.py:31-89``).  Env vars remain the
config plane because they must propagate across multi-host launches
(reference ``coordinator.py:70-82``); here they propagate to every TPU-VM
host process.
"""
import enum
import os

# Working directories (reference const.py:31-38).
DEFAULT_WORKING_DIR = "/tmp/autodist_tpu"
DEFAULT_STRATEGY_DIR = os.path.join(DEFAULT_WORKING_DIR, "strategies")
DEFAULT_TRACE_DIR = os.path.join(DEFAULT_WORKING_DIR, "traces")
DEFAULT_LOG_DIR = os.path.join(DEFAULT_WORKING_DIR, "logs")

# Canonical mesh-axis names.  The reference had a single implicit axis
# (the replica list, strategy.proto:66-68); the TPU build names its mesh
# axes so strategies can target them.
DATA_AXIS = "data"       # data parallelism (≙ reference replicas)
DCN_AXIS = "dcn"         # cross-slice data parallelism (multi-slice pods:
                         # the outer axis rides DCN, `data` rides ICI)
MODEL_AXIS = "model"     # tensor/model parallelism (beyond reference parity)
SEQ_AXIS = "seq"         # sequence/context parallelism (ring attention)
PIPE_AXIS = "pipe"       # pipeline parallelism
EXPERT_AXIS = "expert"   # expert parallelism (MoE)

ALL_AXES = (DATA_AXIS, DCN_AXIS, MODEL_AXIS, SEQ_AXIS, PIPE_AXIS,
            EXPERT_AXIS)


class ENV(enum.Enum):
    """Typed environment flags (reference ``const.py:55-89`` ENV enum).

    Each member's value is a lambda producing the typed default.
    """

    AUTODIST_TPU_WORKER = (lambda v: v or "",)          # non-chief host marker
    AUTODIST_TPU_STRATEGY_ID = (lambda v: v or "",)     # strategy to load
    AUTODIST_TPU_MIN_LOG_LEVEL = (lambda v: v or "INFO",)
    AUTODIST_TPU_IS_TESTING = (lambda v: v == "True" or v == "1",)
    AUTODIST_TPU_WORKING_DIR = (lambda v: v or DEFAULT_WORKING_DIR,)
    AUTODIST_TPU_COORDINATOR = (lambda v: v or "",)     # host:port for jax.distributed
    AUTODIST_TPU_NUM_PROCESSES = (lambda v: int(v) if v else 1,)
    AUTODIST_TPU_PROCESS_ID = (lambda v: int(v) if v else 0,)
    AUTODIST_TPU_DUMP_HLO = (lambda v: v == "True" or v == "1",)  # per-stage HLO dumps
    # Chip generation override for MFU/cost math (e.g. "v5e"); falls back
    # to device_kind detection.
    AUTODIST_TPU_GENERATION = (lambda v: (v or "").lower(),)
    # Opt into XLA's async-collective + latency-hiding-scheduler flags
    # (kernel/lowering.py LATENCY_HIDING_XLA_FLAGS): the compiler-side
    # half of comm/compute overlap — the collective-matmul decomposition
    # restructures the program, these flags let XLA schedule the emitted
    # collectives asynchronously.  "1"/"True" = the default flag list;
    # a value starting with "--" replaces the list verbatim (flag names
    # drift across libtpu versions — the escape hatch must not require
    # a code change).  Applied at ResourceSpec.bootstrap() — before the
    # first backend touch (LIBTPU_INIT_ARGS, the channel they ride, is
    # read once at client init) and only when the
    # target is a TPU backend; see docs/usage/performance.md.
    AUTODIST_TPU_ASYNC_COLLECTIVES = (lambda v: v or "",)
    # Telemetry master switch (telemetry/): default ON (cheap in-memory
    # spans/counters, bounded); any usual falsy spelling disables
    # everything — span() returns a shared no-op and no files are ever
    # written.
    AUTODIST_TPU_TELEMETRY = (
        lambda v: (v or "1").lower() not in ("0", "false", "no", "off"),)
    # Flush destination for telemetry sinks (trace.json / metrics.jsonl /
    # manifest.json); empty = in-memory only (telemetry.configure() can
    # set it programmatically).
    AUTODIST_TPU_TELEMETRY_DIR = (lambda v: v or "",)
    # Per-step record sampling: keep every Nth record (1 = all).
    AUTODIST_TPU_TELEMETRY_SAMPLE = (lambda v: max(int(v), 1) if v else 1,)
    # host:port of the native host-coordination service (runtime/coordination)
    AUTODIST_TPU_COORD_SERVICE = (lambda v: v or "",)
    # Shared-secret token every coordination-service connection must present
    # (generated by the chief's CoordServer when unset, propagated to workers)
    AUTODIST_TPU_COORD_TOKEN = (lambda v: v or "",)
    # Interface the coordination server binds ("" = all interfaces)
    AUTODIST_TPU_COORD_BIND = (lambda v: v or "",)

    @property
    def val(self):
        """Return the typed value of this env var."""
        return self.value[0](os.environ.get(self.name))
