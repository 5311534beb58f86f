"""autodist_tpu: a TPU-native distributed-training strategy compiler.

Brand-new framework with the capabilities of the reference AutoDist
(petuum/autodist, ``/root/reference``): a per-variable, serializable
distribution *strategy* is built from the model + a resource spec,
compiled against the hardware topology, and lowered — here into a single
XLA SPMD program over a ``jax.sharding.Mesh`` (collectives over ICI/DCN)
instead of a rewritten TF graph over SSH/gRPC/NCCL.
"""

__version__ = "0.1.0"

from autodist_tpu.autodist import AutoDist
from autodist_tpu.capture import PipelineTrainable, Trainable, VarInfo
from autodist_tpu.resource import ResourceSpec
from autodist_tpu.runner import DistributedRunner, stack_steps
from autodist_tpu.strategy.builders import (AllReduce, GradAccumulation,
                                            Parallax, PartitionedAR,
                                            PartitionedPS, PS,
                                            PSLoadBalancing,
                                            RandomAxisPartitionAR,
                                            UnevenPartitionedPS, ZeRO)
from autodist_tpu.strategy.gspmd_builders import (FSDPSharded, Sharded,
                                                  TensorParallel)
from autodist_tpu.strategy.parallel_builders import (ExpertParallel,
                                                     Pipeline,
                                                     SequenceParallel)
from autodist_tpu.strategy.ir import Strategy
from autodist_tpu.simulator import AutoStrategy
from autodist_tpu.elastic import ElasticController
from autodist_tpu.train import fit
from autodist_tpu.fetches import fetch

__all__ = [
    "AutoDist", "Trainable", "PipelineTrainable", "VarInfo", "ResourceSpec",
    "DistributedRunner", "stack_steps",
    "Strategy", "AllReduce", "PS", "PSLoadBalancing", "PartitionedPS",
    "UnevenPartitionedPS", "PartitionedAR", "RandomAxisPartitionAR",
    "Parallax", "ZeRO", "AutoStrategy", "GradAccumulation", "fit",
    "Sharded", "TensorParallel", "FSDPSharded",
    "SequenceParallel", "Pipeline", "ExpertParallel", "fetch",
    "ElasticController",
]
