"""distributed_llm_scheduler_tpu — TPU-native memory-constrained DAG
scheduling and execution for LLMs.

A brand-new framework with the capability surface of the reference
``2alaaa/distributed-llm-scheduler`` (DAG extraction → memory-constrained
scheduling → execution → evaluation/visualization), rebuilt TPU-first:

* tasks are XLA-compilable computations with real byte sizes;
* nodes are TPU cores on a ``jax.sharding.Mesh`` under HBM budgets;
* transfers are ``jax.device_put`` / ICI collectives with measured cost;
* the reference's simulated executor survives as a pluggable CPU-runnable
  backend next to the real device backend;
* plus native-scale subsystems the reference lacks: sharded training
  (DP/TP/SP/EP, remat, scanned layers), ring + Ulysses attention for long
  context, multi-slice ICI/DCN topologies, Pallas kernels, pretrained
  checkpoint ingestion, checkpointing, config/CLI, and a native C++
  scheduling engine with bit-identical policies.

See SURVEY.md for the layer map and parity notes.
"""

import os as _os


def _place_compile_cache() -> None:
    """The ONE place the persistent compilation cache is configured.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    nothing here touches the setting.  Otherwise the cache lives at the
    fixed ``<checkout>/.jax_cache`` (git-ignored): the directory is part
    of nothing's identity but must not move between runs, so never a temp
    name, a pid or a time.  Runs before any backend is touched —
    importing this package first is enough."""
    if _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax as _jax

    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(
            _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
            ".jax_cache",
        ),
    )


_place_compile_cache()

from .core.graph import (
    DEFAULT_PARAM_GB,
    GraphValidationError,
    Task,
    TaskGraph,
    TaskStatus,
)
from .core.cluster import Cluster, DeviceState, estimate_cluster_memory_needed
from .core.fusion import fuse_linear_chains
from .core.schedule import Schedule, TaskTiming
from .core.validate import ValidationReport, validate_schedule
from .backends.sim import LinkModel, SimulatedBackend, TieredLinkModel
from .sched.base import BaseScheduler
from .sched.elastic import remainder_graph, reschedule, surviving_work
from .sched.heft import HEFTScheduler
from .sched.pack import GroupPackScheduler
from .sched.pipeline import PipelineStageScheduler
from .sched.policies import (
    ALL_SCHEDULERS,
    CriticalPathScheduler,
    DFSScheduler,
    GreedyScheduler,
    MRUScheduler,
    RoundRobinScheduler,
    get_scheduler,
)
from .sched.refine import RefinedPackScheduler
from .utils.quantize import QParam, quantize_dag

__version__ = "0.1.0"

__all__ = [
    "DEFAULT_PARAM_GB",
    "GraphValidationError",
    "Task",
    "TaskGraph",
    "TaskStatus",
    "Cluster",
    "DeviceState",
    "estimate_cluster_memory_needed",
    "Schedule",
    "TaskTiming",
    "fuse_linear_chains",
    "ValidationReport",
    "validate_schedule",
    "BaseScheduler",
    "ALL_SCHEDULERS",
    "RoundRobinScheduler",
    "DFSScheduler",
    "GreedyScheduler",
    "CriticalPathScheduler",
    "MRUScheduler",
    "HEFTScheduler",
    "PipelineStageScheduler",
    "GroupPackScheduler",
    "RefinedPackScheduler",
    "get_scheduler",
    "LinkModel",
    "TieredLinkModel",
    "SimulatedBackend",
    "QParam",
    "quantize_dag",
    "surviving_work",
    "remainder_graph",
    "reschedule",
]
