"""Cluster model: memory-limited accelerator cores.

Capability parity with the reference's ``Node`` (reference
``schedulers.py:19-29``): each device has a total memory budget, an available
counter, a compute-speed multiplier, a set of resident ("cached") parameters,
and an MRU recency deque.  TPU-first differences:

* a device can be bound to a real ``jax.Device`` (one TPU core of a mesh);
  its memory budget then defaults to the core's HBM capacity, and placement
  decisions made against this model are executed for real by the device
  backend.
* parameter sizes are real bytes (via the owning :class:`TaskGraph`), not a
  0.5 GB constant — the constant remains only as the default for synthetic
  workloads.
* heterogeneous ``compute_speed`` does not exist on a TPU slice (all cores
  are identical); we keep it for the simulated backend and parity tests, and
  reframe heterogeneity on real hardware as per-core HBM budgets.
"""

from __future__ import annotations

import random
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Set

#: budget a host-platform (CPU) device gets when it stands in for a TPU
#: core in tests and rehearsals: CPU devices have no HBM and report no
#: ``memory_stats()``.  Accelerators never take this value.
HOST_STANDIN_GB = 16.0


@dataclass
class DeviceState:
    """One schedulable core: memory budget + parameter cache.

    ``jax_device`` is optionally a live ``jax.Device``; the scheduler layer
    never touches it, only the execution backend does.  Param *recency* is
    tracked by the MRU policy itself under its logical clock (the reference
    also keeps a per-node deque, ``schedulers.py:28``, but its scheduler
    reads its own usage dicts — we keep only the read path).

    ``slice_id`` is the device's TPU slice (pod) membership: transfers
    between cores of the same slice ride ICI; transfers between slices ride
    the much slower DCN (:class:`~..backends.sim.TieredLinkModel`).  The
    reference has no notion of network topology at all.
    """

    node_id: str
    total_memory: float  # GB
    compute_speed: float = 1.0
    jax_device: Optional[Any] = None
    slice_id: int = 0

    available_memory: float = field(init=False)
    cached_params: Set[str] = field(default_factory=set)
    running_tasks: List[str] = field(default_factory=list)
    completed_tasks: List[str] = field(default_factory=list)
    # reference parity: per-node MRU recency window, written on every
    # assignment (reference schedulers.py:29,99 — the reference never reads
    # it back, and neither do our policies, which track recency under the
    # MRU logical clock; the state exists for inspection parity)
    last_used_params: deque = field(
        default_factory=lambda: deque(maxlen=10)
    )

    def __post_init__(self) -> None:
        self.available_memory = self.total_memory

    def reset(self) -> None:
        self.available_memory = self.total_memory
        self.cached_params.clear()
        self.running_tasks.clear()
        self.completed_tasks.clear()
        self.last_used_params.clear()

    @property
    def used_memory(self) -> float:
        return self.total_memory - self.available_memory

    def __repr__(self) -> str:
        return (
            f"DeviceState({self.node_id!r}, {self.available_memory:.2f}/"
            f"{self.total_memory:.2f}GB free, speed={self.compute_speed}, "
            f"{len(self.cached_params)} params cached)"
        )


class Cluster:
    """An ordered collection of :class:`DeviceState`.

    Constructors cover the reference's provisioning profiles (reference
    ``simulation.py:161-190`` and ``test_gpt2.py:278-283``) plus a
    TPU-backed constructor that derives budgets from live device HBM.
    """

    def __init__(self, devices: Sequence[DeviceState]):
        if not devices:
            raise ValueError("cluster needs at least one device")
        ids = [d.node_id for d in devices]
        if len(set(ids)) != len(ids):
            raise ValueError(f"duplicate device ids: {ids}")
        self.devices: List[DeviceState] = list(devices)
        self._by_id: Dict[str, DeviceState] = {d.node_id: d for d in devices}

    def __len__(self) -> int:
        return len(self.devices)

    def __iter__(self):
        return iter(self.devices)

    def __getitem__(self, node_id: str) -> DeviceState:
        return self._by_id[node_id]

    def __contains__(self, node_id: str) -> bool:
        return node_id in self._by_id

    def ids(self) -> List[str]:
        return [d.node_id for d in self.devices]

    def total_memory(self) -> float:
        return sum(d.total_memory for d in self.devices)

    def reset(self) -> None:
        for d in self.devices:
            d.reset()

    # -- provisioning profiles --------------------------------------------
    @classmethod
    def uniform(cls, n: int, memory_gb: float, speed: float = 1.0,
                prefix: str = "core") -> "Cluster":
        return cls([
            DeviceState(f"{prefix}_{i}", memory_gb, speed) for i in range(n)
        ])

    @classmethod
    def heterogeneous(cls, total_memory: float, num_nodes: int,
                      rng: Optional[random.Random] = None) -> "Cluster":
        """Reference memory-regime provisioning profiles.

        2 nodes: 60/40 split, speeds 1.2/1.0; 4 nodes: 35/25/25/15, speeds
        1.2/1.0/1.0/0.8; otherwise equal split with speeds drawn uniformly
        from 0.7-1.3 (reference ``simulation.py:161-190``), seedable here
        (the reference draws unseeded, so its sweeps aren't reproducible).
        """
        rng = rng or random.Random(0)
        if num_nodes == 2:
            fracs, speeds = [0.60, 0.40], [1.2, 1.0]
        elif num_nodes == 4:
            fracs, speeds = [0.35, 0.25, 0.25, 0.15], [1.2, 1.0, 1.0, 0.8]
        else:
            fracs = [1.0 / num_nodes] * num_nodes
            speeds = [rng.uniform(0.7, 1.3) for _ in range(num_nodes)]
        return cls([
            DeviceState(f"node_{i}", total_memory * f, s)
            for i, (f, s) in enumerate(zip(fracs, speeds))
        ])

    @classmethod
    def multislice(cls, n_slices: int, cores_per_slice: int,
                   memory_gb: float, speed: float = 1.0,
                   prefix: str = "core") -> "Cluster":
        """Multi-slice TPU topology (BASELINE config #3: 2 x v5e-8 = 16
        cores, DCN between slices).  Devices are ordered slice-by-slice, so
        contiguous pipeline stages cross DCN only at slice boundaries."""
        return cls([
            DeviceState(
                f"{prefix}_{s}_{i}", memory_gb, speed, slice_id=s
            )
            for s in range(n_slices)
            for i in range(cores_per_slice)
        ])

    def without(self, *node_ids: str) -> "Cluster":
        """A new cluster of fresh DeviceStates minus ``node_ids`` — the
        survivor set after failures (elastic recovery).  Copies every
        identity field (incl. jax_device binding and slice topology) so
        callers can't drift by hand-rebuilding DeviceStates."""
        dead = set(node_ids)
        return Cluster([
            DeviceState(
                d.node_id, d.total_memory, d.compute_speed,
                jax_device=d.jax_device, slice_id=d.slice_id,
            )
            for d in self.devices if d.node_id not in dead
        ])

    def slice_ids(self) -> Dict[str, int]:
        """node_id -> slice_id (for topology-aware cost call sites)."""
        return {d.node_id: d.slice_id for d in self.devices}

    @classmethod
    def laptops(cls) -> "Cluster":
        """The reference's 4-laptop fleet (reference test_gpt2.py:278-283)."""
        profile = [("laptop_0", 8.0, 1.0), ("laptop_1", 8.0, 1.2),
                   ("laptop_2", 6.0, 0.8), ("laptop_3", 6.0, 0.9)]
        return cls([DeviceState(n, m, s) for n, m, s in profile])

    @classmethod
    def from_jax_devices(cls, devices: Optional[Sequence[Any]] = None,
                         hbm_cap_gb: Optional[float] = None) -> "Cluster":
        """Build from live JAX devices (one DeviceState per core).

        HBM budget per core: ``hbm_cap_gb`` when given (emulates a
        constrained memory regime on real hardware — the TPU analog of
        the reference's regime sweep), else the device's own
        ``memory_stats()`` byte limit
        (:func:`..utils.costmodel.device_hbm_bytes`: a TPU that reports
        none is an error, never an assumed capacity; host-platform
        devices stand in at ``HOST_STANDIN_GB``).  Cores are identical,
        so ``compute_speed`` is 1.0.
        """
        import jax

        from ..utils.costmodel import device_hbm_bytes

        devices = list(devices if devices is not None else jax.devices())
        out = []
        for i, dev in enumerate(devices):
            cap = hbm_cap_gb
            if cap is None:
                cap = device_hbm_bytes(dev) / 1024**3
            out.append(DeviceState(
                f"core_{i}", cap, 1.0, jax_device=dev,
                slice_id=getattr(dev, "slice_index", None) or 0,
            ))
        return cls(out)

    def __repr__(self) -> str:
        return (
            f"Cluster({len(self.devices)} devices, "
            f"{self.total_memory():.1f}GB total)"
        )


def estimate_cluster_memory_needed(graph) -> float:
    """Lower-bound cluster memory for a graph: the reference's estimator.

    max single-task activation footprint + per-param cache cost over unique
    params (reference ``simulation.py:194-214``), generalized to real param
    sizes.  Used to size memory regimes.
    """
    return graph.max_task_memory() + graph.total_param_gb()
