"""Measured cost model: calibrate task times on real hardware, persist, apply.

Replaces the reference's class-based compute-time constants
(reference ``test_gpt2.py:33-43``) with measured compiled timings
(SURVEY.md §7 step 6): profile-execute the DAG once on a device, record
per-task wall times, and feed them back into ``Task.compute_time`` so
policies (HEFT/critical-path especially) optimize reality.  Calibrations
persist to JSON keyed by graph name + ``device_kind`` (a calibration from
one kind of device never stands in for another's) so reruns on the same
machine skip measurement; the files are run-time products and are not
committed.
"""

from __future__ import annotations
# dls-lint: allow-file(DET001) calibration measures real step/transfer time

import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

from ..core.graph import TaskGraph
from .config import env_str


@dataclass
class CostModel:
    """task_id -> measured seconds, plus provenance.

    ``dispatch_s`` is the measured per-task HOST dispatch cost (Python
    call overhead of enqueueing one task, separate from device compute):
    real execution pays it serially for every dispatched task, so the
    replay charges it too (``SimulatedBackend(dispatch_s=...)``).  0.0 in
    calibrations predating the field."""

    graph_name: str
    platform: str
    task_seconds: Dict[str, float] = field(default_factory=dict)
    dispatch_s: float = 0.0
    # how the numbers were measured ("profile": serial per-task wall
    # times); "" marks a pre-method-field artifact, which calibrate_cached
    # refuses (mixing semantics silently skews the replay)
    method: str = ""
    # UTC ISO stamp of when the calibration was MEASURED ("" for artifacts
    # predating the field).  A cache hit keeps the original stamp, so
    # consumers can disclose calibration age instead of passing an old
    # cache off as a live measurement.
    measured_at: str = ""
    # True when this model came off disk rather than being measured in
    # this process.  NOT persisted — provenance of the object in hand,
    # set by calibrate_cached, so consumers label cache hits directly
    # instead of inferring them from stamp age.
    cache_hit: bool = False

    def apply(self, graph: TaskGraph) -> int:
        """Overwrite compute_time for tasks present in the model.

        Returns how many tasks were updated.  Unknown tasks keep their
        analytic seed estimate.
        """
        n = 0
        for tid, secs in self.task_seconds.items():
            t = graph.get(tid)
            if t is not None:
                t.compute_time = max(secs, 1e-7)
                n += 1
        return n

    # -- persistence -------------------------------------------------------
    def save(self, path: str) -> str:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(
                {
                    "graph_name": self.graph_name,
                    "platform": self.platform,
                    "task_seconds": self.task_seconds,
                    "dispatch_s": self.dispatch_s,
                    "method": self.method,
                    "measured_at": self.measured_at,
                },
                f,
                indent=1,
            )
        return path

    @classmethod
    def load(cls, path: str) -> "CostModel":
        with open(path) as f:
            d = json.load(f)
        return cls(
            d["graph_name"], d["platform"], d["task_seconds"],
            d.get("dispatch_s", 0.0), d.get("method", ""),
            d.get("measured_at", ""),
        )


def device_hbm_bytes(device: Any = None) -> int:
    """Usable accelerator memory in bytes — KV-budget sizing
    (``models.kv_pages.PagePool.from_budget``) and per-core cluster
    budgets (``Cluster.from_jax_devices``): the device's own
    ``memory_stats()`` byte limit.  An accelerator that reports none is
    an error, not an assumed size; host-platform (CPU) devices have no
    HBM and stand in at ``core.cluster.HOST_STANDIN_GB``.
    """
    import jax

    from ..core.cluster import HOST_STANDIN_GB

    if device is None:
        device = jax.devices()[0]
    stats = device.memory_stats() or {}
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    if limit:
        return int(limit)
    if device.platform == "cpu":
        return int(HOST_STANDIN_GB * 1024**3)
    raise RuntimeError(
        f"{device} ({device.device_kind}) reports no memory_stats() byte "
        "limit; pass an explicit budget instead of assuming one"
    )


def kind_slug(device: Any) -> str:
    """File-name-safe ``device_kind`` ("TPU v5 lite" -> "tpu_v5_lite") —
    the key calibration caches carry, so numbers measured on one kind of
    device are never read back on another."""
    import re

    return re.sub(r"[^a-z0-9]+", "_", device.device_kind.lower()).strip("_")


def readback_fence(x: Any) -> None:
    """Force completion of ``x`` by reading one dependent element back to
    the host.

    Per-device execution is FIFO, so fencing the last enqueued output
    implies everything queued before it completed too; unlike
    ``block_until_ready`` on a whole pytree, the cost is one fixed-size
    transfer (:func:`_fence_rtt` measures it so timed windows can net it
    out).
    """
    import jax
    import numpy as np

    leaf = jax.tree_util.tree_leaves(x)[-1]
    # single-element index, NOT ravel(): ravel dispatches a full copy of
    # the array first, making the fence cost size-dependent and breaking
    # the fixed-RTT subtraction (_fence_rtt measures a 4-float fence)
    np.asarray(jax.device_get(leaf[(0,) * leaf.ndim]))


def time_amortized(call: Any, reps: int, rtt: float) -> float:
    """Seconds per call: enqueue ``reps`` executions back-to-back, force
    completion with ONE readback fence, net out the fence round-trip.

    The one fence-amortized timing idiom, shared by :func:`calibrate` and
    bench.py so the method can't silently diverge between them.
    """
    import time

    t0 = time.perf_counter()
    out = None
    for _ in range(reps):
        out = call()
    readback_fence(out)
    return max(time.perf_counter() - t0 - rtt, 0.0) / reps


def repeat_capture(fn: Any, n: int) -> "list[float]":
    """All ``n`` samples of ``fn()``, in capture order — the raw material
    every derived estimator (min for device time, median for headline
    quotes, min/max for the artifact's spread block) reduces from.  One
    definition so sample collection can't diverge between the calibrator,
    benchlib's ``best_of``, and bench.py's repeat-capture spread."""
    return [fn() for _ in range(n)]


def _output_capped_reps(out: Any, reps: int, budget_bytes: int = 1 << 30) -> int:
    """Cap in-flight repetitions so queued output buffers stay under
    ``budget_bytes``: async dispatch can run ~reps outputs ahead of
    compute, and 32 live copies of a batch*seq*vocab logits tensor would
    OOM a 16 GB chip."""
    import jax
    import numpy as np

    out_bytes = sum(
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(out)
    )
    if out_bytes <= 0:
        return reps
    return max(1, min(reps, budget_bytes // max(out_bytes, 1)))


def _fence_rtt_stats(device: Any, samples: int = 5) -> "tuple[float, float]":
    """(median, spread) of a trivial fence's round-trip: the fixed cost to
    subtract from fenced timings (host dispatch + one tiny transfer) and
    its jitter (the measurement noise floor)."""
    import statistics
    import time

    import jax
    import jax.numpy as jnp

    x = jax.device_put(jnp.zeros((4,), jnp.float32), device)
    readback_fence(x)  # connection warmup (first readback is an outlier)
    ts = []
    for _ in range(samples):
        t0 = time.perf_counter()
        readback_fence(x + 1.0)
        ts.append(time.perf_counter() - t0)
    med = statistics.median(ts)
    spread = max(ts) - min(ts)
    return med, spread


def _fence_rtt(device: Any, samples: int = 5) -> float:
    return _fence_rtt_stats(device, samples)[0]


def calibrate(
    graph: TaskGraph,
    params: Dict[str, Any],
    graph_input: Any,
    device: Optional[Any] = None,
    repeats: int = 3,
) -> CostModel:
    """Measure per-task compute times on one device: serial per-task wall
    times via the device backend's profile mode (each task ends in
    ``block_until_ready``, which is the completion fence on a locally
    attached device), minimum over ``repeats`` runs after one warm-up.

    Serial timing includes each op's real fixed costs (dispatch,
    allocator, thread wakeup), which is what per-task execution actually
    pays, so ``dispatch_s`` stays 0 — charging it separately would
    double-count.
    """
    import jax

    from ..backends.device import DeviceBackend
    from ..core.cluster import Cluster
    from ..sched.policies import get_scheduler

    device = device if device is not None else jax.devices()[0]
    cluster = Cluster.from_jax_devices([device])
    backend = DeviceBackend(cluster)
    schedule = get_scheduler("greedy").schedule(graph, cluster)

    best: Dict[str, float] = {}
    # first execute() warms the jit caches; profile repeats take minima
    backend.execute(graph, schedule, params, graph_input, warmup=True)
    for _ in range(repeats):
        rep = backend.execute(
            graph, schedule, params, graph_input, profile=True, warmup=False
        )
        for tid, t in rep.timings.items():
            dur = t.duration
            if tid not in best or dur < best[tid]:
                best[tid] = dur
    return CostModel(
        graph.name, device.platform, best, method="profile",
        measured_at=_utc_stamp(),
    )


def _utc_stamp() -> str:
    import datetime

    return datetime.datetime.now(datetime.timezone.utc).isoformat(
        timespec="seconds"
    )


def cache_age_days(measured_at: str) -> Optional[float]:
    """Days since a ``measured_at`` stamp; None if blank/unparseable."""
    import datetime

    if not measured_at:
        return None
    try:
        then = datetime.datetime.fromisoformat(measured_at)
    except ValueError:
        return None
    if then.tzinfo is None:  # naive stamp (hand-edited): assume UTC
        then = then.replace(tzinfo=datetime.timezone.utc)
    now = datetime.datetime.now(datetime.timezone.utc)
    # clamp: clock skew / hand-edited future stamps must not surface as
    # "-0.0d old" in the provenance line this feeds
    return max((now - then).total_seconds() / 86400.0, 0.0)


def recalibrate_requested() -> bool:
    """The ``DLS_RECALIBRATE`` knob: bench-level callers pass this as
    ``refresh=`` to re-measure instead of reading a local calibration
    cache.  Library callers (and tests) are NOT env-sensitive — they get
    cache semantics unless they opt in."""
    return (env_str("DLS_RECALIBRATE") or "").strip().lower() not in (
        "", "0", "false", "no", "off"
    )


def calibrate_cached(
    graph: TaskGraph,
    params: Dict[str, Any],
    graph_input: Any,
    cache_dir: str = ".costmodel",
    device: Optional[Any] = None,
    repeats: int = 3,
    refresh: bool = False,
) -> CostModel:
    """Calibrate, or load a previous calibration of this graph on this
    KIND of device (the cache file is keyed by :func:`kind_slug`).

    ``refresh=True`` bypasses the cache and re-measures.  Bench-level
    callers wire it to :func:`recalibrate_requested`; direct library/test
    callers keep plain cache semantics.
    """
    import jax

    device = device if device is not None else jax.devices()[0]
    path = os.path.join(cache_dir, f"{graph.name}_{kind_slug(device)}.json")
    if not refresh and os.path.exists(path):
        cm = CostModel.load(path)
        # method == "": pre-method-field artifact — its per-task semantics
        # (and missing dispatch_s) would silently mix with current ones
        if cm.method and set(cm.task_seconds) == set(graph.task_ids()):
            cm.cache_hit = True
            return cm
    cm = calibrate(graph, params, graph_input, device=device, repeats=repeats)
    cm.save(path)
    return cm
