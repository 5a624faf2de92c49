"""Real device execution backend: placed, compiled, measured.

This is the seam the whole rebuild hinges on (SURVEY.md §3.1): the
scheduler's placement decision (host Python, L2) becomes actual dispatch of
XLA-compiled per-task executables onto accelerator devices (L0).  Where the
reference *simulates* completion inside ``assign_task_to_node`` (reference
``schedulers.py:101-102``) and replays a cost model (reference
``simulation.py:216-278``), here:

* each task's ``fn`` is jit-compiled once per placement device and cached;
* parameters are ``jax.device_put`` onto the core that first needs them
  (the reference's ``param_locations`` bookkeeping made physical);
* a dependency edge whose producer and consumer sit on different cores
  becomes a real device-to-device transfer (ICI on a TPU slice) via
  ``jax.device_put`` of the producer's output;
* execution is asynchronous dispatch in the **schedule's order**: each JAX
  device executes its enqueued ops in FIFO stream order, so the order tasks
  are dispatched from Python IS the per-device execution order.  Dispatching
  honors each node's scheduled task list (``Schedule.per_node``), not bare
  topological order — a policy that computed a 1F1B microbatch interleaving
  (sched/eventsim.py) gets that interleaving in real execution, where
  Kahn-wave dispatch would re-introduce the head-of-line blocking the
  ordering was computed to avoid.  Makespan ends at ONE readback fence
  whose value depends on every device's last output, its fixed round-trip
  netted out (``utils/costmodel.readback_fence``); ``profile`` mode ends
  every task in ``block_until_ready`` instead, which is what
  ``utils/costmodel.calibrate`` times.

Works identically on a real TPU slice and on the CPU-faked 8-device mesh
(``--xla_force_host_platform_device_count``), which is how tests exercise
multi-device behavior without hardware — mirroring the reference's
in-process "multi-node" strategy (SURVEY.md §4).
"""

from __future__ import annotations
# dls-lint: allow-file(DET001) real-device execution timing: wall time IS the measured quantity

import functools
import time
import warnings
import weakref
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from ..core.cluster import Cluster
from ..core.graph import TaskGraph
from ..core.schedule import Schedule, TaskTiming
from ..obs import process_metrics
from ..obs.trace import (
    CAT_CALL,
    CAT_COLLECT,
    CAT_PLAN,
    CAT_SCHEDULE,
    CAT_STAGE,
    PhaseClock,
    annotate,
)


# what one ``execute`` call spends outside its dispatch loop, in call
# order; every call reports each (0.0 where the phase did not run), plus
# ``other_s`` for what none of them covers
_CALL_PHASES = (
    "order_s", "place_s", "plan_s", "warmup_s", "rtt_s", "fence_s",
    "report_s",
)


@dataclass
class DeviceReport:
    """Measured execution result for one placed DAG run."""

    policy: str
    makespan_s: float
    output: Any
    n_devices: int
    transfer_edges: int
    transfer_bytes: int
    param_bytes_placed: Dict[str, int]
    compile_s: float
    # only in profile mode: per-task measured wall times
    timings: Dict[str, TaskTiming] = field(default_factory=dict)
    # per-device HBM peaks, when the platform reports memory_stats
    peak_hbm_bytes: Dict[str, int] = field(default_factory=dict)
    # executable launches issued (== plan steps under planned dispatch,
    # a fused span counting once; == placed tasks on the per-task loop)
    n_dispatches: int = 0
    # host wall seconds spent inside the dispatch loop, per rep (launch +
    # staging; end-of-run fence excluded).  Launches return at enqueue, so
    # on async platforms this IS the host-side dispatch overhead the
    # planned path exists to shrink; on platforms where a launch can
    # block on device compute it is an upper bound.
    dispatch_overhead_s: float = 0.0
    # the call's wall time tiled into phases, always on.  Per rep, the
    # loop wall: planned dispatch reports {loop_s, stage_s (input
    # placement + batched transfers), launch_s}, with loop_s their sum;
    # the per-task loop reports {loop_s}.  Per call: order_s
    # (dispatch_order), place_s (place_params), plan_s (plan build),
    # warmup_s, rtt_s (the fence round-trip probe),
    # fence_s (the host waiting in the end-of-run fence: how far the
    # device is behind the host), report_s (memory stats, registry, this
    # report) and other_s = wall_s less all of these: see leaf_phases()
    dispatch_phases: Dict[str, float] = field(default_factory=dict)
    # host wall of the whole execute() call, entry to return
    wall_s: float = 0.0
    # True when the run used the pre-planned fast path (dispatch_plan)
    planned: bool = False
    # execute(keep_outputs=True): every executed task's output, retained
    # for elastic recovery.  Keys feed reschedule()/execute(ext_outputs=...)
    task_outputs: Dict[str, Any] = field(default_factory=dict)
    # execute(stream_params=True): streaming statistics.  ``streamed`` is
    # the explicit mode flag — a streamed run that happened to load zero
    # params still reports its (all-zero) stats, so the mode is always
    # distinguishable in the JSON
    streamed: bool = False
    param_loads: int = 0
    # batched transfer calls issued (<= param_loads: a task's missing
    # params go up in one device_put) and total bytes streamed — the
    # numerator of the host-link bandwidth bound
    param_load_calls: int = 0
    param_load_bytes: int = 0
    param_evictions: int = 0
    peak_param_bytes: Dict[str, int] = field(default_factory=dict)
    # memprof runs only: the memory doctor's per-device timeline summary
    # (obs/memprof.py) — peaks, watermark attribution buckets, and
    # platform reconciliation where memory_stats() reported
    memory: Optional[Dict[str, Any]] = None
    # traced runs only: (tracer, this execute's span window), what
    # ``attribution`` is computed from when it is first read
    attribution_source: Optional[Tuple[Any, Tuple[float, float]]] = field(
        default=None, repr=False, compare=False
    )

    @functools.cached_property
    def attribution(self) -> Optional[Dict[str, Any]]:
        """Traced runs only: the run doctor's measured critical-path
        summary (obs/attribution.py) over this execute's span window —
        makespan split into compute/transfer/dispatch/idle plus
        stragglers/bubbles.  Computed on first read, from the tracer as
        it is then: read it before the tracer's events are cleared.
        Diagnosis only — never fails."""
        if self.attribution_source is None:
            return None
        from ..obs.attribution import attribute_run

        tracer, window = self.attribution_source
        try:
            att = attribute_run(tracer, window=window)
            return att.summary() if att.critical_path else None
        except Exception:
            return None

    @property
    def total_param_gb_placed(self) -> float:
        return sum(self.param_bytes_placed.values()) / 1024**3

    def leaf_phases(self) -> Dict[str, float]:
        """The phases of ``dispatch_phases`` that tile ``wall_s`` (with
        ``reps=1`` they sum to it by construction): every phase but
        ``loop_s`` where the path splits the loop into ``stage_s`` and
        ``launch_s``."""
        split = "launch_s" in self.dispatch_phases
        return {
            k: v for k, v in self.dispatch_phases.items()
            if not (split and k == "loop_s")
        }

    def summary(self) -> Dict[str, Any]:
        return {
            "policy": self.policy,
            "makespan_ms": self.makespan_s * 1e3,
            "n_devices": self.n_devices,
            "transfer_edges": self.transfer_edges,
            "transfer_mb": self.transfer_bytes / 1024**2,
            "param_gb_placed": self.total_param_gb_placed,
            "compile_s": self.compile_s,
            "n_dispatches": self.n_dispatches,
            "wall_ms": self.wall_s * 1e3,
            "dispatch_overhead_ms": self.dispatch_overhead_s * 1e3,
            "dispatch_phases_ms": {
                k: v * 1e3 for k, v in self.dispatch_phases.items()
            },
            "planned": self.planned,
            "peak_hbm_gb": {
                k: v / 1024**3 for k, v in self.peak_hbm_bytes.items()
            },
            **(
                {
                    "param_loads": self.param_loads,
                    "param_load_calls": self.param_load_calls,
                    "param_load_mb": self.param_load_bytes / 1024**2,
                    "param_evictions": self.param_evictions,
                    "peak_param_gb": {
                        k: v / 1024**3
                        for k, v in self.peak_param_bytes.items()
                    },
                }
                if self.streamed
                else {}
            ),
            **(
                {"attribution": self.attribution}
                if self.attribution is not None
                else {}
            ),
            **(
                {"memory": self.memory}
                if self.memory is not None
                else {}
            ),
        }


def _array_bytes(x: Any) -> int:
    """Bytes of an array or an arbitrary pytree of arrays (train-step tasks
    exchange dicts of grads)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(x):
        try:
            total += leaf.size * leaf.dtype.itemsize
        except Exception:
            pass
    return total


class DeviceBackend:
    """Executes a scheduled TaskGraph on live JAX devices.

    ``cluster`` must be built with ``Cluster.from_jax_devices`` (each
    DeviceState carries its ``jax_device``); the schedule's placement maps
    task -> DeviceState -> real device.
    """

    def __init__(self, cluster: Cluster, pre_analysis: bool = True):
        missing = [d.node_id for d in cluster if d.jax_device is None]
        if missing:
            raise ValueError(
                f"cluster devices {missing} have no bound jax_device; "
                "build the cluster with Cluster.from_jax_devices()"
            )
        self.cluster = cluster
        # opt-out static pre-execution gate (see analysis/):
        # pre_analysis=False per instance, DLS_SKIP_ANALYSIS=1 globally
        self.pre_analysis = pre_analysis
        # fn object -> jitted fn; survives across execute() calls so
        # benchmark reruns don't pay compilation again
        self._jit_cache: Dict[Any, Callable[..., Any]] = {}
        # (fn object, donate_argnums, native positions) -> jitted variant
        # that donates and / or leaves its result's layout to the compiler;
        # separate from _jit_cache (which a fused launch traces through)
        # so tasks sharing one fn but dying-buffer patterns or readers
        # that differ never collide
        self._donate_jit_cache: Dict[
            Tuple[Any, Tuple[int, ...], Tuple[int, ...]], Any
        ] = {}
        # (launch structure, donate_argnums, native positions) -> jitted
        # fused launch
        # (dispatch_plan.launch_structure: member fn objects, in-run
        # wiring by position, exported positions).  No task id, graph or
        # parameter name is in the key or the value: layers, graphs and
        # execute() calls wired alike share one executable
        self._group_cache: Dict[Any, Callable[..., Any]] = {}
        # graph -> True when no task fn carries a jaxpr effect (host
        # callbacks): what lets execute() fuse launches by default; weak
        # so a dead graph releases its entry
        self._effect_free: "weakref.WeakKeyDictionary" = (
            weakref.WeakKeyDictionary()
        )
        # graph -> PreparedCall (dispatch_plan): what the planned path of
        # execute() derives from (graph, schedule, flags) and the placed
        # replicas of the caller's weights, kept between calls; one per
        # live graph (the latest call's), weak so a dead graph releases
        # its plan and its replicas
        self._prepared: "weakref.WeakKeyDictionary" = (
            weakref.WeakKeyDictionary()
        )
        # (fence device, its round-trip seconds), probed on first need
        self._fence_rtt: Optional[Tuple[Any, float]] = None
        # cumulative jit-cache hit/miss counts across every cache above;
        # execute() reports the per-call delta into the metrics registry
        # (obs) as compile.jit_cache_{hits,misses}
        self.jit_cache_hits = 0
        self.jit_cache_misses = 0

    def _fence_device(self):
        """The device the end-of-run fence reads back from."""
        return self.cluster.devices[0].jax_device

    def _gate_on(self) -> bool:
        """Whether this backend's calls run the pre-execution gate."""
        from ..analysis import gate_enabled

        return self.pre_analysis and gate_enabled()

    def _fence_round_trip(self) -> float:
        """The fence's round-trip on an idle device, probed once per
        backend (and again should the fence device change): it corrects
        ``DeviceReport.makespan_s`` by the same draw in every call."""
        dev = self._fence_device()
        if self._fence_rtt is None or self._fence_rtt[0] is not dev:
            from ..utils.costmodel import _fence_rtt

            self._fence_rtt = (dev, _fence_rtt(dev))
        return self._fence_rtt[1]

    def _fence_run(self, last_on_device: Dict[str, Any]) -> int:
        """Fence ALL dispatched work with ONE readback; returns the fence
        count (1) to subtract as RTT.

        One element of each device's last output is pulled onto the fence
        device and their (dependent) combination read back: the bytes
        cannot exist on the host before every contributing device's queue
        drained (per-device queues are FIFO), so the single readback
        proves completion everywhere — one RTT regardless of device
        count.  Per-device sequential fences would over-subtract when an
        early fence's round-trip overlaps a straggler device's remaining
        compute.  Deliberately NO ``block_until_ready`` on the outputs
        first: it would add host work the single-RTT correction does not
        net out, and it adds nothing — the dependent readback already
        implies completion.  Shared by the plan and the per-task loop so
        their makespan measurements cannot drift.
        """
        from ..utils.costmodel import readback_fence

        fence_dev = self._fence_device()
        tips = []
        for out in last_on_device.values():
            leaf = jax.tree_util.tree_leaves(out)[-1]
            tip = leaf[(0,) * leaf.ndim]
            tips.append(jax.device_put(tip, fence_dev))
        combined = tips[0]
        for t in tips[1:]:
            combined = combined + t.astype(combined.dtype)
        readback_fence(combined)
        return 1

    def _timed_fence(
        self, last_on_device: Dict[str, Any], tracer: Any,
    ) -> Tuple[int, float]:
        """:meth:`_fence_run` with the host's wait in it read, always:
        ``(n_fences, fence_s)``.  The wait is the time the device is
        behind the host when the dispatch loop ends.  Entered as the
        profiler annotation ``dls/fence``; with a tracer, the ``fence``
        leaf of the host track."""
        with annotate("fence"):
            t0 = time.perf_counter()
            n_fences = self._fence_run(last_on_device)
            t1 = time.perf_counter()
        if tracer is not None:
            tracer.complete(
                "fence", t0, t1, track="host", cat=CAT_COLLECT,
                devices=len(last_on_device),
            )
        return n_fences, t1 - t0

    # -- placement ---------------------------------------------------------
    def place_params(
        self,
        graph: TaskGraph,
        schedule: Schedule,
        params: Dict[str, Any],
        mem: Any = None,
    ) -> Tuple[Dict[Tuple[str, str], Any], Dict[str, int]]:
        """Put each param onto every device that runs a task needing it.

        Returns ``(param_name, node_id) -> on-device array`` plus the bytes
        placed per node.  A param needed on k devices is replicated k times —
        the physical realization of the reference's ``param_locations`` sets.
        """
        placement = schedule.placement
        placed: Dict[Tuple[str, str], Any] = {}
        bytes_per_node: Dict[str, int] = {d.node_id: 0 for d in self.cluster}
        for tid, node_id in placement.items():
            task = graph[tid]
            dev = self.cluster[node_id].jax_device
            for p in task.params_needed:
                key = (p, node_id)
                if key not in placed:
                    placed[key] = jax.device_put(params[p], dev)
                    nb = _array_bytes(params[p])
                    bytes_per_node[node_id] += nb
                    if mem is not None:
                        mem.alloc(node_id, f"param:{p}", nb, "params")
        # placed values may be pytrees (e.g. QParam int8+scale pairs), so
        # use the pytree-aware barrier
        jax.block_until_ready(list(placed.values()))
        return placed, bytes_per_node

    # -- parameter streaming ----------------------------------------------
    class _ParamStreamer:
        """On-demand parameter residency with eviction under a per-node HBM
        budget — the reference's param-cache/eviction model (reference
        ``schedulers.py:404-442``) made PHYSICAL: a node whose weights
        exceed its budget loads each param at first use and evicts
        residents to make room, so a model larger than a device's HBM
        still executes (slower — streaming trades bandwidth for capacity,
        exactly the constraint the scheduler's policies optimize around).

        Designed to approach the host-link bandwidth bound (an
        on-demand, fence-per-eviction version is latency-bound, because
        every eviction drains the whole device queue):

        * **Plan-aware prefetch**: the schedule's per-node task order is
          known up front (``plan``), so params for the next ``lookahead``
          tasks are loaded while current compute is in flight — loads
          overlap compute and each other instead of serializing.
        * **Belady eviction**: with the plan, the victim is the resident
          param whose next use is farthest in the future (optimal for
          misses); LRU is the planless fallback.
        * **Batched loads**: all of a task's missing params go up in ONE
          ``device_put`` call (one dispatch per task, not per param).
        * **Minimal-wait deletion**: an evicted buffer may still feed
          queued ops, so it enters a graveyard tagged with its last
          consumer's per-node FIFO step; freeing its memory waits only on
          that consumer's output (per-device queues are FIFO, so that one
          wait proves every earlier consumer finished), and a fence-step
          watermark makes waits on already-fenced steps free.  v1 instead
          fenced the node's LATEST output before every eviction — a full
          queue drain per load.

        The ``bytes`` ledger counts resident + graveyard (memory is not
        free until deletion), so ``peak`` stays physically honest.
        """

        def __init__(
            self,
            cluster: Cluster,
            params: Dict[str, Any],
            plan: Optional[Dict[str, List[Tuple[str, Tuple[str, ...]]]]] = None,
            lookahead: int = 8,
            mem: Any = None,
        ):
            self.cluster = cluster
            self.host_params = params
            # optional obs/memprof recorder: loads are param births,
            # graveyard flushes are the matching frees
            self.mem = mem
            self.resident: Dict[str, Dict[str, Any]] = {
                d.node_id: {} for d in cluster
            }
            self.bytes: Dict[str, int] = {d.node_id: 0 for d in cluster}
            self.peak: Dict[str, int] = {d.node_id: 0 for d in cluster}
            self.budget: Dict[str, int] = {
                d.node_id: int(d.total_memory * 1024**3) for d in cluster
            }
            self.last_use: Dict[str, Dict[str, int]] = {
                d.node_id: {} for d in cluster
            }
            # plan: node -> [(tid, param globals)] in dispatch order
            self.plan = plan or {}
            self.pos: Dict[str, int] = {n: -1 for n in self.plan}
            # node -> param -> ascending plan positions where it is used
            self.uses: Dict[str, Dict[str, List[int]]] = {}
            for n, entries in self.plan.items():
                u: Dict[str, List[int]] = {}
                for i, (_tid, globs) in enumerate(entries):
                    for g in globs:
                        u.setdefault(g, []).append(i)
                self.uses[n] = u
            self.lookahead = lookahead
            # eviction-safety bookkeeping (per node): monotonically
            # increasing dispatch step, last fenced step, each param's last
            # consumer (step, output), evicted-but-not-yet-freed buffers
            self.node_step: Dict[str, int] = {d.node_id: 0 for d in cluster}
            self.fenced_step: Dict[str, int] = {d.node_id: 0 for d in cluster}
            self.last_consumer: Dict[str, Dict[str, Tuple[int, Any]]] = {
                d.node_id: {} for d in cluster
            }
            self.graveyard: Dict[str, List[Tuple[int, Any, Any, int, str]]] = {
                d.node_id: [] for d in cluster
            }
            self.loads = 0
            self.load_calls = 0
            self.load_bytes = 0
            # loads that stalled a task's dispatch (param not resident at
            # get_task time) vs loads the prefetcher issued early — the
            # stall count is what latency-bound links actually pay for
            self.demand_misses = 0
            self.evictions = 0
            self._step = 0

        def note_task(self, node_id: str, globs, out: Any) -> None:
            """Record that a task consuming ``globs`` was dispatched with
            output ``out`` — the eviction fence anchor for those params."""
            self.node_step[node_id] += 1
            s = self.node_step[node_id]
            for g in globs:
                self.last_consumer[node_id][g] = (s, out)

        def _next_use(self, node_id: str, name: str) -> float:
            import bisect

            uses = self.uses.get(node_id, {}).get(name)
            if not uses:
                return float("inf")
            i = bisect.bisect_right(uses, self.pos.get(node_id, -1))
            return uses[i] if i < len(uses) else float("inf")

        def _flush(self, node_id: str, need_bytes: int) -> int:
            """Actually free graveyard memory, oldest consumer first, until
            ``need_bytes`` freed or the graveyard empties.  Waits only when
            an entry's consumer step is past the fence watermark — and then
            on that specific output, not the queue tip."""
            g = self.graveyard[node_id]
            g.sort(key=lambda e: e[0])
            freed = 0
            while g and freed < need_bytes:
                step, out, arr, nbytes, name = g.pop(0)
                if step > self.fenced_step[node_id] and out is not None:
                    jax.block_until_ready(out)
                    self.fenced_step[node_id] = step
                for leaf in jax.tree_util.tree_leaves(arr):
                    leaf.delete()
                self.bytes[node_id] -= nbytes
                freed += nbytes
                if self.mem is not None:
                    self.mem.free(node_id, f"param:{name}")
            return freed

        def _evict_one(
            self, node_id: str, pinned: set, horizon: Optional[int]
        ) -> int:
            """Move one victim to the graveyard.  Returns its bytes, 0 when
            nothing is evictable (only pinned residents), or -1 when the
            best victim is needed at/before ``horizon`` (prefetch would
            thrash — caller stops prefetching)."""
            res = self.resident[node_id]
            victims = [p for p in res if p not in pinned]
            if not victims:
                return 0
            if node_id in self.uses:
                victim = max(
                    victims, key=lambda p: self._next_use(node_id, p)
                )
                if (
                    horizon is not None
                    and self._next_use(node_id, victim) <= horizon
                ):
                    return -1
            else:
                lru = self.last_use[node_id]
                victim = min(victims, key=lambda p: lru.get(p, 0))
            arr = res.pop(victim)
            self.last_use[node_id].pop(victim, None)
            step, out = self.last_consumer[node_id].pop(victim, (0, None))
            nbytes = _array_bytes(arr)
            # bytes stay on the ledger until _flush deletes the buffer
            self.graveyard[node_id].append((step, out, arr, nbytes, victim))
            self.evictions += 1
            return nbytes

        def _load(self, node_id: str, names: List[str]) -> None:
            """ONE batched device_put for all of ``names``."""
            dev = self.cluster[node_id].jax_device
            # bridge through numpy: on CPU platforms device_put can ALIAS
            # the host buffer, and evicting an alias would delete the
            # caller's params out from under them; a numpy view forces the
            # device copy to own fresh memory, so delete() is always safe
            import numpy as _np

            hosts = [
                jax.tree_util.tree_map(
                    lambda leaf: _np.asarray(leaf), self.host_params[n]
                )
                for n in names
            ]
            arrs = jax.device_put(hosts, dev)
            self.load_calls += 1
            for n, a in zip(names, arrs):
                self.resident[node_id][n] = a
                # ledger from the PLACED bytes (dtype canonicalization can
                # make them differ from the host estimate; an asymmetric
                # ledger would drift and shrink the effective budget)
                nb = _array_bytes(a)
                self.bytes[node_id] += nb
                self.load_bytes += nb
                self.loads += 1
                self.last_use[node_id][n] = self._step
                if self.mem is not None:
                    self.mem.alloc(node_id, f"param:{n}", nb, "params")
            self.peak[node_id] = max(self.peak[node_id], self.bytes[node_id])

        def _ensure(
            self,
            node_id: str,
            names: List[str],
            pinned: set,
            horizon: Optional[int] = None,
        ) -> bool:
            """Make ``names`` resident, evicting/freeing as needed.  Returns
            False when stopped by the prefetch ``horizon`` (resident set is
            already needed sooner than the prefetch target)."""
            # dedupe: a fused task can alias two local names to one global
            # (fuse_linear_chains merges members sharing a param); loading
            # it twice would orphan a device buffer and inflate the ledger
            missing = list(dict.fromkeys(
                n for n in names if n not in self.resident[node_id]
            ))
            if not missing:
                return True
            need = sum(
                _array_bytes(self.host_params[n]) for n in missing
            )
            budget = self.budget[node_id]
            while self.bytes[node_id] + need > budget:
                deficit = self.bytes[node_id] + need - budget
                if self.graveyard[node_id]:
                    self._flush(node_id, deficit)
                    continue
                r = self._evict_one(node_id, pinned, horizon)
                if r == -1:
                    return False
                if r == 0:
                    if horizon is not None:
                        # prefetch must NEVER overshoot the budget: the
                        # over-budget escape exists for a task's own pinned
                        # params only (it cannot run without them); a
                        # speculative load has no such excuse
                        return False
                    break  # only the task's own params: allow over-budget
            self._load(node_id, missing)
            return True

        def get_task(self, tid: str, node_id: str, param_items) -> Dict[str, Any]:
            """Resident params for ``tid`` (loc -> array), then prefetch the
            next ``lookahead`` planned tasks' params into the budget."""
            self._step += 1
            items = tuple(param_items)
            names = [g for _, g in items]
            entries = self.plan.get(node_id)
            if entries is not None:
                # advance the plan cursor to this task; tasks skipped at
                # dispatch (failed upstreams) fall out of the walk
                i = self.pos[node_id] + 1
                while i < len(entries) and entries[i][0] != tid:
                    i += 1
                if i < len(entries):
                    self.pos[node_id] = i
            pinned = set(names)
            self.demand_misses += sum(
                1 for n in pinned if n not in self.resident[node_id]
            )
            self._ensure(node_id, names, pinned)
            for n in names:
                self.last_use[node_id][n] = self._step
            out = {loc: self.resident[node_id][g] for loc, g in items}
            if entries is not None:
                p = self.pos[node_id]
                stop = min(p + 1 + self.lookahead, len(entries))
                for j in range(p + 1, stop):
                    _t, globs = entries[j]
                    if not self._ensure(
                        node_id, list(globs), pinned | set(globs), horizon=j
                    ):
                        break
            return out

    # -- compilation -------------------------------------------------------
    def _jitted(self, graph: TaskGraph, tid: str,
                donate_argnums: Tuple[int, ...] = (),
                native_pos: Tuple[int, ...] = ()):
        """One jitted callable per distinct fn *object*: tasks that share a
        fn (all layers' ln1 via param_alias) share the jit wrapper, so the
        per-layer compile multiplicity disappears.  XLA still compiles one
        executable per placement device (input sharding is part of the
        cache key) — that per-device cost is inherent.

        ``donate_argnums`` (planned dispatch) selects a donating variant
        and ``native_pos`` (``(0,)``: a task has one result) one whose
        result keeps the layout the compiler writes it in
        (:class:`.dispatch_plan.NativeLaunch`), as for a fused launch
        (:meth:`_grouped_jitted`); cached per (fn, pattern, positions) so
        differing launches never collide; the empty pattern is the shared
        plain cache."""
        task = graph[tid]
        if task.fn is None:
            raise ValueError(
                f"task {tid!r} has no fn; this graph is schedule-only "
                "(synthetic DAGs execute on the simulated backend)"
            )
        if donate_argnums or native_pos:
            key = (task.fn, donate_argnums, native_pos)
            fn = self._donate_jit_cache.get(key)
            if fn is None:
                self.jit_cache_misses += 1
                if native_pos:
                    from .dispatch_plan import NativeLaunch

                    fn = NativeLaunch(task.fn, donate_argnums, None)
                else:
                    fn = jax.jit(task.fn, donate_argnums=donate_argnums)
                self._donate_jit_cache[key] = fn
            else:
                self.jit_cache_hits += 1
            return fn
        return self._jit_of(task.fn)

    def _jit_of(self, fn: Callable[..., Any]):
        """The shared plain jit of one task ``fn`` object (a per-task
        launch calls it; a fused launch traces through it, so the member
        is traced once however many structures hold it)."""
        jfn = self._jit_cache.get(fn)
        if jfn is None:
            self.jit_cache_misses += 1
            jfn = jax.jit(fn)
            self._jit_cache[fn] = jfn
        else:
            self.jit_cache_hits += 1
        return jfn

    def _grouped_jitted(
        self, key: Any, donate_argnums: Tuple[int, ...] = (),
        native_pos: Tuple[int, ...] = (),
    ):
        """Jitted fused launch (dispatch_plan) for one launch *structure*
        ``key = (fns, binds, export_positions)``: the members run in order
        inside ONE executable, ``optimization_barrier`` between them
        keeping per-task numerics bit-identical to separate launches.
        The exports at ``native_pos`` keep the layout the compiler writes
        them in (:class:`.dispatch_plan.NativeLaunch`); the others are
        handed over in the runtime's default.
        Cached per (structure, donate pattern, native positions), never
        per task ids: every launch wired alike — the same span one layer
        down, the next ``execute()``, another graph over the same fns —
        calls the same jit object, and ``compile.group_structures`` in
        ``obs.process_metrics()`` counts how many this backend built."""
        cache_key = (key, donate_argnums, native_pos)
        fn = self._group_cache.get(cache_key)
        if fn is None:
            from .dispatch_plan import NativeLaunch, _build_group_fn

            fns, binds, export_pos = key
            self.jit_cache_misses += 1
            fn = _build_group_fn(
                tuple(self._jit_of(f) for f in fns), binds, export_pos,
            )
            if native_pos:
                fn = NativeLaunch(fn, donate_argnums, tuple(
                    i in native_pos for i in range(len(export_pos))
                ))
            else:
                fn = jax.jit(fn, donate_argnums=donate_argnums or None)
            self._group_cache[cache_key] = fn
            process_metrics().gauge("compile.group_structures").set(
                len(self._group_cache)
            )
        else:
            self.jit_cache_hits += 1
        return fn

    def host_effect_free(
        self, graph: TaskGraph, params: Dict[str, Any], graph_input: Any,
        ext_outputs: Optional[Dict[str, Any]] = None,
    ) -> bool:
        """True when no task ``fn`` of ``graph`` carries a jaxpr effect.

        An effect (``jax.debug.callback``, ``io_callback``, an ordered
        print) is the one thing a fused launch cannot keep: inside one XLA
        program an unordered host callback has no per-launch ordering.
        Read from ``jax.make_jaxpr(fn).effects``, once per distinct ``fn``
        object (16 on the GPT-2 DAGs), against the output avals the graph
        carries (``Task.out_shape``; a task without one is shape-evaluated
        here).  Remembered per graph, so a second ``execute()`` pays a
        dictionary read."""
        known = self._effect_free.get(graph)
        if known is not None:
            return known
        from .dispatch_plan import _sds

        def sds(x: Any) -> Any:
            return jax.tree_util.tree_map(_sds, x)

        avals: Dict[str, Any] = {
            k: sds(v) for k, v in (ext_outputs or {}).items()
        }
        in_aval = sds(graph_input)
        seen: Dict[Any, bool] = {}
        for tid in graph.topo_order:
            task = graph[tid]
            if task.fn in seen and task.out_shape is not None:
                continue
            pd = {loc: sds(params[g]) for loc, g in task.param_items()}
            args = [
                avals[d] if d in avals else graph[d].out_shape
                for d in task.arg_tasks or task.dependencies
            ] or [in_aval]
            if task.fn not in seen:
                jaxpr, out = jax.make_jaxpr(task.fn, return_shape=True)(
                    pd, *args
                )
                seen[task.fn] = bool(jaxpr.effects)
            else:
                out = jax.eval_shape(task.fn, pd, *args)
            if task.out_shape is None:
                avals[tid] = out
        free = not any(seen.values())
        self._effect_free[graph] = free
        return free

    def warmup(
        self,
        graph: TaskGraph,
        schedule: Schedule,
        placed_params: Dict[Tuple[str, str], Any],
        graph_input: Any,
        ext_outputs: Optional[Dict[str, Any]] = None,
        streamer: Optional["DeviceBackend._ParamStreamer"] = None,
    ) -> float:
        """Compile every (fn, placement-device) combination ahead of time;
        returns seconds.

        Runs one full placed execution (outputs discarded) so jit caches are
        hot and subsequent ``execute`` timings measure execution, not
        compilation — the analog of XLA's compile-once/run-many contract.
        """
        t0 = time.perf_counter()
        self._run(
            graph, schedule, placed_params, graph_input, profile=False,
            ext_outputs=ext_outputs, streamer=streamer,
        )
        return time.perf_counter() - t0

    # -- dispatch order ----------------------------------------------------
    @staticmethod
    def dispatch_order(graph: TaskGraph, schedule: Schedule) -> List[str]:
        """Global dispatch linearization honoring per-node scheduled order.

        Per-device XLA streams execute enqueued ops FIFO, so within one node
        the emitted sequence must be exactly ``schedule.per_node[node]`` —
        that list is the policy's decided execution order (1F1B interleaving
        for the pipeline policy).  Across nodes, a task can only be
        dispatched after its producers (Python needs their output handles,
        though not their completion — dispatch is async).  Greedy merge:
        repeatedly emit, among node-queue heads whose deps are all emitted
        (or unplaced, i.e. failed), the one the scheduler assigned earliest.
        If per-node orders are mutually inconsistent (a cross-node ordering
        cycle — no valid policy output does this), the remainder falls back
        to topological order rather than deadlocking.
        """
        placement = schedule.placement
        topo_pos = {tid: i for i, tid in enumerate(graph.topo_order)}
        prio = {tid: i for i, tid in enumerate(schedule.assignment_order)}
        # filter each node's list against `placement` (which keeps the LAST
        # per_node match): a task erroneously present in two nodes' lists is
        # dispatched once, on the node placement says, never twice
        queues = {
            n: [t for t in lst if t in topo_pos and placement.get(t) == n]
            for n, lst in schedule.per_node.items()
            if lst
        }
        queues = {n: q for n, q in queues.items() if q}
        idx = {n: 0 for n in queues}
        emitted: set = set()
        order: List[str] = []

        def head_ready(n: str) -> bool:
            i = idx[n]
            if i >= len(queues[n]):
                return False
            t = queues[n][i]
            return all(
                d in emitted or d not in placement
                for d in graph[t].dependencies
            )

        total = sum(len(q) for q in queues.values())
        while len(order) < total:
            ready_nodes = [n for n in queues if head_ready(n)]
            if not ready_nodes:
                break  # inconsistent per-node orders: topo fallback below
            n = min(
                ready_nodes,
                key=lambda n: (
                    prio.get(
                        queues[n][idx[n]], topo_pos[queues[n][idx[n]]]
                    ),
                    topo_pos[queues[n][idx[n]]],
                ),
            )
            t = queues[n][idx[n]]
            idx[n] += 1
            emitted.add(t)
            order.append(t)
        order.extend(
            t for t in graph.topo_order if t in placement and t not in emitted
        )
        return order

    # -- execution ---------------------------------------------------------
    def _run(
        self,
        graph: TaskGraph,
        schedule: Schedule,
        placed_params: Dict[Tuple[str, str], Any],
        graph_input: Any,
        profile: bool,
        ext_outputs: Optional[Dict[str, Any]] = None,
        streamer: Optional["DeviceBackend._ParamStreamer"] = None,
        fence: bool = True,
        order: Optional[List[str]] = None,
        tracer: Any = None,
        metrics: Any = None,
        mem: Any = None,
    ) -> Tuple[
        Any, Dict[str, TaskTiming], int, int, int, int, Dict[str, Any],
        Dict[str, float],
    ]:
        placement = schedule.placement
        # obs: per-task spans on the device's track (profile timestamps
        # when available, host dispatch windows otherwise) and transfer
        # flow arrows; all behind None checks — disabled runs unchanged
        done_at: Optional[Dict[str, Tuple[str, float]]] = (
            {} if tracer is not None else None
        )
        # ext_outputs seed the value table: surviving outputs of an earlier
        # (partial) run whose producers are not in this graph — the elastic
        # recovery path (sched/elastic.py).  They count as transfers when
        # consumed (they arrive from outside the consuming core).
        outputs: Dict[str, Any] = dict(ext_outputs or {})
        n_ext = len(outputs)
        timings: Dict[str, TaskTiming] = {}
        transfer_edges = 0
        transfer_bytes = 0
        t_start = time.perf_counter()

        if order is None:
            order = self.dispatch_order(graph, schedule)
        # the shared graph input placed once per device, not once per root
        # task (64 roots on the flagship DAG re-placed the same array 64
        # times per rep)
        input_on: Dict[str, Any] = {}
        t_loop0 = time.perf_counter()
        for tid in order:
            if tid not in placement:
                continue  # failed task: skip (fail-and-continue semantics)
            task = graph[tid]
            node_id = placement[tid]
            dev = self.cluster[node_id].jax_device

            arg_ids = task.arg_tasks or task.dependencies
            if arg_ids and any(d not in outputs for d in arg_ids):
                continue  # upstream failed; propagate skip (BEFORE any
                # param loads: a skipped task must not evict live params)

            if streamer is not None:
                pd = streamer.get_task(tid, node_id, task.param_items())
            else:
                pd = {
                    loc: placed_params[(glob, node_id)]
                    for loc, glob in task.param_items()
                }

            flow_srcs = [] if tracer is not None else None
            if arg_ids:
                args = []
                for d in arg_ids:
                    x = outputs[d]
                    if placement.get(d) != node_id:
                        # cross-core edge: physical transfer (ICI on TPU)
                        transfer_edges += 1
                        nb = _array_bytes(x)
                        transfer_bytes += nb
                        x = jax.device_put(x, dev)
                        if tracer is not None:
                            flow_srcs.append((d, nb))
                        if metrics is not None:
                            metrics.counter(
                                "transfer.bytes."
                                f"{placement.get(d, 'ext')}->{node_id}",
                                unit="bytes",
                            ).inc(nb)
                        if mem is not None:
                            mem.alloc(
                                node_id, f"xfer:{d}", nb, "transfers"
                            )
                    args.append(x)
            else:
                inp = input_on.get(node_id)
                if inp is None:
                    inp = jax.device_put(graph_input, dev)
                    input_on[node_id] = inp
                    if mem is not None:
                        mem.alloc(
                            node_id, "input", _array_bytes(graph_input),
                            "activations",
                        )
                args = [inp]

            fn = self._jitted(graph, tid)
            if profile:
                t0 = time.perf_counter()
                out = fn(pd, *args)
                jax.block_until_ready(out)  # out may be a pytree (train DAG)
                t1 = time.perf_counter()
                timings[tid] = TaskTiming(
                    tid, node_id, t0 - t_start, t1 - t_start
                )
            else:
                if tracer is not None:
                    t0 = time.perf_counter()
                out = fn(pd, *args)
                if tracer is not None:
                    t1 = time.perf_counter()
            if tracer is not None:
                # profile mode: span == measured task wall; otherwise the
                # host dispatch window (launch returns at enqueue)
                tracer.complete(
                    tid, t0, t1, track=node_id,
                    cat="task" if profile else "launch",
                )
                done_at[tid] = (node_id, t1)
                for d, nb in flow_srcs:
                    src_pt = done_at.get(d)
                    if src_pt is not None:
                        tracer.flow(
                            "transfer", src_pt[0], src_pt[1], node_id, t0,
                            src=d, dst=tid, bytes=nb,
                        )
            outputs[tid] = out
            if mem is not None:
                mem.alloc(
                    node_id, f"out:{tid}", _array_bytes(out), "activations"
                )
            if streamer is not None:
                streamer.note_task(
                    node_id, [g for _, g in task.param_items()], out
                )

        loop_s = time.perf_counter() - t_loop0

        # fence ALL dispatched work (not just the topologically-last task:
        # multi-leaf graphs and skipped tails would otherwise under-measure).
        # Per-device queues are FIFO, so one fenced value per device proves
        # that device's whole queue drained (_fence_run combines them into
        # ONE readback).
        n_fences = 0
        fence_s = 0.0
        if len(outputs) > n_ext and fence:
            last_on_device: Dict[str, Any] = {}
            for tid in order:
                if tid in outputs:
                    last_on_device[placement[tid]] = outputs[tid]
            n_fences, fence_s = self._timed_fence(last_on_device, tracer)
        final = outputs.get(graph.topo_order[-1]) if graph.topo_order else None
        executed = {
            k: v for k, v in outputs.items()
            if not ext_outputs or k not in ext_outputs
        }
        return (
            final, timings, transfer_edges, transfer_bytes, n_fences,
            len(outputs) - n_ext, executed,
            {"loop_s": loop_s, "fence_s": fence_s},
        )

    def paged_decode_engine(
        self,
        graph: TaskGraph,
        schedule: Schedule,
        config: Any,
        weights: Dict[str, Any],
        pool: Any,
        slots: int,
        pages_per_seq: int,
        seg_steps: int = 8,
        trace: Any = None,
        metrics: Any = None,
        clock: Any = None,
        memprof: Any = None,
        flight: Any = None,
        attention_impl: Optional[str] = None,
        chunk_tokens: Optional[int] = None,
    ):
        """Continuous-batching paged decode engine over a SCHEDULED paged
        decode-step DAG (``frontend.build_paged_decode_dag``).

        Runs the same static pre-execution gate as :meth:`execute` (the
        DEC0xx decode-loop pass checks cache/page-table placement
        coherence) before composing the placed step, so a schedule that
        would mis-place the paged cache is rejected at build time, not
        discovered as garbage tokens.  ``pool`` is the host-side
        ``models.kv_pages.PagePool`` whose geometry must match the
        graph's pool params.
        """
        if self.pre_analysis:
            from ..analysis import pre_execution_gate

            pre_execution_gate(
                graph, self.cluster, schedule, backend="device"
            )
        from .decode_loop import PagedDecodeEngine

        return PagedDecodeEngine(
            graph, schedule, config, weights, pool,
            slots=slots, pages_per_seq=pages_per_seq, seg_steps=seg_steps,
            tracer=trace, metrics=metrics, clock=clock, memprof=memprof,
            flight=flight, attention_impl=attention_impl,
            chunk_tokens=chunk_tokens,
        )

    def execute(
        self,
        graph: TaskGraph,
        schedule: Schedule,
        params: Dict[str, Any],
        graph_input: Any,
        profile: bool = False,
        warmup: bool = True,
        ext_outputs: Optional[Dict[str, Any]] = None,
        keep_outputs: bool = False,
        stream_params: bool = False,
        stream_lookahead: int = 8,
        reps: int = 1,
        planned: Optional[bool] = None,
        coalesce: bool = True,
        donate: Optional[bool] = None,
        fence_rtt: Optional[float] = None,
        trace: Any = None,
        metrics: Any = None,
        memprof: Any = None,
        pre_report: Any = None,
    ) -> DeviceReport:
        """Place params, compile, run, measure.

        There are two ways to run a placed step.  The **plan**
        (:mod:`.dispatch_plan`, ``planned``, the default): an immutable
        launch plan built once (resolved executables, prebuilt param
        bindings, integer value-table indices, batched per-launch
        ``device_put`` staging), so the hot loop issues only
        cached-executable calls — one per fused same-device run
        (``coalesce`` below), not one per task.  The plan, the dispatch
        order, the gate's pass and the placed weights stay on the
        backend (``PreparedCall``, one per live graph): a call with the
        same graph, an equal ``schedule.signature()``, the same flags
        and input shapes builds nothing, and puts only the parameters
        whose ``params[name]`` is no longer the ``jax.Array`` placed (a
        host array is put every call); ``memprof`` calls start from
        nothing.  The **per-task loop** (:meth:`_run`, ``planned=False``):
        one launch a task, order and placement derived anew every call;
        kept because ``profile`` (per-task timing hooks) and
        ``stream_params`` (param residency changes mid-run) need it, and
        chosen for them when ``planned`` is ``None``.  Placement,
        dispatch order, transfer counting and the end-of-run fence are
        the same on both; outputs are bit-identical.

        ``pre_report``: a report ``analysis.analyze()`` just produced
        for this exact (graph, schedule) — the pre-execution gate then
        skips re-running its base passes (accepted only when the
        report's stamped schedule signature matches).

        ``fence_rtt`` supplies a pre-calibrated fence round-trip
        (seconds) in place of the backend's own, which is probed once
        per backend, on the first call that needs it.

        ``donate`` (planned only): donate intermediate buffers that die
        after their last same-device consumer via ``donate_argnums``.
        Default probes the platform (donation is honored on CPU and TPU);
        forced off by ``keep_outputs`` (retained outputs must outlive the
        run — passing ``donate=True`` with ``keep_outputs`` raises).

        ``coalesce`` (planned only, its default): launch runs of
        consecutive same-device tasks as ONE program each
        (:mod:`.dispatch_plan`, "fused launches"), with
        ``optimization_barrier`` between members so per-task outputs stay
        bit-identical: O(runs) launches a step where the per-task plan
        makes O(tasks), through executables keyed by a launch's
        *structure*, so every layer wired alike shares one.  Every span
        is fused, whether its structure occurs once in the plan or fifty
        times, unless a task ``fn`` carries host effects
        (``jax.debug.callback`` and kin, read from each distinct
        ``fn``'s jaxpr once: inside one XLA program an unordered callback
        loses its per-launch ordering) — such a graph keeps per-task
        launches.  ``False`` asks for per-task launches (the parity
        reference).
        ``keep_outputs`` and ``ext_outputs`` compose: every member is
        then exported.  ``DeviceReport.n_dispatches`` counts the launches.

        ``reps > 1`` dispatches the whole placed run ``reps`` times
        back-to-back and fences ONCE at the end; ``makespan_s`` is then
        the per-run amortized wall ``(total - fence_rtt) / reps``: one
        fence amortized over a long window makes the RTT correction's
        residual error negligible next to short programs.  Incompatible
        with ``profile`` (per-task fences) and ``stream_params`` (later
        reps would measure a warm param cache, not the cold streaming
        behavior under test).

        ``ext_outputs`` seeds task outputs produced OUTSIDE this graph —
        the elastic-recovery path (``sched/elastic.py``): a remainder
        graph's tasks may consume, via ``arg_tasks``, outputs of completed
        tasks that survived a node failure.  Keys are the external task
        ids; values are host or device arrays (transferred to the
        consuming core on use).

        ``keep_outputs=True`` retains per-task outputs on the report
        (``task_outputs``) so a LATER failure can recover without
        recomputation: pass the surviving subset to ``surviving_work``'s
        ``have_outputs`` and to the re-execution's ``ext_outputs``.
        Every executed task's output is kept (a fused launch then
        exports each member).  Costs device memory proportional to
        activations held.

        ``stream_params=True`` replaces up-front param placement with
        planned streaming under each node's ``total_memory`` budget
        (:class:`_ParamStreamer`): batched loads prefetched
        ``stream_lookahead`` tasks ahead of the dispatch cursor, Belady
        (farthest-next-use) eviction, and minimal-wait deletion — a node
        whose assigned weights exceed its HBM budget still executes,
        trading host-link bandwidth for capacity (the reference's
        param-cache eviction made physical) while loads overlap compute;
        a task whose own params exceed the budget runs over-budget with
        the peak recorded.  The report carries ``param_loads``/
        ``param_load_calls``/``param_load_bytes``/``param_evictions``/
        ``peak_param_bytes``.

        ``profile=True`` records per-task wall times via per-task
        ``block_until_ready`` (Gantt charts / diagnostics, and what
        ``utils/costmodel.calibrate`` times); serial, so each time
        includes the task's host dispatch.  ``profile=False`` measures
        makespan ending at a single combined readback fence, its
        round-trip netted out.

        ``trace`` / ``metrics`` attach an :class:`..obs.trace.Tracer` /
        :class:`..obs.metrics.MetricsRegistry` to this run: host phase
        spans (schedule / stage / plan / launch / collect), per-launch
        device-track spans, transfer flow arrows, per-edge byte counters,
        jit-cache hit/miss deltas, and makespan/overhead histograms.
        ``None`` (the default) falls back to the ambient pair when
        ``DLS_TRACE=1`` is set, else recording is fully disabled (the
        hot paths guard every record behind a ``None`` check).

        ``memprof`` attaches an :class:`..obs.memprof.MemoryProfiler`:
        the run records param staging, task-output births,
        donation-driven frees, transfer copies, and input staging as
        allocation events on per-device timelines, and the
        report carries ``memory`` (the profiler summary, platform
        ``memory_stats()`` peaks reconciled in where reported).  Warmup
        runs unrecorded, same as the tracer — only the timed reps land
        on the timeline.  Explicit only (no ambient fallback).
        """
        # the call's own wall starts here: checks, the pre-execution gate
        # and whatever else no phase below covers end up in ``other_s``
        t_call0 = time.perf_counter()
        if planned is None:
            planned = not (profile or stream_params)
        elif planned and (profile or stream_params):
            raise ValueError(
                "planned dispatch is incompatible with profile (per-task "
                "timing hooks) and stream_params (param residency changes "
                "mid-run)"
            )
        if not planned:
            coalesce = False
        if donate and keep_outputs:
            raise ValueError(
                "donate=True deletes dying intermediates; keep_outputs "
                "must retain them — drop one of the two"
            )
        if planned:
            from .dispatch_plan import donation_supported

            if donate is None:
                donate = donation_supported() and not keep_outputs
        elif donate:
            raise ValueError("donate=True requires the planned path")
        else:
            donate = False
        if reps < 1:
            raise ValueError(f"reps must be >= 1, got {reps}")
        if reps > 1 and (profile or stream_params):
            raise ValueError(
                "reps > 1 amortizes over identical repeated runs; profile "
                "mode fences per task and stream_params runs must start "
                "cold — measure those with reps=1"
            )
        # the planned path keeps what it derives from its arguments alone
        # (PreparedCall: order, plan, checks, the gate's pass, the placed
        # weights) on the backend.  The schedule's signature is computed
        # anew every call, so a schedule mutated in place misses; memprof
        # records placement itself, so it always starts from nothing
        prep = None
        prep_key = None
        gate_passed = False
        if planned and memprof is None:
            from .dispatch_plan import call_avals

            prep_key = (
                graph.version, schedule.signature(),
                tuple(ext_outputs or ()), donate, coalesce, keep_outputs,
                self._gate_on(),
                tuple((d.node_id, d.jax_device) for d in self.cluster),
                call_avals(graph_input, ext_outputs),
            )
            prep = self._prepared.get(graph)
            if prep is not None and prep.key != prep_key:
                prep = None
        if self.pre_analysis and not (prep is not None and prep.gate_passed):
            # ``pre_report``: a fresh ``analyze()`` report for this exact
            # schedule skips the duplicate base passes (signature-checked)
            from ..analysis import pre_execution_gate

            gate_report = pre_execution_gate(
                graph, self.cluster, schedule, backend="device",
                precomputed=pre_report,
            )
            # kept only when the gate itself ran every pass, and passed
            gate_passed = gate_report is not None and pre_report is None
        if prep is None:
            graph.freeze()
            no_fn = [t.task_id for t in graph if t.fn is None]
            if no_fn:
                raise ValueError(
                    f"tasks {no_fn[:3]} have no fn; this graph is "
                    "schedule-only (synthetic DAGs execute on the "
                    "simulated backend)"
                )
            graph_params = graph.unique_params()
        else:
            graph_params = prep.graph_params
        missing = sorted(graph_params - params.keys())
        if missing:
            raise ValueError(f"params missing for placement: {missing[:5]}")
        if prep is None and coalesce:
            coalesce = self.host_effect_free(
                graph, params, graph_input, ext_outputs
            )
        # obs: explicit trace=/metrics= win; else the DLS_TRACE ambient
        # pair; else None — and every instrumented path below guards on
        # None, so a disabled run records nothing and pays only the checks
        from ..obs import ambient_metrics, ambient_tracer

        tracer = trace if trace is not None else ambient_tracer()
        mreg = metrics if metrics is not None else ambient_metrics()
        jit_hits0 = self.jit_cache_hits
        jit_miss0 = self.jit_cache_misses
        # always on: the call tiles its own wall time into leaf phases
        # (about twenty clock reads a call, none per launch); with a
        # tracer each leaf is also a span of the host track, and the
        # leaves of one call do not overlap
        clock = PhaseClock(tracer, t0=t_call0)
        clock.seconds.update(dict.fromkeys(_CALL_PHASES, 0.0))
        ev_exec = None
        if tracer is not None:
            ev_exec = tracer.begin(
                "execute", cat=CAT_CALL, policy=schedule.policy, reps=reps,
            )
        # one linearization for the stream plan and every rep:
        # dispatch_order is a pure function of (graph, schedule) and
        # costs ~ms on 500-task DAGs
        with clock.phase("order_s", "dispatch_order", CAT_SCHEDULE) as a:
            order_once = (
                prep.order if prep is not None
                else self.dispatch_order(graph, schedule)
            )
            a["tasks"] = len(order_once)
        if stream_params:
            placed, bytes_per_node = {}, {d.node_id: 0 for d in self.cluster}
            # per-node dispatch plan for the streamer's prefetch + Belady
            # eviction: the schedule fixes each node's task order, so the
            # streamer knows exactly which params are needed next
            stream_plan = {}
            for tid in order_once:
                node = schedule.placement.get(tid)
                if node is None:
                    continue
                stream_plan.setdefault(node, []).append(
                    (tid, tuple(g for _, g in graph[tid].param_items()))
                )
        else:
            with clock.phase("place_s", "place_params", CAT_STAGE) as a:
                if prep_key is None:
                    placed, bytes_per_node = self.place_params(
                        graph, schedule, params, mem=memprof
                    )
                else:
                    if prep is None:
                        from .dispatch_plan import PreparedCall

                        # the entry this one replaces goes first, and its
                        # replicas with it: never two sets on the chips
                        self._prepared.pop(graph, None)
                        prep = PreparedCall(
                            prep_key, graph, schedule, order_once,
                            frozenset(graph_params),
                        )
                    try:
                        names_put = prep.place(params, self.cluster)
                    except BaseException:
                        # half placed (a put raised): not kept
                        self._prepared.pop(graph, None)
                        raise
                    placed = prep.placed
                    bytes_per_node = dict(prep.bytes_per_node)
                    a["put"] = names_put
                a["bytes"] = sum(bytes_per_node.values())

        # planned fast path: precompute the immutable dispatch plan at
        # warmup time (resolved executables, prebuilt param bindings,
        # slot-indexed staging, donation patterns) so the timed loop does
        # no per-task bookkeeping at all
        plan = None
        if planned:
            from .dispatch_plan import DispatchPlan

            with clock.phase("plan_s", "plan_build", CAT_PLAN) as a:
                plan = prep.plan if prep is not None else None
                if plan is None:
                    plan = DispatchPlan.build(
                        self, graph, schedule, order_once, placed,
                        ext_keys=tuple(ext_outputs or ()),
                        donate=donate, coalesce=coalesce,
                        keep_outputs=keep_outputs,
                    )
                    outcome = "structure_misses"
                    if prep is not None:
                        prep.plan = plan
                        self._prepared[graph] = prep
                elif names_put:
                    outcome = "placement_misses"
                else:
                    outcome = "hits"
                if prep is not None and gate_passed:
                    prep.gate_passed = True
                a["steps"] = len(plan.steps)

        compile_s = 0.0
        if warmup:
            # warmup runs untraced (its transfers/launches are compile
            # artifacts, not steady-state behavior); one host span
            # covers the whole compile window
            with clock.phase("warmup_s", "warmup", CAT_PLAN) as a:
                if plan is not None:
                    # one full planned execution: jits every resolved
                    # executable (donating variants and coalesced groups
                    # included) and fills the static transfer-byte table.
                    # XLA warns once per lowering when a donated buffer's
                    # shape matches no output; the donation is still honored
                    # (the buffer is freed), so the warning is noise here.
                    t0 = time.perf_counter()
                    with warnings.catch_warnings():
                        warnings.filterwarnings(
                            "ignore",
                            message="Some donated buffers were not usable",
                        )
                        plan.run(graph_input, ext_outputs, fence=True)
                    compile_s = time.perf_counter() - t0
                else:
                    # a throwaway streamer for the warmup pass: jit caches warm
                    # up, and the timed run's streamer starts cold (capacity
                    # misses are the thing being measured)
                    compile_s = self.warmup(
                        graph, schedule, placed, graph_input,
                        ext_outputs=ext_outputs,
                        streamer=(
                            self._ParamStreamer(
                                self.cluster, params, plan=stream_plan,
                                lookahead=stream_lookahead,
                            )
                            if stream_params else None
                        ),
                    )
                a["compile_s"] = compile_s

        # fence round-trip (outside the timed region): the backend's one
        # probe, or the caller's ``fence_rtt``, so every window is
        # corrected by the same draw
        if fence_rtt is not None:
            rtt = fence_rtt
        else:
            with clock.phase("rtt_s", "fence_rtt", CAT_COLLECT) as a:
                rtt = self._fence_round_trip()
                a["rtt_s"] = rtt

        streamer = (
            self._ParamStreamer(
                self.cluster, params, plan=stream_plan,
                lookahead=stream_lookahead, mem=memprof,
            )
            if stream_params else None
        )
        t0 = time.perf_counter()
        phases_total: Dict[str, float] = {}
        for r in range(reps):
            fence = r == reps - 1  # intermediate reps queue without fencing
            t_ph = time.perf_counter() if tracer is not None else 0.0
            if plan is not None:
                (
                    output, timings, tedges, tbytes, n_fences, n_disp,
                    touts, phases,
                ) = plan.run(
                    graph_input, ext_outputs, fence=fence,
                    tracer=tracer, metrics=mreg, mem=memprof,
                )
            else:
                (
                    output, timings, tedges, tbytes, n_fences, n_disp,
                    touts, phases,
                ) = self._run(
                    graph, schedule, placed, graph_input, profile,
                    ext_outputs, streamer, fence=fence, order=order_once,
                    tracer=tracer, metrics=mreg, mem=memprof,
                )
            for k, v in phases.items():
                phases_total[k] = phases_total.get(k, 0.0) + v
            if tracer is not None:
                # encloses the rep's leaves (stage_input, dispatch_loop,
                # fence), as ``execute`` encloses all: their own category
                tracer.complete(
                    f"rep{r}", t_ph, time.perf_counter(),
                    track="host", cat=CAT_CALL,
                    dispatches=n_disp, fenced=fence,
                )
        wall = time.perf_counter() - t0
        makespan = max((wall - n_fences * rtt) / reps, 1e-9)
        # the fence is per call (only the last rep fences); the loop and
        # its staging and launch halves stay per rep
        clock.seconds["fence_s"] = phases_total.pop("fence_s", 0.0)
        loop_s_total = phases_total.get("loop_s", 0.0)
        dispatch_overhead_s = loop_s_total / reps
        dispatch_phases = {k: v / reps for k, v in phases_total.items()}

        with clock.phase("report_s", "report", CAT_COLLECT):
            # per-device peaks where the platform reports them (TPU does; the
            # host platform returns None)
            peaks: Dict[str, int] = {}
            for d in self.cluster:
                stats = d.jax_device.memory_stats() or {}
                if "peak_bytes_in_use" in stats:
                    peaks[d.node_id] = int(stats["peak_bytes_in_use"])
            if memprof is not None:
                # platform truth where PJRT reports it; the profiler's
                # model-derived timeline stands alone elsewhere
                memprof.reconcile(peaks)

            if timings:
                schedule.timings = timings
            if mreg is not None:
                # per-rep counts are identical across reps, so the run totals
                # are a clean multiply; histograms get one sample per execute
                mreg.counter("dispatch.launches").inc(n_disp * reps)
                mreg.counter("dispatch.transfer_edges").inc(tedges * reps)
                mreg.counter("dispatch.transfer_bytes", unit="bytes").inc(
                    tbytes * reps
                )
                mreg.histogram("dispatch.overhead_s", unit="s").observe(
                    dispatch_overhead_s
                )
                mreg.histogram("execute.makespan_s", unit="s").observe(makespan)
                mreg.histogram("execute.compile_s", unit="s").observe(compile_s)
                mreg.counter("compile.jit_cache_hits").inc(
                    self.jit_cache_hits - jit_hits0
                )
                mreg.counter("compile.jit_cache_misses").inc(
                    self.jit_cache_misses - jit_miss0
                )
                if timings:
                    # profile mode: busy fraction per device over the measured
                    # span — the Gantt chart's utilization column as a gauge
                    span_end = max(t.finish for t in timings.values())
                    busy: Dict[str, float] = {}
                    for t in timings.values():
                        busy[t.node_id] = busy.get(t.node_id, 0.0) + t.duration
                    for n, b in busy.items():
                        mreg.gauge(f"device.utilization.{n}", unit="frac").set(
                            b / span_end if span_end > 0 else 0.0
                        )
            report = DeviceReport(
                policy=schedule.policy,
                makespan_s=makespan,
                output=output,
                n_devices=len(self.cluster),
                transfer_edges=tedges,
                transfer_bytes=tbytes,
                param_bytes_placed=bytes_per_node,
                compile_s=compile_s,
                timings=timings,
                peak_hbm_bytes=peaks,
                n_dispatches=n_disp,
                dispatch_overhead_s=dispatch_overhead_s,
                dispatch_phases=dispatch_phases,
                planned=plan is not None,
                task_outputs=touts if keep_outputs else {},
                streamed=streamer is not None,
                param_loads=streamer.loads if streamer else 0,
                param_load_calls=streamer.load_calls if streamer else 0,
                param_load_bytes=streamer.load_bytes if streamer else 0,
                param_evictions=streamer.evictions if streamer else 0,
                peak_param_bytes=dict(streamer.peak) if streamer else {},
                memory=memprof.summary() if memprof is not None else None,
            )
        if ev_exec is not None:
            tracer.end(ev_exec, makespan_s=makespan)
            # run doctor: this execute's span window (window filtering
            # keeps tracers that accumulated other runs correct).  Read
            # when ``report.attribution`` first is, not here: walking
            # the critical path of one 1,561-launch step cost several
            # untraced steps
            report.attribution_source = (
                tracer, (ev_exec["t0"], ev_exec["t1"]),
            )
        report.wall_s = clock.finish(timed_elsewhere=loop_s_total)
        dispatch_phases.update(clock.seconds)
        pm = process_metrics()
        for k, v in dispatch_phases.items():
            pm.histogram(f"execute.phase.{k}", unit="s").observe(v)
        pm.histogram("execute.wall_s", unit="s").observe(report.wall_s)
        if plan is not None:
            pm.histogram("execute.tasks_per_launch").observe(
                sum(len(st.tids) for st in plan.steps) / max(n_disp, 1)
            )
            pm.histogram("execute.native_layout_exports").observe(
                plan.native_layout_exports
            )
        row_form = getattr(graph, "attn_row_form_tasks", None)
        if row_form is not None:
            pm.histogram("execute.attn_row_form_tasks").observe(row_form)
        if prep is not None:
            pm.counter(f"execute.prepared.{outcome}").inc()
        return report
