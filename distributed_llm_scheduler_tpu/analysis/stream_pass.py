"""Pass 9 — static stream-safety prover for ``stream_params`` schedules.

The device backend streams parameters through a per-node HBM budget with
Belady eviction (``backends/device._ParamStreamer``).  How much a
schedule will have to evict is a static question about the residency
plan, answered here by replaying it symbolically — per node, in that
node's dispatch order, accumulating the first-use union of parameter
working sets against the same budget the streamer enforces
(``device.total_memory`` GB, sizes from the graph's authoritative
``param_size_gb`` table):

* ``STR001`` (info) — the node's full parameter union fits the budget:
  the streamer never evicts on this node (resident placement would do).
* ``STR002`` (warning) — the union overflows, but a nonempty prefix of
  the node's task order fits: eviction starts at the split point the
  payload carries.
* ``STR003`` (warning) — no useful prefix fits (the first
  parameter-bearing task already overflows): the node must evict from
  its very first task.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from ..core.cluster import Cluster
from ..core.graph import TaskGraph
from ..core.schedule import Schedule
from .diagnostics import AnalysisReport, Severity

_EPS = 1e-9


def _node_plan(
    graph: TaskGraph, schedule: Schedule, nid: str
) -> List[Tuple[str, Tuple[str, ...]]]:
    """(task, global-params) rows for one node, in its dispatch order —
    the same rows ``DeviceBackend.execute`` feeds ``_ParamStreamer``."""
    rows: List[Tuple[str, Tuple[str, ...]]] = []
    for tid in schedule.per_node.get(nid, []):
        if tid not in graph:
            continue
        rows.append(
            (tid, tuple(g for _, g in graph[tid].param_items()))
        )
    return rows


def analyze_streaming(
    graph: TaskGraph,
    cluster: Cluster,
    schedule: Schedule,
) -> AnalysisReport:
    """Classify every node's streaming residency plan (STR001–STR003)."""
    rep = AnalysisReport()
    for dev in cluster:
        nid = dev.node_id
        plan = _node_plan(graph, schedule, nid)
        if not plan:
            continue
        budget = dev.total_memory
        union: Dict[str, float] = {}
        total = 0.0
        # cumulative first-use union after each task; find the longest
        # fitting prefix and the full-union total in one walk
        prefix_len = 0
        prefix_gb = 0.0
        fits = True
        spill_task = None
        for i, (tid, globals_) in enumerate(plan):
            for g in globals_:
                if g not in union:
                    union[g] = graph.param_size_gb(g)
                    total += union[g]
            if fits and total <= budget + _EPS:
                prefix_len = i + 1
                prefix_gb = total
            elif fits:
                fits = False
                spill_task = tid
        if fits:
            rep.add(
                "STR001",
                Severity.INFO,
                f"{nid} streams {total:.2f} GB of params within its "
                f"{budget:.2f} GB budget: nothing is ever evicted",
                node=nid,
                data={"union_gb": total, "budget_gb": budget},
            )
        elif prefix_gb > 0.0:
            rep.add(
                "STR002",
                Severity.WARNING,
                f"{nid} needs {total:.2f} GB of params against a "
                f"{budget:.2f} GB budget; the first {prefix_len} task(s) "
                f"fit ({prefix_gb:.2f} GB), eviction starts at "
                f"{spill_task!r}",
                node=nid,
                task=spill_task,
                data={
                    "union_gb": total,
                    "budget_gb": budget,
                    "prefix_tasks": prefix_len,
                    "prefix_gb": prefix_gb,
                    "spill_task": spill_task,
                },
            )
        else:
            rep.add(
                "STR003",
                Severity.WARNING,
                f"{nid} must evict from its first parameter-bearing task "
                f"({spill_task!r}): {total:.2f} GB of params against "
                f"{budget:.2f} GB",
                node=nid,
                task=spill_task,
                data={
                    "union_gb": total,
                    "budget_gb": budget,
                    "spill_task": spill_task,
                },
            )
    return rep
