"""Group-pack policy: balanced group packing, equal groups in runs.

Born from a bench regime where the host link is slow next to compute:
with parameter loads dominating, makespan floors at the heaviest
device's param bytes, so the policy balances those bytes first and asks
for no contiguity that would cost balance:

1. bucket tasks by ``group`` (one weight-set per group, exactly the unit
   the reference's param-cache model revolves around — reference
   ``schedulers.py:63-76`` charges per-param load once per node);
2. pack groups onto devices, largest parameter footprint first, each onto
   the device minimizing the resulting param-union load time — classic
   LPT bin balancing with union-aware sizes.  A group tied to a table
   another device already holds goes to that device only while that is
   still the lighter union: at the placed benchmark's shape (GPT-2 medium,
   four chips) ``embed`` lands on core_0 and ``head`` on core_1, each chip
   with its own ``wte``, and the 24 equal ``layer_i`` are dealt out in turn
   — 4 / 4 / 8 / 8 of them, no two consecutive ones on one chip;
3. hand every class of groups LPT cannot tell apart back to the devices
   it chose as consecutive runs (:func:`make_runs_contiguous`): the same
   number on every device, so the same bytes, peaks and fits, but a
   microbatch leaves a chip once per run instead of once per layer.  On
   the wire a hop is cheap (ICI is two orders of magnitude faster than a
   host load); on the chip each hop costs the *host* a put and a launch
   (~0.16 and ~0.13-0.2 ms), and the host is what a placed step waits for:
   25 hops a microbatch were 196 launches and 200 puts a step, 3 hops are
   64 and 24 (PERF.md section 6, PR 43);
4. order execution with the dependency-aware event simulation
   (:mod:`.eventsim`), which recovers 1F1B-style interleaving from the
   DAG structure, and run every chain through
   (:func:`run_chains_through`): the simulation commits a node one task
   ahead, so two microbatches that are ready together come out of it in
   lockstep, a task of each in turn, and the first one's result reaches
   the next chip no sooner than the second's.

In compute-bound regimes it degrades toward plain load balancing — the
evaluator sweep keeps all policies comparable.  (The 21.6-against-23.3 ms
that earlier headers quoted for pack against greedy and pipeline were
cost-model replays under an estimated link, not speeds on a chip.)
"""

from __future__ import annotations

import heapq
from typing import Dict, List, Optional, Set, Tuple

from ..backends.sim import LinkModel
from ..obs import process_metrics
from .base import BaseScheduler, SchedulerRun
from .eventsim import dependency_aware_order
from .pipeline import _group_stats


def run_chains_through(graph, placement, order: List[str]) -> List[str]:
    """``order`` with every chain run to its end: after a task its node
    goes on with the earliest of its own tasks that reads it and needs
    nothing the node does not hold by then — results of its own tasks
    ordered already, and values of other nodes that one of those has read
    — and only then with what ``order`` has next.

    Placement is untouched, the result is still a topological order, and
    no task waits for a value its node was not waiting for already.  On a
    node that runs tasks one at a time a chain started is best finished:
    its last value leaves for the next node after the chain's own tasks,
    not after those of every chain interleaved with it.  It is also what
    lets the placed executor launch a microbatch's pass through a node's
    run of layers as programs of its own (``backends/dispatch_plan.
    _cut_runs`` closes a span where a chain ends)."""
    pos = {tid: i for i, tid in enumerate(order)}
    done: Set[str] = set()
    held: Set[Tuple[str, str]] = set()  # (node, value read from elsewhere)
    out: List[str] = []

    def holds(node: str, tid: str) -> bool:
        return all(
            x in done if placement[x] == node else (node, x) in held
            for x in graph[tid].dependencies if x in placement
        )

    for head in order:
        tid: Optional[str] = head
        while tid is not None and tid not in done:
            node = placement[tid]
            out.append(tid)
            done.add(tid)
            held.update(
                (node, x) for x in graph[tid].dependencies
                if placement.get(x, node) != node
            )
            ready = [
                d for d in graph.dependents(tid)
                if placement.get(d) == node and d not in done
                and holds(node, d)
            ]
            tid = min(ready, key=pos.__getitem__) if ready else None
    return out


def _group_readers(graph, gidx: Dict[str, int]) -> List[Set[int]]:
    """``readers[a]``: the groups (by index) a task of which reads a task
    of group ``a`` — the group-to-group edges of the graph."""
    readers: List[Set[int]] = [set() for _ in gidx]
    for t in graph.tasks():
        b = gidx[t.group or t.task_id]
        for d in t.dependencies:
            a = gidx[graph[d].group or d]
            if a != b:
                readers[a].add(b)
    return readers


def _graph_rank(readers: List[Set[int]]) -> List[int]:
    """Position of every group in the order the graph gives them: group B
    after A where B reads A, first appearance (the index) otherwise — Kahn's
    walk taking the lowest ready index; groups on a cycle (two groups whose
    tasks read each other) follow by index."""
    n = len(readers)
    waits = [0] * n
    for a in range(n):
        for b in readers[a]:
            waits[b] += 1
    ready = [g for g in range(n) if not waits[g]]
    heapq.heapify(ready)
    rank = [-1] * n
    k = 0
    while ready:
        a = heapq.heappop(ready)
        rank[a] = k
        k += 1
        for b in readers[a]:
            waits[b] -= 1
            if not waits[b]:
                heapq.heappush(ready, b)
    for g in range(n):
        if rank[g] < 0:
            rank[g] = k
            k += 1
    return rank


def make_runs_contiguous(
    dev_of: List[int], size: List[float], activ: List[float],
    gparams: List[Set[str]], readers: List[Set[int]],
) -> int:
    """Hand every class of interchangeable groups back to the devices LPT
    chose for it as consecutive runs; ``dev_of`` (group index -> device,
    -1 unplaced) is rewritten in place, the number of groups that moved
    returned.

    A class is the placed groups of one ``(size, activ)`` — parameter-union
    bytes and activation peak — none of whose parameters another group
    needs.  LPT cannot tell them apart: which device got which was decided
    by the sort index alone.  Every device keeps its COUNT of the class, so
    its parameter union keeps its bytes (the members' parameters are theirs
    alone and weigh the same), its activation peak keeps its value (the
    members' peaks are equal) and ``lg + max(dev_act, activ) <=
    total_memory`` holds on every device exactly as it held when LPT placed
    the last group there: per-device loads, peaks and fits are unchanged by
    construction, under unequal caps too.  A class on one device, or of one
    group, has nothing to hand back.

    The class's groups are taken in the graph's order (:func:`_graph_rank`)
    and the devices in index order, except that the device of a group
    outside the class that the first member reads comes first and the
    device of one that reads the last member comes last, where they hold
    members at all (both ties to the lower index).  A class the runs do
    not bring fewer cross-device group edges — groups side by side that
    read one another nowhere, vocabulary shards — keeps LPT's labels."""
    owners: Dict[str, int] = {}
    for ps in gparams:
        for p in ps:
            owners[p] = owners.get(p, 0) + 1
    classes: Dict[Tuple[float, float], List[int]] = {}
    for gi, d in enumerate(dev_of):
        if d >= 0 and all(owners[p] == 1 for p in gparams[gi]):
            classes.setdefault((size[gi], activ[gi]), []).append(gi)
    classes = {
        k: ms for k, ms in classes.items()
        if len({dev_of[gi] for gi in ms}) > 1
    }
    if not classes:
        return 0
    rank = _graph_rank(readers)
    read_by: List[List[int]] = [[] for _ in dev_of]
    for a, rs in enumerate(readers):
        for b in rs:
            read_by[b].append(a)
    moved = 0
    for members in classes.values():
        members.sort(key=rank.__getitem__)
        inside = set(members)
        count: Dict[int, int] = {}
        for gi in members:
            count[dev_of[gi]] = count.get(dev_of[gi], 0) + 1

        def neighbour(groups, taken: Optional[int]) -> Optional[int]:
            devs = [
                dev_of[x] for x in groups
                if x not in inside and dev_of[x] in count
                and dev_of[x] != taken
            ]
            return min(devs) if devs else None

        first = neighbour(read_by[members[0]], None)
        last = neighbour(readers[members[-1]], first)
        runs = [first] if first is not None else []
        runs += [d for d in sorted(count) if d not in (first, last)]
        if last is not None:
            runs.append(last)
        runs_of = list(dev_of)
        it = iter(members)
        for d in runs:
            for _ in range(count[d]):
                runs_of[next(it)] = d

        def crossing(at: List[int]) -> int:
            # the group edges at the class's members that cross devices
            # (an edge between two members is counted at its source)
            return sum(
                sum(at[gi] != at[b] for b in readers[gi] if dev_of[b] >= 0)
                + sum(
                    at[gi] != at[a] for a in read_by[gi]
                    if a not in inside and dev_of[a] >= 0
                )
                for gi in members
            )

        if crossing(runs_of) < crossing(dev_of):
            moved += sum(a != b for a, b in zip(dev_of, runs_of))
            dev_of[:] = runs_of
    return moved


class GroupPackScheduler(BaseScheduler):
    """Balanced group packing (LPT over param-union loads), interchangeable
    groups handed out as consecutive runs."""

    name = "pack"

    def __init__(self, link: Optional[LinkModel] = None):
        self.link = link or LinkModel()

    def plan(self, graph, devices) -> Dict[str, int]:
        """LPT group packing, then :func:`make_runs_contiguous`: group name
        -> device index (unplaceable groups absent), in LPT's placement
        order.  The refinement policy (:mod:`.refine`) reuses this as its
        search seed."""
        n_dev = len(devices)
        groups, compute, activ, gparams = _group_stats(graph)

        def union_gb(names: Set[str]) -> float:
            # sorted-name accumulation: deterministic and native-parity-safe
            return sum(graph.param_size_gb(p) for p in sorted(names))

        size = [union_gb(ps) for ps in gparams]
        dev_params: List[Set[str]] = [set() for _ in range(n_dev)]
        dev_act = [0.0] * n_dev
        dev_of = [-1] * len(groups)
        placed_order: List[int] = []
        # largest parameter footprint first (LPT), ties by group order
        order = sorted(range(len(groups)), key=lambda i: (-size[i], i))
        for gi in order:
            best_d, best_load = None, None
            for d in range(n_dev):
                lg = union_gb(dev_params[d] | gparams[gi])
                if (
                    lg + max(dev_act[d], activ[gi])
                    > devices[d].total_memory + 1e-9
                ):
                    continue
                if best_load is None or lg < best_load:
                    best_d, best_load = d, lg
            if best_d is None:
                continue  # group fits nowhere: its tasks fail below
            dev_of[gi] = best_d
            placed_order.append(gi)
            dev_params[best_d] |= gparams[gi]
            dev_act[best_d] = max(dev_act[best_d], activ[gi])

        readers = _group_readers(
            graph, {g: i for i, g in enumerate(groups)}
        )
        moved = make_runs_contiguous(dev_of, size, activ, gparams, readers)
        registry = process_metrics()
        registry.gauge("sched.pack.groups_made_contiguous").set(moved)
        registry.gauge("sched.pack.cross_node_group_edges").set(sum(
            dev_of[a] >= 0 and dev_of[b] >= 0 and dev_of[a] != dev_of[b]
            for a, rs in enumerate(readers) for b in rs
        ))
        return {groups[gi]: dev_of[gi] for gi in placed_order}

    def run_policy(self, run: SchedulerRun) -> None:
        self.commit(run, self.plan(run.graph, run.cluster.devices))

    def commit(self, run: SchedulerRun, placed: Dict[str, int]) -> None:
        """Assign tasks per the group placement, then order execution with
        the dependency-aware event simulation.

        Graceful degradation: a task whose group fit
        on no device whole — its param union exceeds every budget, the
        config-#5 pressure cliff — or whose planned device can no longer
        hold it is spilled through :meth:`spill_pick` instead of failed,
        so group packing degrades toward greedy per-task placement rather
        than zeroing out.  Completion-under-constraint is the reference's
        headline metric (reference ``simulation.py:418-563``)."""
        graph, devices = run.graph, run.cluster.devices
        for tid in graph.topo_order:
            task = graph[tid]
            if tid not in run.pending:
                continue
            if any(d in run.failed for d in task.dependencies):
                self.fail(run, task)
                continue
            # `placed` may be keyed by group (pack/refine plans) or by
            # task id (the search tier's task-level placements); a task
            # key always wins so search can split groups across devices
            d = placed.get(tid, placed.get(task.group or tid))
            if d is not None and self.can_fit(run, task, devices[d]):
                self.assign(run, task, devices[d])
                continue
            node = self.spill_pick(run, task, devices)
            if node is not None:
                self.assign(run, task, node)
            else:
                self.fail(run, task)

        # dependency-aware execution order (same post-pass as pipeline)
        placement = {
            tid: run.graph[tid].assigned_node for tid in run.assignment_order
        }
        speeds = {d.node_id: d.compute_speed for d in run.cluster}
        exec_order = dependency_aware_order(
            run.graph, placement, speeds, self.link,
            slices=run.cluster.slice_ids(),
        )
        exec_order = run_chains_through(run.graph, placement, exec_order)
        run.assignment_order[:] = exec_order
        pos = {tid: i for i, tid in enumerate(exec_order)}
        for nid, tids in run.per_node.items():
            tids.sort(key=lambda t: pos[t])

    def spill_pick(self, run: SchedulerRun, task, devices):
        """Singleton fallback for a task the group plan could not place:
        the device needing the fewest new param bytes that can fit it
        (locality keeps total load bounded under pressure), ties to the
        lower device index.  Deterministic — strict `<` improvement over
        an index-ascending scan — for native-engine parity."""
        best, best_req = None, None
        for node in devices:
            req = self.memory_requirement(run, task, node)
            if req > node.available_memory + 1e-9:
                continue
            if best_req is None or req < best_req:
                best, best_req = node, req
        return best
