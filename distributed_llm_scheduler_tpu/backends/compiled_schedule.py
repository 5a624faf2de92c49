"""Whole-program per-device lowering: the schedule becomes ONE launch.

The execution ladder so far interprets the schedule at ever coarser
granularity — per-task launches (``_run``), pre-planned launches
(:mod:`.dispatch_plan`), fused same-device segments
(``_run_segmented``) — but every rung still mediates cross-device edges
on the host and pays at least one launch per segment.  This module takes
the last step (ROADMAP "compile the schedule"): the **entire** placed
run lowers into a single jitted program whose cross-device edges are
in-program collectives, so the host issues O(devices) staging puts plus
ONE launch per run, and XLA owns overlap along the whole critical path.

Lowering model (MPMD inside SPMD):

* The participating devices form a 1-D mesh (axis ``"dev"``, mesh order
  = cluster order).  The program is SPMD over that mesh via
  ``jax.shard_map``.
* Per-device heterogeneous compute is a ``lax.switch`` on
  ``lax.axis_index``: phase ``p``'s branch for device ``d`` runs exactly
  device ``d``'s phase-``p`` tasks (each task's computation pinned as
  its own fusion island with ``optimization_barrier``, the same
  bit-identity guarantee as coalesced launches) and returns ``zeros``
  placeholders for other devices' exports, so all branches are
  shape-uniform.  Each task appears in exactly one branch — program size
  stays O(tasks), not O(tasks x devices).
* Cross-device edges are ``lax.ppermute`` point-to-point hops at phase
  boundaries, in the deterministic order fixed by the
  :class:`..sched.linearize.ProgramIR`.  Every device emits every
  collective in the same order (SPMD), so the global collective order is
  deadlock-free by construction — the property the COL00x pass
  (analysis/collective_pass.py) verifies and the pre-execution gate
  enforces.  A received value replaces the consumer's ``zeros`` register
  via an elementwise select (never arithmetic), keeping it bit-exact.
* Parameters load as per-device **slabs**: each device's params flatten
  (per dtype) into one contiguous vector, padded to the mesh-wide max
  and stacked into a ``(n_dev, max)`` array sharded ``P("dev")`` — per-
  device memory stays O(that device's params), not O(model).  Branches
  rebuild their params by static slice+reshape (bytes unchanged, bit-
  exact) behind one ``optimization_barrier``, so task numerics cannot be
  perturbed by fusion into the slab reads.
* Donation: with ``donate=True`` the staged graph-input buffers are
  donated to the program (re-staged per rep); params and the slabs are
  never donated — the "whole-program donation vector" is exactly the
  per-run transient state, which is what makes repeated runs safe.

Semantics note: XLA owns the program, so a value feeding neither the
final output, an exchange, nor the end-of-run fence tip may be
dead-code-eliminated — unlike the interpreted rungs, which dispatch
every placed task.  The DAGs this repo executes route every task into
the final logits, so the distinction is theoretical there.

The single-participating-device special case (every task on one core —
the bench's single-chip legs) skips the mesh entirely: one plain jitted
program with the same per-task barriers.
"""

from __future__ import annotations
# dls-lint: allow-file(DET001) compiled-path timing harness: wall time IS the measured quantity

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.graph import TaskGraph
from ..core.schedule import Schedule
from ..sched.linearize import ProgramIR, linearize
from .rebatch import extract_steps
from .dispatch_plan import propagate_avals


def _leaf_bytes(aval_tree: Any) -> int:
    return sum(
        int(np.prod(s.shape)) * s.dtype.itemsize
        for s in jax.tree_util.tree_leaves(aval_tree)
    )


def _zeros_of(aval_tree: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), aval_tree
    )


def _input_sig(graph_input: Any) -> Tuple:
    """Structural identity of the graph input (treedef + leaf avals) —
    part of the program signature because the lowered program bakes
    placeholder shapes at trace time."""
    leaves, treedef = jax.tree_util.tree_flatten(graph_input)
    return (
        str(treedef),
        tuple(
            (tuple(np.asarray(l).shape), np.asarray(l).dtype.str)
            for l in leaves
        ),
    )


@dataclass
class CompiledSchedule:
    """One whole-program executable for a placed schedule.

    Build with :meth:`build`; run with :meth:`run` (same return contract
    as the other execution paths).  ``signature()`` is the deterministic
    lowering identity: equal signatures mean structurally identical
    programs (same phases, exchanges, slab layouts, donation).
    """

    backend: Any
    graph: TaskGraph
    ir: ProgramIR
    donate: bool
    n_devices: int
    param_bytes_per_node: Dict[str, int]
    transfer_edges: int
    transfer_bytes: int
    _fn: Any = field(repr=False, default=None)
    _slabs: Tuple[Any, ...] = field(repr=False, default=())
    _in_treedef: Any = field(repr=False, default=None)
    _in_shardings: Tuple[Any, ...] = field(repr=False, default=())
    _final_tid: Optional[str] = None
    _final_treedef: Any = field(repr=False, default=None)
    _owner_index: int = 0
    _tip_nodes: Tuple[str, ...] = ()
    _mesh: Any = field(repr=False, default=None)
    _signature: Tuple = ()
    _single_device: Any = field(repr=False, default=None)
    # static memory-profiler tables: (dst_node, tid, bytes) per exchange,
    # and the final output's (node, bytes) — avals are not retained, so
    # the sizes are frozen at build time
    _exchange_table: Tuple = ()
    _final_out: Tuple = ()

    # -- construction ------------------------------------------------------

    @classmethod
    def build(
        cls,
        backend: Any,
        graph: TaskGraph,
        schedule: Schedule,
        params: Dict[str, Any],
        graph_input: Any,
        donate: bool = False,
        pre_analysis: bool = True,
        pre_report: Any = None,
    ) -> "CompiledSchedule":
        """Lower ``schedule`` over ``backend``'s cluster.

        Raises :class:`..analysis.AnalysisError` when the per-node orders
        admit no global collective order (COL002 — always fatal: there is
        no program to build) or, when the gate is enabled, when the
        collective-ordering pass rejects the lowered IR (COL001/COL004).
        """
        from ..analysis import (
            AnalysisError,
            analyze_schedule_lowerability,
            gate_enabled,
            pre_execution_gate,
        )

        graph.freeze()
        device_order = [d.node_id for d in backend.cluster]
        rep, ir = analyze_schedule_lowerability(
            graph, schedule, device_order=device_order
        )
        if ir is None:
            raise AnalysisError(rep)  # COL002: unlowerable, gate or not
        if pre_analysis and gate_enabled():
            pre_execution_gate(
                graph, backend.cluster, schedule, backend="device",
                program=ir, precomputed=pre_report,
            )
        if not ir.order:
            raise ValueError(
                "schedule places no executable tasks; nothing to lower"
            )
        avals = propagate_avals(graph, ir.order, params, graph_input)
        tbytes = sum(
            _leaf_bytes(avals[ex.tid])
            for ph in ir.phases
            for ex in ph.exchanges
        )
        self = cls(
            backend=backend,
            graph=graph,
            ir=ir,
            donate=donate,
            n_devices=len(ir.devices),
            param_bytes_per_node={},
            transfer_edges=ir.n_exchanges,
            transfer_bytes=tbytes,
        )
        self._exchange_table = tuple(
            (ex.dst, ex.tid, _leaf_bytes(avals[ex.tid]))
            for ph in ir.phases
            for ex in ph.exchanges
        )
        if len(ir.devices) == 1:
            self._build_single(params, graph_input, avals)
        else:
            self._build_mesh(params, graph_input, avals)
        if self._final_tid is not None:
            owner = ir.devices[self._owner_index]
            self._final_out = (
                self._final_tid, owner, _leaf_bytes(avals[self._final_tid])
            )
        if pre_analysis and gate_enabled():
            # donation invariant (analysis/donation_pass): the donation
            # vector must cover only per-run transient inputs — donating
            # the aliased param slab would corrupt every later rep
            from ..analysis.donation_pass import analyze_donation

            analyze_donation(self).raise_if_errors()
        return self

    def donation_summary(self) -> Dict[str, Any]:
        """Static donation metadata for ``analysis/donation_pass``: which
        jit argument positions hold the (aliased, rep-crossing) param
        slabs, which hold the per-run transient input leaves, and which
        the program donates."""
        if self._single_device is not None:
            # program(placed_params, x): donation covers the graph input
            return {
                "path": "single",
                "param_argnums": (0,),
                "input_argnums": (1,),
                "donated_argnums": (1,) if self.donate else (),
            }
        n_in = len(self._in_shardings)
        return {
            "path": "mesh",
            "param_argnums": (0,),  # the dtype-keyed slab tuple
            "input_argnums": tuple(range(1, 1 + n_in)),
            "donated_argnums": (
                tuple(range(1, 1 + n_in)) if self.donate else ()
            ),
        }

    def _needed_globals(self, node: str) -> List[str]:
        """Ordered dedupe of the param globals ``node``'s tasks read."""
        seen: Dict[str, None] = {}
        for ph in self.ir.phases:
            for tid in ph.compute.get(node, ()):
                for _, g in self.graph[tid].param_items():
                    seen.setdefault(g)
        return list(seen)

    # -- single-device lowering -------------------------------------------

    def _build_single(
        self, params: Dict[str, Any], graph_input: Any, avals: Dict[str, Any]
    ) -> None:
        node = self.ir.devices[0]
        dev = self.backend.cluster[node].jax_device
        self._single_device = dev
        globs = self._needed_globals(node)
        placed = {g: jax.device_put(params[g], dev) for g in globs}
        jax.block_until_ready(list(placed.values()))
        self.param_bytes_per_node = {
            node: sum(_leaf_bytes(placed[g]) for g in globs)
        }
        final_tid = (
            self.graph.topo_order[-1]
            if self.graph.topo_order
            and self.graph.topo_order[-1] in set(self.ir.order)
            else self.ir.order[-1]
        )
        self._final_tid = final_tid
        self._tip_nodes = (node,)
        self._slabs = (placed,)
        self._signature = (
            "single", node, self.ir.signature(), tuple(globs), self.donate,
            _input_sig(graph_input),
        )
        cache = self.backend._prog_cache.setdefault(self.graph, {})
        cached = cache.get(self._signature)
        if cached is not None:
            self.backend.jit_cache_hits += 1
            self._fn = cached
            return
        self.backend.jit_cache_misses += 1

        steps = extract_steps(self.graph, self.ir.order)
        last_tid = self.ir.order[-1]

        def program(pvals, x):
            vals: Dict[str, Any] = {}
            for tid, fn, pitems, aids in steps:
                pd = {loc: pvals[g] for loc, g in pitems}
                args = [vals[d] for d in aids] if aids else [x]
                vals[tid] = jax.lax.optimization_barrier(fn(pd, *args))
            tip_leaf = jax.tree_util.tree_leaves(vals[last_tid])[-1]
            tip = tip_leaf.reshape(-1)[:1].astype(jnp.float32)
            return vals[final_tid], tip

        donate_argnums = (1,) if self.donate else ()
        self._fn = jax.jit(program, donate_argnums=donate_argnums)
        cache[self._signature] = self._fn

    # -- mesh lowering -----------------------------------------------------

    def _build_mesh(
        self, params: Dict[str, Any], graph_input: Any, avals: Dict[str, Any]
    ) -> None:
        ir = self.ir
        graph = self.graph
        devices = ir.devices
        n_dev = len(devices)
        jax_devs = [self.backend.cluster[d].jax_device for d in devices]
        mesh = Mesh(np.array(jax_devs), ("dev",))
        self._mesh = mesh
        dix = ir.device_index

        # ---- parameter slabs: per-device per-dtype flat concat -----------
        # layout[node][g] = (treedef, ((dtype_key, offset, size, shape),))
        layout: Dict[str, Dict[str, Tuple[Any, Tuple]]] = {}
        parts: Dict[str, Dict[str, List[np.ndarray]]] = {}
        sizes: Dict[str, Dict[str, int]] = {}
        bytes_per_node: Dict[str, int] = {}
        sig_layout = []
        for node in devices:
            layout[node] = {}
            parts[node] = {}
            sizes[node] = {}
            bytes_per_node[node] = 0
            for g in self._needed_globals(node):
                leaves, treedef = jax.tree_util.tree_flatten(params[g])
                entries = []
                for leaf in leaves:
                    arr = np.asarray(leaf)
                    key = arr.dtype.str
                    off = sizes[node].setdefault(key, 0)
                    parts[node].setdefault(key, []).append(arr.reshape(-1))
                    sizes[node][key] = off + arr.size
                    bytes_per_node[node] += arr.nbytes
                    entries.append((key, off, arr.size, tuple(arr.shape)))
                layout[node][g] = (treedef, tuple(entries))
                sig_layout.append((node, g, tuple(entries)))
        self.param_bytes_per_node = bytes_per_node

        dtype_keys = sorted({k for s in sizes.values() for k in s})
        slab_sharding = NamedSharding(mesh, P("dev"))
        slabs = []
        for key in dtype_keys:
            b_max = max(
                (sizes[n].get(key, 0) for n in devices), default=0
            )
            b_max = max(b_max, 1)
            rows = []
            for i, node in enumerate(devices):
                row = np.zeros((b_max,), dtype=np.dtype(key))
                chunks = parts[node].get(key)
                if chunks:
                    flat = np.concatenate(chunks)
                    row[: flat.size] = flat
                rows.append(
                    jax.device_put(row.reshape(1, b_max), jax_devs[i])
                )
            slabs.append(
                jax.make_array_from_single_device_arrays(
                    (n_dev, b_max), slab_sharding, rows
                )
            )
        jax.block_until_ready(slabs)
        self._slabs = tuple(slabs)
        key_pos = {k: i for i, k in enumerate(dtype_keys)}

        # ---- input staging layout ----------------------------------------
        in_leaves, in_treedef = jax.tree_util.tree_flatten(graph_input)
        self._in_treedef = in_treedef
        in_shardings = []
        for leaf in in_leaves:
            nd = np.asarray(leaf).ndim
            in_shardings.append(
                NamedSharding(mesh, P("dev", *([None] * nd)))
            )
        self._in_shardings = tuple(in_shardings)
        n_in = len(in_leaves)

        # ---- program body -------------------------------------------------
        ordered = set(ir.order)
        final_tid = (
            graph.topo_order[-1]
            if graph.topo_order and graph.topo_order[-1] in ordered
            else ir.order[-1]
        )
        self._final_tid = final_tid
        self._final_treedef = jax.tree_util.tree_structure(avals[final_tid])
        placed_on = {
            t: n for ph in ir.phases for n, ts in ph.compute.items()
            for t in ts
        }
        self._owner_index = dix[placed_on[final_tid]]
        self._tip_nodes = devices
        self._signature = (
            "mesh", devices, ir.signature(), tuple(sig_layout),
            tuple(dtype_keys), self.donate, _input_sig(graph_input),
        )
        cache = self.backend._prog_cache.setdefault(graph, {})
        cached = cache.get(self._signature)
        if cached is not None:
            self.backend.jit_cache_hits += 1
            self._fn = cached
            return
        self.backend.jit_cache_misses += 1

        last_tid = {}
        for tid in ir.order:
            last_tid[placed_on[tid]] = tid

        # static per-(phase, device) step tables; extracted once so the
        # traced closures never capture the graph
        phase_steps = {
            (ph.index, node): extract_steps(graph, ph.compute.get(node, ()))
            for ph in ir.phases
            for node in devices
        }
        reconstruct_layout = layout

        def rebuild_params(node: str, globs_needed: List[str], slabs_local):
            out = {}
            for g in globs_needed:
                treedef, entries = reconstruct_layout[node][g]
                leaves = [
                    jax.lax.dynamic_slice_in_dim(
                        slabs_local[key_pos[key]][0], off, size
                    ).reshape(shape)
                    for key, off, size, shape in entries
                ]
                out[g] = jax.tree_util.tree_unflatten(treedef, leaves)
            return out

        ir_phases = ir.phases
        live_out = ir.live_out

        def program(slabs_local, *in_leaf_local):
            idx = jax.lax.axis_index("dev")
            x_local = jax.tree_util.tree_unflatten(
                in_treedef, [leaf[0] for leaf in in_leaf_local]
            )
            regs: Dict[str, Any] = {}
            for ph in ir_phases:
                exports = live_out.get(ph.index, ())
                if exports:
                    branches = []
                    for node in devices:
                        branches.append(
                            _make_branch(
                                phase_steps[(ph.index, node)],
                                node, exports, regs, slabs_local,
                                x_local, avals, rebuild_params, graph,
                            )
                        )
                    outs = jax.lax.switch(idx, branches, jnp.int32(0))
                    for tid, val in zip(exports, outs):
                        regs[tid] = val
                for ex in ph.exchanges:
                    src_i, dst_i = dix[ex.src], dix[ex.dst]
                    old = regs[ex.tid]
                    recv = jax.tree_util.tree_map(
                        lambda v: jax.lax.ppermute(
                            v, "dev", ((src_i, dst_i),)
                        ),
                        old,
                    )
                    keep_old = idx != jnp.int32(dst_i)
                    regs[ex.tid] = jax.tree_util.tree_map(
                        lambda o, r: jnp.where(keep_old, o, r), old, recv
                    )
            # fence tip: each device's last computed value, one element
            def make_tip(node):
                def tip(_):
                    t = last_tid.get(node)
                    if t is None:
                        return jnp.zeros((1,), jnp.float32)
                    leaf = jax.tree_util.tree_leaves(regs[t])[-1]
                    return leaf.reshape(-1)[:1].astype(jnp.float32)
                return tip

            tip = jax.lax.switch(
                idx, [make_tip(n) for n in devices], jnp.int32(0)
            )
            outs = [jnp.expand_dims(tip, 0)]
            fin_leaves = jax.tree_util.tree_leaves(regs[final_tid])
            outs.extend(jnp.expand_dims(l, 0) for l in fin_leaves)
            return tuple(outs)

        from jax import shard_map

        in_specs = (
            tuple(P("dev") for _ in dtype_keys),
            *(
                P("dev", *([None] * np.asarray(l).ndim))
                for l in in_leaves
            ),
        )
        # outputs: the (1,) fence tip, then every final-value leaf; each
        # gains a leading "dev" axis via the local expand_dims above
        out_ranks = [1] + [
            len(s.shape)
            for s in jax.tree_util.tree_leaves(avals[final_tid])
        ]
        out_specs = tuple(
            P("dev", *([None] * nd)) for nd in out_ranks
        )
        mapped = shard_map(
            program,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )
        donate_argnums = (
            tuple(range(1, 1 + n_in)) if self.donate else ()
        )
        self._fn = jax.jit(mapped, donate_argnums=donate_argnums)
        cache[self._signature] = self._fn

    # -- identity ----------------------------------------------------------

    def signature(self) -> Tuple:
        return self._signature

    # -- execution ---------------------------------------------------------

    @property
    def n_launches_per_run(self) -> int:
        """Host calls per run: one staging put per input leaf (each a
        single sharded ``device_put``) plus the program launch."""
        n_in = (
            len(jax.tree_util.tree_leaves(self._in_shardings))
            if self._single_device is None else 1
        )
        return n_in + 1

    def run(
        self,
        graph_input: Any,
        fence: bool = True,
        tracer: Any = None,
        metrics: Any = None,
        mem: Any = None,
    ) -> Tuple[
        Any, Dict, int, int, int, int, Dict[str, Any], Dict[str, float]
    ]:
        """Stage, launch, (optionally) fence.  Same 8-tuple contract as
        ``DispatchPlan.run`` / ``_run_segmented``.

        ``mem`` (obs.memprof.MemoryProfiler, optional): the compiled path
        has no per-task host boundaries, so its memory events are the
        build-time model — per-node param slabs, per-node input staging,
        the static per-exchange transfer table, and the final output —
        recorded once per run (labels replace across reps)."""
        t0 = time.perf_counter()
        if self._single_device is not None:
            x = jax.device_put(graph_input, self._single_device)
            t_stage = time.perf_counter()
            final, tip = self._fn(self._slabs[0], x)
            n_disp = 2
            t_launch = time.perf_counter()
            tips_by_node = {self.ir.devices[0]: tip}
        else:
            leaves = jax.tree_util.tree_leaves(graph_input)
            staged = [
                jax.device_put(
                    np.broadcast_to(
                        np.asarray(leaf)[None],
                        (self.n_devices, *np.asarray(leaf).shape),
                    ),
                    sh,
                )
                for leaf, sh in zip(leaves, self._in_shardings)
            ]
            t_stage = time.perf_counter()
            outs = self._fn(self._slabs, *staged)
            n_disp = len(staged) + 1
            t_launch = time.perf_counter()
            # everything below is result COLLECTION, not dispatch: the
            # jitted call above returns at enqueue, but materializing
            # per-device shards (addressable_shards / shard.data) can
            # block on the program's execution, so it sits outside the
            # launch_s window — like the fence, it measures the device,
            # not the host loop
            tips, fin_rows = outs[0], outs[1:]
            node_by_dev = {
                self.backend.cluster[n].jax_device: n
                for n in self.ir.devices
            }
            tips_by_node = {
                node_by_dev[s.device]: s.data
                for s in tips.addressable_shards
            }
            final = None
            if self._final_tid is not None:
                fin_leaves = []
                for row in fin_rows:
                    shard = next(
                        s for s in row.addressable_shards
                        if s.device
                        == self.backend.cluster[
                            self.ir.devices[self._owner_index]
                        ].jax_device
                    )
                    fin_leaves.append(shard.data[0])
                final = jax.tree_util.tree_unflatten(
                    self._final_treedef, fin_leaves
                )

        n_fences = 0
        fence_s = 0.0
        if fence:
            n_fences, fence_s = self.backend._timed_fence(
                tips_by_node, tracer
            )
            if tracer is not None:
                t_f1 = time.perf_counter()
                # one fused program span per device: the compiled path
                # has no per-task boundaries, so the device rows carry a
                # single cat="program" span each (obs/attribution.py
                # degrades to program-level attribution on these)
                for node in self.ir.devices:
                    n_tasks = sum(
                        len(ph.compute.get(node, ()))
                        for ph in self.ir.phases
                    )
                    tracer.complete(
                        "program", t_stage, t_f1, track=node,
                        cat="program", tasks=n_tasks,
                        phases=len(self.ir.phases),
                    )
        if metrics is not None:
            metrics.counter("compiled.launches").inc(n_disp)
            metrics.counter("compiled.exchanges").inc(self.transfer_edges)
        if mem is not None:
            # recorded after the phase windows close so stage_s/launch_s
            # stay clean; sizes are the static build-time tables
            # mesh staging broadcasts: each device holds one row, so the
            # per-device input footprint equals the host input's bytes
            in_bytes = sum(
                np.asarray(l).nbytes
                for l in jax.tree_util.tree_leaves(graph_input)
            )
            for node in self.ir.devices:
                pb = self.param_bytes_per_node.get(node, 0)
                if pb:
                    mem.alloc(node, "slab:params", pb, "params")
                mem.alloc(node, "input", in_bytes, "activations")
            for dst, tid, nb in self._exchange_table:
                mem.alloc(dst, f"xfer:{tid}", nb, "transfers")
            if self._final_out:
                ftid, owner, nb = self._final_out
                mem.alloc(owner, f"out:{ftid}", nb, "activations")
        phases = {
            "loop_s": t_launch - t0,
            "stage_s": t_stage - t0,
            "launch_s": t_launch - t_stage,
            "fence_s": fence_s,
        }
        return (
            final, {}, self.transfer_edges, self.transfer_bytes,
            n_fences, n_disp, {}, phases,
        )


def _make_branch(
    steps, node, exports, regs, slabs_local, x_local, avals,
    rebuild_params, graph,
):
    """Phase branch for one device: run its tasks (barrier-separated),
    return the phase's export tuple (zeros for other devices' tasks)."""
    globs: Dict[str, None] = {}
    for _tid, _fn, pitems, _aids in steps:
        for _, g in pitems:
            globs.setdefault(g)
    globs_needed = list(globs)

    def branch(_):
        pvals = rebuild_params(node, globs_needed, slabs_local)
        if pvals:
            # pin slab reconstruction as its own computation: task
            # numerics must match the interpreted path, where params
            # arrive as materialized buffers
            flat, td = jax.tree_util.tree_flatten(pvals)
            flat = jax.lax.optimization_barrier(tuple(flat))
            pvals = jax.tree_util.tree_unflatten(td, list(flat))
        vals: Dict[str, Any] = {}
        for tid, fn, pitems, aids in steps:
            pd = {loc: pvals[g] for loc, g in pitems}
            args = (
                [vals[d] if d in vals else regs[d] for d in aids]
                if aids else [x_local]
            )
            vals[tid] = jax.lax.optimization_barrier(fn(pd, *args))
        return tuple(
            vals[t] if t in vals else _zeros_of(avals[t])
            for t in exports
        )

    return branch
