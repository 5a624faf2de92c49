"""Pre-planned per-task dispatch: plan once, launch from a flat table.

The legacy hot loop (``DeviceBackend._run``) re-derives everything per task
per rep: placement dict lookups, param dict comprehensions, per-argument
``device_put`` decisions, upstream-failure checks.  On the flagship GPT-2
DAG that Python bookkeeping is most of the 21.9 ms host dispatch overhead
(BENCH_r05.json) — work whose inputs (graph, schedule, placed params) are
all fixed before the first launch.  This module moves it to plan time:

* **Immutable plan** (:class:`DispatchPlan`): built from the frozen
  graph, the schedule's dispatch linearization, and the placed params,
  and kept on the backend with them (:class:`PreparedCall`) for as long
  as ``execute`` is called with what it was built from.  Each step carries its resolved jitted executable, a
  prebuilt param binding dict, and integer indices into a flat value
  table — the hot loop does list indexing and calls, nothing else.
* **Batched staging**: all of a step's cross-core inputs go up in ONE
  ``jax.device_put([...], dev)`` call (the ``_ParamStreamer._load``
  trick applied to activations).  Transfer edges/bytes are counted
  statically at plan time with the exact per-(task, arg) semantics of the
  legacy loop; bytes are filled during the warmup pass and cached.
* **Donated buffers**: an intermediate output whose globally-last consumer
  is a same-device step is donated to that step via
  ``jax.jit(..., donate_argnums=...)``, so XLA reuses the dying buffer for
  the step's output instead of allocating.  Safety rules (enforced at
  plan time, assertable from the plan): never donate external
  (``ext_outputs``) values or the staged graph input — on-device
  ``device_put`` can return the caller's own array, so deleting it would
  reach outside the run; never donate the final output or a value any
  later step still reads; never donate under ``keep_outputs``; a buffer
  feeding one step at two argument positions is not donated at all.
  Cross-core transfers are fresh copies owned by the consuming step, so
  those are always donated (the producer's original stays live).
* **Fused launches** (what ``execute()`` does on this path unless it must
  not): the global dispatch order is first re-linearized to maximize runs
  of consecutive same-device tasks — legal because async dispatch only
  needs a task's upstreams *enqueued* first, and both
  ``Schedule.per_node`` order and topological dispatch order are preserved
  exactly.  Each run is cut into launches (:func:`_cut_runs`: where
  nothing the launch produced is still awaited by the rest of its run,
  before the head of the next chain, else at :data:`_GROUP_CAP` members)
  and every launch is ONE jitted
  multi-task call: members read in-run values directly and everything
  else (earlier task outputs, ext values, the staged graph input) as
  launch arguments, so per-task placement semantics survive intact.
  ``jax.lax.optimization_barrier`` between member computations keeps each
  task's numerics bit-identical to separate launches (XLA cannot fuse
  across the barrier).  The executable is keyed by the launch's
  *structure* (:func:`launch_structure`: member ``fn`` objects, in-run
  wiring by member position, exported positions, donation pattern) and
  takes each member's weights positionally, so layer 7's launch calls
  the executable layer 0's compiled: O(runs) launches a step, O(distinct
  structures) programs a process, a structure met once in the plan
  included.  The host-effect rule: a task ``fn`` whose
  jaxpr carries effects (``jax.debug.callback``, ``io_callback``) loses
  its per-launch ordering inside one XLA program, so ``execute()`` reads
  the effects of each distinct ``fn`` once and keeps such a graph on
  per-task launches.

Fail-and-continue is preserved statically: tasks with failed (unplaced or
transitively skipped) upstreams are dropped at plan build, mirroring the
legacy loop's per-task check.  The end-of-run fence reads each device's
last planned output, exactly like the legacy loop.
"""

from __future__ import annotations
# dls-lint: allow-file(DET001) dispatch timing harness: wall time IS the measured quantity

import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
from jax.sharding import SingleDeviceSharding

# fast transfer path: with the target sharding and source avals known at
# plan time, calling the runtime's batched_device_put directly skips
# ~30 us/array of argument normalization inside public ``jax.device_put``
# (sharding inference, pytree flatten, aval abstraction).  Semantics match
# the public path for the cross-device moves the plan issues (the public
# path's same-device aliasing shortcut never applies to them).  Private
# API of the pinned jax (pyproject.toml): an upgrade that moves it fails
# here, at import.
from jax._src.lib import xla_client as _xc
# what jit itself asks when it resolves a committed argument's layout
# (``pjit._resolve_in_layouts``); private like the import above
from jax._src.interpreters.pxla import is_default_layout as _is_default_layout
from jax.experimental.layout import Format, Layout

from ..obs.trace import CAT_LAUNCH, CAT_STAGE, annotate


def _fast_put(aval, sharding, xs, devices):
    return _xc.batched_device_put(aval, sharding, xs, devices, True)


def _array_bytes(x: Any) -> int:
    from .device import _array_bytes as f

    return f(x)


def _tuple_getter(slots: Sequence[int]):
    """C-speed multi-index gather over the value table (always a tuple,
    unlike bare ``itemgetter`` which unwraps a single index)."""
    from operator import itemgetter

    if not slots:
        return lambda vals: ()
    if len(slots) == 1:
        s = slots[0]
        return lambda vals: (vals[s],)
    return itemgetter(*slots)


def _compile_in_process(jitted: Any, *args: Any) -> Any:
    """``jitted`` compiled for ``args`` here and now, neither read from nor
    written to the persistent compilation cache: the executable.

    An executable loaded from that cache hands out arrays that report the
    runtime's default layout whatever layout it wrote them in (jaxlib
    0.9.0: ``tests/test_dispatch_plan.py`` shows it on the CPU, where a
    reader compiled against the reported layout reads wrong values; on
    the v5e the runtime refuses the buffer), so a program that keeps
    another layout has to be the process's own.  The cache is off for the
    whole process while this compiles — a compile another thread issues
    meanwhile is not cached either — and for nothing else: the executable
    is called outside."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jitted.lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


class NativeLaunch:
    """A launch's program whose results at ``asked`` keep the layout the
    compiler writes them in.

    ``auto`` leaves those results to the compiler (``Layout.AUTO``).  It
    is compiled once per argument signature, ahead of its first call, and
    asked what it picked (:meth:`picked`): where that is the runtime's
    default for every result — every activation of the GPT-2 DAGs, and
    everything on the CPU — ``auto`` is the launch's executable, the one
    compile it costs today, served by the persistent cache like any
    other.  Where a result comes back in another layout (the logits on
    the v5e: 50,257 is no multiple of 128, so the default puts the batch
    on the sublanes, an order the head's matmul never writes), the launch
    runs a program of its own that names those layouts, compiled in this
    process (:func:`_compile_in_process`); ``auto`` then only answered
    the question, from the cache after the first run of a checkout.
    """

    __slots__ = ("fun", "donate_argnums", "asked", "auto", "_resolved")

    def __init__(
        self, fun: Any, donate_argnums: Tuple[int, ...],
        asked: Optional[Tuple[bool, ...]],
    ):
        """``asked``: which results of the tuple ``fun`` returns are left
        to the compiler; ``None`` where it returns one value (a single
        task), which is."""
        ask = Format(Layout.AUTO)
        self.fun = fun
        self.donate_argnums = donate_argnums or None
        self.asked = asked
        self.auto = jax.jit(
            fun, donate_argnums=self.donate_argnums,
            out_shardings=ask if asked is None else tuple(
                ask if on else None for on in asked
            ),
        )
        # argument signature -> (executable, results it keeps in another
        # layout than the default)
        self._resolved: Dict[Any, Tuple[Any, int]] = {}

    def picked(self, pd: Any, args: Sequence[Any]) -> Tuple[Any, Any]:
        """The compiler's answer for ``(pd, *args)``: the formats ``auto``
        writes its results in, and their avals."""
        compiled = self.auto.lower(pd, *args).compile()
        return compiled.output_formats, compiled.out_info

    def first_call(
        self, pd: Any, args: Sequence[Any],
    ) -> Tuple[Any, Any, int]:
        """Run the launch on ``(pd, *args)`` and return ``(the executable
        every later call with such arguments goes to, the results, how
        many of them it keeps in another layout than the default)``."""
        key = tuple(
            (leaf.aval, leaf.format)
            for leaf in jax.tree_util.tree_leaves((pd, args))
        )
        if key not in self._resolved:
            self._resolved[key] = self.resolve(pd, args)
        fn, n_kept = self._resolved[key]
        return fn, fn(pd, *args), n_kept

    def resolve(self, pd: Any, args: Sequence[Any]) -> Tuple[Any, int]:
        """The executable for ``(pd, *args)`` — arrays, or their shapes
        with the formats they arrive in — and how many of its results it
        keeps in another layout than the default."""

        def keep(picked: Any, avals: Any) -> Any:
            return jax.tree_util.tree_map(
                lambda fmt, aval: None if _is_default_layout(
                    fmt.layout, fmt.sharding, aval) else fmt,
                picked, avals,
            )

        picked, avals = self.picked(pd, args)
        if self.asked is None:
            kept = keep(picked, avals)
        else:
            kept = tuple(
                keep(p, a) if on else None
                for on, p, a in zip(self.asked, picked, avals)
            )
        n_kept = len(jax.tree_util.tree_leaves(kept))
        if not n_kept:
            return self.auto, 0
        named = jax.jit(
            self.fun, donate_argnums=self.donate_argnums, out_shardings=kept,
        )
        return _compile_in_process(named, pd, *args), n_kept


_DONATION_OK: Optional[bool] = None


def donation_supported() -> bool:
    """Probe (once per process) whether this platform honors buffer
    donation: a donated input must actually be deleted.  Platforms that
    ignore ``donate_argnums`` (with a warning) get the undonated path."""
    global _DONATION_OK
    if _DONATION_OK is None:
        import warnings

        import numpy as np

        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                x = jax.device_put(np.ones((4,), np.float32))
                f = jax.jit(lambda v: v + 1.0, donate_argnums=(0,))
                jax.block_until_ready(f(x))
                _DONATION_OK = bool(x.is_deleted())
        except Exception:
            _DONATION_OK = False
    return _DONATION_OK


# sentinel naming a root member's graph-input read in a launch's external
# argument list (the staged per-node input slot backs it at run time)
GRAPH_INPUT = "__graph_input__"

# max members per fused launch — bounds XLA program size / compile time,
# and how long a cross-chip consumer waits on members it does not read
_GROUP_CAP = 32


def _arg_ids(task) -> Sequence[str]:
    return task.arg_tasks or task.dependencies


def group_arg_binds(graph, tids: Tuple[str, ...]):
    """Argument wiring for a (possibly fused) launch over ``tids``.

    Returns ``(binds, ext_list)``.  ``ext_list`` is the ordered tuple of
    external inputs the launch takes after the params: task ids produced
    outside the group, or :data:`GRAPH_INPUT` for a root member's
    graph-input read — one entry per (member, arg position) occurrence,
    duplicates kept, mirroring the legacy loop's per-argument semantics.
    ``binds[i]`` wires member i's arguments: ``('v', j)`` reads the value
    member ``j`` of this launch produced, ``('x', k)`` reads
    ``ext_list[k]`` — positions only, so two launches wired alike compare
    equal whatever their task ids.
    """
    inside: Dict[str, int] = {}
    binds: List[Tuple[Tuple[str, int], ...]] = []
    ext_list: List[str] = []
    for i, tid in enumerate(tids):
        aids = _arg_ids(graph[tid])
        row: List[Tuple[str, int]] = []
        for d in aids or (GRAPH_INPUT,):
            if d in inside:
                row.append(("v", inside[d]))
            else:
                row.append(("x", len(ext_list)))
                ext_list.append(d)
        binds.append(tuple(row))
        inside[tid] = i
    return tuple(binds), tuple(ext_list)


def _program_order(graph, span: Sequence[str]) -> Tuple[str, ...]:
    """The order a fused launch computes ``span`` in: its dataflow-connected
    components one after another (by first member), each in span order.

    Inside one XLA program the member order binds nothing — the compiler
    schedules the islands itself — so the launch is free to name its
    members canonically: two microbatch chains a policy interleaved in
    lockstep and the same two interleaved one task apart become the same
    program.  The plan's ``tids`` keep the span's (per-node) order."""
    pos = {t: i for i, t in enumerate(span)}
    root = list(range(len(span)))

    def find(i: int) -> int:
        while root[i] != i:
            root[i] = root[root[i]]
            i = root[i]
        return i

    for i, t in enumerate(span):
        for d in _arg_ids(graph[t]):
            j = pos.get(d)
            if j is not None:
                a, b = find(i), find(j)
                root[max(a, b)] = min(a, b)
    comps: Dict[int, List[str]] = {}
    for i, t in enumerate(span):
        comps.setdefault(find(i), []).append(t)
    return tuple(t for r in sorted(comps) for t in comps[r])


class FusedLaunch(NamedTuple):
    """What one fused launch runs, by name and by structure."""

    members: Tuple[str, ...]    # task ids in program order
    exports: Tuple[str, ...]    # members whose values leave the launch
    key: Any                    # the executable's structure (below)
    ext_list: Tuple[str, ...]   # external inputs, in argument order


def launch_structure(
    graph, members: Tuple[str, ...], exports: Tuple[str, ...]
) -> FusedLaunch:
    """A fused launch over ``members`` (program order), with ``key`` what
    its program depends on and nothing else: ``(fns, binds,
    export_positions)`` — the member ``fn`` objects, the in-run wiring by
    position, and which members' values leave the launch.  Task ids,
    global parameter names and layer numbers are not in it; the donation
    pattern joins the key where the executable is resolved
    (``DeviceBackend._grouped_jitted``)."""
    binds, ext_list = group_arg_binds(graph, members)
    pos = {t: i for i, t in enumerate(members)}
    key = (
        tuple(graph[t].fn for t in members), binds,
        tuple(pos[t] for t in exports),
    )
    return FusedLaunch(members, exports, key, ext_list)


def _build_group_fn(fns: Tuple[Any, ...], binds, export_pos: Tuple[int, ...]):
    """One callable running ``fns`` in order: (per-member param dicts,
    *external-args) -> tuple of exported outputs.

    Members read values produced inside the launch directly and
    everything else from the external argument list (wiring from
    :func:`group_arg_binds`).  ``optimization_barrier`` between members
    pins each task's computation as its own fusion island, so per-task
    outputs are bit-identical to separate launches.  ``fns`` are the
    backend's per-``fn`` jitted callables: a member is traced once per
    process and argument shape, however many launches and structures it
    appears in.  Nothing here names a task or a graph, so a cached
    executable keeps neither alive.
    """
    last = len(fns) - 1

    def group_fn(pds, *ext_args):
        vals: List[Any] = []
        for i, fn in enumerate(fns):
            args = [
                vals[ref] if kind == "v" else ext_args[ref]
                for kind, ref in binds[i]
            ]
            out = fn(pds[i], *args)
            if i < last:
                out = jax.lax.optimization_barrier(out)
            vals.append(out)
        return tuple(vals[p] for p in export_pos)

    return group_fn


def _cut_runs(graph, placement, order: Sequence[str]) -> List[List[str]]:
    """Cut the (re-linearized) dispatch order into launches: spans of
    consecutive same-device tasks.

    A span closes where nothing it produced is still read by the rest of
    its same-device run — once it holds a quarter of :data:`_GROUP_CAP`, so
    that independent chains a policy runs side by side (waves of a few
    microbatches through one layer) end together and the next wave
    starts the same program again — and at :data:`_GROUP_CAP` members
    where the run never comes clean (one chip: the residual stream is
    always in flight, and the cap's spans repeat down the layers).

    A span also holds one chain.  A chain's *head* reads nothing its run
    has produced (the next microbatch's first task on this chip); a span
    closes before one once it holds that quarter of the cap, or whatever
    it holds where it is the remainder a cap cut left — so a chain longer
    than the cap (pack: a microbatch's pass through a chip's run of
    layers) starts every microbatch's spans at the same task, its value
    leaves for the next chip when its own chain ends, and a run that never
    comes clean (each microbatch's logits are read by the concat at its
    end) is still cut chain by chain.  A head whose ``fn`` no other task
    of the run carries (a microbatch's own slice of the input) makes every
    span that holds it a program of its own; where the policy runs the
    chain through — the next task reads the head — the head is launched
    alone, and the spans after it are the ones every chain shares."""
    spans: List[List[str]] = []
    i, n = 0, len(order)
    while i < n:
        node = placement[order[i]]
        j = i
        while j < n and placement[order[j]] == node:
            j += 1
        run = order[i:j]
        idx = {t: k for k, t in enumerate(run)}
        last_read: Dict[int, int] = {}
        reads_run: set = set()  # positions that read a value of this run
        fn_uses: Dict[Any, int] = {}
        for k, t in enumerate(run):
            fn = graph[t].fn
            fn_uses[fn] = fn_uses.get(fn, 0) + 1
            for d in _arg_ids(graph[t]):
                p = idx.get(d)
                if p is not None:
                    last_read[p] = k
                    reads_run.add(k)
        cur: List[str] = []
        open_until = -1
        remainder = False  # cur began where the cap cut a chain
        for k, t in enumerate(run):
            head = k not in reads_run
            alone = (
                head and fn_uses[graph[t].fn] == 1 and k + 1 < len(run)
                and t in _arg_ids(graph[run[k + 1]])
            )
            if cur and head and (
                alone or remainder or 4 * len(cur) >= _GROUP_CAP
            ):
                spans.append(cur)
                cur, open_until, remainder = [], -1, False
            cur.append(t)
            open_until = max(open_until, last_read.get(k, -1))
            capped = len(cur) >= _GROUP_CAP
            if capped or alone or (
                open_until <= k and 4 * len(cur) >= _GROUP_CAP
            ):
                spans.append(cur)
                cur, open_until, remainder = [], -1, capped
        if cur:
            spans.append(cur)
        i = j
    return spans


def _sds(x: Any):
    """ShapeDtypeStruct of one concrete leaf (host or device array)."""
    import numpy as np

    if not (hasattr(x, "shape") and hasattr(x, "dtype")):
        x = np.asarray(x)
    return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)


def _leaf_avals(out_shape: Any) -> Optional[frozenset]:
    """(shape, dtype) of every leaf of a task's ``out_shape``; None where
    the graph carries none."""
    if out_shape is None:
        return None
    return frozenset(
        (tuple(leaf.shape), leaf.dtype)
        for leaf in jax.tree_util.tree_leaves(out_shape)
    )


def _relinearize(graph, schedule, alive: List[str], done: set) -> List[str]:
    """Reorder ``alive`` to maximize consecutive same-device runs.

    Legal because async dispatch only requires a task's upstreams to be
    *enqueued* (not completed) first: the result preserves each node's
    ``Schedule.per_node`` order exactly (tasks only ever leave the front
    of their node's queue) and is a topological order of the alive
    subgraph.  Greedy: stay on the current node while its next task has
    all upstreams already dispatched; when it blocks, switch to the node
    with the longest immediately-dispatchable prefix (longer runs mean
    fewer launches, and more distance between a producer's launch and its
    consumers' transfers).  A switch target always exists: the earliest
    not-yet-dispatched task of the original order is always its node's
    head with every upstream already dispatched."""
    placement = schedule.placement
    from collections import deque
    from itertools import islice

    queues: Dict[str, Any] = {}
    for t in alive:
        queues.setdefault(placement[t], deque()).append(t)
    node_order = sorted(queues)
    done = set(done)
    out: List[str] = []
    cur: Optional[str] = None

    def ready(t: str) -> bool:
        aids = graph[t].arg_tasks or graph[t].dependencies
        return all(d in done for d in aids)

    def ready_prefix(q) -> int:
        n = 0
        local: set = set()
        for t in islice(q, _GROUP_CAP):
            aids = graph[t].arg_tasks or graph[t].dependencies
            if all(d in done or d in local for d in aids):
                local.add(t)
                n += 1
            else:
                break
        return n

    while len(out) < len(alive):
        q = queues.get(cur)
        if q and ready(q[0]):
            t = q.popleft()
        else:
            best_n, best_len = None, 0
            for n in node_order:
                qn = queues[n]
                if not qn or not ready(qn[0]):
                    continue
                ln = ready_prefix(qn)
                if ln > best_len:
                    best_n, best_len = n, ln
                    if ln >= _GROUP_CAP:
                        break
            if best_n is None:  # impossible per the invariant above
                raise RuntimeError("relinearize: no dispatchable node head")
            cur = best_n
            t = queues[cur].popleft()
        out.append(t)
        done.add(t)
    return out


def _stable_leaves(value: Any) -> Optional[Tuple[Any, ...]]:
    """The leaves of one parameter if every one is a live ``jax.Array``
    (immutable: what was put stays what the caller holds), else None (a
    host ``numpy`` array can be written in place)."""
    leaves = (
        (value,) if isinstance(value, jax.Array)
        else tuple(jax.tree_util.tree_leaves(value))
    )
    for leaf in leaves:
        if (
            not isinstance(leaf, jax.Array)
            or isinstance(leaf, jax.core.Tracer)
            or leaf.is_deleted()
        ):
            return None
    return leaves or None


def call_avals(graph_input: Any, ext_outputs: Optional[Dict[str, Any]]):
    """Hashable shapes and dtypes of what a call feeds the plan from
    outside (the graph input, the ext values by key): every launch's and
    transfer's shape follows from them, the graph and the parameters."""
    leaves, treedef = jax.tree_util.tree_flatten((graph_input, ext_outputs))
    return treedef, tuple(jax.typeof(leaf) for leaf in leaves)


class PreparedCall:
    """What ``DeviceBackend.execute()`` derives from its arguments alone,
    kept on the backend between calls (one per live graph, the latest).

    *Structure* — ``order``, ``plan``, ``graph_params``, ``gate_passed`` —
    is valid for ``key``: the graph (by identity and ``version``), the
    schedule's ``signature()``, the ext keys, the flags, the cluster's
    devices and the call's input avals.  *Placement* — ``placed``,
    ``avals``, ``bytes_per_node`` — is valid name by name while the
    caller's ``params[name]`` IS the array that was put (``sources`` keeps
    those arrays alive, so an ``id`` cannot come back as another array);
    a name with a host array has no source and is put again every call.
    Holds no reference to the graph: a dead graph releases all of it.
    """

    __slots__ = (
        "key", "order", "plan", "graph_params", "gate_passed", "pairs_of",
        "sources", "avals", "placed", "bytes_per_node",
    )

    def __init__(
        self, key: Any, graph, schedule, order: List[str],
        graph_params: frozenset,
    ):
        self.key = key
        self.order = order
        self.plan: Optional[DispatchPlan] = None
        self.graph_params = graph_params    # graph.unique_params()
        self.gate_passed = False
        # param -> the (param, node_id) pairs the schedule needs
        pairs_of: Dict[str, Dict[Tuple[str, str], None]] = {}
        for tid, node_id in schedule.placement.items():
            for p in graph[tid].params_needed:
                pairs_of.setdefault(p, {})[(p, node_id)] = None
        self.pairs_of = {p: tuple(ps) for p, ps in pairs_of.items()}
        self.sources: Dict[str, Tuple[Any, ...]] = {}
        self.avals: Dict[str, Any] = {}     # param -> pytree of avals put
        self.placed: Dict[Tuple[str, str], Any] = {}
        self.bytes_per_node: Dict[str, int] = {}

    def stale_names(self, params: Dict[str, Any]) -> List[str]:
        """The names whose placed replicas are not ``params``' arrays."""
        stale = []
        sources = self.sources
        for name in self.pairs_of:
            kept = sources.get(name)
            value = params[name]
            if kept is None:
                stale.append(name)
            elif len(kept) == 1 and value is kept[0]:
                if value.is_deleted():
                    stale.append(name)
            else:
                leaves = _stable_leaves(value)
                if (
                    leaves is None or len(leaves) != len(kept)
                    or any(a is not b for a, b in zip(leaves, kept))
                ):
                    stale.append(name)
        return stale

    def place(self, params: Dict[str, Any], cluster) -> int:
        """Bring the replicas up to the caller's ``params``: put again,
        onto every device that needs it, each parameter whose array is
        not the one placed (another ``jax.Array`` under the name, a
        deleted one, a host array, a name never placed), re-bind the kept
        plan's steps to the new replicas and leave every other replica
        where it is.  Returns how many names were put; should a put
        raise, the entry is half placed and the caller drops it."""
        stale = self.stale_names(params)
        if not stale:
            return 0
        pairs = [pair for name in stale for pair in self.pairs_of[name]]
        # the replicas these replace go first: a training loop's old
        # weights must not sit beside the new ones on every chip
        if self.plan is not None:
            self.plan.rebind(dict.fromkeys(pairs))
        for pair in pairs:
            self.placed.pop(pair, None)
        reshaped = False
        for name in stale:
            self.sources.pop(name, None)
            avals = jax.tree_util.tree_map(jax.typeof, params[name])
            reshaped |= self.avals.get(name, avals) != avals
            self.avals[name] = avals
        fresh = {
            (name, node_id): jax.device_put(
                params[name], cluster[node_id].jax_device
            )
            for name, node_id in pairs
        }
        # placed values may be pytrees (QParam int8+scale pairs)
        jax.block_until_ready(list(fresh.values()))
        self.placed.update(fresh)
        for name in stale:
            leaves = _stable_leaves(params[name])
            if leaves is not None:
                self.sources[name] = leaves
        self.bytes_per_node = {d.node_id: 0 for d in cluster}
        for name, name_pairs in self.pairs_of.items():
            nbytes = _array_bytes(self.avals[name])
            for _p, node_id in name_pairs:
                self.bytes_per_node[node_id] += nbytes
        if self.plan is not None:
            self.plan.rebind(fresh, reshaped=reshaped)
        return len(stale)


class PlanStep:
    """One launch: a single task or a coalesced same-device group."""

    __slots__ = (
        "tids",          # task ids in this launch (len 1 unless coalesced)
        "node_id",
        "dev",           # jax device the launch runs on
        "fn",            # resolved jitted callable (donating variant baked in)
        "pd",            # prebuilt param binding dict; its values change only
                         # through DispatchPlan.rebind (a parameter re-placed)
        "arg_slots",     # value-table indices of the launch args, in order
        "get_args",      # itemgetter over arg_slots (C-speed gather)
        "xfer_slots",    # unique slots needing device_put onto `dev`
        "get_srcs",      # itemgetter over xfer_slots
        "xfer_map",      # (arg position, index into xfer_slots) pairs
        "xfer_src_tids",  # producer id per xfer slot (tracing: flow arrows)
        "xfer_src_nodes",  # producer node per xfer slot ("ext" for seeds)
        "xfer_shard",    # SingleDeviceSharding(dev) for the fast put path
        "xfer_devs",     # [dev] for the fast put path
        "xfer_avals",    # per-xfer_slots avals, filled on first run;
                         # False => pytree payloads, public path only
        "n_edges",       # transfer edges this launch contributes (static)
        "xfer_bytes",    # per-run transferred bytes; filled on first run
        "donate_slots",  # slots whose ORIGINAL buffer this launch consumes
        "donate_tids",   # producer task id per donate slot (memprof frees)
        "donate_argnums",  # jit donate positions (params dict is argument 0)
        "out_slots",     # value-table indices written (exports, in order)
        "out_tids",      # exported task id per out_slot (memprof births)
        "native_slots",  # out_slots whose layout is left to the compiler
        "program",       # where there are any, the NativeLaunch that ``fn``
                         # is until the step's first call has resolved it
                         # to an executable; else None
        "group",         # True => fn returns a tuple aligned with out_slots
    )


class DispatchPlan:
    """Immutable dispatch program for one (graph, schedule, ext) triple
    (only :meth:`rebind` writes to it: other weights, the same program).

    Built by :meth:`build`; executed by :meth:`run`.  The value table is a
    flat list: slots 0..len(ext)-1 hold external outputs, then one slot per
    device that roots read the graph input from, then one slot per exported
    task output.
    """

    def __init__(
        self,
        backend,
        steps: List[PlanStep],
        n_slots: int,
        ext_slots: Tuple[Tuple[str, int], ...],
        input_slots: Tuple[Tuple[str, Any, int], ...],
        fence_slots: Tuple[Tuple[str, int], ...],
        final_slot: Optional[int],
        keep_list: Tuple[Tuple[str, int], ...],
        transfer_edges: int,
        donate: bool,
        coalesce: bool,
        param_binds: Dict[Tuple[str, str], List[Tuple[Dict[str, Any], str]]],
    ):
        self._backend = backend
        self.steps = steps
        self.n_slots = n_slots
        self.ext_slots = ext_slots
        self.input_slots = input_slots       # (node_id, jax device, slot)
        self.fence_slots = fence_slots       # (node_id, slot)
        self.final_slot = final_slot
        self.keep_list = keep_list           # (tid, slot) when keep_outputs
        self.transfer_edges = transfer_edges
        self.donate = donate
        self.coalesce = coalesce
        # (param, node_id) -> every (step binding dict, local name) that
        # holds its placed array: what rebind() writes through
        self.param_binds = param_binds
        # how many exported values the compiled launches left in another
        # layout than the default (of those PlanStep.native_slots leaves to
        # the compiler): known once the first run has resolved them
        self.native_layout_exports: Optional[int] = None

    # -- construction ------------------------------------------------------
    @classmethod
    def build(
        cls,
        backend,
        graph,
        schedule,
        order: Sequence[str],
        placed_params: Dict[Tuple[str, str], Any],
        ext_keys: Tuple[str, ...] = (),
        donate: bool = False,
        coalesce: bool = False,
        keep_outputs: bool = False,
    ) -> "DispatchPlan":
        """``coalesce``: ``False`` one launch a task; ``True`` one launch
        a same-device span of :func:`_cut_runs`, whether its structure
        occurs once in the plan or fifty times (``execute()``'s default)."""
        placement = schedule.placement
        if keep_outputs:
            donate = False  # retained outputs must all outlive the run

        # static fail-and-continue: identical filter to the legacy loop's
        # per-task upstream check (ext values count as live producers)
        live: set = set(ext_keys)
        alive: List[str] = []
        for tid in order:
            aids = graph[tid].arg_tasks or graph[tid].dependencies
            if aids and any(d not in live for d in aids):
                continue
            live.add(tid)
            alive.append(tid)

        # launches: one task each unless coalescing is on.  Coalescing
        # first re-linearizes the dispatch order (per-node order and topo
        # dispatch preserved), then cuts it into same-device spans.
        if coalesce and alive:
            alive = _relinearize(graph, schedule, alive, set(ext_keys))
            groups = _cut_runs(graph, placement, alive)
        else:
            groups = [[t] for t in alive]

        consumers: Dict[str, List[str]] = {t: [] for t in alive}
        for tid in alive:
            for d in _arg_ids(graph[tid]):
                if d in consumers:
                    consumers[d].append(tid)

        def exports_from(g: Sequence[str]) -> Tuple[str, ...]:
            # a member's value leaves the launch when the run keeps it, a
            # task outside reads it, or nothing reads it (a sink)
            inside = set(g)
            return tuple(
                t for t in g
                if keep_outputs or not consumers[t]
                or any(c not in inside for c in consumers[t])
            )

        # what each launch of several tasks runs; None for a single task
        fused: List[Optional[FusedLaunch]] = []
        for g in groups:
            if len(g) == 1:
                fused.append(None)
                continue
            members = _program_order(graph, g)
            fused.append(
                launch_structure(graph, members, exports_from(members))
            )
        exports_of: List[Tuple[str, ...]] = [
            f.exports if f is not None else tuple(g)
            for g, f in zip(groups, fused)
        ]

        # slot allocation: ext, then per-device graph input, then exports
        slot_of: Dict[str, int] = {}
        for k in ext_keys:
            slot_of[k] = len(slot_of)
        ext_slots = tuple((k, slot_of[k]) for k in ext_keys)
        input_slot: Dict[str, int] = {}
        n_slots = len(slot_of)
        for tid in alive:
            if not (graph[tid].arg_tasks or graph[tid].dependencies):
                node = placement[tid]
                if node not in input_slot:
                    input_slot[node] = n_slots
                    n_slots += 1
        for exports in exports_of:
            for t in exports:
                slot_of[t] = n_slots
                n_slots += 1

        final_tid = graph.topo_order[-1] if graph.topo_order else None
        final_slot = slot_of.get(final_tid) if final_tid else None
        fence: Dict[str, int] = {}
        for gi, g in enumerate(groups):
            # a group's last member always has outside-or-no consumers,
            # so it is exported and the fence can read it
            fence[placement[g[0]]] = slot_of[g[-1]]
        fence_slots = tuple(sorted(fence.items()))

        # per-launch external argument lists (slot-backed launch inputs)
        ext_lists = [
            f.ext_list if f is not None
            else group_arg_binds(graph, tuple(g))[1]
            for g, f in zip(groups, fused)
        ]

        # last consuming group index per slot (donation lifetime analysis)
        last_use: Dict[int, int] = {}
        for gi, ext_list in enumerate(ext_lists):
            for d in ext_list:
                if d != GRAPH_INPUT:
                    last_use[slot_of[d]] = gi

        task_out_slots = set(
            slot_of[t] for exports in exports_of for t in exports
        )
        # reverse map for the memory profiler's donation frees (a donated
        # slot's dying buffer is its producer task's ``out:`` label)
        tid_of_slot = {
            slot_of[t]: t for exports in exports_of for t in exports
        }
        protected = {final_slot} | {s for _, s in fence_slots}
        # an exported value may stay in the layout its producer writes it
        # in when every reader is a launch on its own chip, or nobody but
        # the caller (a sink: the step's output); one that is put onto
        # another chip, or kept for a later call, is handed over in the
        # runtime's default layout
        native_ok = frozenset() if keep_outputs else frozenset(
            t for exports in exports_of for t in exports
            if all(placement[c] == placement[t] for c in consumers[t])
        )

        param_binds: Dict[
            Tuple[str, str], List[Tuple[Dict[str, Any], str]]
        ] = {}

        def bind(tid: str, node: str) -> Dict[str, Any]:
            pd: Dict[str, Any] = {}
            for loc, glob in graph[tid].param_items():
                pd[loc] = placed_params[(glob, node)]
                param_binds.setdefault((glob, node), []).append((pd, loc))
            return pd

        steps: List[PlanStep] = []
        programs: List[Any] = []  # per step: what its executable is keyed by
        # launches of one structure share one program, so a result is left
        # to the compiler only where every launch that shares it may be
        native_of: Dict[Any, Tuple[bool, ...]] = {}
        transfer_edges = 0
        for gi, g in enumerate(groups):
            node = placement[g[0]]
            dev = backend.cluster[node].jax_device
            ext_list = ext_lists[gi]
            arg_slots = tuple(
                input_slot[node] if d == GRAPH_INPUT else slot_of[d]
                for d in ext_list
            )

            xfer_slots: List[int] = []
            xfer_srcs: List[str] = []  # producer per unique slot (tracing)
            xfer_map: List[Tuple[int, int]] = []
            xfer_ext: set = set()  # xfer indices sourced from ext values
            for pos, d in enumerate(ext_list):
                if d == GRAPH_INPUT or placement.get(d) == node:
                    # graph input is pre-staged per node; same-core edges
                    # need no transfer (legacy parity)
                    continue
                s = slot_of[d]
                if s in xfer_slots:
                    ui = xfer_slots.index(s)
                else:
                    ui = len(xfer_slots)
                    xfer_slots.append(s)
                    xfer_srcs.append(d)
                xfer_map.append((pos, ui))
                if d not in placement:
                    xfer_ext.add(ui)
                transfer_edges += 1

            donate_pos: List[int] = []
            donate_slots: List[int] = []
            if donate:
                pos_of_slot: Dict[int, List[int]] = {}
                for pos, s in enumerate(arg_slots):
                    pos_of_slot.setdefault(s, []).append(pos)
                moved = {pos for pos, _ in xfer_map}
                for s, poss in pos_of_slot.items():
                    if len(poss) != 1:
                        continue  # one buffer at two positions: never donate
                    pos = poss[0]
                    if pos in moved:
                        # the device_put copy is owned by this launch; ext
                        # values are excluded (on-device device_put can
                        # alias the caller's array)
                        ui = next(
                            u for p, u in xfer_map if p == pos
                        )
                        if ui not in xfer_ext:
                            donate_pos.append(pos)
                    elif (
                        s in task_out_slots
                        and last_use.get(s) == gi
                        and s not in protected
                    ):
                        donate_pos.append(pos)
                        donate_slots.append(s)
            donate_argnums = tuple(1 + p for p in sorted(donate_pos))

            step = PlanStep()
            step.tids = tuple(g)
            step.node_id = node
            step.dev = dev
            step.arg_slots = arg_slots
            step.get_args = _tuple_getter(arg_slots)
            step.xfer_slots = tuple(xfer_slots)
            step.get_srcs = _tuple_getter(step.xfer_slots)
            step.xfer_map = tuple(xfer_map)
            step.xfer_src_tids = tuple(xfer_srcs)
            step.xfer_src_nodes = tuple(
                placement.get(d, "ext") for d in xfer_srcs
            )
            step.xfer_shard = SingleDeviceSharding(dev) if xfer_slots else None
            step.xfer_devs = [dev]
            step.xfer_avals = None
            step.n_edges = len(xfer_map)
            step.xfer_bytes = None if xfer_map else 0
            step.donate_slots = tuple(donate_slots)
            step.donate_tids = tuple(tid_of_slot[s] for s in donate_slots)
            step.donate_argnums = donate_argnums
            launch = fused[gi]
            step.group = launch is not None
            if launch is not None:
                step.out_slots = tuple(slot_of[t] for t in launch.exports)
                step.out_tids = launch.exports
                step.pd = tuple(bind(t, node) for t in launch.members)
                program = (launch.key, donate_argnums)
            else:
                step.out_slots = (slot_of[g[0]],)
                step.out_tids = (g[0],)
                step.pd = bind(g[0], node)
                program = (graph[g[0]].fn, donate_argnums)
            steps.append(step)
            # a result that can take a donated argument's buffer (jit pairs
            # them by shape and dtype) is that buffer, layout and all: it
            # is left to the compiler only where the graph's avals show
            # that no dying argument of the launch fits it
            dying = [_leaf_avals(graph[ext_list[p]].out_shape)
                     for p in donate_pos]
            made = {t: _leaf_avals(graph[t].out_shape) for t in step.out_tids}
            ok = tuple(
                t in native_ok and all(
                    a is not None and made[t] is not None
                    and a.isdisjoint(made[t])
                    for a in dying
                )
                for t in step.out_tids
            )
            programs.append(program)
            native_of[program] = tuple(
                a and b for a, b in zip(native_of.get(program, ok), ok)
            )

        for step, program in zip(steps, programs):
            native_pos = tuple(
                i for i, ok in enumerate(native_of[program]) if ok
            )
            step.native_slots = tuple(step.out_slots[i] for i in native_pos)
            if step.group:
                step.fn = backend._grouped_jitted(*program, native_pos)
            else:
                step.fn = backend._jitted(
                    graph, step.tids[0], program[1], native_pos
                )
            step.program = step.fn if native_pos else None

        keep_list = tuple(
            (t, slot_of[t]) for exports in exports_of for t in exports
        ) if keep_outputs else ()
        plan = cls(
            backend, steps, n_slots, ext_slots,
            tuple(
                (n, backend.cluster[n].jax_device, s)
                for n, s in sorted(input_slot.items())
            ),
            fence_slots, final_slot, keep_list, transfer_edges,
            donate, coalesce, param_binds,
        )
        # donation self-check (analysis/donation_pass): re-derives the
        # lifetime safety the builder just computed, from the exported
        # metadata alone — a donation bug here frees a live buffer, so
        # it joins the pre-execution gate rather than trusting the
        # builder that produced it
        if donate and getattr(backend, "pre_analysis", True):
            from ..analysis import gate_enabled
            from ..analysis.donation_pass import analyze_donation

            if gate_enabled():
                analyze_donation(plan).raise_if_errors()
        return plan

    # -- analysis metadata -------------------------------------------------
    def donation_table(self) -> Dict[str, Any]:
        """Static donation metadata for ``analysis/donation_pass``:
        per-step slot reads/transfers/donations plus the post-run readers
        (fence, final output, keep list, ext values).  Pure data — the
        pass never touches live buffers or jitted callables, so external
        tooling can verify a plan without being able to run it."""
        return {
            "steps": tuple(
                {
                    "tids": st.tids,
                    "node_id": st.node_id,
                    "arg_slots": st.arg_slots,
                    "xfer_slots": st.xfer_slots,
                    "donate_slots": st.donate_slots,
                    "out_slots": st.out_slots,
                }
                for st in self.steps
            ),
            "fence_slots": self.fence_slots,
            "final_slot": self.final_slot,
            "keep_list": self.keep_list,
            "ext_slots": self.ext_slots,
            "n_slots": self.n_slots,
        }

    # -- identity ----------------------------------------------------------
    def signature(self) -> Tuple:
        """Hashable structural identity: two builds over the same
        (graph, schedule, ext keys, flags) must compare equal.  Contains
        no object identities, only names and slot indices."""
        return (
            self.n_slots,
            self.ext_slots,
            tuple((n, s) for n, _d, s in self.input_slots),
            self.fence_slots,
            self.final_slot,
            self.transfer_edges,
            self.donate,
            self.coalesce,
            tuple(
                (
                    st.tids, st.node_id, st.arg_slots, st.xfer_slots,
                    st.xfer_map, st.donate_slots, st.donate_argnums,
                    st.out_slots, st.native_slots,
                )
                for st in self.steps
            ),
        )

    @property
    def n_launches(self) -> int:
        return len(self.steps)

    # -- parameters re-placed between runs ---------------------------------
    def rebind(
        self, replicas: Dict[Tuple[str, str], Any], reshaped: bool = False,
    ) -> None:
        """Point every step that reads a ``(param, node_id)`` of
        ``replicas`` at the array given for it (``None``: at nothing, so
        the old replica can go before the new one comes): what a kept
        plan needs when the caller hands in other weights under the same
        names.  ``reshaped``: one of them has another shape or dtype than
        the array it replaces, so the transfer sizes and avals learnt on
        the first run are learnt again on the next."""
        for pair, new in replicas.items():
            for pd, loc in self.param_binds.get(pair, ()):
                pd[loc] = new
        if reshaped:
            self.native_layout_exports = None
            for st in self.steps:
                if st.program is not None:
                    st.fn = st.program
                if st.xfer_map:
                    st.xfer_avals = None
                    st.xfer_bytes = None

    # -- execution ---------------------------------------------------------
    def run(
        self,
        graph_input: Any,
        ext_outputs: Optional[Dict[str, Any]] = None,
        fence: bool = True,
        tracer: Any = None,
        metrics: Any = None,
        mem: Any = None,
    ) -> Tuple[Any, Dict, int, int, int, int, Dict[str, Any], Dict[str, float]]:
        """Execute the plan once.  Same return contract as the legacy
        runners plus a phase dict: ``(final, timings, transfer_edges,
        transfer_bytes, n_fences, n_dispatches, executed, phases)`` with
        ``phases = {loop_s, stage_s, launch_s, fence_s}`` — host wall inside
        the dispatch loop (fence excluded), split into staging (input
        placement + batched transfers) and launch (executable calls), and
        the host's wait in the end-of-run fence: the time the device is
        behind the host.  These clock reads are always on (five a run,
        two per step that moves data between chips); so are the profiler
        annotations ``dls/stage_input``, ``dls/dispatch_loop`` and
        ``dls/fence``.

        ``tracer`` (obs.trace.Tracer, optional): records one launch span
        per step on the step's device track, staging spans, transfer
        flow arrows from producer launches, and on the host track the
        leaves ``stage_input``, ``dispatch_loop`` and ``fence``, which do
        not overlap.  ``metrics`` (obs.metrics.MetricsRegistry, optional):
        per-(src->dst) transfer byte counters.  Both default to None and
        every per-launch instrumentation point is behind a None check.

        ``mem`` (obs.memprof.MemoryProfiler, optional): records input
        staging, transfer copies, task-output births, and donation-driven
        frees (the lifetimes :meth:`donation_table` documents) onto the
        per-device timelines."""
        vals: List[Any] = [None] * self.n_slots
        # where each producer's launch ended, for the flow arrows: only a
        # plan that moves data between chips draws any
        done: Optional[Dict[str, Tuple[str, float]]] = (
            {} if tracer is not None and self.transfer_edges else None
        )
        t_loop0 = time.perf_counter()
        stage_s = 0.0
        if ext_outputs:
            for k, s in self.ext_slots:
                vals[s] = ext_outputs[k]
        if self.input_slots:
            with annotate("stage_input"):
                t0 = time.perf_counter()
                for _n, dev, s in self.input_slots:
                    vals[s] = jax.device_put(graph_input, dev)
                    if mem is not None:
                        mem.alloc(
                            _n, "input", _array_bytes(vals[s]),
                            "activations",
                        )
                t1 = time.perf_counter()
            stage_s += t1 - t0
            if tracer is not None:
                tracer.complete(
                    "stage_input", t0, t1, track="host", cat=CAT_STAGE,
                    devices=len(self.input_slots),
                )

        tbytes = 0
        # the first run resolves each launch that left a result's layout
        # to its compiler (NativeLaunch.first_call)
        first_run = self.native_layout_exports is None
        n_native = 0
        t_d0 = time.perf_counter()
        with annotate("dispatch_loop"):
            for step in self.steps:
                per_edge = None
                if step.xfer_slots:
                    args = list(step.get_args(vals))
                    srcs = step.get_srcs(vals)
                    if step.xfer_bytes is None:
                        step.xfer_bytes = sum(
                            _array_bytes(srcs[ui]) for _p, ui in step.xfer_map
                        )
                    if metrics is not None or mem is not None:
                        per_edge = [_array_bytes(x) for x in srcs]
                    t0 = time.perf_counter()
                    if step.xfer_avals:
                        shard, devs = step.xfer_shard, step.xfer_devs
                        moved = [
                            _fast_put(av, shard, [x], devs)
                            for av, x in zip(step.xfer_avals, srcs)
                        ]
                    else:
                        # first (warmup) pass: public path, then cache avals.
                        # Pytree task outputs (dict-of-grads, cache slabs)
                        # have no single aval — those steps stay on the
                        # public path permanently (False sentinel).
                        moved = jax.device_put(srcs, step.dev)
                        if step.xfer_avals is None:
                            step.xfer_avals = (
                                tuple(m.aval for m in moved)
                                if all(hasattr(m, "aval") for m in moved)
                                else False
                            )
                    t1 = time.perf_counter()
                    stage_s += t1 - t0
                    if tracer is not None:
                        tracer.complete(
                            "stage", t0, t1, track=step.node_id, cat="stage",
                            transfers=len(step.xfer_slots),
                        )
                    if metrics is not None:
                        for ui, src_node in enumerate(step.xfer_src_nodes):
                            metrics.counter(
                                f"transfer.bytes.{src_node}->{step.node_id}",
                                unit="bytes",
                            ).inc(per_edge[ui])
                    if mem is not None:
                        for ui, src in enumerate(step.xfer_src_tids):
                            mem.alloc(
                                step.node_id, f"xfer:{src}", per_edge[ui],
                                "transfers",
                            )
                    for pos, ui in step.xfer_map:
                        args[pos] = moved[ui]
                else:
                    args = step.get_args(vals)
                tbytes += step.xfer_bytes
                if tracer is not None:
                    t_l0 = time.perf_counter()
                if first_run and step.program is not None:
                    step.fn, outs, kept = step.program.first_call(
                        step.pd, args
                    )
                    n_native += kept
                else:
                    outs = step.fn(step.pd, *args)
                if step.group:
                    for s, o in zip(step.out_slots, outs):
                        vals[s] = o
                else:
                    vals[step.out_slots[0]] = outs
                if mem is not None:
                    # births, then the donation-consumed producers' deaths —
                    # the exact lifetimes donation_table() documents
                    for t, s in zip(step.out_tids, step.out_slots):
                        mem.alloc(
                            step.node_id, f"out:{t}", _array_bytes(vals[s]),
                            "activations",
                        )
                    for t in step.donate_tids:
                        mem.free(step.node_id, f"out:{t}")
                if tracer is not None:
                    t_l1 = time.perf_counter()
                    name = (
                        step.tids[0] if len(step.tids) == 1
                        else f"{step.tids[0]}+{len(step.tids) - 1}"
                    )
                    tracer.complete(
                        name, t_l0, t_l1, track=step.node_id, cat="launch",
                        tasks=len(step.tids), edges=step.n_edges,
                    )
                    if done is not None:
                        for t in step.tids:
                            done[t] = (step.node_id, t_l1)
                        for src in step.xfer_src_tids:
                            src_pt = done.get(src)
                            if src_pt is not None:
                                tracer.flow(
                                    "transfer", src_pt[0], src_pt[1],
                                    step.node_id, t_l0, src=src,
                                    dst=step.tids[0],
                                )
        t_d1 = time.perf_counter()
        loop_s = t_d1 - t_loop0
        if first_run:
            self.native_layout_exports = n_native
        if tracer is not None:
            # the host-track leaf over the per-launch spans of the device
            # tracks: with stage_input and fence it tiles the rep
            tracer.complete(
                "dispatch_loop", t_d0, t_d1, track="host", cat=CAT_LAUNCH,
                steps=len(self.steps),
            )

        n_fences = 0
        fence_s = 0.0
        if fence and self.steps:
            n_fences, fence_s = self._backend._timed_fence(
                {n: vals[s] for n, s in self.fence_slots}, tracer
            )
        final = vals[self.final_slot] if self.final_slot is not None else None
        executed = {t: vals[s] for t, s in self.keep_list}
        return (
            final, {}, self.transfer_edges, tbytes, n_fences,
            len(self.steps), executed,
            {
                "loop_s": loop_s,
                "stage_s": stage_s,
                "launch_s": loop_s - stage_s,
                "fence_s": fence_s,
            },
        )
