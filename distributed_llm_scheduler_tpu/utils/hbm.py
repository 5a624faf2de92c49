"""Pre-flight HBM accounting from XLA's own memory analysis.

Scheduler ``can_fit`` decisions were bookkeeping-only in round 1: a task's
``memory_required`` came from analytic activation-size estimates, while XLA
allocates temps invisibly (SURVEY.md §7 hard-part #3) — so
"fits in 14 GB" was never verified against what the compiler actually
reserves.  :func:`preflight_task_memory` AOT-compiles each unique
(fn, input-shapes) combination, reads ``compiled.memory_analysis()`` —
XLA's authoritative temp + output buffer sizes — and RAISES each task's
``memory_required`` to the compiled footprint when the analytic estimate
was optimistic.  Estimates are never lowered: the analytic number may
include workspace the analysis attributes elsewhere.

Shape propagation uses ``jax.eval_shape`` through the DAG (no FLOPs spent),
and compilation is cached per (fn, shapes) — with ``param_alias`` fn
sharing, a 537-task flagship graph compiles ~a few dozen distinct
executables.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from ..core.graph import GB, TaskGraph


def _spec_of(x: Any):
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), x
    )


def _key_of(fn: Any, pd_spec: Dict[str, Any], arg_specs: Tuple[Any, ...]):
    import jax

    leaves = jax.tree_util.tree_leaves((pd_spec, arg_specs))
    return (id(fn), tuple((x.shape, str(x.dtype)) for x in leaves))


def preflight_task_memory(
    graph: TaskGraph,
    params: Dict[str, Any],
    graph_input: Any,
) -> Dict[str, float]:
    """Raise each task's ``memory_required`` to XLA's compiled footprint.

    Returns ``task_id -> compiled (temp + output) GB`` for every task with
    an fn (schedule-only graphs are left untouched).  Tasks keep
    ``max(analytic, compiled)``.
    """
    import jax

    out_specs: Dict[str, Any] = {}
    compiled_gb: Dict[str, float] = {}
    cache: Dict[Any, float] = {}
    input_spec = _spec_of(graph_input)

    for tid in graph.topo_order:
        task = graph[tid]
        if task.fn is None:
            continue
        pd_spec = {
            loc: _spec_of(params[glob]) for loc, glob in task.param_items()
        }
        if task.dependencies:
            arg_ids = task.arg_tasks or task.dependencies
            args = tuple(out_specs[d] for d in arg_ids)
        else:
            args = (input_spec,)
        out_specs[tid] = jax.eval_shape(task.fn, pd_spec, *args)

        key = _key_of(task.fn, pd_spec, args)
        entry = cache.get(key)
        if entry is None:
            stats = jax.jit(task.fn).lower(pd_spec, *args).compile().memory_analysis()
            entry = (
                (stats.temp_size_in_bytes + stats.output_size_in_bytes) / GB,
                int(stats.output_size_in_bytes),
            )
            cache[key] = entry
        gb, out_bytes = entry
        compiled_gb[tid] = gb
        if gb > task.memory_required:
            task.memory_required = gb
        # true output size: cost models charge cross-node transfers by this
        # instead of the temp-inflated activation footprint
        task.out_bytes = out_bytes
    return compiled_gb
