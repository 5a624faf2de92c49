"""Static analysis over graphs, schedules, clusters, and sharding specs.

Multi-pass analyzer emitting structured :class:`Diagnostic` records with
stable codes (``DAG001`` cycle, ``MEM003`` hbm-overcommit, ``SHD002``
spec-rank-mismatch, ...) instead of ad-hoc exceptions — see
docs/ANALYSIS.md for the full catalogue.  Entry points:

* :func:`analyze` — run every applicable pass, return one report (the
  ``lint`` CLI subcommand is a thin wrapper over this);
* :func:`pre_execution_gate` — the cheap corruption subset the backends
  run before executing a schedule; raises :class:`AnalysisError`.
  Opt out per-call with ``pre_analysis=False`` on the backend or globally
  with ``DLS_SKIP_ANALYSIS=1`` in the environment;
* ``core.validate.validate_schedule`` — the historical API, now a thin
  shim over the schedule + memory passes.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional, Tuple

from ..core.cluster import Cluster
from ..core.graph import TaskGraph
from ..core.schedule import Schedule
from .diagnostics import (
    CODES,
    JSON_SCHEMA,
    AnalysisError,
    AnalysisReport,
    Diagnostic,
    Severity,
)
from .collective_pass import analyze_collectives_jaxpr
from .cost_pass import analyze_cost
from .decode_pass import analyze_decode
from .determinism_pass import analyze_determinism
from .donation_pass import analyze_donation
from .fixes import fix_duplicate_dependencies, fix_per_node_order
from .graph_pass import analyze_graph
from .lifecycle_pass import analyze_lifecycle
from .page_pass import analyze_pages, analyze_serve_artifact
from .hb_pass import StageOp, analyze_happens_before, stage_programs_1f1b
from .incremental import AnalysisDelta, IncrementalAnalyzer
from .memory_pass import analyze_memory, node_memory_slice
from .parallel_sweep import sweep_parallel_collectives
from .pipeline_pass import analyze_pipeline
from .quant_pass import analyze_quantization
from .schedule_pass import analyze_schedule
from .sharding_pass import analyze_sharding
from .stream_pass import analyze_streaming
from .typecheck_pass import analyze_typecheck

__all__ = [
    "CODES",
    "AnalysisDelta",
    "AnalysisError",
    "AnalysisReport",
    "Diagnostic",
    "IncrementalAnalyzer",
    "JSON_SCHEMA",
    "Severity",
    "StageOp",
    "analyze",
    "analyze_collectives_jaxpr",
    "analyze_cost",
    "analyze_decode",
    "analyze_determinism",
    "analyze_donation",
    "analyze_happens_before",
    "analyze_lifecycle",
    "analyze_pages",
    "analyze_serve_artifact",
    "analyze_graph",
    "analyze_memory",
    "analyze_pipeline",
    "analyze_quantization",
    "analyze_schedule",
    "analyze_sharding",
    "analyze_streaming",
    "analyze_typecheck",
    "fix_duplicate_dependencies",
    "fix_per_node_order",
    "gate_enabled",
    "node_memory_slice",
    "pre_execution_gate",
    "stage_programs_1f1b",
    "sweep_parallel_collectives",
]

#: Setting this env var to anything non-empty (and not "0") disables the
#: backend pre-execution gate globally.
SKIP_ENV = "DLS_SKIP_ANALYSIS"


def gate_enabled() -> bool:
    from ..utils.config import env_str

    return env_str(SKIP_ENV, "0") in ("", "0")


def analyze(
    graph: TaskGraph,
    cluster: Optional[Cluster] = None,
    schedule: Optional[Schedule] = None,
    *,
    strict: bool = False,
    param_shapes: Optional[Dict[str, Tuple[int, ...]]] = None,
    mesh_axes: Optional[Dict[str, int]] = None,
    family: str = "gpt2",
    seq_parallel: bool = False,
    param_specs: Optional[Dict[str, Any]] = None,
    compiled_gb: Optional[Dict[str, float]] = None,
    analytic_gb: Optional[Dict[str, float]] = None,
    stage_programs: Optional[Dict[str, Any]] = None,
    plan: Optional[Any] = None,
    params: Optional[Dict[str, Any]] = None,
    graph_input: Any = None,
    page_events: Any = None,
    request_log: Any = None,
    request_log_final: bool = False,
    chunk_tokens: Optional[int] = None,
    decode_budget: Optional[int] = None,
) -> AnalysisReport:
    """Run every pass the provided inputs make applicable.

    Graph hygiene always runs; schedule-consistency, memory, pipeline,
    typecheck (TYP001-TYP003, fed by ``params`` — concrete arrays or a
    spec table — and ``graph_input`` when available), and stream-safety
    (STR001-STR003) passes run when ``cluster`` and ``schedule`` are
    given; the sharding pass runs when ``param_shapes`` + ``mesh_axes``
    are given; the quantization pass runs when ``param_specs`` is given
    (``param_specs`` also feeds the typecheck pass's QNT metadata); the
    cost pass runs when ``compiled_gb`` (an
    ``utils.hbm.preflight_task_memory`` result, with ``analytic_gb`` the
    pre-preflight snapshot) is given; the MPMD happens-before pass runs
    when ``stage_programs`` (per-stage op sequences, see
    :mod:`.hb_pass`) is given; the donation pass runs when ``plan`` (a
    DispatchPlan or its metadata dict, see
    :mod:`.donation_pass`) is given; the page-lifetime prover runs when
    ``page_events`` (a ``PageOwnershipLog``/snapshot, see
    :mod:`.page_pass`) is given; the request-lifecycle checker runs when
    ``request_log`` (a ``RequestLog``/snapshot/row list, with
    ``request_log_final=True`` for completed runs) is given.

    The returned report is stamped with ``schedule.signature()`` when a
    schedule was analyzed, so it can be handed straight back to
    :func:`pre_execution_gate` as ``precomputed=`` without re-running
    the base passes.
    """
    rep = analyze_graph(graph)
    # DEC005 (kernel eligibility) needs the pool spec shapes; either the
    # quantization spec table or the typecheck param table carries them
    rep.extend(
        analyze_decode(graph, cluster, schedule,
                       param_specs=param_specs or params,
                       chunk_tokens=chunk_tokens,
                       decode_budget=decode_budget)
    )
    if cluster is not None and schedule is not None:
        rep.extend(analyze_schedule(graph, cluster, schedule))
        rep.extend(analyze_memory(graph, cluster, schedule, strict=strict))
        rep.extend(analyze_pipeline(graph, schedule))
        rep.extend(
            analyze_typecheck(
                graph,
                cluster,
                schedule,
                params=params,
                param_specs=param_specs,
                graph_input=graph_input,
            )
        )
        rep.extend(analyze_streaming(graph, cluster, schedule))
    if param_shapes is not None and mesh_axes is not None:
        rep.extend(
            analyze_sharding(
                param_shapes,
                mesh_axes,
                family,
                seq_parallel=seq_parallel,
            )
        )
    if param_specs is not None:
        rep.extend(analyze_quantization(graph, param_specs))
    if compiled_gb is not None:
        rep.extend(analyze_cost(graph, compiled_gb, analytic_gb))
    if stage_programs is not None:
        rep.extend(analyze_happens_before(stage_programs))
    if plan is not None:
        rep.extend(analyze_donation(plan))
    if page_events is not None:
        rep.extend(analyze_pages(page_events))
    if request_log is not None:
        rep.extend(
            analyze_lifecycle(request_log, final=request_log_final)
        )
    if schedule is not None:
        rep.schedule_signature = schedule.signature()
    return rep


# Schedules the backends accept by contract are a superset of what the
# full analyzer calls clean: the device backend legalizes per-node order
# inversions (``dispatch_order``) and drops tasks whose dependencies were
# never placed (graceful degradation), and both backends accept schedules
# covering only part of the graph.  The gate therefore checks only the
# defects that would *corrupt* a replay or dispatch, per backend.
_GATE_CODES = {
    "sim": frozenset(
        {"DAG001", "DAG002", "DAG005", "DAG007", "DEC001", "DEC003",
         "SCH001", "SCH002", "SCH003", "SCH009", "PIP001", "PIP002"}
    ),
    "device": frozenset(
        {"DAG001", "DAG002", "DAG005", "DAG007", "DEC001", "DEC003",
         "SCH001", "SCH002", "SCH003"}
    ),
}


def pre_execution_gate(
    graph: TaskGraph,
    cluster: Cluster,
    schedule: Schedule,
    backend: str = "sim",
    plan: Optional[Any] = None,
    stage_programs: Optional[Dict[str, Any]] = None,
    precomputed: Optional[AnalysisReport] = None,
) -> Optional[AnalysisReport]:
    """Cheap (O(V+E)) corruption check run by the backends before work.

    Raises :class:`AnalysisError` when the schedule would corrupt this
    backend's execution; returns the (possibly empty) report otherwise,
    or ``None`` when the gate is disabled via ``DLS_SKIP_ANALYSIS``.

    ``precomputed``: a report :func:`analyze` just produced for THIS
    schedule — accepted, and the base passes skipped, only when its
    stamped ``schedule_signature`` matches ``schedule.signature()`` (the
    identity dispatch is a pure function of); on any mismatch the gate
    silently falls back to running the passes itself.  Reports from
    other sources (e.g. ``IncrementalAnalyzer.report``) must not be
    passed here: they cover a narrower pass suite than the gate
    filters.  Extras (``plan`` / ``stage_programs``) still run fresh:
    the precomputed report predates those artifacts.

    ``plan``: a DispatchPlan or its donation metadata — the
    donation-alias pass joins the gate (DON001-DON003: a donated buffer read, donated
    twice, or donated across a device boundary corrupts silently).

    ``stage_programs`` (MPMD lowerings): per-stage op sequences — the
    happens-before pass joins the gate (COL005 wait cycles, COL006
    unmatched channel cardinality; COL007 is a warning and never gates).
    """
    if not gate_enabled():
        return None
    codes = _GATE_CODES[backend]
    reused = (
        precomputed is not None
        and precomputed.schedule_signature is not None
        and precomputed.schedule_signature == schedule.signature()
    )
    if reused:
        # the caller just analyzed this exact scheduling decision: its
        # diagnostics cover everything the base passes would re-derive
        # (analyze()'s SCH004 permutation check subsumes the sim replay's
        # unplaced-order scan)
        rep = AnalysisReport(list(precomputed.diagnostics))
    else:
        rep = analyze_graph(graph)
        rep.extend(analyze_decode(graph, cluster, schedule))
        rep.extend(analyze_schedule(graph, cluster, schedule))
    if plan is not None:
        rep.extend(analyze_donation(plan))
        codes = codes | {"DON001", "DON002", "DON003"}
    if stage_programs is not None:
        rep.extend(analyze_happens_before(stage_programs))
        codes = codes | {"COL005", "COL006"}
    if backend == "sim":
        if not reused:
            rep.extend(analyze_pipeline(graph, schedule))
            # the replay indexes placement[tid] for every ordered task
            placed = {t for ts in schedule.per_node.values() for t in ts}
            for tid in schedule.assignment_order:
                if tid not in placed:
                    rep.add(
                        "SCH004",
                        Severity.ERROR,
                        f"assignment_order task {tid!r} has no placement",
                        task=tid,
                    )
                    break
        codes = codes | {"SCH004"}
    gated = AnalysisReport(
        [d for d in rep.diagnostics if d.code in codes]
    )
    gated.raise_if_errors()
    return gated


def _spec_shapes(specs: Optional[Dict[str, Any]]) -> Dict[str, Tuple[int, ...]]:
    """Shape dict from a ModelDAG ``param_specs`` mapping; QParam entries
    report their int8 payload's shape (the sharded axis layout)."""
    from ..utils.quantize import QParam

    out: Dict[str, Tuple[int, ...]] = {}
    for name, spec in (specs or {}).items():
        if isinstance(spec, QParam):
            spec = spec.q
        shape = getattr(spec, "shape", None)
        if shape is not None:
            out[name] = tuple(shape)
    return out
