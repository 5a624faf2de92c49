"""Collective-ordering analysis (COL00x): deadlock-freedom for SPMD
strategies.

Collectives are rendezvous points: when per-device programs disagree on
which collective comes next on a mesh axis, a real multi-chip mesh hangs
(the CPU-faked mesh would too, if the divergence survived lowering).
:func:`analyze_collectives_jaxpr` verifies the property statically: it
walks a traced jaxpr (e.g. ``parallel/ring_attention.py``'s shard_map
body) and checks that ``cond``/``switch`` branches issue matching
collective sequences per axis (COL003) — divergent branch sequences are
exactly how a "same program" SPMD lowering smuggles in per-device
divergence — plus COL004 permutation validity on every ``ppermute``
encountered (repeated sources or destinations make the rendezvous
ill-defined).  ``parallel_sweep.py`` runs it over every strategy in
``parallel/``; the MPMD happens-before checks (COL005-COL007) live in
:mod:`.hb_pass`.
"""

from __future__ import annotations

from typing import Any, List, Sequence, Tuple

from .diagnostics import AnalysisReport, Severity

#: collective primitives that rendezvous over a mesh axis (jaxpr walk)
_COLLECTIVE_PRIMS = frozenset(
    {
        "ppermute", "psum", "pmax", "pmin", "all_gather", "all_to_all",
        "reduce_scatter", "psum_scatter", "pbroadcast",
    }
)


def _check_perm(
    rep: AnalysisReport, perm: Sequence[Tuple[int, int]], where: str,
) -> None:
    srcs = [s for s, _ in perm]
    dsts = [d for _, d in perm]
    bad = []
    if len(set(srcs)) != len(srcs):
        bad.append("repeated source")
    if len(set(dsts)) != len(dsts):
        bad.append("repeated destination")
    if bad:
        rep.add(
            "COL004",
            Severity.ERROR,
            f"{where}: perm {list(perm)} is not a valid partial "
            f"permutation ({', '.join(bad)})",
        )


# -- jaxpr walk (SPMD strategies) ---------------------------------------


def _walk_jaxpr(jaxpr: Any, rep: AnalysisReport, where: str) -> List[Tuple]:
    """Collective sequence of one (sub)jaxpr, recursing into control
    flow.  ``cond``/``switch`` branches are compared pairwise (COL003);
    the sequence of the first branch stands in for the whole op (after a
    divergence is reported, one representative keeps the walk going)."""
    seq: List[Tuple] = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _COLLECTIVE_PRIMS:
            axes = eqn.params.get("axis_name", eqn.params.get("axes"))
            perm = eqn.params.get("perm")
            seq.append((name, axes, tuple(perm) if perm else None))
            if name == "ppermute" and perm:
                _check_perm(rep, perm, where)
            continue
        if name == "cond":
            branches = eqn.params.get("branches", ())
            branch_seqs = [
                _walk_jaxpr(b.jaxpr, rep, f"{where}/cond[{i}]")
                for i, b in enumerate(branches)
            ]
            ref = branch_seqs[0] if branch_seqs else []
            for i, bs in enumerate(branch_seqs[1:], start=1):
                if bs != ref:
                    rep.add(
                        "COL003",
                        Severity.ERROR,
                        f"{where}: cond/switch branch {i} issues "
                        f"{len(bs)} collective(s) {bs} but branch 0 "
                        f"issues {len(ref)} {ref} — per-device "
                        "divergence inside one SPMD program",
                    )
            seq.extend(ref)
            continue
        # recurse into every other sub-jaxpr (scan/while bodies, pjit,
        # shard_map, custom calls): their collectives execute on every
        # device in program order
        for sub in _subjaxprs(eqn):
            seq.extend(_walk_jaxpr(sub, rep, f"{where}/{name}"))
    return seq


#: eqn params holding the primal jaxpr of a custom-derivative call
#: (``custom_jvp_call``/``custom_vjp_call``; jax renamed both the
#: primitive and the param across versions, so resolve by name first
#: rather than trusting duck-typing alone — a collective wrapped in a
#: custom-derivative rule must never be silently skipped)
_CUSTOM_CALL_PARAMS = ("call_jaxpr", "fun_jaxpr")


def _subjaxprs(eqn: Any):
    """Sub-jaxprs of one eqn: scan/while bodies, pjit/shard_map programs,
    and custom_jvp_call/custom_vjp_call primal jaxprs.  Each distinct
    jaxpr yields once (the custom-call params are also reachable through
    the generic duck-typed walk on some jax versions)."""
    seen: set = set()

    def emit(v):
        j = getattr(v, "jaxpr", None)
        if j is None or not hasattr(j, "eqns"):
            j = v if hasattr(v, "eqns") else None
        if j is not None and id(j) not in seen:
            # dls-lint: allow(DET004) in-process jaxpr dedup, never serialized
            seen.add(id(j))
            yield j

    if eqn.primitive.name.startswith(("custom_jvp_call", "custom_vjp_call")):
        for key in _CUSTOM_CALL_PARAMS:
            v = eqn.params.get(key)
            if v is not None:
                yield from emit(v)
    for v in eqn.params.values():
        yield from emit(v)
        if isinstance(v, (tuple, list)):
            for w in v:
                yield from emit(w)


def analyze_collectives_jaxpr(
    fn_or_jaxpr: Any, *example_args: Any, where: str = "program"
) -> AnalysisReport:
    """COL003/COL004 over a traced function or a closed jaxpr.

    Pass either a ``jax.make_jaxpr`` result (or anything exposing
    ``.jaxpr.eqns``) or a callable plus example arguments to trace.  The
    walk records the collective sequence and errors when control-flow
    branches would issue divergent sequences (COL003) or a ``ppermute``
    permutation is malformed (COL004).
    """
    rep = AnalysisReport()
    jaxpr = fn_or_jaxpr
    if callable(fn_or_jaxpr) and not hasattr(fn_or_jaxpr, "eqns"):
        import jax

        jaxpr = jax.make_jaxpr(fn_or_jaxpr)(*example_args)
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    _walk_jaxpr(inner, rep, where)
    return rep
