"""Arithmetic from request rows to numbers: percentiles, TTFT, TPOT,
tokens in the window, generator lateness.  Pure Python on plain dicts,
so the tests check it on hand-made rows."""

from __future__ import annotations

import math
from typing import Any, Dict, Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """``q``-th percentile (0..100), linear interpolation between the
    closest ranks (numpy's default).  ``None`` for no values."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    if len(xs) == 1:
        return xs[0]
    k = (len(xs) - 1) * (q / 100.0)
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def request_records(
    arrivals: Iterable[Any],
    rows: Iterable[Dict[str, Any]],
    first_stamp: Dict[str, float],
    t0: float,
    t_end: float,
) -> List[Dict[str, Any]]:
    """One record per request that was due in the window.

    ``arrivals`` carry ``rid``, ``t`` (due offset from ``t0``),
    ``prompt_len`` and ``max_new_tokens``; ``rows`` are the front-end's
    request rows; ``first_stamp`` the harness's own first-token times
    (absolute, same clock as ``t0``); ``t_end`` when the run (window plus
    drain) ended.  A request with no first token — shed, or unfinished
    when the run ended — is ranked with the time it had waited at
    ``t_end`` and is ``failed``; so is one that retired with another
    number of tokens than it asked for.  TTFT ends at the harness's
    stamp; TPOT runs from the row's first delivery to its retirement."""
    by_rid = {str(r["rid"]): r for r in rows}
    out = []
    for a in arrivals:
        rid = str(a.rid)
        row = by_rid.get(rid, {})
        due = t0 + float(a.t)
        t_first = first_stamp.get(rid)
        t_retire = row.get("t_retire")
        n = int(row.get("n_tokens") or 0)
        done = (row.get("state") == "retired" and t_first is not None
                and n == int(a.max_new_tokens))
        rec = {
            "rid": rid,
            "due": due,
            "prompt_len": int(a.prompt_len),
            "max_new_tokens": int(a.max_new_tokens),
            "n_tokens": n,
            "failed": not done,
            "t_first": t_first,
            "t_retire": t_retire,
            "ttft_ms": ((t_first if t_first is not None else t_end) - due)
            * 1e3,
            "tpot_ms": None,
            "queue_wait_ms": None,
            # the row's first delivery is the first token on the
            # program's clock; the harness's stamp stands for it
            "deliveries": [(float(t), int(k))
                           for t, k in row.get("deliveries", [])[1:]],
        }
        if done and n > 1:
            # from the rows' own deliveries: the harness's stamp is up to
            # a tick late, which is most of a short answer's decode time
            rec["tpot_ms"] = ((t_retire - float(row["deliveries"][0][0]))
                              / (n - 1) * 1e3)
        if row.get("t_admit") is not None:
            rec["queue_wait_ms"] = (row["t_admit"] - due) * 1e3
        out.append(rec)
    return out


def closed_before(records: Iterable[Dict[str, Any]],
                  t_cut: Optional[float]) -> List[Dict[str, Any]]:
    """The records as a traced run may read them: a latency whose
    interval had not closed by ``t_cut`` (when the profiler came on) is
    blanked, since starting and stopping the profiler stalls the host
    for seconds and would be most of it.  TTFT closes at the first
    token, TPOT at retirement.  ``t_cut`` ``None`` (no profiler) leaves
    every record as it is."""
    if t_cut is None:
        return list(records)
    out = []
    for r in records:
        r = dict(r)
        if r["t_first"] is None or r["t_first"] >= t_cut:
            r["ttft_ms"] = None
        if r["t_retire"] is None or r["t_retire"] >= t_cut:
            r["tpot_ms"] = None
        out.append(r)
    return out


def tokens_in_window(records: Iterable[Dict[str, Any]], t0: float,
                     t1: float) -> int:
    """Output tokens that became host-visible inside ``[t0, t1]``: the
    first token at the harness's stamp, the rest at their delivery."""
    total = 0
    for r in records:
        if r["t_first"] is not None and t0 <= r["t_first"] <= t1:
            total += 1
        for t, k in r["deliveries"]:
            if t0 <= t <= t1:
                total += k
    return total


def lateness_ms(pairs: Iterable[Sequence[float]]) -> Dict[str, Any]:
    """``pairs`` of (due, actually injected): how late the generator ran."""
    late = [(got - due) * 1e3 for due, got in pairs]
    return {"n": len(late), "p50_ms": percentile(late, 50),
            "max_ms": max(late) if late else None}
