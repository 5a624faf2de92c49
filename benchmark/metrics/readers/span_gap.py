"""A percentile of the host time between consecutive program spans of
one name (the end of one to the start of the next), in ms: what the
host loop does between two device programs."""

from benchmark import stats


def read(ctx, params):
    spans = sorted(
        (e for e in ctx.get("spans", []) if e.get("type") == "span"
         and e.get("name") == params["span"] and e.get("t1") is not None),
        key=lambda e: e["t0"])
    lo = ctx.get("t0", float("-inf"))
    hi = lo + ctx.get("seconds", float("inf"))
    gaps = [(b["t0"] - a["t1"]) * 1e3 for a, b in zip(spans, spans[1:])
            if lo <= a["t0"] and b["t1"] <= hi]
    return stats.percentile(gaps, float(params["q"]))
