"""The program's spans of one name, reduced one of two ways.

``stat`` ``share`` (the default): the time the spans cover inside the
traced slice over the slice's length, each span cut to the slice.
``stat`` ``p50``: the median duration, in ms, of the spans that lie
inside the window.

No span of that name gives ``None`` and the metric is left out — unless
the run drew spans named ``witness``: a program that draws those draws
this span whenever its condition holds, so none of them is a share of 0.
"""

from benchmark import stats


def _named(ctx, name):
    return [e for e in ctx.get("spans", []) if e.get("type") == "span"
            and e.get("name") == name and e.get("t1") is not None]


def read(ctx, params):
    spans = _named(ctx, params["span"])
    if params.get("stat", "share") == "p50":
        lo = ctx.get("t0", float("-inf"))
        hi = lo + ctx.get("seconds", float("inf"))
        return stats.percentile(
            [(e["t1"] - e["t0"]) * 1e3 for e in spans
             if lo <= e["t0"] and e["t1"] <= hi], 50)
    if "slice" not in ctx:
        return None
    if not spans and not _named(ctx, params.get("witness")):
        return None
    lo, hi = ctx["slice"]
    covered = sum(max(min(e["t1"], hi) - max(e["t0"], lo), 0.0)
                  for e in spans)
    return covered / (hi - lo)
