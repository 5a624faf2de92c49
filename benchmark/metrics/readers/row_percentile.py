"""A percentile of one field of the request records (all requests due in
the window; a field that is ``None`` for a request — TPOT of one that
failed, or in a traced run of one still in flight when the profiler came
on — leaves that request out)."""

from benchmark import stats


def read(ctx, params):
    vals = [r[params["field"]] for r in ctx.get("records", [])
            if r.get(params["field"]) is not None]
    return stats.percentile(vals, float(params["q"]))
