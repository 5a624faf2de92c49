"""The mean of one field of the request records (a field that is
``None`` for a request — TPOT of one that failed — leaves it out)."""


def read(ctx, params):
    vals = [r[params["field"]] for r in ctx.get("records", [])
            if r.get(params["field"]) is not None]
    return sum(vals) / len(vals) if vals else None
