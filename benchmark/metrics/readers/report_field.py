"""The median over the window's steps of one field of the executor's
own report (``DeviceReport``), scaled."""

import statistics


def read(ctx, params):
    vals = [r[params["field"]] for r in ctx.get("reports", [])
            if r.get(params["field"]) is not None]
    if not vals:
        return None
    return statistics.median(vals) * float(params.get("scale", 1.0))
