"""The mean of one argument of the program's spans of one name that
began inside the traced slice, over ``per_event`` units of work: what
the program counted for exactly the slice the device metrics of the same
line read.  ``None`` where the run drew no such span with that argument
(a program without the counter, an untraced run), so the metric is left
out."""

from benchmark.metrics.readers import module_time


def read(ctx, params):
    lo, hi = ctx.get("slice", (None, None))
    if lo is None:
        return None
    vals = [float(e["args"][params["arg"]]) for e in ctx.get("spans", ())
            if e.get("type") == "span" and e.get("name") == params["span"]
            and params["arg"] in e.get("args", {}) and lo <= e["t0"] <= hi]
    units = module_time._units(ctx, params.get("per_event"))
    if not vals or not units:
        return None
    return sum(vals) / len(vals) / units * float(params.get("scale", 1.0))
