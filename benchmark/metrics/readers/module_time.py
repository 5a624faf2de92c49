"""Device time of the events matching a regex on one line of the device
planes, per event and per unit of work, from the profiler's trace.

``per_event``: units of work in one event — a number, a path into the
configuration (``["engine", "seg_steps"]``), or the mean of an argument
of the program's spans of one name inside the traced slice
(``{"span": "prefill_chunk", "arg": "tokens"}``)."""

from benchmark import xplane


def _units(ctx, spec):
    if spec is None:
        return 1.0
    if isinstance(spec, (int, float)):
        return float(spec)
    if isinstance(spec, list):
        node = ctx["config"]
        for key in spec:
            node = node[key]
        return float(node)
    lo, hi = ctx["slice"]
    vals = [e["args"][spec["arg"]] for e in ctx["spans"]
            if e.get("type") == "span" and e.get("name") == spec["span"]
            and lo <= e["t0"] <= hi]
    return sum(vals) / len(vals) if vals else None


def read(ctx, params):
    trace = ctx.get("trace")
    if trace is None:
        return None
    total, n = xplane.matching_on_devices(
        trace, ctx["n_devices"], params.get("line") or xplane.MODULES_LINE,
        params["pattern"])
    units = _units(ctx, params.get("per_event"))
    if not n or not units:
        return None
    return total / n / units * float(params.get("scale", 1.0))
