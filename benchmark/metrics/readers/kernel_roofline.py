"""A kernel's share of its roofline, in %: the least time the chip could
take for one call (operations or bytes from ``benchmark/costs.py`` over
the published peak) over the mean device time of the kernel's events."""

from benchmark import costs, peaks, xplane


def read(ctx, params):
    trace = ctx.get("trace")
    if trace is None:
        return None
    total, n = xplane.matching_on_devices(
        trace, ctx["n_devices"], params.get("line") or xplane.OPS_LINE,
        params["pattern"])
    if not n:
        return None
    work = getattr(costs, params["cost"])(ctx)
    if not work:
        return None
    least_s = work / peaks.peaks_for(ctx["device_kind"])[params["peak"]]
    return 100.0 * least_s / (total / n / 1e9)
