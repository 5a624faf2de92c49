"""A whole module's share of a roofline, in %: the least time the chip
could take for one unit of the module's work (operations or bytes from a
function of ``benchmark/<costs>.py`` over the published peak) over the
module's mean device time a unit — ``module_time``'s events, line and
``per_event`` units (a decode segment's steps), ``op_in_module``'s
``costs`` / ``cost`` / ``peak``.  ``None`` where the trace has no such
module or the cost reads nothing, so the metric is left out."""

import importlib

from benchmark import peaks, xplane
from benchmark.metrics.readers import module_time


def read(ctx, params):
    trace = ctx.get("trace")
    if trace is None:
        return None
    total, n = xplane.matching_on_devices(
        trace, ctx["n_devices"], params.get("line") or xplane.MODULES_LINE,
        params["pattern"])
    units = module_time._units(ctx, params.get("per_event"))
    if not n or not units:
        return None
    work = getattr(importlib.import_module(
        "benchmark." + params["costs"]), params["cost"])(ctx)
    if not work:
        return None
    least_s = work / peaks.peaks_for(ctx["device_kind"])[params["peak"]]
    return 100.0 * least_s / (total / n / units / 1e9)
