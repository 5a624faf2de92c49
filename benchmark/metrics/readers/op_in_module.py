"""Device time of the ops matching a regex that ran INSIDE the module
events matching another — a kernel's calls in decode segments apart from
its calls in prefill programs — per module event and unit of work, or as
a share of the kernel's roofline.

``within``: regex on the "XLA Modules" line.  Without ``cost``: summed
op time over (module events x ``per_event`` units), scaled.  With
``cost`` (a function of ``benchmark/<costs>.py``, ``costs`` naming the
module): 100 x least time of one call over the ops' mean time, as
``kernel_roofline`` computes it.  ``None`` where the trace has no such
op or module, so the metric is left out."""

import importlib

from benchmark import peaks, xplane
from benchmark.metrics.readers import module_time


def _inside(trace, n_devices, pattern, within):
    """(summed ns, count) of matching ops that start inside a matching
    module event, and the number of those module events."""
    import re

    rx_op, rx_mod = re.compile(pattern), re.compile(within)
    total, n, mods = 0.0, 0, 0
    for plane in xplane.device_planes(trace)[:n_devices]:
        spans = sorted((s, s + d) for name, s, d in
                       xplane.line_events(plane, xplane.MODULES_LINE)
                       if rx_mod.search(name))
        mods += len(spans)
        for name, start, dur in xplane.line_events(plane, xplane.OPS_LINE):
            if rx_op.search(name) and any(a <= start < b for a, b in spans):
                total, n = total + dur, n + 1
    return total, n, mods


def read(ctx, params):
    trace = ctx.get("trace")
    if trace is None:
        return None
    total, n, mods = _inside(trace, ctx["n_devices"], params["pattern"],
                             params["within"])
    if not n or not mods:
        return None
    if "cost" in params:
        work = getattr(importlib.import_module(
            "benchmark." + params["costs"]), params["cost"])(ctx)
        if not work:
            return None
        least_s = work / peaks.peaks_for(ctx["device_kind"])[params["peak"]]
        return 100.0 * least_s / (total / n / 1e9)
    units = module_time._units(ctx, params.get("per_event"))
    if not units:
        return None
    return total / mods / units * float(params.get("scale", 1.0))
