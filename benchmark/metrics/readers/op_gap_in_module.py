"""Device time BETWEEN two named ops inside the module events matching a
regex: from the end of each op matching ``after`` to the start of the
next op matching ``before`` in the same module event — what ran on the
device between two kernels, whatever XLA named it.  Summed over (module
events x ``per_event`` units), scaled.  ``None`` where the trace has no
such module or no such pair, so the metric is left out."""

import re

from benchmark import xplane
from benchmark.metrics.readers import module_time


def read(ctx, params):
    trace = ctx.get("trace")
    if trace is None:
        return None
    rx_a, rx_b = re.compile(params["after"]), re.compile(params["before"])
    rx_mod = re.compile(params["within"])
    total, pairs, mods = 0.0, 0, 0
    for plane in xplane.device_planes(trace)[:ctx["n_devices"]]:
        spans = sorted((s, s + d) for name, s, d in
                       xplane.line_events(plane, xplane.MODULES_LINE)
                       if rx_mod.search(name))
        mods += len(spans)
        marks = sorted(
            (start, dur, bool(rx_a.search(name)))
            for name, start, dur in xplane.line_events(plane, xplane.OPS_LINE)
            if (rx_a.search(name) or rx_b.search(name))
            and any(a <= start < b for a, b in spans))
        ended = None
        for start, dur, is_after in marks:
            if is_after:
                ended = start + dur
            elif ended is not None:
                total, pairs, ended = total + start - ended, pairs + 1, None
    units = module_time._units(ctx, params.get("per_event"))
    if not pairs or not mods or not units:
        return None
    return total / mods / units * float(params.get("scale", 1.0))
