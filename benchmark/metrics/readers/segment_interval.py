"""The decode-step interval tiled on the device's clock.

Between the starts of two consecutive decode-segment programs (events
matching ``segment`` on the "XLA Modules" line of a device plane) the
device ran the earlier segment, then whatever prefill programs were
queued behind it (events matching ``prefill`` that start inside the
interval), and for the rest had no program of either kind:

    interval = segment + prefill + idle        (by construction)

``part`` names the term read; the value is its sum over the pairs, over
pairs x ``per_event`` units of work (``module_time``'s convention: the
segment's steps), scaled.  Only a *continuing* pair counts — one in
which some slot decoded in both segments, so that a user waited through
the interval.  A pair whose interval overlaps a host annotation named
``skip`` (the front-end asleep beside an empty engine) is none; and
where the program's own ``segment`` spans say how many slots continued
(argument ``continuing``; mapped onto the trace's clock through the
sync marker, entered at the slice's start), a pair whose later segment
says 0 is none either: one request's last segment, then the next one's
whole prefill with nobody decoding.  ``None`` where the trace holds no
continuing pair, so the metric is left out.

The tiling is computed once a context and logged: every part per step,
and the idle part split by the host annotation (``dls/<name>``, on the
host planes of the same trace, so on the device's clock) that covers it
— whether the device waited under ``fold``, ``admit``, a chunk's
dispatch or the segment's own dispatch and readback.
"""

import re

from benchmark import harness, xplane
from benchmark.metrics.readers import module_time

ANNOTATION = "dls/"
PARTS = ("interval", "segment", "prefill", "idle")


def _annotations(trace):
    """(name, start, end) of the program's host annotations, nested ones
    cut out of what encloses them (``dls/prefill`` lies inside the
    engine's ``dls/admit``), so that every instant has one name."""
    rx = re.compile(xplane.DEVICE_PLANE)
    spans = sorted(
        ((name, start, start + dur) for p in trace["planes"]
         if not rx.search(p["name"]) for ln in p["lines"]
         for name, start, dur in ln["events"]
         if name.startswith(ANNOTATION) and dur > 0),
        key=lambda s: (s[1], -s[2]))
    out, stack = [], []   # stack of [name, cursor, end]

    def close(upto):
        while stack and stack[-1][2] <= upto:
            name, cursor, end = stack.pop()
            if end > cursor:
                out.append((name, cursor, end))
            if stack:
                stack[-1][1] = max(stack[-1][1], end)

    for name, start, end in spans:
        close(start)
        if stack:
            end = min(end, stack[-1][2])
            if start > stack[-1][1]:
                out.append((stack[-1][0], stack[-1][1], start))
            stack[-1][1] = start
        stack.append([name, start, end])
    close(float("inf"))
    return out


def all_new(ctx):
    """``(start, end)`` on the trace's clock of the program's ``segment``
    spans that say no slot continued into them; empty where the program
    does not say or the trace has no sync marker."""
    trace, lo = ctx.get("trace"), ctx.get("slice", (None,))[0]
    offset = xplane.sync_offset_ns(trace, lo) if lo is not None else None
    if offset is None:
        return []
    return [(e["t0"] * 1e9 + offset, e["t1"] * 1e9 + offset)
            for e in ctx.get("spans", ())
            if e.get("type") == "span" and e.get("name") == "segment"
            and e.get("t1") is not None
            and e.get("args", {}).get("continuing") == 0]


def tile(trace, n_devices, segment, prefill, skip=None, new=()):
    """Sums in ns over the continuing pairs of every device used:
    ``{"pairs", "skipped", "interval", "segment", "prefill", "idle",
    "idle_under": {annotation: ns}}``.  ``new``: :func:`all_new`."""
    rx_seg, rx_pre = re.compile(segment), re.compile(prefill)
    notes = _annotations(trace)
    asleep = [(a, b) for name, a, b in notes if name == skip]
    out = {k: 0.0 for k in PARTS}
    out.update(pairs=0, skipped=0, idle_under={})
    for plane in xplane.device_planes(trace)[:n_devices]:
        mods = sorted(xplane.line_events(plane, xplane.MODULES_LINE),
                      key=lambda e: e[1])
        segs = [e for e in mods if rx_seg.search(e[0])]
        pres = [e for e in mods if rx_pre.search(e[0])]
        for a, b in zip(segs, segs[1:]):
            t0, t1 = a[1], b[1]
            # the later segment starts inside the span that dispatched it
            if (any(s < t1 and e > t0 for s, e in asleep)
                    or any(s <= t1 <= e for s, e in new)):
                out["skipped"] += 1
                continue
            ran = [a] + [e for e in pres if t0 <= e[1] < t1]
            gaps = xplane.idle_gaps(ran, (t0, t1))
            idle = sum(g1 - g0 for g0, g1 in gaps)
            out["pairs"] += 1
            out["interval"] += t1 - t0
            out["segment"] += a[2]
            out["idle"] += idle
            out["prefill"] += (t1 - t0) - a[2] - idle
            for name, sec in xplane.attribute_gaps(
                    gaps, notes, uncovered="no annotation").items():
                out["idle_under"][name] = (
                    out["idle_under"].get(name, 0.0) + sec * 1e9)
    return out


def read(ctx, params):
    trace = ctx.get("trace")
    if trace is None:
        return None
    key = ("segment_interval", params["segment"], params["prefill"],
           params.get("skip"))
    tiling = ctx.get(key)
    units = module_time._units(ctx, params.get("per_event"))
    if tiling is None:
        tiling = ctx[key] = tile(
            trace, ctx["n_devices"], params["segment"], params["prefill"],
            params.get("skip"), all_new(ctx))
    if not tiling["pairs"] or not units:
        return None
    per = float(params.get("scale", 1.0)) / tiling["pairs"] / units
    if not tiling.get("logged"):
        tiling["logged"] = True
        harness.log(
            "segment interval: "
            + ", ".join(f"{k} {tiling[k] * per:.4f}" for k in PARTS)
            + f" a step over {tiling['pairs']} pairs ({tiling['skipped']} "
            "with no continuing slot left out); idle under " + ", ".join(
                f"{name} {v:.4f}" for name, v in xplane.top(
                    tiling["idle_under"], scale=per)))
    return tiling[params["part"]] * per
