"""Reduction of a profiler trace (``.xplane.pb``) to numbers.

``load`` turns the protobuf into plain data — planes, their lines, and
events as ``[name, start_ns, duration_ns]`` — with nothing but JAX
(``jax.profiler.ProfileData``); everything after that is pure Python on
that data, so the tests check it on a small recorded trace kept as JSON
(``fixtures/``).  Which planes are devices and which lines hold ops and
modules are parameters with the TPU's names as defaults; kernel and
module names are regexes in each metric's own file.

* busy time: the union of the intervals in which an op ran on a device;
* per-name time: the *self* time of each op (a ``while`` that contains
  its body's ops is charged only what they leave), and the summed
  duration and count of the events matching a regex;
* idle gaps: the longest intervals with no op on the device, each
  attributed to the host span (program tracer, mapped onto the trace's
  clock through one marker annotation) that covers most of it.
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = r"^/device:TPU:\d+$"
OPS_LINE = r"^XLA Ops$"
MODULES_LINE = r"^XLA Modules$"
SYNC_MARKER = "benchmark_sync"

Event = Sequence[Any]  # [name, start_ns, duration_ns]


def find_trace_file(log_dir: str) -> str:
    files = sorted(glob.glob(
        os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def short_name(name: str) -> str:
    """An op's event carries its whole HLO line as its name
    (``%fusion.12 = bf16[...] fusion(...)``): keep the op's own name."""
    return name.split(" = ", 1)[0].lstrip("%")


def op_kind(name: str) -> str:
    """``fusion.12`` -> ``fusion``: what the same op of another layer or
    step shares, for totals that fit in ten lines."""
    return re.sub(r"[.\d]+$", "", name) or name


def load(path: str) -> Dict[str, Any]:
    """The trace as plain data (see the module docstring)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({
                "name": line.name,
                "events": [[short_name(e.name), float(e.start_ns),
                            float(e.duration_ns)] for e in line.events],
            })
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_json(path: str) -> Dict[str, Any]:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def inventory(trace: Dict[str, Any]) -> List[str]:
    """One line per (plane, line) with its event count: what to look at
    before writing a regex against a trace."""
    return [f"{p['name']} / {ln['name']}: {len(ln['events'])} events"
            for p in trace["planes"] for ln in p["lines"]]


def device_planes(trace: Dict[str, Any],
                  pattern: Optional[str] = None) -> List[Dict[str, Any]]:
    rx = re.compile(pattern or DEVICE_PLANE)
    return sorted((p for p in trace["planes"] if rx.search(p["name"])),
                  key=lambda p: p["name"])


def line_events(plane: Dict[str, Any], line_pattern: str) -> List[Event]:
    """The events of every line of ``plane`` whose name matches."""
    rx = re.compile(line_pattern)
    out: List[Event] = []
    for ln in plane["lines"]:
        if rx.search(ln["name"]):
            out.extend(ln["events"])
    return out


def _merged(events: Iterable[Event]) -> List[Tuple[float, float]]:
    spans = sorted((e[1], e[1] + e[2]) for e in events if e[2] > 0)
    out: List[Tuple[float, float]] = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def busy_ns(events: Iterable[Event]) -> float:
    """Length of the union of the events' intervals."""
    return sum(b - a for a, b in _merged(events))


def self_times(events: Iterable[Event]) -> Dict[str, float]:
    """Per name, duration minus what nested events cover (ns)."""
    evs = sorted((e for e in events if e[2] > 0),
                 key=lambda e: (e[1], -e[2]))
    out: Dict[str, float] = {}
    stack: List[List[Any]] = []  # [name, end, self]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _end, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for name, start, dur in evs:
        close(start)
        end = start + dur
        if stack:
            end = min(end, stack[-1][1])
            stack[-1][2] -= end - start
        stack.append([name, end, end - start])
    close(float("inf"))
    return out


def matching(events: Iterable[Event], pattern: str) -> Tuple[float, int]:
    """(summed duration in ns, count) of the events whose name matches."""
    rx = re.compile(pattern)
    total, n = 0.0, 0
    for name, _start, dur in events:
        if rx.search(name):
            total, n = total + dur, n + 1
    return total, n


def matching_on_devices(trace: Dict[str, Any], n_devices: int,
                        line_pattern: str, pattern: str) -> Tuple[float, int]:
    """``matching`` summed over the first ``n_devices`` device planes."""
    total, n = 0.0, 0
    for plane in device_planes(trace)[:n_devices]:
        t, k = matching(line_events(plane, line_pattern), pattern)
        total, n = total + t, n + k
    return total, n


def idle_gaps(events: Iterable[Event], window: Tuple[float, float]
              ) -> List[Tuple[float, float]]:
    """Intervals of ``window`` (ns) in which no event ran."""
    w0, w1 = window
    gaps, cursor = [], w0
    for a, b in _merged(events):
        if b <= w0 or a >= w1:
            continue
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if cursor < w1:
        gaps.append((cursor, w1))
    return gaps


def sync_offset_ns(trace: Dict[str, Any], t_host_s: float,
                   marker: str = SYNC_MARKER) -> Optional[float]:
    """Trace-clock ns of host time 0: the marker annotation was entered at
    host time ``t_host_s``.  ``None`` when the trace lacks the marker."""
    for p in trace["planes"]:
        for ln in p["lines"]:
            for name, start, _dur in ln["events"]:
                if name == marker:
                    return start - t_host_s * 1e9
    return None


def attribute_gaps(gaps: Sequence[Tuple[float, float]],
                   host_spans: Sequence[Tuple[str, float, float]],
                   uncovered: str = "host: between program spans",
                   ) -> Dict[str, float]:
    """Seconds of idle per host activity: each gap is split among the
    host spans that overlap it (``host_spans`` on the trace's clock, ns;
    spans of one track do not nest), and what no span covers goes to
    ``uncovered``."""
    out: Dict[str, float] = {}
    spans = sorted(host_spans, key=lambda s: s[1])
    for g0, g1 in gaps:
        covered = 0.0
        for name, s0, s1 in spans:
            if s1 <= g0:
                continue
            if s0 >= g1:
                break
            part = min(g1, s1) - max(g0, s0)
            if part > 0:
                out[name] = out.get(name, 0.0) + part / 1e9
                covered += part
        rest = (g1 - g0) - covered
        if rest > 0:
            out[uncovered] = out.get(uncovered, 0.0) + rest / 1e9
    return out


def top(d: Dict[str, float], n: int = 10, scale: float = 1.0
        ) -> List[List[Any]]:
    return [[k, v * scale] for k, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def summarize(trace: Dict[str, Any], window_s: float,
              host_spans_s: Sequence[Tuple[str, float, float]] = (),
              t_sync_host_s: Optional[float] = None,
              n_devices: Optional[int] = None,
              device_plane: Optional[str] = None,
              ops_line: Optional[str] = None) -> Dict[str, Any]:
    """Busy seconds (mean over the devices used), the ops that took most
    self time, and idle seconds by what the host was doing on device 0.

    ``window_s`` is the traced window as the host measured it;
    ``host_spans_s`` are (name, t0, t1) on the host clock, mapped onto
    the trace's clock through the sync marker entered at
    ``t_sync_host_s``."""
    device_plane, ops_line = device_plane or DEVICE_PLANE, ops_line or OPS_LINE
    planes = device_planes(trace, device_plane)
    if n_devices is not None:
        planes = planes[:n_devices]
    if not planes:
        raise ValueError(
            f"no device plane matches {device_plane!r}; the trace has "
            f"{[p['name'] for p in trace['planes']]}")
    busy, ops = [], {}
    for p in planes:
        evs = line_events(p, ops_line)
        busy.append(busy_ns(evs) / 1e9)
        for k, v in self_times(evs).items():
            k = op_kind(k)
            ops[k] = ops.get(k, 0.0) + v / 1e9 / len(planes)
    out: Dict[str, Any] = {
        "busy_s": sum(busy) / len(busy), "busy_s_per_device": busy,
        "window_s": window_s, "device_ops": top(ops),
        "idle_gaps": [],
    }
    evs0 = line_events(planes[0], ops_line)
    if evs0:
        first = min(e[1] for e in evs0)
        last = max(e[1] + e[2] for e in evs0)
        offset = (sync_offset_ns(trace, t_sync_host_s)
                  if t_sync_host_s is not None else None)
        spans_ns = ([(n, a * 1e9 + offset, b * 1e9 + offset)
                     for n, a, b in host_spans_s] if offset is not None
                    else [])
        gaps = idle_gaps(evs0, (first, last))
        out["device_span_s"] = (last - first) / 1e9
        out["idle_gaps"] = top(attribute_gaps(gaps, spans_ns))
        out["longest_gap_ms"] = (
            max((b - a) for a, b in gaps) / 1e6 if gaps else 0.0)
    return out
