"""Structured span tracer: the host-side event recorder behind DLS_TRACE.

One :class:`Tracer` instance records everything a run emits — nested
spans (phases of ``DeviceBackend.execute``, per-launch dispatch windows,
decode-engine segments), instant markers (fences, retires), counter
samples (page-pool occupancy, queue depth), and flow edges (cross-device
transfers) — as plain dicts on a Python list.  Nothing is interpreted at
record time; :mod:`..obs.export` renders the list as a Chrome/Perfetto
``traceEvents`` JSON after the run.

Design constraints, in order:

* **Nothing per launch or per token when off.**  Tracing is opt-in;
  every per-launch and per-token recording point guards with ``if
  tracer is not None`` and does *no* work otherwise.  There is
  deliberately no no-op tracer object: a None check is cheaper than a
  dispatched no-op method call, and the call sites stay honest about
  what runs in the disabled path.  What stays on without a tracer is
  per *call* and per *tick*: the phase clock of ``execute`` (about
  twenty clock reads, :class:`PhaseClock`) and the profiler annotations
  below.  The measured cost of an attached tracer is in PERF.md.
* **Injectable clock.**  ``Tracer(clock=...)`` takes any ``() -> float``
  seconds source; tests drive a fake clock and assert exact span
  nesting/ordering.  Default is ``time.perf_counter`` — the same
  timebase the backend's measured timings use, so profile-mode task
  walls and tracer spans land on one consistent timeline.
* **Host-side only.**  Spans bound *host* observations (dispatch
  windows, segment round-trips): a span says when the host entered and
  left a piece of its own code, never when the device ran.  Device-side
  truth comes from the profiler's device trace, or from profile-mode
  ``block_until_ready`` timings, which callers record via
  :meth:`Tracer.complete` with explicit timestamps.
* **The phase boundaries also go to the profiler.**  The leaf phases of
  ``execute`` (``dispatch_order``, ``place_params``, ``plan_build``,
  ``warmup``, ``fence_rtt``, ``stage_input``, ``dispatch_loop``,
  ``fence``, ``report``) and of the serving tick (``admit``,
  ``prefill``, ``prefill_chunk``, ``segment``, ``fold``, ``idle_wait``)
  are entered as ``jax.profiler.TraceAnnotation("dls/<name>")``
  (:func:`annotate`), tracer or not: a profile taken by anyone shows
  the host phases on the device trace's own clock.  Nothing per launch
  or per token is annotated.

Track names are free-form strings; by convention ``"host"``
(:data:`HOST_TRACK`) carries the execute phases and every device node_id
(``node_0`` …) carries its launches.  The span catalogue is documented in
``docs/OBSERVABILITY.md``.
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Any, Callable, Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation

from .clockutil import resolve_clock

HOST_TRACK = "host"

# event categories (Chrome "cat" field): the execute phase machine plus
# the decode engine's lifecycle — see docs/OBSERVABILITY.md
CAT_CALL = "call"           # enclosing spans: ``execute`` and ``rep<r>``
CAT_SCHEDULE = "schedule"   # dispatch-order linearization
CAT_PLAN = "plan"           # plan build + warmup compilation
CAT_STAGE = "stage"         # param placement + transfer staging
CAT_LAUNCH = "launch"       # executable calls (tasks, fused groups)
CAT_COLLECT = "collect"     # end-of-run fence + readbacks
CAT_TASK = "task"           # per-task device spans (profile timings)
CAT_TRANSFER = "transfer"   # cross-device flow edges
CAT_DECODE = "decode"       # paged decode engine lifecycle

ANNOTATION_PREFIX = "dls/"


def annotate(name: str) -> TraceAnnotation:
    """``with annotate("fold"):`` enters ``dls/fold`` on the profiler's
    host timeline; without a profiler session it does nothing."""
    return TraceAnnotation(ANNOTATION_PREFIX + name)


class Tracer:
    """Append-only event recorder with an injectable clock.

    Events are dicts with a ``type`` discriminant:

    * ``span``:    {name, track, cat, t0, t1, args}
    * ``instant``: {name, track, cat, t, args}
    * ``counter``: {name, t, value}
    * ``flow``:    {name, cat, id, src_track, src_ts, dst_track, dst_ts,
                    args}

    Timestamps are raw clock values (seconds); the exporter normalizes
    to the earliest event.  Not thread-safe — the dispatch loop and the
    decode engine are single-threaded host code, and keeping the record
    path to a dict literal + ``list.append`` is what keeps enabled-mode
    overhead per launch in the sub-microsecond range.
    """

    def __init__(self, clock: Optional[Callable[[], float]] = None):
        self.clock: Callable[[], float] = resolve_clock(clock)
        self.events: List[Dict[str, Any]] = []
        self._open: List[Dict[str, Any]] = []
        self._flow_id = 0

    def now(self) -> float:
        return self.clock()

    # -- spans -------------------------------------------------------------
    def begin(
        self, name: str, track: str = HOST_TRACK, cat: str = "host",
        **args: Any,
    ) -> Dict[str, Any]:
        """Open a span; close it with :meth:`end`.  For phases whose
        boundaries straddle control flow (the rep loop); prefer
        :meth:`span` where a ``with`` block fits."""
        ev = {
            "type": "span", "name": name, "track": track, "cat": cat,
            "t0": self.clock(), "t1": None, "args": args,
        }
        self._open.append(ev)
        return ev

    def end(self, ev: Dict[str, Any], **args: Any) -> Dict[str, Any]:
        ev["t1"] = self.clock()
        if args:
            ev["args"].update(args)
        if ev in self._open:
            self._open.remove(ev)
        self.events.append(ev)
        return ev

    @contextmanager
    def span(
        self, name: str, track: str = HOST_TRACK, cat: str = "host",
        **args: Any,
    ) -> Iterator[Dict[str, Any]]:
        ev = self.begin(name, track=track, cat=cat, **args)
        try:
            yield ev
        finally:
            self.end(ev)

    def complete(
        self, name: str, t0: float, t1: float,
        track: str = HOST_TRACK, cat: str = "host", **args: Any,
    ) -> Dict[str, Any]:
        """Record a span with caller-measured timestamps (profile-mode
        task timings, replayed schedules)."""
        ev = {
            "type": "span", "name": name, "track": track, "cat": cat,
            "t0": t0, "t1": t1, "args": args,
        }
        self.events.append(ev)
        return ev

    # -- points ------------------------------------------------------------
    def instant(
        self, name: str, track: str = HOST_TRACK, cat: str = "host",
        t: Optional[float] = None, **args: Any,
    ) -> Dict[str, Any]:
        ev = {
            "type": "instant", "name": name, "track": track, "cat": cat,
            "t": self.clock() if t is None else t, "args": args,
        }
        self.events.append(ev)
        return ev

    def counter(
        self, name: str, value: float, t: Optional[float] = None,
    ) -> Dict[str, Any]:
        """One sample of a counter track (pool occupancy, queue depth).
        Each distinct ``name`` renders as its own Perfetto counter row."""
        ev = {
            "type": "counter", "name": name,
            "t": self.clock() if t is None else t, "value": value,
        }
        self.events.append(ev)
        return ev

    def flow(
        self, name: str, src_track: str, src_ts: float,
        dst_track: str, dst_ts: float, cat: str = CAT_TRANSFER,
        **args: Any,
    ) -> Dict[str, Any]:
        """A flow arrow between two points on (usually different) tracks —
        the cross-device transfer edge.  The exporter emits the Chrome
        ``s``/``f`` pair binding to the enclosing slices."""
        self._flow_id += 1
        ev = {
            "type": "flow", "name": name, "cat": cat, "id": self._flow_id,
            "src_track": src_track, "src_ts": src_ts,
            "dst_track": dst_track, "dst_ts": dst_ts, "args": args,
        }
        self.events.append(ev)
        return ev

    # -- introspection -----------------------------------------------------
    def tracks(self) -> List[str]:
        """Distinct span/instant tracks, host first, then sorted."""
        seen: Dict[str, None] = {}
        for ev in self.events:
            if ev["type"] in ("span", "instant"):
                seen.setdefault(ev["track"])
        rest = sorted(t for t in seen if t != HOST_TRACK)
        return ([HOST_TRACK] if HOST_TRACK in seen else []) + rest

    def counter_names(self) -> List[str]:
        return sorted({
            ev["name"] for ev in self.events if ev["type"] == "counter"
        })

    def __len__(self) -> int:
        return len(self.events)


class PhaseClock:
    """Tiles one call's wall time into named leaf phases, always on.

    ``with clock.phase("order_s", "dispatch_order", CAT_SCHEDULE) as args``
    reads the clock on entry and exit, adds the difference to
    ``seconds["order_s"]``, enters the profiler annotation
    ``dls/dispatch_order`` and, with a tracer, records the span on the
    host track with whatever the body put into ``args``.  Phases do not
    nest, so ``finish`` can give what no phase covered as ``other_s``:
    the leaves then sum to the wall of the call by construction.
    """

    def __init__(
        self, tracer: Any = None, t0: Optional[float] = None,
        clock: Optional[Callable[[], float]] = None,
    ):
        self.clock: Callable[[], float] = resolve_clock(clock)
        self.tracer = tracer
        self.seconds: Dict[str, float] = {}
        self.t0 = self.clock() if t0 is None else t0

    @contextmanager
    def phase(
        self, key: str, span: str, cat: str,
    ) -> Iterator[Dict[str, Any]]:
        args: Dict[str, Any] = {}
        with annotate(span):
            t0 = self.clock()
            try:
                yield args
            finally:
                t1 = self.clock()
                self.seconds[key] = self.seconds.get(key, 0.0) + (t1 - t0)
                if self.tracer is not None:
                    self.tracer.complete(
                        span, t0, t1, track=HOST_TRACK, cat=cat, **args
                    )

    def finish(self, timed_elsewhere: float = 0.0) -> float:
        """The wall since ``t0``; what neither a phase nor the seconds
        ``timed_elsewhere`` (by the callee, under its own names) covered
        goes to ``seconds["other_s"]``."""
        wall = self.clock() - self.t0
        self.seconds["other_s"] = (
            wall - timed_elsewhere - sum(self.seconds.values())
        )
        return wall
