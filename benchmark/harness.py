"""What every runner shares: finding a cell's files by name, owning the
chip, the compile cache and the compile counter, reading metrics through
their readers, and the result line.

Data-driven by construction: a cell is an entry of ``BENCHMARK.json``
plus ``workloads/<cell>.json``, ``traffic/<traffic>.json`` and its
configuration's ``file``; a metric is ``metrics/<metric>.json`` naming a
reader ``metrics/readers/<reader>.py``; a runner is
``runners/<runner>.py``, named by the configuration file.  There is no
table of names in code, so a later PR adds a cell, a configuration, a
mix or a metric by adding files and appending entries.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent
REQUIRED_PLATFORM = "tpu"


def log(msg: str) -> None:
    """Earlier lines of standard output: everything but the result."""
    print(f"benchmark: {msg}", flush=True)


def _load_json(path: Path) -> Dict[str, Any]:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@dataclass
class Cell:
    """One entry of ``workloads`` with every file that belongs to it."""

    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    params: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: Path


def _reported_in(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    """Find cell ``name`` in the checkout's ``BENCHMARK.json`` and read
    its files from this directory."""
    root = HERE.parent
    spec = _load_json(root / "BENCHMARK.json")
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(
            f"no workload {name!r} in BENCHMARK.json; it has "
            f"{[w['name'] for w in spec['workloads']]}")
    cfg_entry = next(c for c in spec["configs"] if c["name"] == entry["config"])
    e2e = [m for m in spec["end_to_end"] if _reported_in(m, name)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if _reported_in(m, name) and m["moves"] in moved]
    return Cell(
        name=name, chips=int(entry["chips"]), config_name=entry["config"],
        config=_load_json(root / cfg_entry["file"]),
        traffic_name=entry["traffic"],
        traffic=_load_json(HERE / "traffic" / f"{entry['traffic']}.json"),
        params=_load_json(HERE / "workloads" / f"{name}.json"),
        end_to_end=e2e, per_layer=per_layer, root=root,
    )


def _module(rel: str):
    """``benchmark/<rel>.py``, found by name."""
    return importlib.import_module("benchmark." + rel.replace("/", "."))


def load_runner(cell: Cell):
    return _module(f"runners/{cell.config['runner']}")


def load_reference(config: Dict[str, Any]):
    """The plain reference (and weight maker) the configuration names."""
    return _module(f"reference/{config['reference']}")


def read_metrics(defs: List[Dict[str, Any]],
                 ctx: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Each metric through its own reader; one that finds nothing to read
    returns ``None`` and is left out of the line."""
    out: Dict[str, Dict[str, Any]] = {}
    for m in defs:
        how = _load_json(HERE / "metrics" / f"{m['name']}.json")
        value = _module(f"metrics/readers/{how['reader']}").read(
            ctx, how.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -- the chip, the cache, the compile counter ----------------------------------


def configure_jax() -> None:
    """Before first use of JAX: every program is persisted, however fast
    it compiled (jax's defaults skip those under a second, which is most
    of them).  Where the cache lives is the program's own rule, applied
    when its package is imported: ``JAX_COMPILATION_CACHE_DIR`` if set,
    else the fixed ``<checkout>/.jax_cache``."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chip(chips: int) -> List[Any]:
    """The devices this cell runs on, or exit non-zero with no result."""
    import jax

    devs = jax.devices()
    if devs[0].platform != REQUIRED_PLATFORM:
        print(f"benchmark: needs platform {REQUIRED_PLATFORM!r}, "
              f"jax.devices() gives {devs[0].platform!r}; no result",
              file=sys.stderr)
        raise SystemExit(3)
    if len(devs) < chips:
        print(f"benchmark: the cell needs {chips} chip(s), found "
              f"{len(devs)}; no result", file=sys.stderr)
        raise SystemExit(3)
    return devs[:chips]


class CompileCounter:
    """Counts what JAX compiles (``jax.monitoring``): the window must see
    none, and set-up reports how much the persistent cache served."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        from jax import monitoring

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw: Any) -> None:
        if event == self._COMPILE:
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event: str, **_kw: Any) -> None:
        if event.endswith("/cache_hits"):
            self.cache_hits += 1
        elif event.endswith("/cache_misses"):
            self.cache_misses += 1

    def snapshot(self) -> Dict[str, float]:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def device_block(devices: List[Any]) -> Dict[str, Any]:
    """The device as JAX reports it, with the peak of the fullest chip."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


def compared(name: str, value: float, limit: float, ok: bool) -> Dict[str, Any]:
    """One number of the ``correct`` decision, printed beside its limit."""
    log(f"compared {name} = {value!r} (limit {limit!r}) -> "
        f"{'ok' if ok else 'NOT CORRECT'}")
    return {"name": name, "value": value, "limit": limit, "ok": bool(ok)}


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Any], device: Dict[str, Any],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    line: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed), "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    return json.dumps(line)


class TraceSlice:
    """The profiler over the last ``length`` seconds of the window.

    Tracing a whole window of a 48-layer model overflows the device's
    trace buffer, and stopping the profiler stalls the host for seconds;
    so a traced run traces the end of its window and stops after it.
    ``poll`` is called between steps or ticks with the host clock's
    ``now`` and only starts and stops the profiler; ``finish``, called
    once the run has ended, parses the file and hands the trace back as
    plain data.  Whatever a run reads from request rows it reads from
    the time before ``t_before``: the profiler moves what comes after."""

    def __init__(self, root: Path, cell: str, length: float,
                 enabled: bool, clock: Any) -> None:
        self.dir = Path(root) / ".bench_trace" / cell
        self.length = length
        self.enabled = enabled
        self.clock = clock
        self.window_end: Optional[float] = None
        self.t_before: Optional[float] = None   # the profiler not yet on
        self.t_start: Optional[float] = None
        self.t_sync: Optional[float] = None
        self.t_stop: Optional[float] = None
        self.trace: Optional[Dict[str, Any]] = None

    def poll(self, now: float, window_end: float) -> None:
        if not self.enabled or self.t_stop is not None:
            return
        if self.t_start is None and now >= window_end - self.length:
            import shutil

            import jax

            from . import xplane

            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            self.t_before = self.clock()
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self.t_start = self.clock()
            with jax.profiler.TraceAnnotation(xplane.SYNC_MARKER):
                self.t_sync = self.clock()
        elif self.t_start is not None and now >= window_end:
            self._stop()

    def _stop(self) -> None:
        import jax

        self.t_stop = self.clock()
        jax.profiler.stop_trace()

    def finish(self) -> Optional[Dict[str, Any]]:
        """Stop if still tracing; the trace as plain data, or ``None``."""
        if self.t_start is not None and self.trace is None:
            from . import xplane

            if self.t_stop is None:
                self._stop()
            self.trace = xplane.load(xplane.find_trace_file(str(self.dir)))
            for row in xplane.inventory(self.trace):
                log(f"trace: {row}")
        return self.trace
