"""Perf-regression gating: fresh bench leg vs committed baseline.

The repo commits bench artifacts (``BENCH_MEDIUM_r05.json`` et al.);
this module compares a freshly-measured artifact against one of them
metric by metric, with per-metric direction ("lower is better" for
makespans and overheads, "higher is better" for speedups and MFU,
boolean for oracle checks) and per-metric relative tolerances, and
renders a structured verdict the ``regress`` CLI turns into an exit
code.  CI runs it on the 8-virtual-device CPU mesh with loose
tolerances; a 20% makespan regression fails the build, the committed
baseline compared against itself passes by construction.

Tolerance semantics are inclusive: a lower-is-better metric regresses
only when ``fresh > baseline * (1 + tol)`` — landing exactly on the
edge is still ``ok``.  A metric present in the baseline but absent
from the fresh artifact is a ``missing`` failure (a silently-dropped
bench leg must not read as a pass).
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

# direction per known bench-artifact metric; anything not listed here is
# compared only when explicitly requested via `metrics=` (and must then
# appear in one of the maps)
LOWER_BETTER = (
    "value",                  # headline makespan (ms)
    "fused_forward_ms",
    "fused_scalar_ms",
    "dispatch_overhead",
    "peak_hbm_gb_modeled",
    "kv_pages_peak",
    "singlechip_replay_ms",
    "fence_rtt_ms",
    "serve.ttft_p99_ms",
    "serve.queue_wait_p95_ms",
    "serve.prefix.ttft_p99_ms",
    "serve.prefix.pages_leaked",
    "serve.chunked.tpot_p99_ms",
    "serve.chunked.ttft_p99_ms",
    "serve.chunked.pages_leaked",
    # the interference-attribution tiling invariant: buckets must sum
    # to each request's e2e exactly, so the worst residual is pinned 0
    "serve.attribution.max_residual_s",
    # soak health slopes (dls.soak/1 artifact): clamped to >= 0, a
    # healthy run sits at or near 0 — any growth is a leak/degradation
    "soak.page_leak_slope_pages_s",
    "soak.hbm_slope_bytes_s",
    "soak.jit_cache_slope_entries_s",
    "soak.ttft_p95_slope_s_per_s",
    "soak.queue_wait_p95_slope_s_per_s",
    "soak.throughput_decay_tok_s2",
    # fleet failover legs: drain/restart counts and residual leaks are
    # deterministic virtual-time outcomes — fewer is better, and the
    # healthy (no-injection) leg must stay at exactly zero
    "fleet.drains",
    "fleet.restarts",
    "fleet.migrations",
    "fleet.pages_leaked",
    "fleet.healthy_drains",
    # paged decode legs: any leaked page is an engine bug
    "decode.pages_leaked",
    "decode.kernel_pages_leaked",
    # searched-placement bench (dls search_bench artifact): simulated
    # makespans, deterministic given seed + budget
    "search.makespan_ms",
    "search.replay_ms",
    "search.best_hand_replay_ms",
)

# lower-is-better metric FAMILIES, matched by prefix: per-device peak
# HBM appears flattened as ``peak_hbm_bytes.<node>`` (one metric per
# device), so direction cannot be an exact-name lookup
LOWER_BETTER_PREFIXES = ("peak_hbm_bytes",)

# per-metric default tolerances, consulted before ``default_tolerance``:
# modeled memory metrics are deterministic given the committed cost
# caches, so they get a tight band — a placement change that moves a
# device's peak by >2% should be a deliberate baseline recapture, not
# ambient noise
METRIC_DEFAULT_TOLERANCES = {
    "peak_hbm_gb_modeled": 0.02,
    "peak_hbm_bytes": 0.02,
    "kv_pages_peak": 0.0,
    # serve bench metrics run on a VirtualClock — every timestamp is a
    # deterministic function of the seed, so any drift is a behavior
    # change, not noise
    "serve.goodput_tok_s": 0.0,
    "serve.ttft_p99_ms": 0.0,
    "serve.queue_wait_p95_ms": 0.0,
    # the shared-prefix legs ride the same VirtualClock: goodput, tail
    # latency, aliasing hit counts, and leak counts are all exact
    "serve.prefix.goodput_tok_s": 0.0,
    "serve.prefix.ttft_p99_ms": 0.0,
    "serve.prefix.goodput_gain": 0.0,
    "serve.prefix.shared_page_hits": 0.0,
    "serve.prefix.pages_leaked": 0.0,
    # the chunked-prefill legs are the same VirtualClock determinism:
    # both legs replay the identical seeded arrival stream, so tail
    # latencies, the tpot gain ratio, and leak counts are exact
    "serve.chunked.tpot_p99_ms": 0.0,
    "serve.chunked.ttft_p99_ms": 0.0,
    "serve.chunked.goodput_tok_s": 0.0,
    "serve.chunked.tpot_p99_gain": 0.0,
    "serve.chunked.pages_leaked": 0.0,
    "serve.attribution.max_residual_s": 0.0,
    # soak slopes share the serve bench's VirtualClock determinism: the
    # timestamps and token counts behind every Theil-Sen fit are pure
    # functions of the seed, so exact match is the right band even
    # though healthy hbm/jit/latency slopes are nonzero
    "soak": 0.0,
    # paged decode legs: leak counts and parity are deterministic;
    # throughputs and speedups are wall-clock on shared CI hosts, so
    # they get wide bands (the hard >=1.0x/>=1.1x floors live in the
    # decode_bench gates, not here)
    "decode.pages_leaked": 0.0,
    "decode.kernel_pages_leaked": 0.0,
    "decode.paged_tok_s": 0.35,
    "decode.paged_speedup": 0.35,
    "decode.kernel_vs_gather_speedup": 0.35,
    # search bench legs are seeded simulation end to end — placements,
    # makespans, and margins are pure functions of (seed, budget), so
    # any drift is a behavior change, not noise (family-wide)
    "search": 0.0,
    # fleet legs run every replica on the lockstep VirtualClock: routing
    # decisions, drain/restart counts, and goodput are pure functions of
    # the seed, so the whole family is exact-match (family-wide)
    "fleet": 0.0,
}
HIGHER_BETTER = (
    "vs_baseline",
    "mfu_single_chip",
    "serve.goodput_tok_s",
    "serve.prefix.goodput_tok_s",
    "serve.prefix.goodput_gain",
    "serve.prefix.shared_page_hits",
    "serve.chunked.goodput_tok_s",
    "serve.chunked.tpot_p99_gain",
    "soak.goodput_tok_s",
    "fleet.goodput_tok_s",
    "fleet.goodput_gain_vs_rr",
    "decode.paged_tok_s",
    "decode.paged_speedup",
    "decode.kernel_vs_gather_speedup",
    "search.margin_vs_hand_pct",
    "search.ici_slow_margin_pct",
    "search.ici_fast_margin_pct",
)
BOOL_METRICS = (
    "oracle_ok",
    "serve.chunked.token_parity",
    "decode.paged_tokens_exact",
    "decode.kernel_tokens_exact",
    "decode.kernel_parity_ok",
    "fleet.deterministic",
    "search.beats_hand",
    "search.beats_ici_extreme",
)

# the default comparison set: quality metrics only — environment
# measurements (fence RTT, replay wall) drift with the machine and are
# opted into explicitly
DEFAULT_METRICS = (
    "value",
    "vs_baseline",
    "dispatch_overhead",
    "peak_hbm_gb_modeled",
    "kv_pages_peak",
    "mfu_single_chip",
    "oracle_ok",
    "serve.goodput_tok_s",
    "serve.ttft_p99_ms",
    "serve.queue_wait_p95_ms",
    "serve.prefix.goodput_tok_s",
    "serve.prefix.ttft_p99_ms",
    "serve.prefix.goodput_gain",
    "serve.prefix.shared_page_hits",
    "serve.prefix.pages_leaked",
    "serve.chunked.tpot_p99_ms",
    "serve.chunked.ttft_p99_ms",
    "serve.chunked.goodput_tok_s",
    "serve.chunked.tpot_p99_gain",
    "serve.chunked.token_parity",
    "serve.chunked.pages_leaked",
    "serve.attribution.max_residual_s",
    "fleet.goodput_tok_s",
    "fleet.goodput_gain_vs_rr",
    "fleet.drains",
    "fleet.restarts",
    "fleet.pages_leaked",
    "fleet.healthy_drains",
    "fleet.deterministic",
    "decode.paged_tokens_exact",
    "decode.pages_leaked",
    "decode.kernel_tokens_exact",
    "decode.kernel_parity_ok",
    "decode.kernel_pages_leaked",
    "search.makespan_ms",
    "search.replay_ms",
    "search.margin_vs_hand_pct",
    "search.ici_slow_margin_pct",
    "search.ici_fast_margin_pct",
    "search.beats_hand",
    "search.beats_ici_extreme",
    # the digest is a string: zero-tolerance equality via the
    # non-numeric branch — same seed + budget must reproduce the
    # placement bit-for-bit across machines and processes
    "search.placement_digest",
)

DEFAULT_TOLERANCE = 0.10


@dataclass
class MetricCheck:
    metric: str
    direction: str  # "lower" | "higher" | "bool"
    baseline: Any
    fresh: Any
    tolerance: float
    status: str  # "ok" | "improved" | "regressed" | "missing"

    def to_json(self) -> Dict[str, Any]:
        out = {
            "metric": self.metric, "direction": self.direction,
            "baseline": self.baseline, "fresh": self.fresh,
            "tolerance": self.tolerance, "status": self.status,
        }
        if (
            isinstance(self.baseline, (int, float))
            and not isinstance(self.baseline, bool)
            and isinstance(self.fresh, (int, float))
            and self.baseline
        ):
            out["ratio"] = self.fresh / self.baseline
        return out


@dataclass
class RegressVerdict:
    checks: List[MetricCheck] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return all(c.status in ("ok", "improved") for c in self.checks)

    @property
    def exit_code(self) -> int:
        return 0 if self.ok else 1

    def failures(self) -> List[MetricCheck]:
        return [
            c for c in self.checks
            if c.status in ("regressed", "missing")
        ]

    def to_json(self) -> Dict[str, Any]:
        return {
            "ok": self.ok,
            "n_checks": len(self.checks),
            "n_regressed": sum(
                1 for c in self.checks if c.status == "regressed"
            ),
            "n_missing": sum(
                1 for c in self.checks if c.status == "missing"
            ),
            "checks": [c.to_json() for c in self.checks],
        }

    def render(self) -> str:
        lines = []
        for c in self.checks:
            mark = {
                "ok": " ", "improved": "+", "regressed": "!",
                "missing": "?",
            }[c.status]
            lines.append(
                f"[{mark}] {c.metric:<24} baseline={c.baseline!r:<12} "
                f"fresh={c.fresh!r:<12} tol={c.tolerance:.0%} "
                f"-> {c.status}"
            )
        verdict = "PASS" if self.ok else "FAIL"
        lines.append(
            f"regress: {verdict} "
            f"({len(self.checks)} checks, {len(self.failures())} failing)"
        )
        return "\n".join(lines)


def load_artifact(path_or_obj: Any) -> Dict[str, Any]:
    """Load a bench artifact; unwraps the driver capture format
    (``{"n", "cmd", "rc", "parsed": {...}}``) down to the metric dict."""
    obj = path_or_obj
    if isinstance(path_or_obj, (str, os.PathLike)):
        with open(path_or_obj) as f:
            obj = json.load(f)
    if not isinstance(obj, dict):
        raise ValueError("bench artifact must be a JSON object")
    if "metric" not in obj and isinstance(obj.get("parsed"), dict):
        obj = obj["parsed"]
    return obj


def _direction(metric: str) -> Optional[str]:
    if metric in BOOL_METRICS:
        return "bool"
    if metric in LOWER_BETTER:
        return "lower"
    if metric in HIGHER_BETTER:
        return "higher"
    family = metric.split(".", 1)[0]
    if family in LOWER_BETTER_PREFIXES:
        return "lower"
    return None


def _default_tol(metric: str, fallback: float) -> float:
    tol = METRIC_DEFAULT_TOLERANCES.get(metric)
    if tol is None:
        tol = METRIC_DEFAULT_TOLERANCES.get(metric.split(".", 1)[0])
    return fallback if tol is None else tol


def compare_artifacts(
    fresh: Any,
    baseline: Any,
    tolerances: Optional[Dict[str, float]] = None,
    metrics: Optional[Sequence[str]] = None,
    default_tolerance: float = DEFAULT_TOLERANCE,
) -> RegressVerdict:
    """Compare two bench artifacts (paths or dicts) metric by metric.

    Only metrics present in the *baseline* are checked (the baseline
    defines the contract); of those, the default set is
    :data:`DEFAULT_METRICS` unless ``metrics`` narrows or extends it.
    ``tolerances`` maps metric name → relative tolerance, with
    ``default_tolerance`` as the fallback.
    """
    fresh = load_artifact(fresh)
    baseline = load_artifact(baseline)
    tolerances = tolerances or {}
    wanted = list(metrics) if metrics is not None else [
        m for m in DEFAULT_METRICS if m in baseline
    ]
    checks: List[MetricCheck] = []
    for m in wanted:
        direction = _direction(m)
        if direction is None:
            direction = "lower"  # explicit unknown metrics: conservative
        if m not in baseline:
            continue
        base = baseline[m]
        tol = float(
            tolerances.get(m, _default_tol(m, default_tolerance))
        )
        if m not in fresh or fresh[m] is None:
            checks.append(MetricCheck(m, direction, base, None, tol,
                                      "missing"))
            continue
        new = fresh[m]
        if direction == "bool":
            if bool(base) and not bool(new):
                status = "regressed"
            elif not bool(base) and bool(new):
                status = "improved"
            else:
                status = "ok"
        elif not isinstance(base, (int, float)) or isinstance(base, bool) \
                or not isinstance(new, (int, float)):
            status = "ok" if new == base else "regressed"
        elif direction == "lower":
            if new > base * (1.0 + tol):
                status = "regressed"
            elif new < base * (1.0 - tol):
                status = "improved"
            else:
                status = "ok"
        else:  # higher is better
            if new < base * (1.0 - tol):
                status = "regressed"
            elif new > base * (1.0 + tol):
                status = "improved"
            else:
                status = "ok"
        checks.append(MetricCheck(m, direction, base, new, tol, status))
    return RegressVerdict(checks=checks)


def parse_tolerances(specs: Sequence[str]) -> Dict[str, float]:
    """Parse CLI ``--tolerance metric=frac`` specs (repeatable)."""
    out: Dict[str, float] = {}
    for spec in specs:
        if "=" not in spec:
            raise ValueError(
                f"tolerance spec {spec!r} is not metric=frac"
            )
        k, v = spec.split("=", 1)
        out[k.strip()] = float(v)
    return out


__all__ = [
    "BOOL_METRICS",
    "DEFAULT_METRICS",
    "DEFAULT_TOLERANCE",
    "HIGHER_BETTER",
    "LOWER_BETTER",
    "LOWER_BETTER_PREFIXES",
    "METRIC_DEFAULT_TOLERANCES",
    "MetricCheck",
    "RegressVerdict",
    "compare_artifacts",
    "load_artifact",
    "parse_tolerances",
]
