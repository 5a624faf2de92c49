"""Measured link model: calibrate transfer bandwidth/latency, persist, apply.

The replay's :class:`~..backends.sim.LinkModel` default constants are
invented (50/1000 GB/s), so link-aware policies would optimize a fiction
(SURVEY.md §7 hard-part #2).  This module measures what the device
backend actually pays:

* **param load** (host → device): ``jax.device_put`` of a host numpy array,
  the physical realization of the reference's ``node.cached_params.add``
  (reference ``schedulers.py:86-90``, charged zero there);
* **interconnect** (device → device): ``jax.device_put`` of a committed
  device array onto a sibling device — ICI on a TPU slice, a buffer copy on
  the CPU mesh.

A size sweep (1 KB → 64 MB, best-of-k per size) is fit to the affine model
``t(bytes) = latency + bytes / bandwidth`` by least squares, which is the
exact functional form ``LinkModel`` charges — so the calibration slots in
with no model mismatch.  Results persist to
``.costmodel/link_<device_kind>.json`` next to the task-time calibrations
(:mod:`.costmodel`; run-time products, not committed), with provenance so
a reader can tell measured numbers from estimates.

Single-chip caveat, disclosed: with one chip there is no sibling device,
so the interconnect leg cannot be measured — it keeps the documented
estimate and is marked ``"estimated"`` in provenance.  A four-chip host
(and the virtual CPU mesh) measures both legs.
"""

from __future__ import annotations
# dls-lint: allow-file(DET001) link calibration measures real transfer time

import json
import os
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

# v5e ballpark estimates used when a leg cannot be measured (1 real chip has
# no ICI sibling): ~100 GB/s effective per-hop ICI, ~20 GB/s host->HBM.
EST_ICI_GBPS = 100.0
EST_HOST_GBPS = 20.0
EST_LATENCY_S = 5e-6

_SIZES = (1 << 10, 1 << 14, 1 << 18, 1 << 22, 1 << 25, 1 << 26)


def _fit_affine(samples: Sequence[Tuple[int, float]]) -> Tuple[float, float]:
    """Least-squares fit of t = latency + bytes/bandwidth.

    Returns (latency_s, bandwidth_gbps); latency clamped non-negative and
    bandwidth positive (tiny-transfer noise can otherwise produce a negative
    intercept or slope).
    """
    n = len(samples)
    xs = [b for b, _ in samples]
    ys = [t for _, t in samples]
    mx = sum(xs) / n
    my = sum(ys) / n
    sxx = sum((x - mx) ** 2 for x in xs)
    sxy = sum((x - mx) * (y - my) for x, y in zip(xs, ys))
    slope = sxy / sxx if sxx > 0 else 0.0
    if slope <= 0:
        # Noise made the fit non-monotonic (observed on the CPU mesh under
        # concurrent load: the 4 MB sample can time faster than the 256 KB
        # one).  An infinite bandwidth here silently zeroes every transfer
        # charge downstream — which once flipped a rank check's predicted
        # order run-to-run.  Degraded two-point estimate: latency from the
        # fastest (smallest-cost) sample, bandwidth from the largest
        # sample net of that latency — both finite, both conservative
        # (transfers get over-charged slightly, never erased), and the
        # latency floor survives so the caller's min-over-legs doesn't
        # collapse to the clamp.
        b_max, t_max = max(samples, key=lambda s: s[0])
        lat = max(min(ys), 0.0)
        if t_max > lat and b_max > 0:
            return lat, (b_max / (t_max - lat)) / 1024**3
        if t_max > 0 and b_max > 0:
            return 0.0, (b_max / t_max) / 1024**3
        return max(my, 0.0), float("inf")
    lat = max(my - slope * mx, 0.0)
    gbps = (1.0 / slope) / 1024**3
    return lat, gbps


@dataclass
class LinkCalibration:
    """Measured (or estimated) link parameters, with provenance per leg.

    ``param_load_gbps`` comes from a best-of-k *burst* probe per size —
    the right model for the device backend's isolated per-task loads.
    ``sustained_gbps`` times a back-to-back transfer train — the right
    model for parameter *streaming*, which moves hundreds of MB in a
    row; streaming makespans are judged against the sustained floor,
    not the burst one."""

    platform: str
    param_load_gbps: float = EST_HOST_GBPS
    interconnect_gbps: float = EST_ICI_GBPS
    latency_s: float = EST_LATENCY_S
    sustained_gbps: Optional[float] = None
    provenance: Dict[str, str] = field(
        default_factory=lambda: {
            "param_load": "estimated",
            "interconnect": "estimated",
        }
    )
    samples: Dict[str, List[List[float]]] = field(default_factory=dict)
    measured_at: str = ""

    def to_link_model(self):
        from ..backends.sim import LinkModel

        return LinkModel(
            param_load_gbps=self.param_load_gbps,
            interconnect_gbps=self.interconnect_gbps,
            latency_s=self.latency_s,
        )

    # -- persistence -------------------------------------------------------
    def save(self, path: str) -> str:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(
                {
                    "platform": self.platform,
                    "param_load_gbps": self.param_load_gbps,
                    "interconnect_gbps": self.interconnect_gbps,
                    "latency_s": self.latency_s,
                    "provenance": self.provenance,
                    "samples": self.samples,
                    "measured_at": self.measured_at,
                    "sustained_gbps": self.sustained_gbps,
                },
                f,
                indent=1,
            )
        return path

    @classmethod
    def load(cls, path: str) -> "LinkCalibration":
        with open(path) as f:
            d = json.load(f)
        return cls(
            platform=d["platform"],
            param_load_gbps=d["param_load_gbps"],
            interconnect_gbps=d["interconnect_gbps"],
            latency_s=d["latency_s"],
            provenance=d.get("provenance", {}),
            samples=d.get("samples", {}),
            measured_at=d.get("measured_at", ""),
            sustained_gbps=d.get("sustained_gbps"),
        )


def _time_transfer(make_src, dst_device, repeats: int) -> float:
    """Best-of-``repeats`` wall time for one device_put; the source is
    rebuilt each round so caching can't short-circuit the copy."""
    import jax

    best = float("inf")
    for _ in range(repeats):
        src = make_src()
        t0 = time.perf_counter()
        out = jax.device_put(src, dst_device)
        out.block_until_ready()
        best = min(best, time.perf_counter() - t0)
        del out
    return best


def calibrate_link(
    devices: Optional[Sequence[Any]] = None,
    sizes: Sequence[int] = _SIZES,
    repeats: int = 5,
    sustained: bool = False,
) -> LinkCalibration:
    """Measure host->device and device->device transfer costs.

    ``devices``: target devices (default ``jax.devices()``).  The first is
    the host-load target; the first two (if available) form the
    interconnect pair.  One warmup transfer per leg absorbs one-time
    allocator/compile costs before timing.

    ``sustained=True`` additionally times a back-to-back transfer train
    (the streaming-regime rate — class docstring).  Opt-in because it
    moves up to 2x8x16 MB and only streaming consumers
    (``eval/stream_bench``) read it.
    """
    import jax
    import numpy as np

    devices = list(devices if devices is not None else jax.devices())
    dev0 = devices[0]
    cal = LinkCalibration(platform=dev0.platform)

    # host -> device (param load leg)
    host_samples: List[Tuple[int, float]] = []
    jax.device_put(np.ones(1024, np.uint8), dev0).block_until_ready()
    for size in sizes:
        arr = np.random.default_rng(0).integers(
            0, 255, size, dtype=np.uint8
        )
        t = _time_transfer(lambda a=arr: a.copy(), dev0, repeats)
        host_samples.append((size, t))
    lat_h, gbps_h = _fit_affine(host_samples)
    cal.param_load_gbps = gbps_h
    cal.provenance["param_load"] = "measured"
    cal.samples["param_load"] = [[s, t] for s, t in host_samples]

    # sustained host->device rate: a back-to-back train of puts, timed as
    # one window.  Streaming workloads live in this regime, which need
    # not match the burst rate (class docstring).  Train size: 8 buffers
    # of the largest swept size, capped at 16 MB each.
    if sustained:
        chunk = min(max(sizes), 16 << 20)
        n_bufs = 8
        # best-of-2 windows, same estimator spirit as the burst leg's
        # best-of-k: one window can land entirely inside a transient
        # stall.  Fresh source buffers per window (the _time_transfer
        # rebuild contract): re-putting identical arrays could be
        # elided/amortized by the runtime and over-read the rate.
        windows: List[float] = []
        for w in range(2):
            train = [
                np.random.default_rng(w * n_bufs + r).integers(
                    0, 255, chunk, dtype=np.uint8
                )
                for r in range(n_bufs)
            ]
            t0 = time.perf_counter()
            outs = [jax.device_put(a, dev0) for a in train]
            jax.block_until_ready(outs)
            windows.append(time.perf_counter() - t0)
            del outs
        t_train = min((w for w in windows if w > 0), default=0.0)
        if t_train > 0:
            cal.sustained_gbps = (n_bufs * chunk) / t_train / 1024**3
            cal.provenance["sustained"] = "measured"
            cal.samples["sustained"] = [
                [n_bufs * chunk, w] for w in windows
            ]

    # device -> device (interconnect leg) — needs a sibling device
    lat_d = None
    if len(devices) >= 2:
        dev1 = devices[1]
        ici_samples: List[Tuple[int, float]] = []
        warm = jax.device_put(np.ones(1024, np.uint8), dev0)
        jax.device_put(warm, dev1).block_until_ready()
        for size in sizes:
            # distinct source buffer per repeat (honoring _time_transfer's
            # rebuild contract: a repeated put of the identical committed
            # buffer could be elided/amortized by the runtime)
            pool = [
                jax.device_put(
                    np.random.default_rng(r).integers(0, 255, size, np.uint8),
                    dev0,
                )
                for r in range(repeats)
            ]
            jax.block_until_ready(pool)
            it = iter(pool)
            t = _time_transfer(lambda it=it: next(it), dev1, repeats)
            ici_samples.append((size, t))
        lat_d, gbps_d = _fit_affine(ici_samples)
        cal.interconnect_gbps = gbps_d
        cal.provenance["interconnect"] = "measured"
        cal.samples["interconnect"] = [[s, t] for s, t in ici_samples]

    # one shared latency floor: the smaller measured intercept (LinkModel
    # has a single latency knob; the floor is dominated by dispatch, which
    # both legs share)
    lats = [lat_h] + ([lat_d] if lat_d is not None else [])
    cal.latency_s = max(min(lats), 1e-7)
    from .costmodel import _utc_stamp

    cal.measured_at = _utc_stamp()
    return cal


def calibrate_link_cached(
    cache_dir: str = ".costmodel",
    devices: Optional[Sequence[Any]] = None,
    repeats: int = 5,
    refresh: bool = False,
) -> LinkCalibration:
    """Calibrate, or load a previous calibration for this KIND of device
    (the cache file is keyed by ``costmodel.kind_slug``).

    ``refresh=True`` bypasses the cache and re-measures — same knob as
    ``costmodel.calibrate_cached``; bench callers wire it to
    ``costmodel.recalibrate_requested``.
    """
    import jax

    from .costmodel import kind_slug

    devices = list(devices if devices is not None else jax.devices())
    path = os.path.join(cache_dir, f"link_{kind_slug(devices[0])}.json")
    if not refresh and os.path.exists(path):
        prior = LinkCalibration.load(path)
        # staleness check (cf. costmodel.calibrate_cached's task-set check):
        # a cache written in a 1-device session carries only an *estimated*
        # interconnect; once siblings exist, re-measure rather than letting
        # the estimate masquerade as calibration forever
        if (
            prior.provenance.get("interconnect") == "measured"
            or len(devices) < 2
        ):
            return prior
    cal = calibrate_link(devices, repeats=repeats)
    cal.save(path)
    return cal
