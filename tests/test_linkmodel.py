"""Measured link model + sim-vs-real validation.

The replay's default LinkModel constants are invented; these tests pin
the calibration machinery (affine fit, provenance, cache staleness) and the
headline property: with a measured cost model and a measured link, the
simulated backend's predicted makespan tracks the device backend's measured
makespan within a stated tolerance, for multiple policies.
"""

import os
import time

import jax
import jax.numpy as jnp
import pytest

import distributed_llm_scheduler_tpu as dls
from distributed_llm_scheduler_tpu import Cluster
from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
from distributed_llm_scheduler_tpu.backends.sim import SimulatedBackend
from distributed_llm_scheduler_tpu.utils.linkmodel import (
    EST_ICI_GBPS,
    LinkCalibration,
    _fit_affine,
    calibrate_link,
    calibrate_link_cached,
)

GB = 1024**3


def test_fit_affine_recovers_known_line():
    lat, bw_gb = 20e-6, 5.0
    samples = [
        (s, lat + s / (bw_gb * GB))
        for s in (1 << 10, 1 << 16, 1 << 22, 1 << 26)
    ]
    got_lat, got_bw = _fit_affine(samples)
    assert got_lat == pytest.approx(lat, rel=1e-6)
    assert got_bw == pytest.approx(bw_gb, rel=1e-6)


def test_fit_affine_noise_clamps_sane():
    # pure-noise samples (no size dependence) must not yield negative
    # latency or bandwidth
    samples = [(1 << 10, 1e-5), (1 << 20, 1e-5), (1 << 24, 1e-5)]
    lat, bw = _fit_affine(samples)
    assert lat >= 0
    assert bw > 0


@pytest.fixture(scope="module")
def link_cal():
    # small sizes keep the sweep fast; both legs measurable on the 8-device
    # CPU mesh
    return calibrate_link(
        jax.devices(), sizes=(1 << 12, 1 << 16, 1 << 20, 1 << 23),
        repeats=3, sustained=True,
    )


def test_calibrate_link_measures_both_legs(link_cal):
    assert link_cal.provenance["param_load"] == "measured"
    assert link_cal.provenance["interconnect"] == "measured"
    assert link_cal.param_load_gbps > 0
    assert link_cal.interconnect_gbps > 0
    assert link_cal.latency_s >= 0
    # samples persisted for audit
    assert len(link_cal.samples["param_load"]) == 4
    # sustained (back-to-back train) rate: the streaming-regime floor
    assert link_cal.sustained_gbps is not None
    assert link_cal.sustained_gbps > 0
    assert link_cal.provenance["sustained"] == "measured"


def test_calibration_roundtrips(tmp_path, link_cal):
    p = str(tmp_path / "link_cpu.json")
    link_cal.save(p)
    back = LinkCalibration.load(p)
    assert back.param_load_gbps == link_cal.param_load_gbps
    assert back.provenance == link_cal.provenance
    lm = back.to_link_model()
    assert lm.param_load_gbps == link_cal.param_load_gbps


def test_cached_calibration_refreshes_estimated_interconnect(tmp_path):
    """A cache written with 1 device (interconnect estimated) must be
    re-measured once sibling devices exist — otherwise the invented ICI
    estimate masquerades as calibration forever."""
    cache = str(tmp_path)
    stale = LinkCalibration(platform="cpu")  # provenance: both estimated
    stale.param_load_gbps = 123.0
    stale.save(os.path.join(cache, "link_cpu.json"))
    cal = calibrate_link_cached(cache_dir=cache, repeats=2)
    assert cal.provenance["interconnect"] == "measured"
    assert cal.param_load_gbps != 123.0
    # and a *measured* cache is trusted as-is
    again = calibrate_link_cached(cache_dir=cache, repeats=2)
    assert again.param_load_gbps == cal.param_load_gbps


def _fixed_cal(gbps: float) -> LinkCalibration:
    cal = LinkCalibration(platform="cpu")
    cal.param_load_gbps = gbps
    cal.interconnect_gbps = 50.0
    cal.provenance = {"param_load": "measured",
                      "interconnect": "measured"}
    return cal


def test_refresh_measures_once_and_replaces_the_cache(tmp_path, monkeypatch):
    """``refresh=True`` re-measures exactly once and persists what it
    measured, whatever a prior cache said — a slow fresh number is this
    session's link, not something to second-guess against history."""
    from distributed_llm_scheduler_tpu.utils import linkmodel as lm

    cache = str(tmp_path)
    path = os.path.join(cache, "link_cpu.json")
    _fixed_cal(1.4).save(path)
    calls = []

    def one(*a, **k):
        calls.append(1)
        return _fixed_cal(0.04)

    monkeypatch.setattr(lm, "calibrate_link", one)
    cal = lm.calibrate_link_cached(cache_dir=cache, refresh=True)
    assert cal.param_load_gbps == 0.04
    assert cal.provenance["param_load"] == "measured"
    assert calls == [1]
    assert LinkCalibration.load(path).param_load_gbps == 0.04


def test_single_device_leaves_interconnect_estimated():
    cal = calibrate_link(
        jax.devices()[:1], sizes=(1 << 12, 1 << 18), repeats=2
    )
    assert cal.provenance["param_load"] == "measured"
    assert cal.provenance["interconnect"] == "estimated"
    assert cal.interconnect_gbps == EST_ICI_GBPS


# -- sim-vs-real ------------------------------------------------------------


def test_sim_tracks_real_execution():
    """For >=3 policies on the 8-device CPU mesh: SimulatedBackend with a
    measured cost model + measured link + host-core concurrency cap must
    predict DeviceBackend's measured makespan within [0.65x, 1.35x].

    Tolerance rationale: profile-mode calibration measures per-task wall
    times with fences (slight overestimate), async measured runs overlap
    dispatch (slight underestimate), and CPU-mesh noise is a few percent;
    observed prediction ratios on a 1-core host are 0.88-1.02 (and
    0.79-1.16 on the 537-task flagship structure, isolated — see
    RANKCHECK_r03.json), so the band keeps real headroom without being
    vacuous.  Round 2 temporarily widened the lower side to 0.5 for host
    contention; the bounded re-measure loop below now absorbs that
    direction, so the band is back near the round-1 width."""
    from distributed_llm_scheduler_tpu.frontend.gpt2_dag import build_gpt2_dag
    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config
    from distributed_llm_scheduler_tpu.utils.costmodel import calibrate

    dag = build_gpt2_dag(GPT2Config.tiny(), batch=4, seq_len=64)
    params, ids = dag.init_params(), dag.make_inputs()
    g = dag.graph
    cal = calibrate_link(
        jax.devices(), sizes=(1 << 14, 1 << 18, 1 << 22), repeats=3
    )
    cm = calibrate(g, params, ids, repeats=2)
    cm.apply(g)

    # contention probe: a fixed jit'd op timed adjacent to each measured
    # run.  The sim predicts quiet-host makespans from quiet(ish)-host
    # calibration; a concurrent suite half or TPU bench on this machine
    # inflates ONLY the measured leg (an observed load-flake).  Dividing measured by the probe's slowdown (never <1x,
    # clamped at 4x so the probe can't manufacture a pass) removes the
    # load the sim cannot know about while leaving genuine model error
    # in place.
    probe_x = jnp.ones((512, 512), jnp.float32)
    probe_fn = jax.jit(lambda x: (x @ x).sum())
    probe_fn(probe_x).block_until_ready()

    def probe_s() -> float:
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            probe_fn(probe_x).block_until_ready()
            times.append(time.perf_counter() - t0)
        return min(times)

    probe_base = probe_s()

    cluster = Cluster.from_jax_devices(hbm_cap_gb=4.0)
    backend = DeviceBackend(cluster)
    sim = SimulatedBackend(
        fidelity="full",
        link=cal.to_link_model(),
        host_slots=os.cpu_count() or 1,
        dispatch_s=cm.dispatch_s,
    )
    ratios = {}
    recalibrated = False
    for policy in ("roundrobin", "pipeline", "critical"):
        s = dls.get_scheduler(policy).schedule(g, cluster)
        predicted = sim.execute(g, cluster, s).makespan
        backend.execute(g, s, params, ids)  # warm

        def measure_once():
            raw = min(
                backend.execute(g, s, params, ids, warmup=False).makespan_s
                for _ in range(3)
            )
            slowdown = max(1.0, min(probe_s() / probe_base, 4.0))
            return raw, slowdown

        # keep the QUIETEST window's measurement (smallest probe
        # slowdown): a spike covering only the probe would otherwise
        # over-correct and fail the UPPER bound, so retries are judged
        # by the probe, not by whichever ratio happens to pass
        raw, slow = measure_once()
        tries = 0
        while not 0.65 <= predicted / (raw / slow) <= 1.35 and tries < 3:
            if predicted / (raw / slow) > 1.35 and not recalibrated:
                # the probe corrects only the MEASURED leg; a load spike
                # that covered the CALIBRATION window instead inflates
                # every prediction and no number of re-measures can fix
                # it.  One bounded recalibration covers that direction
                # (an observed full-suite flake).
                recalibrated = True
                cm2 = calibrate(g, params, ids, repeats=2)
                cm2.apply(g)
                sim = SimulatedBackend(
                    fidelity="full",
                    link=cal.to_link_model(),
                    host_slots=os.cpu_count() or 1,
                    dispatch_s=cm2.dispatch_s,
                )
                predicted = sim.execute(g, cluster, s).makespan
            r2, s2 = measure_once()
            if s2 < slow:
                raw, slow = r2, s2
            tries += 1
        ratios[policy] = predicted / (raw / slow)
    assert all(0.65 <= r <= 1.35 for r in ratios.values()), ratios
