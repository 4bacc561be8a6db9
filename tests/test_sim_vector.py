"""Vectorized lockstep simulator: conformance with the object-based
reference engine, the bulk fast path's statistical agreement, fleet-wide
pricing, max_steps truncation reporting, the serve-time feasibility
snapshot, and the arrival/window accounting property tests."""

import dataclasses

import numpy as np
import pytest

from repro.core.controller import StaticController
from repro.serving import device_model as dm
from repro.serving.cluster import (ClusterEngine, VectorClusterEngine,
                                   gpu_fleet, paper_controller_factory,
                                   run_churn_cluster, run_partition_cluster)
from repro.serving.engine import OpenLoopQueue
from repro.serving.metrics import TailLatencyWindow
from repro.serving.workload import (PAPER_JOBS, ChurnJob, churn_trace,
                                    mixed_partition_trace)


def _static_cf(job, ex):
    return StaticController(bs=8, mtl=1)


# ---------------------------------------------------------------------------
# conformance: the vectorized engine must be BIT-identical to the reference
# (same reports, same event order, same churn log) — argmin over the clock
# array replaces the heap, nothing else may change.
# ---------------------------------------------------------------------------
def _pair(jobs, fleet, *, seed=0, **kw):
    eo = ClusterEngine(jobs, list(fleet), seed=seed, **kw)
    ev = VectorClusterEngine(jobs, list(fleet), seed=seed, **kw)
    return eo, ev


def _assert_identical(eo, ev, ro, rv):
    assert ro == rv
    assert eo.event_log == ev.event_log
    assert eo.churn_log == ev.churn_log
    assert eo.steps_run == ev.steps_run


def test_vector_conformance_paper_scenario():
    jobs = PAPER_JOBS[:12]
    eo, ev = _pair(jobs, gpu_fleet(5),
                   controller_factory=paper_controller_factory("hybrid"))
    _assert_identical(eo, ev, eo.run(sim_time_limit=30.0),
                      ev.run(sim_time_limit=30.0))
    assert len(eo.event_log) > 100     # the scenario actually stepped


@pytest.mark.parametrize("policy", ["dynamic", "surface"])
def test_vector_conformance_churn_scenario(policy):
    trace = churn_trace(horizon_s=40.0, n_initial=3, n_churn=4,
                        mean_lifetime_s=15.0, seed=1)
    ro = run_churn_cluster(policy, trace=list(trace), n_devices=3,
                           horizon_s=40.0, seed=1)
    rv = run_churn_cluster(policy, trace=list(trace), n_devices=3,
                           horizon_s=40.0, seed=1, vectorized=True)
    assert ro == rv
    assert ro["aggregate"]["admissions"] > 0


def test_vector_conformance_partition_scenario():
    trace = mixed_partition_trace(horizon_s=40.0, n_light=3, seed=1)
    ro = run_partition_cluster("het", trace=list(trace), n_devices=2,
                               horizon_s=40.0, seed=1)
    rv = run_partition_cluster("het", trace=list(trace), n_devices=2,
                               horizon_s=40.0, seed=1, vectorized=True)
    assert ro == rv


@pytest.mark.slow
def test_vector_conformance_bench_cluster_full():
    """The BENCH_cluster scenario (12 jobs x 5 devices, 90 s) under every
    controller mode, pinned bit-identical."""
    jobs = PAPER_JOBS[:12]
    for mode in ("auto", "hybrid", "B", "MT", "clipper"):
        eo, ev = _pair(jobs, gpu_fleet(5),
                       controller_factory=paper_controller_factory(mode))
        _assert_identical(eo, ev, eo.run(sim_time_limit=90.0),
                          ev.run(sim_time_limit=90.0))


@pytest.mark.slow
def test_vector_conformance_bench_churn_full():
    """The BENCH_churn scenario (14 tenancies on 5 devices, 120 s) under
    every placement policy, pinned bit-identical."""
    trace = churn_trace(horizon_s=120.0, n_initial=4, n_churn=10,
                        mean_lifetime_s=30.0, seed=1)
    for policy in ("union", "dynamic", "surface"):
        ro = run_churn_cluster(policy, trace=list(trace), n_devices=5,
                               horizon_s=120.0, seed=1)
        rv = run_churn_cluster(policy, trace=list(trace), n_devices=5,
                               horizon_s=120.0, seed=1, vectorized=True)
        assert ro == rv


# ---------------------------------------------------------------------------
# the bulk fast path (static fleets): statistically equivalent, not
# bit-identical — same latency law, chunked RNG
# ---------------------------------------------------------------------------
def _static_scenario(n):
    jobs = [dataclasses.replace(PAPER_JOBS[0], job_id=10_000 + i)
            for i in range(n)]
    return jobs, gpu_fleet(n)


def test_bulk_path_statistical_agreement():
    jobs, fleet = _static_scenario(20)
    eo, ev = _pair(jobs, fleet, controller_factory=_static_cf)
    ro = eo.run(sim_time_limit=2.0)
    rv = ev.run(sim_time_limit=2.0)
    ao, av = ro["aggregate"], rv["aggregate"]
    assert not ao["truncated"] and not av["truncated"]
    assert ao["conserved"] and av["conserved"]
    ratio = av["aggregate_throughput"] / ao["aggregate_throughput"]
    assert 0.97 < ratio < 1.03
    # the bulk path really engaged (it prices whole fleets per round, so
    # its event_log stays empty)
    assert not ev.event_log and len(eo.event_log) > 100


def test_bulk_falls_back_to_exact_near_step_budget():
    """When the step budget would truncate the run, the bulk path must
    decline (truncation semantics stay honest) — and the exact vector path
    is then bit-identical to the reference, truncated flag included."""
    jobs, fleet = _static_scenario(5)
    eo, ev = _pair(jobs, fleet, controller_factory=_static_cf)
    ro = eo.run(sim_time_limit=5.0, max_steps=40)
    rv = ev.run(sim_time_limit=5.0, max_steps=40)
    assert ro == rv
    assert ro["aggregate"]["truncated"] is True


# ---------------------------------------------------------------------------
# fleet-wide pricing: one vectorized call == the scalar loop
# ---------------------------------------------------------------------------
def test_fleet_step_latency_matches_scalar_loop():
    devices, profiles = [], []
    for i, j in enumerate(PAPER_JOBS[:10]):
        devices.append(dm.TESLA_P40 if i % 2 else dm.TESLA_P40.share(0.5))
        profiles.append(j.profile())
    for bs, mtl in ((1, 1), (8, 1), (4, 3), (32, 10)):
        got = dm.fleet_step_latency(devices, profiles, bs, mtl)
        want = np.array([dm.mt_latency(d, p, bs, mtl)
                         for d, p in zip(devices, profiles)])
        np.testing.assert_allclose(got, want, rtol=1e-12, atol=0.0)
    # mtl=1 degenerates to the batch path up to exact IEEE identities
    got1 = dm.fleet_step_latency(devices, profiles, 8, 1)
    want1 = np.array([dm.batch_latency(d, p, 8)
                      for d, p in zip(devices, profiles)])
    assert np.array_equal(got1, want1)


# ---------------------------------------------------------------------------
# max_steps truncation is reported, not silent
# ---------------------------------------------------------------------------
def test_truncated_flag_set_when_step_budget_hit():
    jobs = PAPER_JOBS[:4]
    eng = ClusterEngine(jobs, gpu_fleet(2),
                        controller_factory=_static_cf, seed=0)
    rep = eng.run(sim_time_limit=60.0, max_steps=20)
    assert rep["aggregate"]["truncated"] is True
    assert eng.steps_run == 20


def test_truncated_flag_clear_on_horizon_completion():
    jobs = PAPER_JOBS[:4]
    eng = ClusterEngine(jobs, gpu_fleet(2),
                        controller_factory=_static_cf, seed=0)
    rep = eng.run(sim_time_limit=2.0)
    assert rep["aggregate"]["truncated"] is False


def test_bench_check_fails_on_truncated_row(tmp_path, monkeypatch):
    """--check must flag a fresh row carrying truncated=1 even when every
    gated metric still clears its threshold."""
    import json

    from benchmarks import run as brun

    def fake_suite():
        return [("fake/row", 0.0, "thr=100.0/s,truncated=1")]

    monkeypatch.setattr(brun, "suites", lambda: {"fake": fake_suite})
    (tmp_path / "BENCH_fake.json").write_text(json.dumps({
        "suite": "fake",
        "rows": [{"name": "fake/row", "us_per_call": 0.0,
                  "derived": "thr=100.0/s"}],
    }))
    assert brun.check_against(str(tmp_path)) >= 1


# ---------------------------------------------------------------------------
# feasibility snapshot: report() reflects the placement the job was
# actually served under, not whatever co-residents exist at report time
# ---------------------------------------------------------------------------
def test_feasibility_snapshot_survives_later_coresidents():
    # a compute-bound profile whose bs=1 latency sits just under the SLO
    # on a whole Tesla P40 but blows through it on a 1/4 slice (the
    # steady-state floor scales with 1/share)
    prof = dm.JobProfile(name="steady-bound", host_ms=0.1, gpu1_ms=3.0,
                         amort=0.3, flops=26.0e9, param_bytes=50e6)
    tight = dataclasses.replace(PAPER_JOBS[0], job_id=501, slo_ms=4.0,
                                profile_override=prof)
    churn = [ChurnJob(job=tight, admit_s=0.0, depart_s=10.0)]
    # after the tight job departs, a crowd lands on the same device
    for k in range(3):
        churn.append(ChurnJob(
            job=dataclasses.replace(PAPER_JOBS[2], job_id=510 + k),
            admit_s=20.0, depart_s=None))
    eng = ClusterEngine([], gpu_fleet(1), churn=churn,
                        controller_factory=_static_cf, seed=0)
    rep = eng.run(sim_time_limit=40.0)
    row = next(r for r in rep["per_job"] if r["job_id"] == 501)
    # served alone -> feasible; the stale recomputation would price it
    # against the 3 co-residents it never shared the device with
    assert row["feasible"] is True
    assert eng._feasible_now(0) is False


# ---------------------------------------------------------------------------
# piecewise arrival integral (OpenLoopQueue bugfix): the Poisson mean is
# the integral of rate_fn over the window, not rate_fn(win_start) * window
# ---------------------------------------------------------------------------
def test_expected_arrivals_constant_rate_bit_identical():
    q_off = OpenLoopQueue(lambda t: 7.5, max_queue=10, seed=0)
    q_on = OpenLoopQueue(lambda t: 7.5, max_queue=10, seed=0,
                         piecewise_s=0.37)
    for a, b in ((0.0, 1.0), (2.0, 13.5), (5.0, 5.0), (3.0, 2.0)):
        assert q_off.expected_arrivals(a, b) == q_on.expected_arrivals(a, b)
        if b > a:
            assert q_on.expected_arrivals(a, b) == 7.5 * (b - a)


def test_expected_arrivals_piecewise_matches_brute_force():
    def rate(t):
        return 20.0 + 15.0 * np.sin(0.7 * t)

    q = OpenLoopQueue(rate, max_queue=10, seed=0, piecewise_s=0.05)
    for a, b in ((0.0, 4.0), (1.3, 9.7), (6.0, 6.4)):
        tt = np.linspace(a, b, 20001)
        want = float(np.trapezoid([rate(t) for t in tt], tt))
        got = q.expected_arrivals(a, b)
        np.testing.assert_allclose(got, want, rtol=1e-3)


def test_expected_arrivals_burst_boundary_not_mispriced():
    """The original bug: a stall-stretched window that starts in the burst
    phase was priced at the burst rate for its WHOLE length."""
    period, burst, base = 30.0, 60.0, 20.0

    def rate(t):
        return burst if (t % period) / period < 0.3 else base

    legacy = OpenLoopQueue(rate, max_queue=10, seed=0)
    fixed = OpenLoopQueue(rate, max_queue=10, seed=0,
                          piecewise_s=period / 8.0)
    # window [0, 30]: 30% at 60/s + 70% at 20/s = 960 expected arrivals
    exact = 0.3 * period * burst + 0.7 * period * base
    assert legacy.expected_arrivals(0.0, period) == burst * period  # 1800
    got = fixed.expected_arrivals(0.0, period)
    # trapezoid knots straddle the jump; error bounded by one segment
    assert abs(got - exact) < (burst - base) * (period / 8.0)
    assert abs(got - exact) < 0.2 * abs(burst * period - exact)

    # a queue that REGISTERS the jump points gets the exact left-Riemann
    # integral — no residual mispricing at all, on any window
    def breaks(a, b):
        out, t = [], np.floor(a / period) * period
        while t <= b:
            for x in (t, t + 0.3 * period):
                if a < x < b:
                    out.append(x)
            t += period
        return out

    stepped = OpenLoopQueue(rate, max_queue=10, seed=0, step_breaks=breaks)
    assert abs(stepped.expected_arrivals(0.0, period) - exact) <= 1e-9
    # hand-integrated windows straddling jumps at odd offsets:
    # [3, 47.5]: 6s@60 + 21s@20 + 9s@60 + 8.5s@20
    assert abs(stepped.expected_arrivals(3.0, 47.5)
               - (360.0 + 420.0 + 540.0 + 170.0)) <= 1e-9
    # [8.9, 9.1] straddles the burst-off edge at 9.0
    assert abs(stepped.expected_arrivals(8.9, 9.1)
               - (0.1 * burst + 0.1 * base)) <= 1e-9
    # constant sub-window: bit-identical to the single-point product
    assert stepped.expected_arrivals(10.0, 20.0) \
        == legacy.expected_arrivals(10.0, 20.0)


def test_poisson_split_statistical_agreement():
    """Sampling arrivals in one window == splitting the window into
    sub-intervals (Poisson superposition), in expectation."""
    def rate(t):
        return 40.0 if t < 5.0 else 10.0

    means = []
    for seed in range(300):
        q = OpenLoopQueue(rate, max_queue=10**9, seed=seed,
                          piecewise_s=1.0)
        q.step(0.0, 10.0, 0)
        means.append(q.submitted)
    mean_target = q.expected_arrivals(0.0, 10.0)
    # the trapezoid knot straddling the jump shaves the exact 250 to 235;
    # the sampler must hit ITS integral, and that integral must be within
    # one segment's worth of the exact one
    assert abs(mean_target - 250.0) <= (40.0 - 10.0) * 1.0 / 2.0
    assert abs(np.mean(means) - mean_target) < 3 * np.sqrt(250.0 / 300)


# ---------------------------------------------------------------------------
# TailLatencyWindow.add_many wrap-around property: whatever the call
# pattern, p95 == np.quantile over the last `window` of the full stream
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_tail_window_oversize_add_many_matches_quantile(seed):
    rng = np.random.default_rng(seed)
    win = TailLatencyWindow(window=50)
    stream: list = []
    # first call alone exceeds the window, then assorted smaller calls
    sizes = [120] + [int(x) for x in rng.integers(1, 60, size=12)]
    for sz in sizes:
        batch = rng.exponential(0.05, size=sz)
        win.add_many(batch)
        stream.extend(batch.tolist())
        want = float(np.quantile(np.asarray(stream[-50:]), 0.95))
        np.testing.assert_allclose(win.p95, want, rtol=1e-12)
        assert len(win) == min(len(stream), 50)
