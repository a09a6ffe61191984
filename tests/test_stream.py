"""Streaming fleet monitor (ISSUE 5).

Four groups:

* the shared step-integration kernel — pinned against the historical
  scalar ``_integrate_readings`` formula (single source of truth);
* stream↔offline parity — replaying a fleet's poll series through
  ``MonitorService`` reproduces ``integrate_polled`` / ``fleet_audit``
  on the same reading schedules within float accumulation order;
* stream edge cases — out-of-order, duplicate, delayed and dropped
  samples, silent devices, empty windows, single-sample devices — all
  degrade gracefully instead of raising;
* online estimators and queries — update-period convergence to the
  offline §4.1 estimator, windowed/by-label queries, health flags,
  telemetry integration.
"""
import numpy as np
import pytest

from repro.core import load as loads
from repro.core import microbench
from repro.core.engine_backend.numpy_backend import step_integrate
from repro.core.fleet_engine import SensorBank, fleet_audit
from repro.core.meter import Workload, _integrate_readings
from repro.core.sensor import OnboardSensor
from repro.core import profiles
from repro.core.stream import (IngestBuffer, MonitorService,
                               OnlinePeriodEstimator, StreamCorrections,
                               replay, stream_fleet)
from repro.core.telemetry import (CALIBRATED_TOLERANCE, SHUNT_TOLERANCE,
                                  FleetLedger)

MIXED_NAMES = ["a100"] * 10 + ["v100"] * 5 + ["h100_instant"] * 5
BURST = Workload("burst", loads.multi_phase_workload(
    [(0.130, 215.0), (0.070, 165.0)]))


def _legacy_integrate(ts, vals, t0, t1):
    """The pre-refactor scalar rectangle rule (the pinned reference)."""
    sel = (ts >= t0) & (ts <= t1)
    if not np.any(sel):
        return 0.0
    t = ts[sel]
    v = vals[sel]
    dt = np.diff(np.concatenate([t, [t1]]))
    return float(np.sum(v * dt))


# ---------------------------------------------------------------------------
# shared step-integration kernel
# ---------------------------------------------------------------------------

def test_step_integrate_matches_legacy_scalar():
    rng = np.random.default_rng(0)
    for _ in range(20):
        m = int(rng.integers(1, 60))
        ts = np.sort(rng.uniform(0.0, 10.0, m))
        vals = rng.uniform(50.0, 250.0, m)
        t0 = float(rng.uniform(-1.0, 9.0))
        t1 = t0 + float(rng.uniform(0.0, 6.0))
        got = step_integrate(ts[None, :], vals[None, :],
                             np.array([t0]), np.array([t1]))[0]
        assert got == pytest.approx(_legacy_integrate(ts, vals, t0, t1),
                                    rel=1e-12, abs=1e-9)


def test_integrate_readings_delegates_to_kernel():
    ts = np.arange(100) * 0.01
    vals = 100.0 + 10.0 * np.sin(ts)
    for (a, b) in [(0.05, 0.73), (0.0, 0.99), (0.5, 0.5), (0.9, 0.2),
                   (2.0, 3.0), (-1.0, 0.31)]:
        assert _integrate_readings(ts, vals, a, b) == pytest.approx(
            _legacy_integrate(ts, vals, a, b), rel=1e-12, abs=1e-12)


def test_step_integrate_padded_rows_and_empty_windows():
    ts = np.array([[0.1, 0.2, 0.3, np.inf, np.inf],
                   [0.5, np.inf, np.inf, np.inf, np.inf]])
    vals = np.array([[10.0, 20.0, 30.0, 7.0, 7.0],
                     [100.0, 3.0, 3.0, 3.0, 3.0]])
    # row 0 full window; row 1 single sample held to t1
    out = step_integrate(ts, vals, np.array([0.0, 0.0]),
                         np.array([0.4, 1.0]))
    assert out[0] == pytest.approx(10 * 0.1 + 20 * 0.1 + 30 * 0.1)
    assert out[1] == pytest.approx(100.0 * 0.5)
    # empty / inverted windows integrate to exactly 0
    out = step_integrate(ts, vals, np.array([0.31, 2.0]),
                         np.array([0.4, 1.0]))
    assert out[0] == 0.0  # no sample inside [0.31, 0.4]... (0.3 < 0.31)
    out = step_integrate(ts, vals, np.array([0.4, 0.9]),
                         np.array([0.0, 0.1]))
    assert np.all(out == 0.0)


def test_step_integrate_empty_series_is_zero():
    """A zero-sample series integrates to 0, like the pre-refactor
    scalar path."""
    out = step_integrate(np.empty((2, 0)), np.empty((2, 0)),
                         np.array([0.0, 1.0]), np.array([1.0, 2.0]))
    np.testing.assert_array_equal(out, [0.0, 0.0])
    assert _integrate_readings(np.empty(0), np.empty(0), 0.0, 1.0) == 0.0


def test_step_integrate_trapezoid():
    ts = np.array([[0.0, 1.0, 2.0]])
    vals = np.array([[0.0, 100.0, 50.0]])
    out = step_integrate(ts, vals, np.array([0.0]), np.array([2.0]),
                         trapezoid=True)
    assert out[0] == pytest.approx(0.5 * (0 + 100) + 0.5 * (100 + 50))


# ---------------------------------------------------------------------------
# stream ↔ offline parity
# ---------------------------------------------------------------------------

def test_stream_matches_offline_integrate_polled_mixed_fleet():
    n = len(MIXED_NAMES)
    ws = loads.mixed_fleet_workloads(n, seed=7, as_bank=True)
    res = stream_fleet(n, profile=MIXED_NAMES, workload=ws, seed=0,
                       compare=True)
    np.testing.assert_allclose(res.naive_stream_j, res.naive_offline_j,
                               rtol=1e-11)
    np.testing.assert_allclose(res.corrected_stream_j,
                               res.corrected_offline_j, rtol=1e-11)
    # the §5 corrections actually move the estimate (they are not a no-op)
    assert np.max(np.abs(res.corrected_stream_j
                         - res.naive_stream_j)) > 1e-3


def test_stream_matches_fleet_audit_naive():
    n = len(MIXED_NAMES)
    ws = loads.mixed_fleet_workloads(n, seed=7, as_bank=True)
    audit = fleet_audit(n, profile=MIXED_NAMES, workload=ws, seed=0)
    res = stream_fleet(n, profile=MIXED_NAMES, workload=ws, seed=0)
    np.testing.assert_allclose(res.naive_stream_j, audit.naive_j,
                               rtol=1e-11)


def test_stream_shared_workload_parity():
    res = stream_fleet(8, profile="a100", workload=BURST, seed=3,
                       compare=True)
    np.testing.assert_allclose(res.naive_stream_j, res.naive_offline_j,
                               rtol=1e-11)
    audit = fleet_audit(8, profile="a100", workload=BURST, seed=3)
    np.testing.assert_allclose(res.naive_stream_j, audit.naive_j,
                               rtol=1e-11)


def test_stream_chunked_equals_unchunked():
    n = len(MIXED_NAMES)
    ws = loads.mixed_fleet_workloads(n, seed=11, as_bank=True)
    whole = stream_fleet(n, profile=MIXED_NAMES, workload=ws, seed=0)
    chunked = stream_fleet(n, profile=MIXED_NAMES, workload=ws, seed=0,
                           chunk_devices=7)
    np.testing.assert_array_equal(chunked.naive_stream_j,
                                  whole.naive_stream_j)
    np.testing.assert_array_equal(chunked.corrected_stream_j,
                                  whole.corrected_stream_j)


def test_stream_scenario_spec_slab_generation():
    spec = loads.FleetScenarioSpec(n=12, seed=5)
    ws = spec.workload_set()
    ref = stream_fleet(12, profile="a100", workload=ws, seed=1)
    got = stream_fleet(12, profile="a100", workload=spec, seed=1,
                       chunk_devices=5)
    np.testing.assert_array_equal(got.naive_stream_j, ref.naive_stream_j)


# ---------------------------------------------------------------------------
# edge cases: disorder, duplication, loss, silence
# ---------------------------------------------------------------------------

def _attached_bank(n=6, seed=0):
    bank = SensorBank.from_catalog(["a100"] * n, seeds=np.arange(n) + seed)
    tl = BURST.timeline.shift(0.3)
    bank.attach(tl, t_end=tl.t_end + 1.0)
    return bank


def test_shuffled_and_duplicated_slabs_are_exact():
    """Within-slab disorder is sorted, duplicates dropped: the result is
    *bitwise* the clean replay.  The clean reference forces the
    flattened ingest path — the messy stream necessarily flows through
    it, and this pin is about the resort/dedup being exact (the grid
    fast path matches it within float accumulation order, pinned in
    test_stream_backend.py)."""
    bank = _attached_bank()
    clean = MonitorService(6)
    replay(bank, clean, 0.0, 1.0, grid=False)
    messy = MonitorService(6)
    rep = replay(bank, messy, 0.0, 1.0, shuffle=True, dup_fraction=0.3,
                 seed=4)
    assert rep["duplicates"] > 0
    np.testing.assert_array_equal(messy.state.energy_j,
                                  clean.state.energy_j)
    np.testing.assert_array_equal(messy.state.win_corr_j,
                                  clean.state.win_corr_j)


def test_delayed_samples_count_late_and_do_not_raise():
    bank = _attached_bank()
    mon = MonitorService(6)
    rep = replay(bank, mon, 0.0, 1.0, delay_fraction=0.2, seed=2)
    assert rep["late"] > 0
    clean = MonitorService(6)
    replay(bank, clean, 0.0, 1.0)
    # late samples are dropped; rectangle integration fills the gaps, so
    # totals stay close to the clean replay
    np.testing.assert_allclose(mon.state.energy_j, clean.state.energy_j,
                               rtol=0.05)


def test_dropped_samples_keep_totals_close():
    bank = _attached_bank()
    mon = MonitorService(6)
    replay(bank, mon, 0.0, 1.0, drop_fraction=0.1, seed=9)
    clean = MonitorService(6)
    replay(bank, clean, 0.0, 1.0)
    np.testing.assert_allclose(mon.state.energy_j, clean.state.energy_j,
                               rtol=0.05)


def test_silent_device_flags_and_max_hold_cap():
    mon = MonitorService(2, max_hold_s=0.5, ring_slots=4)
    # device 0 polls steadily to t=1.0 then goes silent; device 1 sends a
    # single sample and goes silent immediately
    ts0 = 0.1 * np.arange(11)
    mon.ingest(np.zeros(11, np.int64), ts0, np.full(11, 100.0))
    mon.ingest([1], [0.0], [80.0])
    flags = mon.flags(t=5.0)
    assert bool(flags["silent"][0]) and bool(flags["silent"][1])
    fe = mon.fleet_energy(t=5.0)
    # gap-aware rectangle: any sampling gap longer than max_hold_s stops
    # extrapolating after max_hold_s (steady 0.1 s polls are unaffected)
    assert fe.per_device_j[0] == pytest.approx(100.0 * 1.0 + 100.0 * 0.5)
    assert fe.per_device_j[1] == pytest.approx(80.0 * 0.5)


def test_single_sample_and_never_reporting_devices():
    mon = MonitorService(3, ring_slots=4)
    mon.ingest([0], [0.5], [120.0])
    fe = mon.fleet_energy(t=2.0)
    assert fe.per_device_j[0] == pytest.approx(120.0 * 1.5)
    assert fe.per_device_j[1] == 0.0 and fe.per_device_j[2] == 0.0
    assert fe.n_reporting == 1
    assert np.isnan(mon.update_period_s()).all()
    e, cov = mon.energy_between(0.6, 0.7)
    assert cov[0] and e[0] == pytest.approx(120.0 * 0.1)


def test_empty_and_precoverage_windows_degrade_gracefully():
    mon = MonitorService(1, ring_slots=4)
    ts = 0.1 * np.arange(1, 30)          # 2.9 s of samples, ring keeps 4
    mon.ingest(np.zeros(len(ts), np.int64), ts, np.full(len(ts), 50.0))
    # window entirely before the first sample: zero, covered
    e, cov = mon.energy_between(0.0, 0.05)
    assert cov[0] and e[0] == 0.0
    # window older than ring coverage: nan + not covered, no raise
    e, cov = mon.energy_between(0.5, 0.6)
    assert not cov[0] and np.isnan(e[0])
    # recent window inside ring coverage: exact
    e, cov = mon.energy_between(2.65, 2.85)
    assert cov[0] and e[0] == pytest.approx(50.0 * 0.2)


def test_invalid_samples_and_bad_inputs():
    mon = MonitorService(2)
    rep = mon.ingest([0, 1], [np.nan, 1.0], [100.0, np.inf])
    assert rep.invalid == 2 and rep.accepted == 0
    with pytest.raises(ValueError):
        mon.ingest([0, 2], [0.0, 0.0], [1.0, 1.0])    # id out of range
    with pytest.raises(ValueError):
        mon.ingest([0], [0.0, 1.0], [1.0])            # shape mismatch
    with pytest.raises(ValueError):
        MonitorService(2, integration="simpson")
    with pytest.raises(ValueError):
        MonitorService(0)
    mon2 = MonitorService(2)
    mon2.ingest([0], [0.0], [1.0])
    with pytest.raises(RuntimeError):
        mon2.set_windows(0.0, 1.0)       # windows after first ingest


def test_energy_between_rejects_inverted_and_nan_windows():
    """Edge contract (docs/streaming.md): malformed windows raise at the
    API boundary instead of returning silently-wrong zeros."""
    mon = MonitorService(2)
    mon.ingest([0, 1], [0.0, 0.0], [100.0, 100.0])
    with pytest.raises(ValueError):
        mon.energy_between(1.0, 0.5)
    with pytest.raises(ValueError):
        mon.energy_between(np.nan, 1.0)
    with pytest.raises(ValueError):
        mon.energy_between(0.0, np.nan)
    # degenerate t0 == t1: exactly zero wherever covered
    e, cov = mon.energy_between(0.0, 0.0)
    assert np.all(e[cov] == 0.0)


def test_by_label_empty_groups_report_nan_means():
    """Groups with no covered device answer total_j = 0 but nan
    mean/std — 'no data' must not masquerade as 'measured zero'."""
    mon = MonitorService(2, labels=np.array(["a", "b"], dtype=object))
    for d in mon.by_label().values():
        assert d["n_covered"] == 0 and d["total_j"] == 0.0
        assert np.isnan(d["mean_j"]) and np.isnan(d["std_j"])


def test_window_energy_past_query_reports_nan_not_overstatement():
    """A still-open window that already streamed past the query instant
    cannot be rewound: the device reports nan instead of the inflated
    through-newest-sample value; closed windows stay exact."""
    mon = MonitorService(1)
    mon.set_windows(0.0, 20.0)
    ts = 0.5 * np.arange(20)                 # samples to t = 9.5
    mon.ingest(np.zeros(20, np.int64), ts, np.full(20, 100.0))
    assert np.isnan(mon.window_energy(t=5.0, corrected=False)[0])
    # live/future instants still serve the rectangle tail
    assert mon.window_energy(t=10.0, corrected=False)[0] == \
        pytest.approx(100.0 * 10.0)
    # instants before the window opens are exactly 0
    assert mon.window_energy(t=0.0, corrected=False)[0] == 0.0
    # a *closed* window is exact for any later query instant
    mon2 = MonitorService(1)
    mon2.set_windows(0.0, 2.0)
    mon2.ingest(np.zeros(20, np.int64), ts, np.full(20, 100.0))
    assert mon2.window_energy(t=5.0, corrected=False)[0] == \
        pytest.approx(100.0 * 2.0)


def test_integrate_polled_vector_grid_offset():
    """Per-device grid_offset equals the per-group scalar calls (fleets
    mixing averaging windows re-synchronise in one pass)."""
    bank = _attached_bank(n=6)
    a = np.full(6, 0.3)
    b = np.full(6, 0.5)
    offs = np.array([0.0, -0.025, -0.1, 0.0, -0.025, -0.1])
    got = bank.integrate_polled(0.0, 1.0, 0.001, a, b, grid_offset=offs)
    for w in np.unique(offs):
        rows = offs == w
        ref = bank.integrate_polled(0.0, 1.0, 0.001, a, b,
                                    grid_offset=float(w))
        np.testing.assert_allclose(got[rows], ref[rows], rtol=1e-12)


def test_trapezoid_integration_mode():
    mon = MonitorService(1, integration="trapezoid")
    mon.ingest([0, 0, 0], [0.0, 1.0, 2.0], [0.0, 100.0, 50.0])
    assert mon.state.energy_j[0] == pytest.approx(
        0.5 * (0 + 100) + 0.5 * (100 + 50))


# ---------------------------------------------------------------------------
# online estimators, queries, flags, telemetry
# ---------------------------------------------------------------------------

def test_online_period_estimator_unit():
    est = OnlinePeriodEstimator(2, min_runs=3)
    est.record(np.zeros(8, np.int64), np.full(8, 0.1))
    est.record(np.array([0]), np.array([0.2]))       # one outlier run
    out = est.estimates()
    assert out[0] == pytest.approx(0.1, rel=1e-9)    # median bin mean
    assert np.isnan(out[1])
    assert est.n_runs[0] == 9


@pytest.mark.parametrize("change", ["record", "record_most", "replace",
                                    "min_runs"])
def test_online_period_estimates_recompute_only_changed_rows(change):
    """Estimates kept between calls equal a fresh estimator's on the same
    histograms, whether rows were recorded, the arrays replaced (growth,
    restore) or ``min_runs`` changed in between."""
    rng = np.random.default_rng(5)
    est = OnlinePeriodEstimator(40, min_runs=3)
    for _ in range(3):
        est.record(rng.integers(0, 40, 60), rng.uniform(0.01, 0.3, 60))
        first = est.estimates()
    first[:] = -1.0                     # a copy: the cache is untouched
    if change == "record":
        est.record(rng.integers(0, 8, 30), rng.uniform(0.5, 2.0, 30))
    elif change == "record_most":
        est.record(np.arange(30), rng.uniform(0.5, 2.0, 30))
    elif change == "replace":
        est.counts = np.concatenate([est.counts, est.counts[:5] * 3])
        est.sums = np.concatenate([est.sums, est.sums[:5] * 3])
    else:
        est.min_runs = 6
    fresh = OnlinePeriodEstimator(est.counts.shape[0],
                                  min_runs=est.min_runs)
    fresh.counts, fresh.sums = est.counts.copy(), est.sums.copy()
    np.testing.assert_array_equal(est.estimates(), fresh.estimates())


def test_online_period_matches_offline_estimator():
    """Streaming the §4.1 square-wave capture through the monitor lands
    on the same update period as the offline median-of-complete-runs."""
    prof = profiles.get("a100")
    sensor = OnboardSensor(prof, seed=7)
    offline = microbench.estimate_update_period(sensor, duration_s=4.0)

    bank = SensorBank.from_catalog(["a100"], seeds=[7])
    wave = loads.square_wave(period_s=0.020, n_cycles=int(4.0 / 0.020),
                             p_high=220.0, p_low=70.0, seed=11)
    bank.attach(wave, t_end=4.0)
    mon = MonitorService(1)
    replay(bank, mon, 0.0, 4.0, period_s=0.001, tick_s=0.25)
    online = float(mon.update_period_s()[0])
    assert online == pytest.approx(0.100, rel=0.05)
    assert online == pytest.approx(offline, rel=0.05)


def test_complete_run_durations_shared_rule():
    ts = 0.001 * np.arange(600)
    vals = np.searchsorted([0.03, 0.13, 0.33, 0.53], ts, side="right")
    runs = microbench.complete_run_durations(ts, vals)
    assert len(runs) == 3
    assert np.median(runs) == pytest.approx(0.2, abs=1e-9)
    # fewer than two changes -> no complete run
    assert len(microbench.complete_run_durations(ts, np.zeros(600))) == 0


def test_by_label_and_reading_stats():
    n = 8
    labels = np.array(["train"] * 4 + ["serve"] * 4, dtype=object)
    mon = MonitorService(n, labels=labels, ring_slots=8)
    ts = np.tile(0.1 * np.arange(1, 11), n)
    dev = np.repeat(np.arange(n), 10)
    v = np.where(dev < 4, 200.0, 100.0)
    mon.ingest(dev, ts, v)
    by = mon.by_label()
    assert set(by) == {"train", "serve"}
    assert by["train"]["total_j"] == pytest.approx(4 * 200.0 * 0.9)
    assert by["serve"]["total_j"] == pytest.approx(4 * 100.0 * 0.9)
    # windowed breakdown over ring coverage
    by_w = mon.by_label(t0=0.55, t1=0.95)
    assert by_w["train"]["total_j"] == pytest.approx(4 * 200.0 * 0.4)
    stats = mon.reading_stats()
    assert stats["train"]["mean_err"] == pytest.approx(200.0)
    assert stats["serve"]["worst_abs"] == pytest.approx(100.0)


def test_anomaly_envelope_and_drift_flags():
    mon = MonitorService(2, envelope_w=(0.0, 150.0), drift_tau_s=0.1,
                         drift_rel=0.05, drift_abs_w=1.0)
    ts = 0.01 * np.arange(1, 101)
    # stream tick by tick (the EWMA tracks recency across slabs):
    # device 0 holds steady, device 1 ramps up and leaves the envelope
    for lo in range(0, 100, 10):
        sl = ts[lo:lo + 10]
        mon.ingest(np.zeros(10, np.int64), sl, np.full(10, 100.0))
        mon.ingest(np.ones(10, np.int64), sl, 100.0 + sl * 100.0)
    flags = mon.flags()
    assert not flags["anomalous"][0]
    assert bool(flags["anomalous"][1])      # peaked at 200 W > 150 W
    assert not flags["drifting"][0]
    assert bool(flags["drifting"][1])


def test_fleet_energy_uncertainty_tolerances():
    corr = StreamCorrections.identity(2)
    corr.calibrated[0] = True
    mon = MonitorService(2, corrections=corr)
    mon.ingest([0, 0, 1, 1], [0.0, 1.0, 0.0, 1.0],
               [100.0, 100.0, 100.0, 100.0])
    fe = mon.fleet_energy()
    assert fe.sigma_worstcase_j == pytest.approx(
        100.0 * CALIBRATED_TOLERANCE + 100.0 * SHUNT_TOLERANCE)
    assert fe.sigma_independent_j <= fe.sigma_worstcase_j


def test_register_monitor_in_fleet_ledger():
    labels = np.array(["a", "b"], dtype=object)
    mon = MonitorService(2, labels=labels)
    mon.ingest([0, 0, 1, 1], [0.0, 2.0, 0.0, 2.0],
               [100.0, 100.0, 50.0, 50.0])
    led = FleetLedger()
    led.register_monitor(mon)
    s = led.summary()
    assert s.n_devices == 2
    assert s.total_j == pytest.approx(300.0)
    by = led.by_label()
    assert by["a"].total_j == pytest.approx(200.0)
    assert by["b"].total_j == pytest.approx(100.0)


def test_ingest_buffer_ring_ordering():
    buf = IngestBuffer(1, 4)
    dev = np.zeros(6, np.int64)
    ordi = np.arange(6)
    cnt = np.full(6, 6)
    t = np.arange(6.0)
    e = np.cumsum(t)
    buf.write(dev, ordi, cnt, t, t * 10, e, e, np.array([0]),
              np.array([6]))
    ts, vs, er, ec = buf.sorted_view()
    np.testing.assert_array_equal(ts[0], [2.0, 3.0, 4.0, 5.0])
    assert int(buf.n_written[0]) == 6
    with pytest.raises(ValueError):
        IngestBuffer(1, -1)
    none = IngestBuffer(1, 0)
    with pytest.raises(RuntimeError):
        none.sorted_view()


def test_monitor_bounded_state_reporting():
    mon = MonitorService(1000, ring_slots=4)
    per_device = mon.nbytes() / 1000
    assert per_device < 1000     # a few hundred bytes per device
