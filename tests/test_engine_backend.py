"""Execution-backend contracts (ISSUE 3, pallas tier in ISSUE 6).

Three groups:

* registry semantics (resolve/auto-detect/unknown names);
* numpy↔accelerated kernel and end-to-end parity — every accelerated
  backend (jax, pallas via the shared ``accel_backend`` fixture) must
  reproduce the numpy backend within one reporting quantum on every
  transient kind in the catalog, for shared and per-device timelines,
  through both measurement protocols (skipped when jax is missing, e.g.
  in the numpy-only core CI job);
* ``integrate_polled`` degenerate windows (``a == b``, ``b < a``, window
  entirely off the poll grid), pinned against the scalar
  ``meter._integrate_readings`` reference on both backends.
"""
import numpy as np
import pytest

from _tol import assert_tier_close

from repro.core import load as loads
from repro.core import profiles
from repro.core.engine_backend import (available_backends, get_backend,
                                       has_jax, resolve_backend)
from repro.core.engine_backend.pytrees import TimelineArrays
from repro.core.fleet_engine import SensorBank, fleet_audit
from repro.core.ground_truth import TimelineBank
from repro.core.meter import (GoodPracticeConfig, Workload, WorkloadSet,
                              _integrate_readings,
                              measure_good_practice_batch,
                              measure_naive_batch)

# one of each behavioural class: part-time boxcar, long-window boxcar,
# fast Volta grid, logarithmic transients, estimation-based Fermi
MIXED = ["a100", "h100_average", "v100", "rtx3090_530", "kepler",
         "maxwell", "fermi2", "gh200_gpu", "tpu_v5e_dash"]

TL = loads.square_wave(0.230, 16, 220.0, 90.0)

needs_jax = pytest.mark.skipif(not has_jax(), reason="jax not installed")


def _per_device_timelines(n, seed=0):
    rng = np.random.default_rng(seed)
    return [loads.square_wave(float(rng.uniform(0.1, 0.4)),
                              int(rng.integers(4, 12)),
                              float(rng.uniform(150, 250)),
                              float(rng.uniform(60, 120)), seed=seed + i)
            for i in range(n)]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_numpy_backend_always_available():
    assert "numpy" in available_backends()
    assert resolve_backend(None) == "numpy"
    assert resolve_backend("numpy") == "numpy"
    be = get_backend("numpy")
    assert be.name == "numpy"


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend("cuda")
    with pytest.raises(ValueError, match="unknown backend"):
        SensorBank.from_catalog(["a100"], backend="cuda")


def test_auto_resolves_to_an_available_backend():
    assert resolve_backend("auto") in available_backends()


@needs_jax
def test_jax_backend_listed_and_loadable():
    assert available_backends() == ("numpy", "jax", "pallas")
    assert resolve_backend("auto") == "jax"
    assert get_backend("jax").name == "jax"


@needs_jax
def test_pallas_backend_listed_and_loadable():
    assert "pallas" in available_backends()
    assert resolve_backend("pallas") == "pallas"
    assert get_backend("pallas").name == "pallas"


@needs_jax
def test_pallas_interpret_follows_platform_only(monkeypatch):
    import jax
    from repro.core.engine_backend import pallas_backend
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert pallas_backend._interpret() == (jax.default_backend() == "cpu")


@needs_jax
def test_compile_cache_follows_env(monkeypatch, tmp_path):
    import jax
    from repro.core.engine_backend import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


@needs_jax
def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch):
    import os
    import jax
    from repro.core.engine_backend import use_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = use_compile_cache()
        assert path == use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")


def test_bank_records_backend_and_propagates_to_views():
    bank = SensorBank.from_catalog(["a100", "v100"], base_seed=0)
    assert bank.backend == "numpy"
    assert bank.subset(np.array([1])).backend == "numpy"
    other = bank.with_backend("numpy")
    assert other.true_gain[0] == bank.true_gain[0]   # rows shared, not redrawn


# ---------------------------------------------------------------------------
# kernel-level parity
# ---------------------------------------------------------------------------

def test_kernel_parity_boxcar_and_integral(accel_backend):
    npb, jxb = get_backend("numpy"), get_backend(accel_backend)
    tls = TimelineBank.from_timelines(_per_device_timelines(6, seed=3))
    rng = np.random.default_rng(0)
    t1 = rng.uniform(-0.5, 3.0, size=(6, 40))
    t0 = t1 - rng.uniform(0.0, 0.3, size=(6, 40))
    arr = tls.arrays
    np.testing.assert_allclose(jxb.timeline_integral(arr, t0, t1),
                               npb.timeline_integral(arr, t0, t1),
                               rtol=1e-12, atol=1e-9)
    np.testing.assert_allclose(jxb.boxcar_means(arr, t0, t1),
                               npb.boxcar_means(arr, t0, t1),
                               rtol=1e-12, atol=1e-9)


def test_kernel_parity_boxcar_single_row_broadcast(accel_backend):
    npb, jxb = get_backend("numpy"), get_backend(accel_backend)
    bank = TimelineBank.from_timelines([TL])
    rng = np.random.default_rng(1)
    t1 = rng.uniform(0.0, 4.0, size=(5, 30))
    t0 = t1 - 0.025
    np.testing.assert_allclose(jxb.boxcar_means(bank.arrays, t0, t1),
                               npb.boxcar_means(bank.arrays, t0, t1),
                               rtol=1e-12, atol=1e-9)


def test_kernel_parity_log_filter(accel_backend):
    npb, jxb = get_backend("numpy"), get_backend(accel_backend)
    tls = TimelineBank.from_timelines(_per_device_timelines(4, seed=9))
    rng = np.random.default_rng(2)
    ticks = np.sort(rng.uniform(0.0, 3.0, size=(4, 25)), axis=1)
    tau = rng.uniform(0.2, 1.0, size=4)
    got = jxb.log_filter(tls.arrays, ticks, tau)
    ref = npb.log_filter(tls.arrays, ticks, tau)
    # the associative scan reorders the recurrence's float ops, so allow
    # tiny drift — far below one reporting quantum (0.01 W)
    assert_tier_close(got, ref, accel_backend, 1e-9, 1e-9)


def test_kernel_parity_poll_counts_and_query_slots(accel_backend):
    npb, jxb = get_backend("numpy"), get_backend(accel_backend)
    bank = SensorBank.from_catalog(MIXED, base_seed=17)
    bank.attach(TL, t_end=5.0)
    sched = bank._schedule
    from repro.core.engine_backend.pytrees import PollGrid
    n = bank.n_devices
    grid = PollGrid(0.0, np.full(n, 4.0), 0.001, -0.025)
    rng = np.random.default_rng(3)
    a = rng.uniform(0.0, 2.0, size=n)
    b = a + rng.uniform(0.0, 2.0, size=n)
    ref = npb.poll_counts(sched, grid, a, b)
    got = jxb.poll_counts(sched, grid, a, b)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))
    tq = rng.uniform(0.0, 5.0, size=(n, 16))
    np.testing.assert_array_equal(npb.query_slots(sched, tq),
                                  jxb.query_slots(sched, tq))


# ---------------------------------------------------------------------------
# end-to-end parity: every transient kind, both timeline shapes
# ---------------------------------------------------------------------------

def test_backend_parity_shared_timeline_all_kinds(accel_backend):
    """Accelerated readings match numpy within one reporting quantum, per
    device, across every transient kind in the catalog (the acceptance
    pin)."""
    b_np = SensorBank.from_catalog(MIXED, base_seed=42)
    b_jx = SensorBank.from_catalog(MIXED, base_seed=42,
                                   backend=accel_backend)
    b_np.attach(TL, t_end=6.0)
    b_jx.attach(TL, t_end=6.0)
    qs = np.linspace(0.0, 6.0, 400)
    v_np, v_jx = b_np.query(qs), b_jx.query(qs)
    for i, name in enumerate(MIXED):
        quantum = profiles.get(name).quantum_w
        np.testing.assert_allclose(v_jx[i], v_np[i], atol=quantum + 1e-12,
                                   err_msg=f"device {i} ({name})")


def test_backend_parity_per_device_timelines_all_kinds(accel_backend):
    tb = TimelineBank.from_timelines(_per_device_timelines(len(MIXED),
                                                           seed=5))
    b_np = SensorBank.from_catalog(MIXED, base_seed=11)
    b_jx = SensorBank.from_catalog(MIXED, base_seed=11,
                                   backend=accel_backend)
    b_np.attach(tb, t_end=6.0)
    b_jx.attach(tb, t_end=6.0)
    qs = np.linspace(0.0, 6.0, 400)
    v_np, v_jx = b_np.query(qs), b_jx.query(qs)
    for i, name in enumerate(MIXED):
        quantum = profiles.get(name).quantum_w
        np.testing.assert_allclose(v_jx[i], v_np[i], atol=quantum + 1e-12,
                                   err_msg=f"device {i} ({name})")


def test_backend_parity_catalog_profiles_scalar_contract(accel_backend):
    """Every catalog profile that publishes readings also honours the
    scalar-equivalence contract under the accelerated backends."""
    names = [n for n, p in profiles.CATALOG.items() if p.supported]
    bank = SensorBank.from_catalog(names, base_seed=3,
                                   backend=accel_backend)
    bank.attach(TL, t_end=4.0)
    qs = np.linspace(0.0, 4.0, 200)
    got = bank.query(qs)
    for i, name in enumerate(names):
        s = bank.scalar_reference(i)
        s.attach(TL, t_end=4.0)
        quantum = profiles.get(name).quantum_w
        np.testing.assert_allclose(got[i], s.query(qs),
                                   atol=quantum + 1e-12,
                                   err_msg=f"device {i} ({name})")


def test_backend_parity_naive_batch(accel_backend):
    wls = WorkloadSet([Workload(f"w{i}", tl) for i, tl in
                       enumerate(_per_device_timelines(len(MIXED), seed=2))])
    b_np = SensorBank.from_catalog(MIXED, base_seed=7)
    b_jx = SensorBank.from_catalog(MIXED, base_seed=7,
                                   backend=accel_backend)
    e_np = measure_naive_batch(b_np, wls)
    e_jx = measure_naive_batch(b_jx, wls)
    np.testing.assert_allclose(e_jx, e_np, rtol=1e-9, atol=1e-6)


def test_backend_parity_good_practice_batch(accel_backend):
    from repro.core.calibrate import CalibrationRecord
    names = ["a100", "v100", "kepler", "fermi2"]
    wl = Workload("w", loads.multi_phase_workload([(0.130, 215.0),
                                                   (0.070, 165.0)]))
    calibs = {}
    for n in set(names):
        p = profiles.get(n)
        calibs[n] = CalibrationRecord(
            "d", n, p.update_period_s, p.window_s, "instant",
            2.5 * p.update_period_s, sampled_fraction=p.sampled_fraction)
    cfg = GoodPracticeConfig(n_trials=2)
    b_np = SensorBank.from_catalog(names, base_seed=5)
    est_np = measure_good_practice_batch(b_np, wl, calibs, cfg)
    est_jx = measure_good_practice_batch(b_np, wl, calibs, cfg,
                                         backend=accel_backend)
    # a filter state within float32 rounding can land a reading on the
    # other side of a quantum boundary: the pallas bound covers that
    assert_tier_close(est_jx.joules_per_rep, est_np.joules_per_rep,
                      accel_backend, 1e-9, 1e-6)
    assert_tier_close(est_jx.trial_values, est_np.trial_values,
                      accel_backend, 1e-9, 1e-6)


def test_backend_parity_fleet_audit_stats(accel_backend):
    names = ["a100"] * 30 + ["v100"] * 20 + ["maxwell"] * 10
    r_np = fleet_audit(60, profile=names, seed=4)
    r_jx = fleet_audit(60, profile=names, seed=4, backend=accel_backend)
    np.testing.assert_allclose(r_jx.naive_j, r_np.naive_j,
                               rtol=1e-9, atol=1e-6)


# ---------------------------------------------------------------------------
# integrate_polled degenerate windows (both backends, scalar-pinned)
# ---------------------------------------------------------------------------

DEGENERATE = [
    ("a_eq_b_on_grid", 1.0, 1.0),
    ("a_eq_b_off_grid", 1.0005, 1.0005),
    ("b_lt_a", 2.0, 1.0),
    ("before_grid", -3.0, -1.0),
    ("after_grid", 9.0, 11.0),
    ("inside_one_step", 1.0002, 1.0008),   # no poll instant falls inside
]


def _degenerate_backends():
    return [None] + (["jax", "pallas"] if has_jax() else [])


@pytest.mark.parametrize("name,a,b", DEGENERATE)
def test_integrate_polled_degenerate_windows(name, a, b):
    """Empty/degenerate windows integrate to exactly 0.0 on every device,
    matching the scalar reference (`j1 = min(j1, m_i - 1)` must not leave
    a phantom step when the selected range is empty)."""
    names = ["a100", "v100", "kepler"]
    for backend in _degenerate_backends():
        bank = SensorBank.from_catalog(names, base_seed=5, backend=backend)
        bank.attach(TL, t_end=5.0)
        got = bank.integrate_polled(0.0, 4.0, 0.001, a, b)
        for i in range(len(names)):
            s = bank.scalar_reference(i)
            s.attach(TL, t_end=5.0)
            ts, vals = s.poll(0.0, 4.0, period_s=0.001)
            ref = _integrate_readings(ts, vals, a, b)
            assert got[i] == pytest.approx(ref, abs=1e-12), \
                f"{name} device {i} backend={backend or 'numpy'}"
            assert got[i] == 0.0


def test_integrate_polled_window_past_grid_end_matches_scalar():
    """b beyond the last poll instant: the final reading extends to b,
    exactly as `_integrate_readings` does on the scalar series."""
    names = ["a100", "v100"]
    for backend in _degenerate_backends():
        bank = SensorBank.from_catalog(names, base_seed=3, backend=backend)
        bank.attach(TL, t_end=6.0)
        got = bank.integrate_polled(0.0, 4.0, 0.001, 3.9, 4.5)
        for i in range(len(names)):
            s = bank.scalar_reference(i)
            s.attach(TL, t_end=6.0)
            ts, vals = s.poll(0.0, 4.0, period_s=0.001)
            ref = _integrate_readings(ts, vals, 3.9, 4.5)
            assert got[i] == pytest.approx(ref, abs=1e-9)
            assert got[i] > 0.0


def test_integrate_polled_single_poll_instant():
    """A window containing exactly one poll instant: only the partial
    step from that instant to b contributes."""
    bank = SensorBank.from_catalog(["a100"], base_seed=1)
    bank.attach(TL, t_end=5.0)
    got = bank.integrate_polled(0.0, 4.0, 0.001, 0.9995, 1.0009)
    s = bank.scalar_reference(0)
    s.attach(TL, t_end=5.0)
    ts, vals = s.poll(0.0, 4.0, period_s=0.001)
    ref = _integrate_readings(ts, vals, 0.9995, 1.0009)
    assert got[0] == pytest.approx(ref, abs=1e-12)
    assert got[0] > 0.0


# ---------------------------------------------------------------------------
# pytree containers
# ---------------------------------------------------------------------------

def test_timeline_arrays_roundtrip_view():
    tb = TimelineBank.from_timelines(_per_device_timelines(3, seed=8))
    arr = tb.arrays
    assert isinstance(arr, TimelineArrays)
    assert arr.n_rows == 3
    assert arr.edges is tb.edges          # zero-copy view
    np.testing.assert_array_equal(arr.t_start, tb.t_start)
    np.testing.assert_array_equal(arr.t_end, tb.t_end)


@needs_jax
def test_timeline_arrays_is_jax_pytree():
    import jax
    tb = TimelineBank.from_timelines([TL])
    leaves = jax.tree_util.tree_leaves(tb.arrays)
    assert len(leaves) == 4
