"""The monitor's history tier and ``fleet_series``.

* the tier against a plain per-device loop over the accepted samples,
  through both ingest paths, with late, duplicate and out-of-order
  samples and collector restarts;
* a boundary answers the same from the tier as from the ring where both
  cover it;
* ``fleet_series`` against the per-instant reductions of the direct
  path, with quarantined devices and partial coverage, and its edge
  cases;
* copy-on-write: a held snapshot answers bitwise the same after further
  ingest, publication copies none of the tier, and a slab writes the
  ring and the tier in place once no snapshot holds them;
* history off leaves every other array and answer as it was.
"""
import numpy as np
import pytest

from repro.core.stream import HealthPolicy, MonitorService, StreamCorrections
from repro.core.stream.state import NO_BOUNDARY
from repro.serve.monitor_service import MonitorQuery, MonitorQueryService

STEP, STEPS = 0.5, 6


@pytest.fixture(params=["numpy", "jax", "pallas"])
def backend(request):
    from repro.core.engine_backend import available_backends
    if request.param not in available_backends():
        pytest.skip(f"backend '{request.param}' not available")
    return request.param


def _corr(n, seed=0):
    rng = np.random.default_rng(seed)
    return StreamCorrections(
        gain=rng.uniform(0.9, 1.1, n), offset_w=rng.uniform(-3.0, 3.0, n),
        time_shift_s=rng.uniform(0.0, 0.03, n),
        baseline_w=rng.uniform(0.0, 5.0, n), ref_period_s=np.full(n, 0.1),
        calibrated=rng.random(n) < 0.5)


def _monitor(n, backend, history=True, seed=0, **kw):
    labels = np.array(["train", "serve", "idle"], dtype=object)[
        np.arange(n) % 3]
    hist = dict(history_step_s=STEP, history_steps=STEPS) if history else {}
    mon = MonitorService(n, corrections=_corr(n, seed), labels=labels,
                         max_hold_s=0.35, ring_slots=8, backend=backend,
                         **hist, **kw)
    mon.set_windows(0.5, 2.5)
    return mon


def _grid_stream(n, n_slabs=14, seed=0):
    """Rectangular slabs: collectors of n/4 devices, 5 polls at 0.1 s
    each; one collector restarts (its clock jumps back) in slab 9 and a
    quarter of the devices join in slab 3."""
    rng = np.random.default_rng(seed)
    out = []
    for s in range(n_slabs):
        for c in range(4):
            dev = np.arange(c * n // 4, (c + 1) * n // 4)
            if s < 3:
                dev = dev[dev % 4 != 0]
            ts = 0.5 * s + 0.1 * np.arange(5) + 0.013 * c
            if s == 9 and c == 2:
                ts = ts - 1.2                      # restart: all late
            out.append((dev, ts, 60.0 + 200.0 * rng.random((dev.size, 5))))
    return out


def _flat_stream(n, n_slabs=14, seed=0):
    """Messy flat slabs: jittered times, duplicates, late samples,
    shuffled arrival, a silent stretch for some devices."""
    rng = np.random.default_rng(seed)
    out = []
    for s in range(n_slabs):
        k = int(rng.integers(4 * n, 6 * n))
        dev = rng.integers(0, n, k)
        t = 0.5 * s + rng.uniform(0.0, 0.5, k)
        if 5 <= s < 9:
            keep = dev % 5 != 1                    # silent devices
            dev, t = dev[keep], t[keep]
        v = 60.0 + 200.0 * rng.random(dev.size)
        dup = rng.random(dev.size) < 0.05
        dev = np.concatenate([dev, dev[dup]])
        t = np.concatenate([t, t[dup]])
        v = np.concatenate([v, v[dup] + 1.0])
        late = rng.random(dev.size) < 0.05
        t = np.where(late, t - 0.7, t)
        perm = rng.permutation(dev.size)
        out.append((dev[perm], t[perm], v[perm]))
    return out


def _feed(mon, stream, grid):
    for dev, t, v in stream:
        if grid:
            mon.ingest_grid(dev, t, v)
        else:
            mon.ingest(dev, t, v)


def _expected_tier(stream, grid, n, corr, hold):
    """Per device, the accepted samples (the monitor's stated policy,
    one sample at a time) and the held energy at every boundary from
    its first sample to its newest, newest STEPS + 1 kept."""
    acc = [[] for _ in range(n)]
    for dev, t, v in stream:
        if grid:
            dev, t, v = (np.repeat(dev, t.size), np.tile(t, dev.size),
                         v.ravel())
        order = np.lexsort((t, dev))
        for i in order:
            d = int(dev[i])
            if acc[d] and t[i] <= acc[d][-1][0]:
                continue                            # late or duplicate
            acc[d].append((float(t[i]), float(v[i])))
    raw, cor = {}, {}
    for d in range(n):
        if not acc[d]:
            continue
        ts = np.array([a[0] for a in acc[d]])
        vs = np.array([a[1] for a in acc[d]]) - corr.baseline_w[d]
        vc = (vs - corr.offset_w[d]) / corr.gain[d]
        e, ec = [0.0], [0.0]
        for j in range(1, ts.size):
            h = min(ts[j] - ts[j - 1], hold)
            e.append(e[-1] + vs[j - 1] * h)
            ec.append(ec[-1] + vc[j - 1] * h)
        b_hi = int(np.ceil(ts[-1] / STEP)) - 1
        while (b_hi + 1) * STEP < ts[-1]:
            b_hi += 1
        while b_hi * STEP >= ts[-1]:
            b_hi -= 1
        b_lo = b_hi + 1
        while (b_lo - 1) * STEP >= ts[0]:
            b_lo -= 1
        for b in range(max(b_lo, b_hi - STEPS), b_hi + 1):
            j = np.searchsorted(ts, b * STEP, side="right") - 1
            h = min(b * STEP - ts[j], hold)
            raw[d, b] = e[j] + vs[j] * h
            cor[d, b] = ec[j] + vc[j] * h
    return raw, cor


def _tier_entries(mon):
    h = mon.history
    e_raw, e_corr = np.asarray(h.e_raw), np.asarray(h.e_corr)
    raw, cor = {}, {}
    for d in np.flatnonzero(h.b_last != NO_BOUNDARY):
        lo = max(h.b_first[d], h.b_last[d] - h.steps)
        for b in range(lo, h.b_last[d] + 1):
            raw[d, b] = e_raw[b % h.slots, d]
            cor[d, b] = e_corr[b % h.slots, d]
    return raw, cor


@pytest.mark.parametrize("grid", [True, False], ids=["grid", "flat"])
def test_tier_matches_per_device_reference(backend, grid):
    n = 48
    stream = (_grid_stream if grid else _flat_stream)(n, seed=3)
    mon = _monitor(n, backend)
    _feed(mon, stream, grid)
    raw, cor = _tier_entries(mon)
    want_raw, want_cor = _expected_tier(stream, grid, n, mon.corrections,
                                        0.35)
    assert set(raw) == set(want_raw)
    keys = sorted(raw)
    got = np.array([[raw[k], cor[k]] for k in keys])
    want = np.array([[want_raw[k], want_cor[k]] for k in keys])
    # the pallas tier folds each slab in 32 bits (KERNEL_RTOL); the
    # float64 tiers differ from the loop by summation order only
    rtol = 1e-5 if backend == "pallas" else 1e-12
    np.testing.assert_allclose(got, want, rtol=rtol, atol=1e-9)


@pytest.mark.parametrize("grid", [True, False], ids=["grid", "flat"])
def test_boundary_same_from_tier_and_ring(backend, grid):
    n = 40
    mon = _monitor(n, backend)
    stream = (_grid_stream if grid else _flat_stream)(n, seed=5)
    for s, (dev, t, v) in enumerate(stream):
        mon.ingest_grid(dev, t, v) if grid else mon.ingest(dev, t, v)
        if s % 5:
            continue
        snap = mon.snapshot()
        tq = STEP * np.arange(0, 16)
        on = snap.on_tier(tq)
        e_t, c_t = snap.energy_at_batch(tq[on])
        e_r, c_r = snap._ring_energy_at(tq[on], True)
        both = c_t & c_r
        if backend == "numpy":
            np.testing.assert_array_equal(e_t[both], e_r[both])
        else:
            np.testing.assert_allclose(e_t[both], e_r[both], rtol=1e-13)
        # the ring covers nothing the tier does not
        assert not np.any(c_r & ~c_t & ~np.isnan(e_r))


def _quarantining_monitor(backend, n=60):
    """A health-tracked monitor whose stream leaves a fifth of the fleet
    silent long enough to be quarantined, and a few devices that never
    report."""
    mon = _monitor(n, backend, health=HealthPolicy(), silent_after_s=0.3,
                   health_every_s=0.25, strict_ids=False)
    rng = np.random.default_rng(11)
    dev_all = np.arange(n - 3)
    for s in range(16):
        dev = dev_all if s < 8 else dev_all[dev_all % 5 != 2]
        ts = 0.5 * s + 0.1 * np.arange(5)
        mon.ingest_grid(dev, ts, 80.0 + 100.0 * rng.random((dev.size, 5)))
    return mon


@pytest.mark.parametrize("corrected", [True, False])
def test_fleet_series_matches_direct_reductions(backend, corrected):
    mon = _quarantining_monitor(backend)
    snap = mon.snapshot()
    assert snap.health_summary()["n_quarantined"] > 0
    # from before the horizon (partial coverage) to past the newest
    # boundary
    fs = snap.fleet_series(2.0, 8.0, 2 * STEP, corrected)
    assert fs.t.tolist() == [2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0]
    e, cov = snap.energy_at_batch(fs.t, corrected)
    assert (~cov).any() and cov.any()
    for q, t in enumerate(fs.t):
        fe = snap.fleet_from_rows(float(t), corrected, e[q], cov[q])
        np.testing.assert_allclose(fs.total_j[q], fe.total_j, rtol=1e-12)
        np.testing.assert_allclose(
            [fs.sigma_independent_j[q], fs.sigma_worstcase_j[q]],
            [fe.sigma_independent_j, fe.sigma_worstcase_j], rtol=1e-12)
        assert fs.n_quarantined[q] == fe.n_quarantined
        assert fs.coverage[q] == fe.coverage
        assert fs.n_covered[q] == int(cov[q].sum())
    active = snap.active_mask
    for q in range(fs.t.size - 1):
        de, dc = snap.between_from_rows(e[q], cov[q], e[q + 1], cov[q + 1])
        inc = dc & active
        assert fs.n_power[q] == inc.sum()
        np.testing.assert_allclose(fs.power_w[q], de[inc].sum() / 1.0,
                                   rtol=1e-12)
        assert np.isfinite(fs.power_w[q])


def test_fleet_series_through_the_executor(backend):
    mon = _quarantining_monitor(backend)
    svc = MonitorQueryService(mon)
    q = MonitorQuery.fleet_series(3.0, 7.5, STEP)
    a, b = svc.query_many([q, q])
    assert a is b
    direct = mon.fleet_series(3.0, 7.5, STEP)
    np.testing.assert_array_equal(a.total_j, direct.total_j)
    np.testing.assert_array_equal(a.power_w, direct.power_w)
    assert svc.stats()["series_calls"] == 1
    assert svc.stats()["instants_tier"] == a.t.size
    svc.query(q)                                    # cached: no call
    assert svc.stats()["series_calls"] == 1
    eb = svc.query(MonitorQuery.energy_between(6.0, 7.0))
    assert svc.stats()["instants_tier"] == a.t.size + 2
    np.testing.assert_array_equal(eb[0], mon.energy_between(6.0, 7.0)[0])
    svc.query(MonitorQuery.fleet_energy(7.05))      # not a boundary
    assert svc.stats()["instants_ring"] == 1


@pytest.mark.parametrize("make", [
    lambda: MonitorQuery.fleet_series(2.0, 1.0, 1.0),
    lambda: MonitorQuery.fleet_series(float("nan"), 1.0, 1.0),
    lambda: MonitorQuery.fleet_series(0.0, float("nan"), 1.0),
    lambda: MonitorQuery.fleet_series(0.0, float("inf"), 1.0),
    lambda: MonitorQuery.fleet_series(0.0, 1.0, 0.0),
    lambda: MonitorQuery.fleet_series(0.0, 1.0, -1.0),
    lambda: MonitorQuery.fleet_series(0.0, 1.0, float("nan")),
], ids=["reversed", "nan_t0", "nan_t1", "inf_t1", "zero_step",
        "negative_step", "nan_step"])
def test_fleet_series_edge_cases_raise_at_construction(make):
    with pytest.raises(ValueError):
        make()


def test_fleet_series_step_not_a_multiple_raises():
    mon = _monitor(6, "numpy")
    svc = MonitorQueryService(mon)
    with pytest.raises(ValueError, match="multiple"):
        svc.submit(MonitorQuery.fleet_series(0.0, 3.0, 0.75))
    with pytest.raises(ValueError, match="multiple"):
        mon.fleet_series(0.0, 3.0, 0.3)
    plain = MonitorQueryService(_monitor(6, "numpy", history=False))
    with pytest.raises(ValueError, match="history"):
        plain.submit(MonitorQuery.fleet_series(0.0, 3.0, 1.0))


def _fingerprint(snap):
    fs = snap.fleet_series(0.0, 4.0, STEP)
    e, c = snap.energy_at_batch(STEP * np.arange(9))
    return [fs.total_j, fs.power_w, fs.n_covered, fs.sigma_worstcase_j,
            e, c, snap.energy_between(1.0, 3.0)[0]]


def test_held_snapshot_bitwise_stable_after_ingest(backend):
    mon = _monitor(32, backend)
    stream = _grid_stream(32, seed=2)
    _feed(mon, stream[:20], True)
    snap = mon.snapshot()
    before = _fingerprint(snap)
    _feed(mon, stream[20:], True)
    for a, b in zip(_fingerprint(snap), before):
        np.testing.assert_array_equal(a, b)
    assert mon.snapshot().epoch > snap.epoch
    assert not np.array_equal(_fingerprint(mon.snapshot())[0], before[0])


def test_publication_copies_none_of_the_tier(backend):
    mon = _monitor(16, backend)
    dev = np.arange(16)
    mon.ingest_grid(dev, 1.0 + 0.1 * np.arange(1, 5), np.full((16, 4), 90.))
    mon.ingest_grid(dev, np.array([1.55]), np.full((16, 1), 95.0))
    first = mon.snapshot()._history
    # a slab that passes no boundary writes nothing: the next snapshot
    # holds the very same arrays
    mon.ingest_grid(dev, np.array([1.6, 1.7]), np.full((16, 2), 99.0))
    second = mon.snapshot()._history
    assert second.e_raw is first.e_raw and second.e_corr is first.e_corr
    # a slab that passes one writes new arrays, the held ones untouched
    held = np.array(first.e_corr)
    mon.ingest_grid(dev, np.array([1.8, 2.1]), np.full((16, 2), 99.0))
    third = mon.snapshot()._history
    assert third.e_corr is not first.e_corr
    np.testing.assert_array_equal(np.asarray(first.e_corr), held)


@pytest.mark.parametrize("held", [False, True], ids=["dropped", "held"])
def test_ingest_writes_in_place_unless_a_snapshot_is_held(backend, held):
    """Once no snapshot holds the ring and the tier, the next slab
    writes them in place (the tier donated on the accelerated tiers);
    while one is held it writes new arrays and the held ones keep their
    bits."""
    mon = _monitor(16, backend)
    dev = np.arange(16)
    mon.ingest_grid(dev, 1.0 + 0.1 * np.arange(1, 5), np.full((16, 4), 90.))
    snap = mon.snapshot()
    ring, tier = mon.ring.t, mon.history.e_corr
    bits = (ring.copy(), np.array(tier))
    if not held:
        del snap
    mon.ingest_grid(dev, np.array([1.8, 2.1]), np.full((16, 2), 99.0))
    assert (mon.ring.t is ring) is not held
    if backend == "numpy":
        assert (mon.history.e_corr is tier) is not held
    else:
        assert tier.is_deleted() is not held
    if held:
        np.testing.assert_array_equal(ring, bits[0])
        np.testing.assert_array_equal(np.asarray(tier), bits[1])
        assert snap._ring_view.t.base is ring


def test_history_off_leaves_the_path_unchanged(backend):
    n = 36
    stream = _grid_stream(n, seed=8)
    off = _monitor(n, backend, history=False)
    on = _monitor(n, backend)
    _feed(off, stream, True)
    _feed(on, stream, True)
    assert off.history is None and off.snapshot()._history is None
    for f in ("last_t", "energy_j", "energy_corr_j", "win_corr_j", "ewma_w",
              "n_samples", "run_t"):
        np.testing.assert_array_equal(getattr(off.state, f),
                                      getattr(on.state, f), f)
    for f in ("t", "v", "e_raw", "e_corr", "n_written"):
        np.testing.assert_array_equal(getattr(off.ring, f),
                                      getattr(on.ring, f), f)
    # off the tier's boundaries both answer from the ring, bitwise alike
    tq = np.array([6.03, 6.37, 6.91])
    for a, b in zip(off.energy_between(6.03, 6.91), on.energy_between(6.03,
                                                                      6.91)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(off.snapshot().energy_at_batch(tq)[0],
                                  on.snapshot().energy_at_batch(tq)[0])
    assert off.nbytes() < on.nbytes()
