"""numpy↔accelerated parity for the streaming kernels (ISSUE 5/6).

The streaming monitor's hot path — ``step_integrate``,
``stream_ingest`` and the rectangular ``stream_ingest_grid`` — has one
implementation per execution backend.  Every accelerated tier (jax and
pallas, via the shared ``accel_backend`` fixture) must reproduce the
numpy reference on random slabs (raw kernel outputs) and end-to-end
through ``MonitorService`` / ``stream_fleet`` (the offline-parity pin
must hold on every backend).  Skipped without jax (e.g. the numpy-only
core CI job); the CI accelerated jobs run this module explicitly.
"""
import numpy as np
import pytest

from _tol import assert_tier_close
from repro.core import load as loads
from repro.core.engine_backend import get_backend, has_jax
from repro.core.engine_backend import numpy_backend as nb
from repro.core.engine_backend.pytrees import SlabOperands, pad_operands
from repro.core.stream import MonitorService, replay, stream_fleet
from repro.core.fleet_engine import SensorBank
from repro.core.meter import Workload

needs_jax = pytest.mark.skipif(not has_jax(), reason="jax not installed")

MIXED_NAMES = ["a100"] * 8 + ["v100"] * 4 + ["h100_instant"] * 4


def _random_slab(rng, k=300, u=11):
    dev = np.sort(rng.integers(0, u, k))
    # make groups contiguous ids 0..u'-1
    uniq, seg = np.unique(dev, return_inverse=True)
    uu = len(uniq)
    t = np.empty(k)
    for g in range(uu):
        m = seg == g
        t[m] = np.sort(rng.uniform(0.0, 5.0, m.sum()))
    v = rng.uniform(60.0, 250.0, k)
    # force some exact value repeats so run tracking sees real runs
    rep = rng.random(k) < 0.3
    v[rep] = np.round(v[rep] / 25.0) * 25.0
    first = np.r_[True, seg[1:] != seg[:-1]]
    start_idx = np.flatnonzero(first)
    end_idx = np.r_[start_idx[1:] - 1, k - 1]
    state = dict(
        prev_t=rng.uniform(-1.0, 0.0, uu),
        prev_v=rng.uniform(60.0, 250.0, uu),
        has_prev=rng.random(uu) > 0.3,
        n_changes=rng.integers(0, 4, uu),
        gain=rng.uniform(0.95, 1.05, uu),
        offset=rng.uniform(-3.0, 3.0, uu),
        tshift=np.full(uu, 0.025),
        win_a=np.full(uu, 1.0),
        win_b=np.full(uu, 4.0),
        max_hold=np.where(rng.random(uu) < 0.5, np.inf, 0.5),
        env_lo=np.full(uu, 0.0),
        env_hi=np.full(uu, 240.0),
    )
    state["run_t"] = np.where(state["has_prev"], state["prev_t"],
                              t[start_idx])
    return (t, v, seg, first, start_idx, end_idx, state)


@pytest.mark.parametrize("trapezoid", [False, True])
def test_stream_ingest_kernel_parity(accel_backend, trapezoid):
    jb = get_backend(accel_backend)
    rng = np.random.default_rng(42)
    for trial in range(3):
        t, v, seg, first, start_idx, end_idx, st = _random_slab(rng)
        args = (t, v, seg, first, start_idx, end_idx, SlabOperands(**st),
                trapezoid)
        outn = nb.stream_ingest(*args)
        outj = jb.stream_ingest(*args)
        assert len(outn) == len(outj)
        for i, (a, b) in enumerate(zip(outn, outj)):
            assert_tier_close(b, a, accel_backend, 1e-12, 1e-12,
                              err_msg=f"output {i} (trial {trial})")


def _group_slab(rng, sizes, *, change=True, first_change=False,
                window=(1.0, 4.0)):
    """A slab of ``len(sizes)`` groups of the given sample counts.  With
    ``change`` off every group repeats its stored reading (no change);
    with ``first_change`` a group's readings are constant and differ from
    the stored one, so its only change is its first sample."""
    sizes = np.asarray(sizes)
    u, k = sizes.size, int(sizes.sum())
    seg = np.repeat(np.arange(u), sizes)
    first = np.r_[True, seg[1:] != seg[:-1]]
    start_idx = np.flatnonzero(first)
    end_idx = np.r_[start_idx[1:] - 1, k - 1]
    t = np.concatenate([np.sort(rng.uniform(0.0, 5.0, n)) for n in sizes])
    level = np.round(rng.uniform(60.0, 250.0, u) / 25.0) * 25.0
    if change and not first_change:
        v = np.round(rng.uniform(60.0, 250.0, k) / 50.0) * 50.0
    else:
        v = level[seg]
    prev_v = level + 25.0 if first_change else level
    prev_t = rng.uniform(-1.0, -0.1, u)
    ops = SlabOperands(prev_t, prev_v, np.ones(u, bool), prev_t - 0.3,
                       rng.integers(0, 3, u), rng.uniform(0.95, 1.05, u),
                       rng.uniform(-3.0, 3.0, u), np.full(u, 0.025),
                       np.full(u, window[0]), np.full(u, window[1]),
                       np.full(u, np.inf), np.zeros(u), np.full(u, 240.0))
    return (t, v, seg, first, start_idx, end_idx, ops)


_FOLD_CASES = {
    # the window lies after every sample: no increment opens it
    "zero_window": lambda rng: _group_slab(rng, rng.integers(1, 9, 40),
                                           window=(10.0, 11.0)),
    "single_sample_groups": lambda rng: _group_slab(rng, np.ones(70, int)),
    "change_only_at_first": lambda rng: _group_slab(
        rng, rng.integers(1, 9, 40), first_change=True),
    "no_change_carries_run_t": lambda rng: _group_slab(
        rng, rng.integers(1, 9, 40), change=False),
    # >= 4,096 samples over >= 500 groups: the scans span many levels
    "large_slab": lambda rng: _group_slab(rng, rng.integers(1, 15, 600)),
    # slab of exactly 1,024 samples: the padded tail group is empty
    "no_tail_samples": lambda rng: _group_slab(rng, np.full(128, 8)),
    # one padded tail sample, and empty padded groups after the tail
    "one_tail_sample": lambda rng: _group_slab(rng, np.r_[np.full(127, 8),
                                                          7]),
}


def _reference_by_group(args):
    """The numpy reference run on each group of the slab alone: groups
    are independent, so this is the slab's result with no rounding
    carried from one group into the next (the reference's whole-slab
    prefix difference for ``cum_e``/``cum_ec`` rounds at the slab's
    total: 1.1e-12 relative on the large case)."""
    t, v, seg, first, start_idx, end_idx, ops, trapezoid = args
    outs = []
    for g, (s, e) in enumerate(zip(start_idx, end_idx)):
        n = e - s + 1
        outs.append(nb.stream_ingest(
            t[s:e + 1], v[s:e + 1], np.zeros(n, int), first[s:e + 1],
            np.array([0]), np.array([n - 1]),
            SlabOperands(*(x[g:g + 1] for x in ops)), trapezoid))
    return [np.concatenate(o) for o in zip(*outs)]


@pytest.mark.parametrize("case", sorted(_FOLD_CASES))
def test_stream_ingest_fold_edge_groups(accel_backend, case):
    """The segmented-scan group fold matches the numpy reference on the
    group shapes its scans restart at, with counts exact and all-zero
    window sums exactly zero."""
    jb = get_backend(accel_backend)
    rng = np.random.default_rng(sorted(_FOLD_CASES).index(case))
    args = _FOLD_CASES[case](rng) + (False,)
    outn = nb.stream_ingest(*args)
    outg = _reference_by_group(args)
    outj = jb.stream_ingest(*args)
    assert len(outn) == len(outj) == 16
    for i, (a, b) in enumerate(zip(outg, outj)):
        assert_tier_close(b, a, accel_backend, 1e-12, 1e-12,
                          err_msg=f"output {i}")
    for i in (3, 4, 10, 15):    # new_n_changes, counts, n_out, run_rec
        np.testing.assert_array_equal(outj[i], outn[i],
                                      err_msg=f"output {i}")
    t, start_idx, ops = args[0], args[4], args[6]
    run_t, n_changes = ops.run_t, ops.n_changes
    if case == "zero_window":
        assert np.all(outj[7] == 0.0) and np.all(outj[8] == 0.0)
    if case == "no_change_carries_run_t":
        np.testing.assert_array_equal(outj[2], run_t)
        np.testing.assert_array_equal(outj[3], n_changes)
    if case == "change_only_at_first":
        np.testing.assert_array_equal(outj[2], t[start_idx])
        assert_tier_close(outj[14][start_idx], t[start_idx] - run_t,
                          accel_backend, 1e-12, 1e-12)
    if case == "single_sample_groups":
        np.testing.assert_array_equal(outj[4], 1)


@needs_jax
def test_ingest_fold_lowers_without_scatter():
    """The group fold reduces by scans and gathers: a scatter, which a
    TPU runs one update at a time, must not come back into it."""
    import jax
    from repro.core.engine_backend import jax_backend
    from repro.core.engine_backend import precision

    k, u = 1024, 16
    with precision.x64():
        f = lambda n: jax.ShapeDtypeStruct((n,), precision.FLOAT)
        i = lambda n: jax.ShapeDtypeStruct((n,), precision.INT)
        b = jax.ShapeDtypeStruct((k,), bool)
        text = jax.jit(jax_backend.ingest_fold).lower(
            f(k), f(k), i(k), i(u), i(u), f(u), i(u),
            f(k), f(k), f(k), f(k), f(k), b, b).as_text()
    assert "stablehlo.gather" in text
    assert "scatter" not in text


def _padded_call(kernel, rng, n_pad):
    """One slab through the numpy reference kernel, as given and with
    ``n_pad`` padding rows appended the way the padding tiers append
    them: ``SLAB_PAD`` operands, zero readings, and on the flat path one
    sample per padding group at the slab's last time."""
    if kernel == "stream_ingest":
        t, v, seg, first, start_idx, end_idx, st = _random_slab(rng)
        ops, u, k = SlabOperands(**st), len(start_idx), len(t)
        tail = np.arange(n_pad)
        return ops, nb.stream_ingest(
            t, v, seg, first, start_idx, end_idx, ops), nb.stream_ingest(
            np.r_[t, np.full(n_pad, t[-1])], np.r_[v, np.zeros(n_pad)],
            np.r_[seg, u + tail], np.r_[first, np.ones(n_pad, bool)],
            np.r_[start_idx, k + tail], np.r_[end_idx, k + tail],
            pad_operands(ops, u + n_pad))
    d, m = 9, 12
    ts = 2.0 + np.cumsum(rng.uniform(0.01, 0.1, m))
    v = np.round(rng.uniform(60.0, 250.0, (d, m)) / 25.0) * 25.0
    has_prev = rng.random(d) > 0.3
    prev_t = rng.uniform(1.0, 2.0, d)
    ops = SlabOperands(
        prev_t, rng.uniform(60.0, 250.0, d), has_prev,
        np.where(has_prev, prev_t, ts[0]), rng.integers(0, 4, d),
        rng.uniform(0.95, 1.05, d), rng.uniform(-3.0, 3.0, d),
        np.full(d, 0.025), np.full(d, 2.2), np.full(d, 2.6),
        np.full(d, np.inf), np.full(d, 70.0), np.full(d, 240.0))
    return ops, nb.stream_ingest_grid(ts, v, ops), nb.stream_ingest_grid(
        ts, np.r_[v, np.zeros((n_pad, m))], pad_operands(ops, d + n_pad))


@pytest.mark.parametrize("kernel", ["stream_ingest", "stream_ingest_grid"])
def test_padding_rows_fold_to_zeros(kernel):
    """Rows padded with ``SLAB_PAD`` fold to nothing in the reference:
    no energy, window energy, change or out-of-envelope sample, and
    finite values throughout; the real rows' results stay bitwise those
    of the unpadded slab, and padding keeps each operand's dtype."""
    n_pad = 3
    ops, want, got = _padded_call(kernel, np.random.default_rng(9), n_pad)
    assert [x.dtype for x in pad_operands(ops, 40)] == [x.dtype for x in ops]
    # energies, window energies, change counts, n_out and run_rec
    zero = ((3, 5, 6, 7, 8, 10, 15) if kernel == "stream_ingest"
            else (2, 3, 4, 5, 6, 11, 15))
    for i, (a, b) in enumerate(zip(want, got)):
        assert np.all(np.isfinite(b)), i
        np.testing.assert_array_equal(b[:len(a)], a, err_msg=f"output {i}")
        if i in zero:
            assert not np.any(b[len(a):]), i


@pytest.mark.parametrize("trapezoid", [False, True])
def test_step_integrate_kernel_parity(accel_backend, trapezoid):
    jb = get_backend(accel_backend)
    rng = np.random.default_rng(7)
    n, m = 13, 50
    ts = np.sort(rng.uniform(0.0, 10.0, (n, m)), axis=1)
    nv = rng.integers(1, m, n)
    for i in range(n):
        ts[i, nv[i]:] = np.inf
    vals = rng.uniform(50.0, 250.0, (n, m))
    t0 = rng.uniform(-1.0, 5.0, n)
    t1 = t0 + rng.uniform(0.0, 8.0, n)
    outn = nb.step_integrate(ts, vals, t0, t1, trapezoid=trapezoid)
    outj = jb.step_integrate(ts, vals, t0, t1, trapezoid=trapezoid)
    assert_tier_close(outj, outn, accel_backend, 1e-12, 1e-12)


def test_monitor_end_to_end_backend_parity(accel_backend):
    """Same fleet replayed through a numpy-kernel and an accelerated
    monitor: identical ingestion decisions, energies within float
    accumulation order, and the offline parity pin holds on the
    accelerated tier."""
    n = len(MIXED_NAMES)
    ws = loads.mixed_fleet_workloads(n, seed=7, as_bank=True)
    rn = stream_fleet(n, profile=MIXED_NAMES, workload=ws, seed=0,
                      backend="numpy", compare=True)
    rj = stream_fleet(n, profile=MIXED_NAMES, workload=ws, seed=0,
                      backend=accel_backend, compare=True)
    be = accel_backend
    assert_tier_close(rj.naive_stream_j, rn.naive_stream_j, be, 1e-11)
    assert_tier_close(rj.corrected_stream_j, rn.corrected_stream_j, be,
                      1e-11)
    assert_tier_close(rj.naive_stream_j, rj.naive_offline_j, be, 1e-11)
    assert_tier_close(rj.corrected_stream_j, rj.corrected_offline_j, be,
                      1e-11)
    assert rn.monitor.counters == rj.monitor.counters


def test_monitor_messy_stream_matches_numpy(accel_backend):
    bank = SensorBank.from_catalog(["a100"] * 5, seeds=np.arange(5))
    wl = Workload("w", loads.multi_phase_workload([(0.13, 215.0),
                                                   (0.07, 165.0)]))
    tl = wl.timeline.shift(0.3)
    bank.attach(tl, t_end=tl.t_end + 1.0)
    mons = {}
    for be in ("numpy", accel_backend):
        mon = MonitorService(5, backend=be)
        replay(bank, mon, 0.0, 1.0, shuffle=True, dup_fraction=0.2,
               delay_fraction=0.1, seed=5)
        mons[be] = mon
    acc = mons[accel_backend]
    assert mons["numpy"].counters == acc.counters
    assert_tier_close(acc.state.energy_j, mons["numpy"].state.energy_j,
                      accel_backend, 1e-12)
    assert_tier_close(acc.update_period_s(),
                      mons["numpy"].update_period_s(), accel_backend, 1e-9,
                      equal_nan=True)


@pytest.mark.parametrize("trapezoid", [False, True])
def test_stream_ingest_grid_kernel_parity(accel_backend, trapezoid):
    """The rectangular fast-path kernel matches numpy on random [D, M]
    slabs, including the empty-slab passthrough."""
    jb = get_backend(accel_backend)
    rng = np.random.default_rng(11)
    for trial in range(3):
        d = int(rng.integers(1, 30))
        m = int(rng.integers(1, 40))
        ts = np.cumsum(rng.uniform(0.001, 0.1, m)) + 2.0
        v = rng.uniform(60.0, 250.0, (d, m))
        rep = rng.random((d, m)) < 0.4
        v[rep] = np.round(v[rep] / 25.0) * 25.0
        has_prev = rng.random(d) > 0.3
        prev_t = rng.uniform(0.0, 2.0, d)
        args = (ts, v, SlabOperands(
            prev_t, rng.uniform(60.0, 250.0, d), has_prev,
            np.where(has_prev, prev_t, ts[0]),
            rng.integers(0, 4, d), rng.uniform(0.95, 1.05, d),
            rng.uniform(-3.0, 3.0, d), np.full(d, 0.025),
            np.full(d, 2.2), np.full(d, 3.4),
            np.where(rng.random(d) < 0.5, np.inf, 0.05),
            np.full(d, 0.0), np.full(d, 240.0)), trapezoid)
        outn = nb.stream_ingest_grid(*args)
        outj = jb.stream_ingest_grid(*args)
        assert len(outn) == len(outj) == 16
        for i, (a, b) in enumerate(zip(outn, outj)):
            assert_tier_close(b, a, accel_backend, 1e-12, 1e-12,
                              err_msg=f"output {i} (trial {trial})")
    empty = (np.zeros(0), np.zeros((3, 0)), SlabOperands(
        np.zeros(3), np.ones(3), np.ones(3, dtype=bool), np.zeros(3),
        np.zeros(3, dtype=np.int64), np.ones(3), np.zeros(3), np.zeros(3),
        np.zeros(3), np.ones(3), np.full(3, np.inf), np.zeros(3),
        np.full(3, 240.0)), trapezoid)
    for a, b in zip(nb.stream_ingest_grid(*empty),
                    jb.stream_ingest_grid(*empty)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_monitor_grid_path_matches_flat_path(accel_backend):
    """A clean replay through ``ingest_grid`` reproduces the flattened
    ``ingest`` path: identical counters, ring contents, run tracking
    and per-label moments (the fast path changes the route, never the
    answer)."""
    bank = SensorBank.from_catalog(["a100"] * 4 + ["v100"] * 3,
                                   seeds=np.arange(7))
    wl = Workload("w", loads.multi_phase_workload([(0.13, 215.0),
                                                   (0.07, 165.0)]))
    tl = wl.timeline.shift(0.3)
    bank.attach(tl, t_end=tl.t_end + 1.0)
    mons = {}
    for grid in (False, True):
        mon = MonitorService(7, backend=accel_backend)
        replay(bank, mon, 0.0, 1.0, grid=grid)
        mons[grid] = mon
    assert mons[True].counters == mons[False].counters
    be = accel_backend
    assert_tier_close(mons[True].state.energy_j, mons[False].state.energy_j,
                      be, 1e-11)
    assert_tier_close(mons[True].state.energy_corr_j,
                      mons[False].state.energy_corr_j, be, 1e-11)
    np.testing.assert_array_equal(mons[True].state.n_changes,
                                  mons[False].state.n_changes)
    np.testing.assert_array_equal(mons[True].state.run_t,
                                  mons[False].state.run_t)
    for arr in ("t", "v", "e_raw", "e_corr"):
        assert_tier_close(getattr(mons[True].ring, arr),
                          getattr(mons[False].ring, arr), be, 1e-11,
                          err_msg=f"ring.{arr}")
    assert_tier_close(mons[True].update_period_s(),
                      mons[False].update_period_s(), be, 1e-12,
                      equal_nan=True)
    for lbl, sf in mons[False].reading_stats().items():
        sg = mons[True].reading_stats()[lbl]
        for key, val in sf.items():
            assert_tier_close(sg[key], val, be, 1e-9,
                              err_msg=f"{lbl}.{key}")


def test_monitor_grid_path_falls_back_on_dirty_slabs(accel_backend):
    """Slabs violating the rectangular contract (non-finite readings,
    stale times) reroute through the general ingest path with its drop
    accounting intact."""
    mon = MonitorService(3, backend=accel_backend)
    ts = np.array([0.1, 0.2, 0.3])
    vals = np.full((3, 3), 100.0)
    vals[1, 1] = np.nan
    rep = mon.ingest_grid(np.arange(3), ts, vals)
    assert rep.invalid == 1 and rep.accepted == 8
    # a repeat of the same slab is all duplicates/late via the fallback
    # (the nan hole at t=0.2 is now behind its device's newest sample)
    rep2 = mon.ingest_grid(np.arange(3), ts, np.full((3, 3), 100.0))
    assert rep2.accepted == 0
    assert rep2.duplicates == 3 and rep2.late == 6
    assert mon.counters["accepted"] == 8


def test_jax_ingest_run_tracking_carries_state_across_slabs():
    """The O(slab) run tracking (carried ``run_t`` + in-slab ordinal
    arithmetic, replacing the full-ring cummax) is equivalent to the
    numpy reference across slab boundaries: runs spanning two slabs
    still record their full duration."""
    if not has_jax():
        pytest.skip("jax not installed")
    rng = np.random.default_rng(3)
    mons = {be: MonitorService(4, backend=be, ring_slots=4)
            for be in ("numpy", "jax")}
    t_base = 0.0
    for _ in range(6):      # several slabs; runs span the boundaries
        k = int(rng.integers(3, 9))
        dev = np.repeat(np.arange(4), k)
        t = np.tile(t_base + np.cumsum(rng.uniform(0.01, 0.1, k)), 4)
        v = np.round(rng.uniform(60.0, 250.0, 4 * k) / 50.0) * 50.0
        for mon in mons.values():
            mon.ingest(dev, t, v)
        t_base = float(t.max())
    np.testing.assert_array_equal(mons["jax"].state.run_t,
                                  mons["numpy"].state.run_t)
    np.testing.assert_array_equal(mons["jax"].state.n_changes,
                                  mons["numpy"].state.n_changes)
    np.testing.assert_allclose(mons["jax"].update_period_s(),
                               mons["numpy"].update_period_s(),
                               rtol=1e-12, equal_nan=True)
