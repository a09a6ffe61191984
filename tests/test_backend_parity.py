"""Cross-backend property harness for the streaming hot loops (ISSUE 6).

Every accelerated kernel tier (jax and pallas) must agree with the
numpy reference not just on well-behaved slabs but on the adversarial
inputs a real collection pipeline produces: out-of-order arrival,
duplicated samples, sampling gaps, devices that never report,
non-finite readings, single-sample series and zero-length query
windows.  Two layers of coverage:

* **Deterministic adversarial streams** — hand-built worst-case slab
  sequences pushed through :class:`MonitorService` on every backend
  (always run; this is the tier-1 floor).
* **Property tests** — `hypothesis`-driven random slab/window/timeline
  generation over the raw kernels ``stream_ingest``,
  ``stream_ingest_grid``, ``step_integrate`` and ``log_filter``.
  Imported through the ``_hyp`` shim so environments without
  `hypothesis` skip these instead of failing collection.

Backends are looped *inside* the property tests (a function-scoped
fixture cannot feed ``@given``); the deterministic tests use the shared
``accel_backend`` fixture for per-tier reporting.
"""
import numpy as np
import pytest

from _hyp import HAVE_HYPOTHESIS, given, settings, st
from _tol import assert_tier_close
from repro.core import load as loads
from repro.core.engine_backend import available_backends, get_backend
from repro.core.engine_backend import numpy_backend as nb
from repro.core.engine_backend.pytrees import SlabOperands
from repro.core.ground_truth import TimelineBank
from repro.core.stream import MonitorService


def _accel_backends():
    return [b for b in available_backends() if b != "numpy"]


needs_accel = pytest.mark.skipif(
    not _accel_backends(),
    reason="no accelerated backend available (jax not installed)")

# run-tracking / counter outputs must be bitwise identical; cumulative
# float outputs only up to accumulation order (the pallas tier's 32-bit
# kernels to the reporting-quantum bound of ``_tol``)
KERNEL_RTOL = 1e-12
KERNEL_ATOL = 1e-12


# ---------------------------------------------------------------------------
# slab generators
# ---------------------------------------------------------------------------
def _valid_ingest_slab(rng, k, u, *, single_sample=False):
    """A contract-respecting ``stream_ingest`` slab: grouped samples,
    strictly increasing times per group, finite readings."""
    if single_sample:
        k = u
        seg = np.arange(u)
    else:
        dev = np.sort(rng.integers(0, u, k))
        _, seg = np.unique(dev, return_inverse=True)
    uu = int(seg.max()) + 1
    t = np.empty(k)
    for g in range(uu):
        m = seg == g
        t[m] = np.cumsum(rng.uniform(1e-4, 0.2, m.sum()))
    v = rng.uniform(60.0, 250.0, k)
    rep = rng.random(k) < 0.35            # exact repeats → real runs
    v[rep] = np.round(v[rep] / 25.0) * 25.0
    first = np.r_[True, seg[1:] != seg[:-1]]
    start_idx = np.flatnonzero(first)
    end_idx = np.r_[start_idx[1:] - 1, k - 1]
    has_prev = rng.random(uu) > 0.3
    prev_t = rng.uniform(-1.0, 0.0, uu)
    state = dict(
        prev_t=prev_t,
        prev_v=np.where(rng.random(uu) < 0.3,
                        np.round(rng.uniform(60.0, 250.0, uu) / 25.0) * 25.0,
                        rng.uniform(60.0, 250.0, uu)),
        has_prev=has_prev,
        run_t=np.where(has_prev, prev_t, t[start_idx]),
        n_changes=rng.integers(0, 4, uu),
        gain=rng.uniform(0.95, 1.05, uu),
        offset=rng.uniform(-3.0, 3.0, uu),
        tshift=rng.uniform(0.0, 0.05, uu),
        win_a=rng.uniform(0.0, 2.0, uu),
        win_b=rng.uniform(2.0, 5.0, uu),
        max_hold=np.where(rng.random(uu) < 0.5, np.inf, 0.5),
        env_lo=np.where(rng.random(uu) < 0.5, -np.inf, 70.0),
        env_hi=np.where(rng.random(uu) < 0.5, np.inf, 240.0),
    )
    # exercise zero-length and inverted windows too
    degen = rng.random(uu) < 0.2
    state["win_b"] = np.where(degen, state["win_a"], state["win_b"])
    return t, v, seg, first, start_idx, end_idx, state


def _ingest_args(slab, trapezoid):
    t, v, seg, first, start_idx, end_idx, s = slab
    return (t, v, seg, first, start_idx, end_idx, SlabOperands(**s),
            trapezoid)


def _assert_tuples_close(outn, outj, label, backend):
    assert len(outn) == len(outj)
    for i, (a, b) in enumerate(zip(outn, outj)):
        assert_tier_close(b, a, backend, KERNEL_RTOL, KERNEL_ATOL,
                          err_msg=f"{label}: output {i}")


# ---------------------------------------------------------------------------
# deterministic adversarial streams through MonitorService
# ---------------------------------------------------------------------------
def _adversarial_stream(case, rng):
    """Build a worst-case slab sequence for a 6-device monitor.

    Returns a list of ``(dev, t, v)`` triples fed to ``ingest`` in
    order.  The monitor must make identical accept/duplicate/late/
    invalid decisions on every backend.
    """
    n = 6
    base_t = np.arange(1, 9) * 0.1

    def slab(devs, ts, vs):
        return (np.asarray(devs, dtype=np.int64),
                np.asarray(ts, dtype=np.float64),
                np.asarray(vs, dtype=np.float64))

    if case == "out_of_order":
        # shuffled within a slab: monitor re-sorts, nothing dropped
        dev = np.repeat(np.arange(4), len(base_t))
        t = np.tile(base_t, 4)
        v = 100.0 + 10.0 * dev + np.round(t * 10)
        perm = rng.permutation(len(dev))
        return [slab(dev[perm], t[perm], v[perm])]
    if case == "duplicates":
        # exact (dev, t) re-sends inside a slab and across slabs
        s1 = slab([0, 0, 0, 1, 1], [0.1, 0.2, 0.2, 0.1, 0.3],
                  [100.0, 110.0, 110.0, 90.0, 95.0])
        s2 = slab([0, 1, 1], [0.2, 0.3, 0.4], [110.0, 95.0, 97.0])
        return [s1, s2]
    if case == "late_cross_slab":
        # timestamps that regress across slab boundaries arrive late
        s1 = slab([0, 0, 1], [0.5, 0.6, 0.5], [100.0, 101.0, 90.0])
        s2 = slab([0, 0, 1], [0.3, 0.7, 0.2], [99.0, 102.0, 80.0])
        return [s1, s2]
    if case == "gaps_and_empty_devices":
        # devices 4 and 5 never report; device 2 has a long silent gap
        s1 = slab([0, 1, 2], [0.1, 0.1, 0.1], [100.0, 110.0, 120.0])
        s2 = slab([0, 1], [0.2, 0.2], [100.0, 111.0])
        s3 = slab([0, 1, 2], [0.3, 0.3, 5.0], [101.0, 111.0, 125.0])
        return [s1, s2, s3]
    if case == "non_finite":
        # nan/inf readings and timestamps must be rejected identically
        s1 = slab([0, 1, 2, 3], [0.1, 0.1, 0.1, 0.1],
                  [100.0, np.nan, np.inf, -np.inf])
        s2 = slab([0, 1, 2], [np.nan, 0.2, np.inf], [101.0, 110.0, 120.0])
        s3 = slab([0, 1], [0.3, 0.3], [102.0, 111.0])
        return [s1, s2, s3]
    if case == "single_sample_series":
        # one isolated sample per device — no deltas anywhere
        return [slab([d], [0.1 + 0.01 * d], [100.0 + d]) for d in range(n)]
    if case == "chaos":
        # everything at once, three slabs of it
        out = []
        for _ in range(3):
            k = 40
            dev = rng.integers(0, n, k)
            t = rng.uniform(0.0, 2.0, k)
            v = rng.uniform(60.0, 250.0, k)
            v[rng.random(k) < 0.1] = np.nan
            t[rng.random(k) < 0.05] = np.inf
            dup = rng.random(k) < 0.2
            out.append(slab(np.r_[dev, dev[dup]], np.r_[t, t[dup]],
                            np.r_[v, v[dup]]))
        return out
    raise AssertionError(case)


ADVERSARIAL_CASES = ["out_of_order", "duplicates", "late_cross_slab",
                     "gaps_and_empty_devices", "non_finite",
                     "single_sample_series", "chaos"]


def _monitor(backend):
    return MonitorService(6, backend=backend, max_hold_s=0.5,
                          envelope_w=(0.0, 300.0), ring_slots=4)


def _assert_monitors_match(mn, mj, label, backend):
    assert mn.counters == mj.counters, label
    sn, sj = mn.state, mj.state
    np.testing.assert_array_equal(sj.has, sn.has, err_msg=label)
    np.testing.assert_array_equal(sj.n_samples, sn.n_samples,
                                  err_msg=label)
    np.testing.assert_array_equal(sj.n_changes, sn.n_changes,
                                  err_msg=label)
    np.testing.assert_array_equal(sj.n_out, sn.n_out, err_msg=label)
    for fld in ("last_t", "last_v", "first_t", "run_t"):
        np.testing.assert_allclose(getattr(sj, fld), getattr(sn, fld),
                                   rtol=0, atol=0, err_msg=label)
    for fld in ("energy_j", "energy_corr_j", "win_j", "win_corr_j"):
        assert_tier_close(getattr(sj, fld), getattr(sn, fld), backend,
                          1e-12, 1e-12, err_msg=label)
    assert_tier_close(mj.update_period_s(), mn.update_period_s(), backend,
                      1e-9, equal_nan=True, err_msg=label)


@pytest.mark.parametrize("case", ADVERSARIAL_CASES)
def test_monitor_adversarial_stream_parity(accel_backend, case):
    rng_n = np.random.default_rng(123)
    rng_j = np.random.default_rng(123)
    mn, mj = _monitor("numpy"), _monitor(accel_backend)
    mn.set_windows(np.full(6, 0.15), np.full(6, 0.45))
    mj.set_windows(np.full(6, 0.15), np.full(6, 0.45))
    for (dn, tn, vn), (dj, tj, vj) in zip(_adversarial_stream(case, rng_n),
                                          _adversarial_stream(case, rng_j)):
        rn = mn.ingest(dn, tn, vn)
        rj = mj.ingest(dj, tj, vj)
        assert rn == rj, f"{case}: ingest reports differ"
    _assert_monitors_match(mn, mj, case, accel_backend)


def test_step_integrate_zero_length_and_empty_rows(accel_backend):
    """Zero-length windows, inverted windows, windows fully outside
    coverage, and rows with zero valid samples all integrate to 0 —
    identically on every backend."""
    jb = get_backend(accel_backend)
    ts = np.array([[0.1, 0.2, 0.3, np.inf],
                   [np.inf, np.inf, np.inf, np.inf],   # empty row
                   [1.0, np.inf, np.inf, np.inf],      # single sample
                   [0.1, 0.2, 0.3, 0.4]])
    vals = np.array([[100.0, 110.0, 120.0, 0.0],
                     [0.0, 0.0, 0.0, 0.0],
                     [50.0, 0.0, 0.0, 0.0],
                     [100.0, 100.0, 100.0, 100.0]])
    t0 = np.array([0.2, 0.1, 1.0, 9.0])   # zero-length / empty / point /
    t1 = np.array([0.2, 0.1, 1.0, 9.5])   # outside coverage
    for trapezoid in (False, True):
        outn = nb.step_integrate(ts, vals, t0, t1, trapezoid=trapezoid)
        outj = jb.step_integrate(ts, vals, t0, t1, trapezoid=trapezoid)
        np.testing.assert_allclose(np.asarray(outj), outn,
                                   rtol=KERNEL_RTOL, atol=KERNEL_ATOL)
        np.testing.assert_allclose(outn, 0.0, atol=1e-15)


def test_stream_ingest_single_sample_series(accel_backend):
    """Every segment holds exactly one sample (the degenerate slab the
    blocked kernels must not mis-seam)."""
    jb = get_backend(accel_backend)
    rng = np.random.default_rng(3)
    for trapezoid in (False, True):
        slab = _valid_ingest_slab(rng, 8, 8, single_sample=True)
        args = _ingest_args(slab, trapezoid)
        _assert_tuples_close(nb.stream_ingest(*args),
                             jb.stream_ingest(*args),
                             f"single-sample trapezoid={trapezoid}",
                             accel_backend)


# ---------------------------------------------------------------------------
# hypothesis property tests (skipped when hypothesis is absent)
# ---------------------------------------------------------------------------
@needs_accel
@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**32 - 1), k=st.integers(1, 160),
       u=st.integers(1, 10), trapezoid=st.booleans())
def test_property_stream_ingest_parity(seed, k, u, trapezoid):
    rng = np.random.default_rng(seed)
    slab = _valid_ingest_slab(rng, k, u)
    args = _ingest_args(slab, trapezoid)
    outn = nb.stream_ingest(*args)
    for be in _accel_backends():
        _assert_tuples_close(outn, get_backend(be).stream_ingest(*args),
                             f"{be} seed={seed}", be)


@needs_accel
@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**32 - 1), d=st.integers(1, 24),
       m=st.integers(1, 32), trapezoid=st.booleans())
def test_property_stream_ingest_grid_parity(seed, d, m, trapezoid):
    rng = np.random.default_rng(seed)
    ts = np.cumsum(rng.uniform(1e-4, 0.1, m)) + 2.0
    v = rng.uniform(60.0, 250.0, (d, m))
    rep = rng.random((d, m)) < 0.4
    v[rep] = np.round(v[rep] / 25.0) * 25.0
    has_prev = rng.random(d) > 0.3
    prev_t = rng.uniform(0.0, 2.0, d)
    win_a = rng.uniform(1.5, 3.0, d)
    win_b = np.where(rng.random(d) < 0.2, win_a,      # zero-length windows
                     win_a + rng.uniform(0.0, 2.0, d))
    args = (ts, v, SlabOperands(
        prev_t, rng.uniform(60.0, 250.0, d), has_prev,
        np.where(has_prev, prev_t, ts[0]), rng.integers(0, 4, d),
        rng.uniform(0.95, 1.05, d), rng.uniform(-3.0, 3.0, d),
        rng.uniform(0.0, 0.05, d), win_a, win_b,
        np.where(rng.random(d) < 0.5, np.inf, 0.05),
        np.full(d, 0.0), np.full(d, 240.0)), trapezoid)
    outn = nb.stream_ingest_grid(*args)
    for be in _accel_backends():
        outj = get_backend(be).stream_ingest_grid(*args)
        _assert_tuples_close(outn, outj, f"{be} seed={seed}", be)


@needs_accel
@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**32 - 1), n=st.integers(1, 12),
       m=st.integers(1, 24), trapezoid=st.booleans())
def test_property_step_integrate_parity(seed, n, m, trapezoid):
    rng = np.random.default_rng(seed)
    ts = np.sort(rng.uniform(0.0, 10.0, (n, m)), axis=1)
    nv = rng.integers(0, m + 1, n)        # rows may be fully empty
    for i in range(n):
        ts[i, nv[i]:] = np.inf
    vals = rng.uniform(50.0, 250.0, (n, m))
    t0 = rng.uniform(-1.0, 5.0, n)
    span = rng.uniform(0.0, 8.0, n)
    span[rng.random(n) < 0.25] = 0.0      # zero-length windows
    t1 = t0 + span
    outn = nb.step_integrate(ts, vals, t0, t1, trapezoid=trapezoid)
    for be in _accel_backends():
        outj = get_backend(be).step_integrate(ts, vals, t0, t1,
                                              trapezoid=trapezoid)
        assert_tier_close(outj, outn, be, KERNEL_RTOL, KERNEL_ATOL,
                          err_msg=f"{be} seed={seed}")


@needs_accel
@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**32 - 1), g=st.integers(1, 8),
       q=st.integers(1, 20))
def test_property_log_filter_parity(seed, g, q):
    rng = np.random.default_rng(seed)
    tls = [loads.square_wave(float(rng.uniform(0.05, 0.4)),
                             int(rng.integers(1, 10)),
                             float(rng.uniform(150, 250)),
                             float(rng.uniform(60, 120)),
                             seed=int(rng.integers(0, 1000)))
           for _ in range(g)]
    tl = TimelineBank.from_timelines(tls).arrays
    ticks = np.sort(rng.uniform(-0.5, 4.0, (g, q)), axis=1)
    tau = rng.uniform(0.05, 1.0, g)
    ref = nb.log_filter(tl, ticks, tau)
    for be in _accel_backends():
        got = get_backend(be).log_filter(tl, ticks, tau)
        # associative scans reorder the recurrence's float ops
        assert_tier_close(got, ref, be, 1e-9, 1e-9,
                          err_msg=f"{be} seed={seed}")


@needs_accel
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**32 - 1))
def test_property_monitor_chaotic_stream_parity(seed):
    """Random lossy streams — shuffles, duplicates, regressions,
    non-finite readings — yield identical monitor state everywhere."""
    rng = np.random.default_rng(seed)
    slabs = []
    for _ in range(3):
        k = int(rng.integers(1, 60))
        dev = rng.integers(0, 6, k)
        t = rng.uniform(0.0, 2.0, k)
        v = rng.uniform(40.0, 320.0, k)
        v[rng.random(k) < 0.08] = np.nan
        t[rng.random(k) < 0.04] = np.inf
        slabs.append((dev, t, v))
    mons = []
    for be in ["numpy"] + _accel_backends():
        mon = _monitor(be)
        mon.set_windows(np.full(6, 0.2), np.full(6, 1.4))
        for dev, t, v in slabs:
            mon.ingest(dev.copy(), t.copy(), v.copy())
        mons.append((be, mon))
    ref = mons[0][1]
    for be, mon in mons[1:]:
        _assert_monitors_match(ref, mon, f"{be} seed={seed}", be)


def test_hypothesis_shim_status():
    """Record (not assert) shim mode so CI logs show which layer ran."""
    assert HAVE_HYPOTHESIS in (True, False)
