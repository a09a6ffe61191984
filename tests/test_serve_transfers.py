"""The dashboard refresh's transfer contract, and the by-label reduction
on the device.

A flush of the executor plans every history-tier call, places the
snapshot's operands in one explicit ``jax.device_put`` of one float64
and one int32 buffer (on the snapshot's first flush), puts every call's
instants up in one float64 buffer, and brings every result back in one
``jax.device_get`` of one packed buffer.  Under
``jax.transfer_guard("disallow")`` an implicit upload (a NumPy array
passed to a jitted function) raises; the guard cannot see fetches on the
CPU, so the calls of ``jax.device_put`` and ``jax.device_get`` are
counted too.  The answers equal the numpy tier's to 1e-12.

``history_by_label`` is checked numpy against jax at the kernel and
through the snapshot: quarantined devices, a label with no covered
device, instants past the tier's horizon, a monitor grown with a new
label, the executor against the direct path, and a ring instant that
keeps the host path.
"""
import glob
import os

import numpy as np
import pytest

from repro.common import trace
from repro.core.engine_backend import get_backend, has_jax
from repro.core.stream import (HealthPolicy, MonitorService,
                               StreamCorrections, restore_monitor,
                               save_monitor)
from repro.serve.monitor_service import MonitorQuery, MonitorQueryService

pytestmark = pytest.mark.skipif(not has_jax(), reason="jax not installed")

STEP, STEPS = 0.5, 6
LABELS = ("train", "serve", "idle", "spare")
# the posture of the dashboard cell's CPU tests that quarantines devices
# of a clean stream: the job's phases read as drift against the mean
DRIFTING = dict(drift_tau_s=0.5, drift_rel=0.1, drift_abs_w=1.0)


def _monitor(backend, n=48, seed=0):
    """A health-tracked monitor with a 6-step tier whose stream has
    drifting phases; the ``spare`` devices never report."""
    rng = np.random.default_rng(seed)
    labels = np.array(LABELS[:3], dtype=object)[np.arange(n) % 3]
    labels[-4:] = "spare"
    corr = StreamCorrections(
        gain=rng.uniform(0.9, 1.1, n), offset_w=rng.uniform(-3.0, 3.0, n),
        time_shift_s=rng.uniform(0.0, 0.03, n),
        baseline_w=rng.uniform(0.0, 5.0, n), ref_period_s=np.full(n, 0.1),
        calibrated=rng.random(n) < 0.5)
    mon = MonitorService(n, corrections=corr, labels=labels,
                         max_hold_s=0.35, ring_slots=8, backend=backend,
                         history_step_s=STEP, history_steps=STEPS,
                         health=HealthPolicy(), health_every_s=0.25,
                         strict_ids=False, **DRIFTING)
    mon.set_windows(0.5, 2.5)
    return mon


def _slab(mon, s, rng):
    """Slab ``s``: 5 polls at 0.1 s of every device but the spares, the
    power switching between two phases every 2 s; one device in five
    falls silent after 5 s, so the tier holds older boundaries for it
    than for the rest."""
    n = mon.n_devices
    dev = np.arange(n - 4)
    if s >= 10:
        dev = dev[dev % 5 != 2]
    ts = 0.5 * s + 0.1 * np.arange(5)
    level = 100.0 if (s // 4) % 2 == 0 else 250.0
    mon.ingest_grid(dev, ts, level + 20.0 * rng.random((dev.size, 5)))
    return float(ts[-1])


def _fed(backend, n_slabs=16, seed=0):
    mon = _monitor(backend, seed=seed)
    rng = np.random.default_rng(seed + 1)
    for s in range(n_slabs):
        newest = _slab(mon, s, rng)
    return mon, np.floor(newest / STEP) * STEP


def _panels(end):
    """The dashboard cell's refresh: both series over 5 s (past the 3-s
    tier), by_label over the last step, the two stat panels."""
    return [MonitorQuery.fleet_series(end - 5.0, end, STEP, True),
            MonitorQuery.fleet_series(end - 5.0, end, STEP, False),
            MonitorQuery.by_label(end - STEP, end),
            MonitorQuery.fleet_energy(),
            MonitorQuery.window_energy()]


def _count_calls(monkeypatch, jax):
    """Count calls of ``jax.device_put`` and ``jax.device_get``."""
    calls = {"device_put": 0, "device_get": 0}
    for name in calls:
        real = getattr(jax, name)

        def counted(*a, _real=real, _name=name, **kw):
            calls[_name] += 1
            return _real(*a, **kw)
        monkeypatch.setattr(jax, name, counted)
    return calls


def _flat(x):
    """Every number of an answer, in a fixed order."""
    if isinstance(x, dict):
        return [v for k in sorted(x) for v in _flat(x[k])]
    if isinstance(x, (tuple, list)):
        return [v for item in x for v in _flat(item)]
    if hasattr(x, "__dataclass_fields__"):
        return [v for k in sorted(x.__dataclass_fields__)
                for v in _flat(getattr(x, k))]
    if x is None or isinstance(x, (bool, str)):
        return [x]
    return [np.asarray(x)]


def _assert_close(got, want):
    a, b = _flat(got), _flat(want)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        if not isinstance(x, np.ndarray):
            assert x == y
        elif x.dtype.kind == "f":
            np.testing.assert_allclose(x, y, rtol=1e-12, atol=1e-9)
        else:
            np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_refresh_makes_one_upload_and_one_fetch(backend, monkeypatch,
                                                tmp_path):
    """The refresh on a monitor restored from the numpy tier's
    checkpoint, so that both tiers answer from the same state."""
    import jax
    ref, end = _fed("numpy")
    save_monitor(ref, str(tmp_path / "ckpt"))
    warm = restore_monitor(str(tmp_path / "ckpt"), backend=backend)
    MonitorQueryService(warm).query_many(_panels(end))    # compiles
    mon = restore_monitor(str(tmp_path / "ckpt"), backend=backend)
    svc = MonitorQueryService(mon)
    calls = _count_calls(monkeypatch, jax)
    with jax.transfer_guard("disallow"):
        got = svc.query_many(_panels(end))
    # the snapshot's two operand buffers in one put, the flush's
    # instants in another; every result in one fetch
    assert calls == {"device_put": 2, "device_get": 1}
    # a second flush on the same snapshot places nothing again
    with jax.transfer_guard("disallow"):
        svc.query(MonitorQuery.by_label(end - 2 * STEP, end - STEP))
    assert calls == {"device_put": 3, "device_get": 2}
    st = svc.stats()
    assert (st["labels_device"], st["labels_host"]) == (2, 0)
    _assert_close(got, MonitorQueryService(ref).query_many(_panels(end)))


def _events(trace_dir):
    """The ``repro.*`` host events as ``(name, args)``."""
    import jax
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    return [(ev.name[len(trace.PREFIX):], dict(ev.stats))
            for plane in jax.profiler.ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(trace.PREFIX)]


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_execute_span_counts_the_buffers(backend, tmp_path):
    import jax
    mon, end = _fed(backend)
    MonitorQueryService(mon).query_many(_panels(end))     # compiles
    end = np.floor(_slab(mon, 16, np.random.default_rng(7)) / STEP) * STEP
    svc = MonitorQueryService(mon)
    with jax.profiler.trace(str(tmp_path)):
        svc.query_many(_panels(end))
        svc.query(MonitorQuery.by_label(end - 2 * STEP, end - STEP))
        # a ring instant: energy-at on the ring, by_label on the host
        svc.query(MonitorQuery.by_label(end - 0.25, end))
    args = [a for name, a in _events(str(tmp_path))
            if name == "serve.execute"]
    if backend == "numpy":
        assert args == [{"h2d": 0, "d2h": 0}] * 3
    else:
        assert args == [{"h2d": 3, "d2h": 1}, {"h2d": 1, "d2h": 1},
                        {"h2d": 11, "d2h": 3}]
    st = svc.stats()
    assert (st["labels_device"], st["labels_host"]) == (2, 1)


# ---------------------------------------------------------------------------
# history_by_label: numpy against jax
# ---------------------------------------------------------------------------

def _on(backend, mon, tmp_path):
    """``mon`` restored on ``backend`` from its checkpoint: the same
    state, whatever precision the tier's ingest keeps."""
    path = str(tmp_path / f"ckpt_{backend}")
    save_monitor(mon, path)
    return restore_monitor(path, backend=backend)


def _by_label(backend, t0, t1, tmp_path, grow=False):
    """``by_label(t0, t1)`` of a monitor fed on the numpy tier and
    restored on ``backend``, and its snapshot."""
    mon, _ = _fed("numpy")
    if grow:
        mon.grow(mon.n_devices + 5, labels=np.array(
            ["new"] * 3 + ["train"] * 2, dtype=object))
        rng = np.random.default_rng(3)
        for s in range(16, 19):
            dev = np.arange(mon.n_devices)
            dev = dev[((dev < mon.n_devices - 9) & (dev % 5 != 2))
                      | (dev >= mon.n_devices - 5)]
            ts = 0.5 * s + 0.1 * np.arange(5)
            mon.ingest_grid(dev, ts, 150.0 + 20.0 * rng.random((dev.size,
                                                                5)))
    snap = _on(backend, mon, tmp_path).snapshot()
    return snap.by_label(t0, t1), snap


@pytest.mark.parametrize("t0,t1,grow", [
    (7.0, 7.5, False),          # the newest step: quarantines
    (4.5, 6.0, False),          # the reporting devices' oldest boundary
    (2.0, 7.5, False),          # past their horizon: the silent ones
    (7.5, 7.5, False),          # an empty window: exact zeros
    (8.0, 9.0, True),           # a new label after grow
], ids=["newest", "horizon", "past_horizon", "empty", "grown"])
def test_by_label_on_the_tier_matches_numpy(accel_backend, t0, t1, grow,
                                            tmp_path):
    want, snap = _by_label("numpy", t0, t1, tmp_path, grow)
    got, _ = _by_label(accel_backend, t0, t1, tmp_path, grow)
    assert snap.on_tier(np.array([t0, t1])).all()
    assert list(got) == list(want)
    _assert_close(got, want)
    # the spare devices never report: nan moments, no device covered
    assert want["spare"]["n_covered"] == 0 == got["spare"]["n_covered"]
    assert want["spare"]["total_j"] == 0.0 == got["spare"]["total_j"]
    assert np.isnan(got["spare"]["mean_j"]) and np.isnan(
        got["spare"]["std_j"])
    assert sum(d["n_devices"] for d in got.values()) == snap.n_devices
    if grow:
        assert got["new"]["n_devices"] == 3 and got["new"]["n_covered"] > 0


def test_by_label_cases_are_reached(tmp_path):
    """The cases above hold what they name: quarantined devices, and
    partial coverage past the horizon."""
    newest, snap = _by_label("numpy", 7.0, 7.5, tmp_path)
    assert snap.health_summary()["n_quarantined"] > 0
    assert sum(d["n_quarantined"] for d in newest.values()) > 0
    past, _ = _by_label("numpy", 2.0, 7.5, tmp_path)
    seen = lambda by: sum(d["n_covered"] + d["n_quarantined"]  # noqa: E731
                          for d in by.values())
    assert 0 < seen(past) < seen(newest)
    empty, _ = _by_label("numpy", 7.5, 7.5, tmp_path)
    assert all(d["total_j"] == 0.0 for d in empty.values())


def test_by_label_kernel_numpy_against_jax(tmp_path):
    """The kernels alone, on a snapshot's operands: counts exact, sums
    and moments to 1e-12."""
    mon, _ = _fed("numpy")
    snaps = {b: _on(b, mon, tmp_path).snapshot() for b in ("numpy", "jax")}
    tq = np.array([6.5, 7.5])
    out = {}
    for b, snap in snaps.items():
        h = snap._history
        out[b] = get_backend(b).history_by_label(
            h.e_corr, snap._tier_ops[0], h.boundary_of(tq)[0] % h.slots,
            tq, len(LABELS))
    for x, y in zip(out["jax"], out["numpy"]):
        if y.dtype.kind == "f":
            np.testing.assert_allclose(x, y, rtol=1e-12)
        else:
            np.testing.assert_array_equal(x, y)
    n_cov, n_q, total, mean, m2, n_at = out["numpy"]
    assert n_at.shape == (2,) and n_cov.shape == (len(LABELS),)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_executor_by_label_is_the_direct_path(backend):
    mon, end = _fed(backend)
    snap = mon.snapshot()
    svc = MonitorQueryService(mon)
    qs = [MonitorQuery.by_label(end - STEP, end),
          MonitorQuery.by_label(end - STEP, end, corrected=False),
          MonitorQuery.by_label(end - 0.25, end),       # a ring instant
          MonitorQuery.by_label()]
    got = svc.query_many(qs)
    for q, g in zip(qs, got):
        want = snap.by_label(q.t0, q.t1, q.corrected)
        assert list(g) == list(want)
        for lb in want:
            for k in want[lb]:
                a, b = g[lb][k], want[lb][k]
                assert a == b or (np.isnan(a) and np.isnan(b)), (q, lb, k)
    st = svc.stats()
    assert (st["labels_device"], st["labels_host"]) == (2, 2)
    assert st["instants_ring"] == 1
