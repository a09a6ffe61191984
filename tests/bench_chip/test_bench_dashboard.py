"""CPU tests of the dashboard cell's harness, at tiny sizes.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench_chip

Each tiny run is a copy of the benchmark cut to 1,024 devices (the
Pallas kernels run in the interpreter), with a history of 12 steps so
that set-up fills 12 s of stream; the panels keep their 300-s range, so
the older instants of every series lie beyond the tier and the answers
are partly covered.
"""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

from test_bench_chip import no_chip_check, run_cell, tiny_root  # noqa: F401

import registry  # noqa: E402
import trace_reduce  # noqa: E402

CELL = "monitor_serve"


def dashboard_root(tmp_path, **monitor) -> str:
    """``tiny_root`` with the dashboard cell's history and slabs cut, and
    ``monitor`` settings of the configuration replaced."""
    root = tiny_root(tmp_path)
    path = os.path.join(root, "bench", "configs",
                        "dashboard_a100mix_100k.json")
    cfg = json.load(open(path))
    cfg["monitor"].update(history_steps=12, **monitor)
    json.dump(cfg, open(path, "w"))
    path = os.path.join(root, "bench", "traffic", "dashboards_5m.json")
    t = json.load(open(path))
    t["devices_per_slab"] = 256
    json.dump(t, open(path, "w"))
    return root


# a posture that quarantines devices of a clean stream: the job's phases
# read as drift against the lifetime mean
DRIFTING = dict(drift_tau_s=0.5, drift_rel=0.1, drift_abs_w=1.0)


@pytest.mark.parametrize("monitor", [{}, DRIFTING],
                         ids=["clean", "quarantines"])
def test_control_fails_and_program_passes(tmp_path, monitor):
    """The program passes every limit, the float32 control fails at
    least one; with quarantined devices too."""
    import control
    bench = registry.Bench(dashboard_root(tmp_path, **monitor))
    r = control.readings(bench, CELL, seed=2**31 + 9, seconds=1.0)
    lim = bench.traffic(bench.cell(CELL)["traffic"])["check"]["limits"]
    assert set(r["program"]) == set(lim)
    assert all(v <= lim[k] for k, v in r["program"].items()), r
    assert any(not v <= lim[k] for k, v in r["control"].items()), r


def test_quarantines_reach_the_checked_answers(tmp_path):
    bench = registry.Bench(dashboard_root(tmp_path, **DRIFTING))
    cell = bench.cell(CELL)
    cfg = bench.config(cell["config"])
    drv = bench.driver(cfg["driver"]).Driver(
        cfg, bench.traffic(cell["traffic"]), 2**31 + 9,
        lambda name: __import__("contextlib").nullcontext())
    drv.window(1.0)
    got = drv.program_outputs()
    n_q = [a["counts"]["series_corr"]["n_quarantined"].max()
           for a in got["answers"]]
    assert max(n_q) > 0
    # beyond the 12-step tier, instants are partly covered
    n_cov = got["answers"][-1]["counts"]["series_corr"]["n_covered"]
    assert n_cov.min() < cfg["n_devices"] == n_cov.max()


def _break(monkeypatch, fault: str):
    """Break the timed path underneath the harness."""
    from repro.core.stream import snapshot, state
    if fault == "series_shifted":
        series = snapshot.MonitorSnapshot.fleet_series

        def shifted(self, t0, t1, step_s, corrected=True):
            return series(self, t0 - step_s, t1 - step_s, step_s, corrected)

        monkeypatch.setattr(snapshot.MonitorSnapshot, "fleet_series",
                            shifted)
    elif fault == "tier_never_written":
        monkeypatch.setattr(state.HistoryTier, "write",
                            lambda self, dev, b, e_raw, e_corr: None)
    elif fault == "quarantine_ignored":
        monkeypatch.setattr(snapshot.MonitorSnapshot, "active_mask",
                            property(lambda self: None))


@pytest.mark.parametrize("fault", ["series_shifted", "tier_never_written",
                                   "quarantine_ignored"])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch,
                                          no_chip_check, fault):  # noqa: F811
    root = dashboard_root(tmp_path, **DRIFTING)
    _break(monkeypatch, fault)
    out = run_cell(root, CELL, seconds=0.5)
    assert out["correct"] is False, out["checks"]


def test_traced_run_reports_the_serving_spans(tmp_path,
                                              no_chip_check):  # noqa: F811
    out = run_cell(dashboard_root(tmp_path), CELL, trace=1)
    assert out["correct"]
    # no device plane on the CPU: the device readers stay silent
    assert set(out["metrics"]) == {"ingest_host_ms", "serve_host_ms",
                                   "publish_ms"}
    assert out["compiles_in_window"] == 0


NEW_READERS = ("serve_host_ms", "serve_device_ms", "series_roofline",
               "publish_ms")


def test_new_readers_are_silent_without_their_spans():
    bench = registry.Bench(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    ms = 1_000_000
    ops = {"/device:TPU:0": [("fold", 12 * ms, 16 * ms)]}
    tr = trace_reduce.reduce_trace(
        ops, [("window", 0, 100 * ms), ("ingest", 10 * ms, 30 * ms)])
    peaks = {"hbm_bytes_per_s": 819e9}
    for ctx in ({"trace": None, "peaks": peaks, "rec": {}},
                {"trace": tr, "peaks": peaks, "rec": {"ingest_bytes": 1}}):
        for name in NEW_READERS:
            assert bench.reader(name)(ctx) is None, name
    # with the spans: per-refresh host and device time, the roofline
    ops = {"/device:TPU:0": [("history_series", 52 * ms, 54 * ms),
                             ("history_series", 72 * ms, 74 * ms)]}
    spans = [("window", 0, 100 * ms), ("publish", 40 * ms, 50 * ms),
             ("refresh", 50 * ms, 60 * ms), ("publish", 60 * ms, 70 * ms),
             ("refresh", 70 * ms, 80 * ms)]
    ctx = {"trace": trace_reduce.reduce_trace(ops, spans), "peaks": peaks,
           "rec": {"series_bytes": int(819e9 * 0.002)}}
    got = {name: bench.reader(name)(ctx) for name in NEW_READERS}
    assert got == pytest.approx({"serve_host_ms": 8.0,
                                 "serve_device_ms": 2.0,
                                 "series_roofline": 50.0,
                                 "publish_ms": 10.0})


def test_reference_grid_fold_is_the_flat_fold():
    """The dashboard reference's fold of a clean rectangular slab is the
    monitor reference's fold of the same slab flattened, bit for bit."""
    from drivers.monitor import fleet_arrays
    from reference.dashboard import DashboardReference
    from reference.monitor import MonitorReference
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    bench = registry.Bench(root)
    cfg = dict(bench.config("dashboard_a100mix_100k"), n_devices=300)
    fleet = fleet_arrays(cfg)
    rng = np.random.default_rng(4)
    grid = DashboardReference(fleet, cfg["monitor"])
    flat = MonitorReference(fleet, cfg["monitor"])
    for s in range(30):
        dev = np.arange(s % 3 * 100, s % 3 * 100 + 100)
        ts = (s // 3) + 0.1 * np.arange(10) + 0.05
        vals = rng.uniform(100.0, 250.0, (100, 10))
        grid.ingest_grid(dev, ts, vals)
        flat.ingest(np.repeat(dev, 10), np.tile(ts, 100), vals.ravel())
    for k in grid.st:
        np.testing.assert_array_equal(grid.st[k], flat.st[k], k)
    for k in grid.ring:
        np.testing.assert_array_equal(grid.ring[k], flat.ring[k], k)
    for k in grid.health:
        np.testing.assert_array_equal(grid.health[k], flat.health[k], k)
    assert grid.counters() == flat.counters()
    with pytest.raises(ValueError, match="clean"):
        grid.ingest_grid(dev, ts, vals)         # not past the newest
