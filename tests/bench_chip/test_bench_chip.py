"""CPU tests of the chip benchmark's harness, at tiny sizes.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench_chip

The monitor's Pallas kernels run in the interpreter here.  Each tiny run
is a copy of the benchmark whose configuration has 1,024 devices; the
chip check is switched off in the test, never through an option of the
harness.
"""
from __future__ import annotations

import io
import json
import os
import shutil
import subprocess
import sys
from contextlib import redirect_stdout

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
for _p in (BENCH, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import registry  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

N_TINY = 1024
SLAB = {"grid_clean": 256, "flat_readme_faults": 128}


def tiny_root(tmp_path, n: int = N_TINY) -> str:
    """A copy of the benchmark with every configuration cut to ``n``
    devices and every traffic mix to small slabs."""
    root = str(tmp_path / "root")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for c in spec["configs"]:
        path = os.path.join(root, c["file"])
        cfg = json.load(open(path))
        cfg["n_devices"] = n
        json.dump(cfg, open(path, "w"))
    for name, d in SLAB.items():
        path = os.path.join(root, "bench", "traffic", f"{name}.json")
        t = json.load(open(path))
        t["devices_per_slab"] = d
        json.dump(t, open(path, "w"))
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return root


@pytest.fixture
def no_chip_check(monkeypatch):
    monkeypatch.setattr(run, "chip_error", lambda devices, chips: None)


def run_cell(root, workload, seed=2**31 + 5, seconds=1.0, trace=0):
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root)
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


# -- discovery -------------------------------------------------------------

def test_every_piece_is_found_by_name():
    bench = registry.Bench(ROOT)
    spec = bench.spec
    for w in spec["workloads"]:
        cell = bench.cell(w["name"])
        cfg = bench.config(cell["config"])
        assert bench.traffic(cell["traffic"])["name"] == cell["traffic"]
        assert hasattr(bench.driver(cfg["driver"]), "Driver")
        assert "setup_s" in {m["name"] for m in
                             bench.metrics(w["name"], per_layer=False)}
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(bench.reader(m["name"]))
    with pytest.raises(KeyError):
        bench.cell("no_such_cell")


def test_a_new_traffic_file_is_found_and_runs(tmp_path, no_chip_check):
    root = tiny_root(tmp_path)
    t = json.load(open(os.path.join(root, "bench", "traffic",
                                    "grid_clean.json")))
    t["name"] = "grid_clean_5_ticks"
    t["ticks_per_slab"] = 5
    json.dump(t, open(os.path.join(root, "bench", "traffic",
                                   "grid_clean_5_ticks.json"), "w"))
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["workloads"].append(dict(spec["workloads"][0], name="grid_5",
                                  traffic="grid_clean_5_ticks"))
    for m in spec["end_to_end"]:
        if "monitor_grid" in m.get("workloads", []):
            m["workloads"].append("grid_5")
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    out = run_cell(root, "grid_5")
    assert out["correct"]
    assert set(out["metrics"]) == {"samples_per_s", "slab_p95_ms",
                                   "setup_s"}


# -- the last line ---------------------------------------------------------

def test_last_line_contract(tmp_path, no_chip_check):
    root = tiny_root(tmp_path)
    out = run_cell(root, "monitor_grid")
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"samples_per_s", "slab_p95_ms",
                                   "setup_s"}
    for m in out["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(out["device"])
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}
    assert out["compiles_in_window"] == 0


def test_traced_run_reports_per_layer_metrics(tmp_path, no_chip_check):
    root = tiny_root(tmp_path)
    out = run_cell(root, "monitor_grid", trace=1)
    assert out["correct"]
    # no device plane on the CPU: the device readers stay silent
    assert set(out["metrics"]) == {"ingest_host_ms"}
    assert out["device"]["window_s"] > 0
    assert "breakdown" in out


def test_refused_without_a_tpu(tmp_path):
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", "monitor_grid", "--seed", "1",
                       "--seconds", "1", "--trace", "0"], root=ROOT)
    assert rc != 0
    assert buf.getvalue() == ""


def test_refused_with_only_the_benchmark_files(tmp_path):
    root = tmp_path / "bare"
    for path in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["paths"]:
        shutil.copytree(os.path.join(ROOT, path), root / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "monitor_grid", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=root, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""


# -- trace reduction -------------------------------------------------------

def test_trace_reduction_on_a_synthetic_trace():
    ms = 1_000_000
    spans = [("window", 0, 100 * ms), ("ingest", 10 * ms, 30 * ms),
             ("flush", 50 * ms, 90 * ms)]
    ops = {"/device:TPU:0": [("fold", 12 * ms, 16 * ms),
                             ("fold", 14 * ms, 20 * ms),   # overlaps
                             ("query", 60 * ms, 70 * ms),
                             ("late", 95 * ms, 120 * ms)]}  # cut at 100
    r = trace_reduce.reduce_trace(ops, spans)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.008 + 0.010 + 0.005)
    assert r["device_s"]["ingest"] == pytest.approx(0.008)
    assert r["device_s"]["flush"] == pytest.approx(0.010)
    assert r["span_s"] == pytest.approx({"ingest": 0.02, "flush": 0.04})
    assert r["span_count"] == {"ingest": 1, "flush": 1}
    assert r["device_ops"][0] == ["fold", pytest.approx(0.010)]
    # gaps: 20-60 (ingest 10, harness 20, flush 10), 70-95 (flush 20,
    # harness 5), 0-12 (harness 10, ingest 2): named by what covers most
    assert r["idle_gaps"] == [["harness", pytest.approx(0.040)],
                              ["flush", pytest.approx(0.025)],
                              ["harness", pytest.approx(0.012)]]


# -- the copied fault injector ---------------------------------------------

def test_fault_injector_copy_matches_the_program():
    from faults import FaultInjector, FaultSpec
    from repro.core.stream.replay import FaultInjector as ProgInjector
    from repro.core.stream.replay import FaultSpec as ProgSpec
    kw = dict(clock_drift=0.005, restart_every_s=2.0, corrupt_fraction=0.02,
              dropout_fraction=0.1, dup_fraction=0.01, delay_fraction=0.02,
              shuffle=True, seed=11)
    ours = FaultInjector(FaultSpec(**kw), 500, 0.0, 4.0)
    prog = ProgInjector(ProgSpec(**kw), 500, 0.0, 4.0)
    rng = np.random.default_rng(0)
    for seq in range(6):
        dev = np.repeat(np.arange(500), 5)
        t = np.tile(seq * 0.5 + 0.1 * np.arange(5), 500)
        v = rng.uniform(60, 300, dev.size)
        a, b = ours.apply(seq, dev, t, v), prog.apply(seq, dev, t, v)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
    assert ours.log.counts == prog.log.counts
    for x, y in zip(ours.flush(), prog.flush()):
        np.testing.assert_array_equal(x, y)


# -- what decides correct --------------------------------------------------

@pytest.mark.parametrize("workload", ["monitor_grid", "monitor_faulty"])
def test_control_fails_and_program_passes(tmp_path, workload):
    """The float32 control fails at least one limit; the program passes
    every one (tiny size: the limits are set from chip readings at the
    cells' own sizes, PERF.md)."""
    import control
    bench = registry.Bench(tiny_root(tmp_path))
    r = control.readings(bench, workload, seed=2**31 + 9, seconds=1.0)
    lim = bench.traffic(bench.cell(workload)["traffic"])["check"]["limits"]
    assert all(v <= lim[k] for k, v in r["program"].items()), r
    assert any(not v <= lim[k] for k, v in r["control"].items()), r


def _break(monkeypatch, fault: str):
    """Break the timed path underneath the harness."""
    from repro.core.stream import monitor
    from repro.core.engine_backend import pallas_backend
    if fault == "state_unchanged":
        monkeypatch.setattr(monitor.MonitorService, "ingest_grid",
                            lambda self, dev, ts, vals: None)
        monkeypatch.setattr(monitor.MonitorService, "ingest",
                            lambda self, dev, t, v: None)
    elif fault == "half_batch":
        grid, flat = (monitor.MonitorService.ingest_grid,
                      monitor.MonitorService.ingest)
        monkeypatch.setattr(
            monitor.MonitorService, "ingest_grid",
            lambda self, dev, ts, vals: grid(self, dev[: len(dev) // 2], ts,
                                             vals[: len(dev) // 2]))
        monkeypatch.setattr(
            monitor.MonitorService, "ingest",
            lambda self, dev, t, v: flat(self, dev[: len(dev) // 2],
                                         t[: len(dev) // 2],
                                         v[: len(dev) // 2]))
    elif fault == "answer_altered":
        kgrid, kflat = (pallas_backend.stream_ingest_grid,
                        pallas_backend.stream_ingest)

        def grid(*a, **k):
            out = list(kgrid(*a, **k))
            out[3] = out[3] * (1 + 1e-3)        # raw energy increments
            return tuple(out)

        def flat(*a, **k):
            out = list(kflat(*a, **k))
            out[5] = out[5] * (1 + 1e-3)
            return tuple(out)

        monkeypatch.setattr(pallas_backend, "stream_ingest_grid", grid)
        monkeypatch.setattr(pallas_backend, "stream_ingest", flat)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
@pytest.mark.parametrize("workload", ["monitor_grid", "monitor_faulty"])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch,
                                          no_chip_check, workload, fault):
    root = tiny_root(tmp_path, n=512)
    _break(monkeypatch, fault)
    out = run_cell(root, workload, seconds=0.5)
    assert out["correct"] is False, out["checks"]
