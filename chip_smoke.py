#!/usr/bin/env python3
"""Smoke run of the streaming monitor, the query service and the fleet
audit on a TPU, each phase checked against the numpy float64 reference.

    python3 chip_smoke.py               # one chip: phases (a)-(e)
    python3 chip_smoke.py --four-chips  # the sharded audit on four chips

Phases, each through the entry points a user calls:

(a) grid    100,000 GPUs (the a100/h100_instant/v100 mix) polled at the
            sensors' own 100 ms cadence, 20 ticks per slab, through
            ``stream_fleet`` into ``MonitorService`` on the clean
            rectangular path;
(b) faulty  10,000 GPUs under the README's ``FaultSpec`` mix (clock
            drift, collector restarts, corrupt samples, node deaths)
            through ``replay`` on the general path;
(c) serve   a few hundred mixed ``MonitorQuery``s through
            ``MonitorQueryService.flush``, interleaved with ingest;
(d) audit   a 100,000-GPU ``fleet_audit`` (jax tier only);
(k) kernels ``step_integrate`` and ``log_filter``, which no phase above
            reaches through the public path, called directly;

and (a)-(c) and (k) run once on the pallas tier and once on the jax
tier (e).
Each phase prints its compile and run seconds, its size and its largest
error against the reference, and fails past its tolerance.  Every
output field is compared on its own: counts, flags and counters
exactly, a float field relative to its own largest magnitude.  The
pallas tier's 32-bit kernels are held to ``precision.KERNEL_RTOL``, the
float64 jax tier to ``JAX_RTOL`` (which a float32 result would fail).

The last line of standard output is ``{"ok": true, "device": {...}}``.
The script exits non-zero, printing no such line, when jax finds no TPU
or any phase fails.  It runs in one process: the chip belongs to the
process that first touches jax.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

from repro.core import load as loads  # noqa: E402
from repro.core.engine_backend import (precision,  # noqa: E402
                                       use_compile_cache)
from repro.core.fleet_engine import SensorBank, fleet_audit  # noqa: E402
from repro.core.meter import Workload  # noqa: E402
from repro.core.stream import (FaultSpec, HealthPolicy,  # noqa: E402
                               MonitorService, replay, stream_fleet)
from repro.serve.monitor_service import (MonitorQuery,  # noqa: E402
                                         MonitorQueryService)

# float64 on both sides: only reduction order differs
JAX_RTOL = 1e-9
TOL = {"pallas": precision.KERNEL_RTOL, "jax": JAX_RTOL}
PERIOD_S = 0.1      # A100/H100 update period (core/profiles.py)
TICK_S = 2.0        # 20 poll ticks per slab
# a 3.2 s multi-phase job starting at 0.3 s: the grid spans 4 s, 40 ticks
WORKLOAD = Workload("smoke", loads.multi_phase_workload(
    [(1.3, 215.0), (0.7, 165.0), (1.2, 240.0)]))
FAULTS = FaultSpec(clock_drift=0.005, restart_every_s=2.0,
                   corrupt_fraction=0.02, dropout_fraction=0.1, seed=11)


def profile_mix(n: int) -> list:
    """The benchmark fleet's sensor mix (``benchmarks/fleet.py``)."""
    return (["a100"] * (n // 2) + ["h100_instant"] * (n // 4)
            + ["v100"] * (n - n // 2 - n // 4))


class CompileClock:
    """Seconds XLA spent compiling, summed from jax's backend-compile
    events (tracing and lowering are nested for nested jits, so they are
    left in the run time)."""

    def __init__(self):
        import jax.monitoring
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration


def fields(x, path: str = ""):
    """``(path, array)`` for every numeric field of a query result or
    state, in a fixed order (dataclass fields, sorted dict keys, list
    positions)."""
    if dataclasses.is_dataclass(x):
        x = {f.name: getattr(x, f.name) for f in dataclasses.fields(x)}
    if isinstance(x, dict):
        for k in sorted(x, key=str):
            yield from fields(x[k], f"{path}.{k}")
    elif isinstance(x, (list, tuple)):
        for i, v in enumerate(x):
            yield from fields(v, f"{path}[{i}]")
    else:
        a = np.asarray(x)
        if a.dtype.kind in "biuf":
            yield path, a


def field_err(got: np.ndarray, ref: np.ndarray) -> float:
    """One field's error: integer and boolean fields (counts, flags,
    counters) must match exactly, else inf; a float field's largest
    difference relative to its own largest finite magnitude, nan where
    the two disagree on which entries are nan or infinite."""
    if got.shape != ref.shape:
        return float("nan")
    if ref.dtype.kind in "biu":
        return 0.0 if np.array_equal(got, ref) else float("inf")
    got, ref = got.astype(np.float64), ref.astype(np.float64)
    fin = np.isfinite(ref)
    if not (np.array_equal(np.isfinite(got), fin)
            and np.array_equal(got[~fin], ref[~fin], equal_nan=True)):
        return float("nan")
    if not fin.any():
        return 0.0
    diff = float(np.max(np.abs(got[fin] - ref[fin])))
    scale = float(np.max(np.abs(ref[fin])))
    return diff / scale if scale > 0 else (0.0 if diff == 0 else np.inf)


def compare(got, ref) -> tuple:
    """Largest error over the fields of ``got`` against ``ref``, each
    field against itself (see :func:`field_err`), and that field's
    path; nan if the two differ in their fields."""
    g, r = list(fields(got)), list(fields(ref))
    if [p for p, _ in g] != [p for p, _ in r]:
        return float("nan"), "field list"
    worst, where = 0.0, ""
    for (path, a), (_, b) in zip(g, r):
        e = field_err(a, b)
        if not e <= worst:      # nan or larger
            worst, where = e, path
            if np.isnan(e):
                break
    return worst, where


def monitor_state(mon: MonitorService) -> dict:
    st = mon.state
    return {k: getattr(st, k) for k in ("energy_j", "energy_corr_j",
                                        "win_j", "win_corr_j", "n_samples",
                                        "n_changes")}


# -- phases: each returns (result to compare, samples or devices) ----------

def phase_grid(backend: str, n: int):
    r = stream_fleet(n, profile=profile_mix(n), workload=WORKLOAD,
                     seed=0, period_s=PERIOD_S, tick_s=TICK_S,
                     backend=backend)
    c = r.monitor.counters
    return ({"state": monitor_state(r.monitor), "counters": c},
            c["accepted"])


def _fleet_bank(n: int) -> tuple:
    bank = SensorBank.from_catalog(profile_mix(n), seeds=np.arange(n))
    tl = WORKLOAD.timeline.shift(0.3 - WORKLOAD.timeline.t_start)
    bank.attach(tl, t_end=tl.t_end + 1.0)
    return bank, float(tl.t_end + 0.5)


def phase_faulty(backend: str, n: int):
    bank, t1 = _fleet_bank(n)
    mon = MonitorService(n, strict_ids=False, health=HealthPolicy(),
                         health_every_s=0.5, silent_after_s=1.0,
                         backend=backend)
    c = replay(bank, mon, 0.0, t1, period_s=PERIOD_S, tick_s=0.5,
               faults=FAULTS)
    return {"state": monitor_state(mon), "counters": c}, c["accepted"]


def _queries(t: float, k: int) -> list:
    """``k`` distinct queries of every kind about stream time ``t``."""
    qs = []
    for i in range(k):
        tq = t - 0.05 * i
        qs += [MonitorQuery.fleet_energy(tq, corrected=bool(i % 2)),
               MonitorQuery.window_energy(tq),
               MonitorQuery.energy_between(tq - 0.5, tq),
               MonitorQuery.by_label(tq - 0.4, tq, corrected=bool(i % 2))]
    return qs


def phase_serve(backend: str, n: int):
    bank, t1 = _fleet_bank(n)
    labels = np.array(profile_mix(n), dtype=object)
    mon = MonitorService(n, labels=labels, backend=backend, ring_slots=16)
    mon.set_windows(np.full(n, 0.3), np.full(n, 0.3 + WORKLOAD.duration_s))
    svc = MonitorQueryService(mon)
    answers = []

    def serve(monitor, t_emitted):
        for q in _queries(t_emitted, 15):
            svc.submit(q)
        flushed = svc.flush()
        answers.extend(flushed[k] for k in sorted(flushed))

    replay(bank, mon, 0.0, t1, period_s=PERIOD_S, tick_s=0.5,
           progress=serve)
    return answers, len(answers)


def phase_kernels(backend: str, n: int):
    """The two streaming kernels no phase above reaches through the
    public path — step integration of sampled series (``meter``'s §5
    integration) and the logarithmic sensor filter (Kepler/Maxwell
    profiles) — called directly at fleet width on seeded data."""
    from repro.core.engine_backend import get_backend
    from repro.core.ground_truth import TimelineBank
    be = get_backend(backend)
    rng = np.random.default_rng(5)
    ts = np.cumsum(rng.uniform(0.05, 0.15, (n, 20)), axis=1)
    vals = np.round(rng.uniform(60.0, 300.0, (n, 20)), 2)
    t0 = rng.uniform(0.0, 0.5, n)
    t1 = t0 + rng.uniform(0.5, 2.0, n)
    tls = [loads.square_wave(0.2, 8, 240.0, 80.0, period_jitter_s=0.02,
                             seed=i) for i in range(n)]
    tl = TimelineBank.from_timelines(tls).arrays
    ticks = np.sort(rng.uniform(0.0, 3.0, (n, 30)), axis=1)
    tau = rng.uniform(0.05, 0.5, n)
    return {"step": be.step_integrate(ts, vals, t0, t1, trapezoid=True),
            "log_filter": be.log_filter(tl, ticks, tau)}, n


def phase_audit(backend: str, n: int, mesh=None):
    r = fleet_audit(n, profile=profile_mix(n), seed=3, backend=backend,
                    mesh=mesh)
    return {"naive_j": r.naive_j, "naive_err": r.naive_err}, n


def run_phase(label, fn, backend, n, ref, clock, tol, **kw):
    c0, t0 = clock.total, time.perf_counter()
    got, size = fn(backend, n, **kw)
    wall = time.perf_counter() - t0
    compile_s = clock.total - c0
    err, where = compare(got, ref)
    ok = err <= tol
    print(f"phase {label} backend={backend} n_devices={n} size={size} "
          f"compile_s={compile_s:.3f} run_s={wall - compile_s:.3f} "
          f"max_rel_err={err:.3e} at={where or '-'} tol={tol:.0e} "
          f"{'PASS' if ok else 'FAIL'}", flush=True)
    return ok


def one_chip(clock) -> bool:
    from repro.core.engine_backend import pallas_backend
    if pallas_backend._interpret():
        print("pallas kernels would run interpreted", file=sys.stderr)
        return False
    phases = [("a_grid", phase_grid, 100_000),
              ("b_faulty", phase_faulty, 10_000),
              ("c_serve", phase_serve, 10_000),
              ("k_kernels", phase_kernels, 100_000)]
    ok = True
    for label, fn, n in phases:
        t0 = time.perf_counter()
        ref, _ = fn("numpy", n)
        print(f"reference {label} numpy n_devices={n} "
              f"s={time.perf_counter() - t0:.3f}", flush=True)
        for backend in ("pallas", "jax"):
            ok &= run_phase(label, fn, backend, n, ref, clock,
                            TOL[backend])
    n = 100_000
    ref, _ = phase_audit("numpy", n)
    ok &= run_phase("d_audit", phase_audit, "jax", n, ref, clock, JAX_RTOL)
    return ok


def four_chips(clock, n: int = 400_000) -> bool:
    """The sharded audit over a 4-device mesh against the one-device jax
    audit of the same rows, compared row by row."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.launch.mesh import data_mesh

    mesh = data_mesh(4)
    probe = jax.device_put(np.arange(4.0),
                           NamedSharding(mesh, PartitionSpec("data")))
    placed = {s.device.id for s in probe.addressable_shards}
    print(f"mesh devices={[d.id for d in mesh.devices.flat]} "
          f"shards_on={sorted(placed)}", flush=True)
    if len(placed) != 4:
        return False
    t0 = time.perf_counter()
    ref, _ = phase_audit("jax", n)
    print(f"reference audit jax one-device n_devices={n} "
          f"s={time.perf_counter() - t0:.3f}", flush=True)
    return run_phase("sharded_audit", phase_audit, "jax", n, ref, clock,
                     JAX_RTOL, mesh=mesh)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded audit on a 4-chip mesh")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: jax platform is {dev.platform!r}", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"need {want} TPU chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    cache = use_compile_cache()
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache}", flush=True)
    clock = CompileClock()
    ok = four_chips(clock) if args.four_chips else one_chip(clock)
    if not ok:
        print("chip smoke FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
