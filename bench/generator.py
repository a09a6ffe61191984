"""The benchmark's traffic generator: one general generator, driven by
a configuration file and a traffic file, run in set-up only.

``poll_cycle`` makes one cycle of the fleet's nvidia-smi poll stream:
the sensors are simulated by the numpy ``SensorBank`` (never the tier
under test), each device running the configuration's job, polled every
``poll_period_s``.  Slabs come in stream-second order, then by
collector (``devices_per_slab`` devices each), each ``ticks_per_slab``
polls long: ``iter_poll_slabs(grid=True)`` order.  A traffic file with
``faults`` flattens each slab and passes it through the benchmark's
copy of the fault injector, seeded with the run's seed.  The window
replays the cycle with its times moved by ``k * cycle_s`` in cycle
``k``.
"""
from __future__ import annotations

import os
import sys

import numpy as np

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)


def sensor_names(cfg: dict) -> list:
    """Each device's sensor class: the mix's shares in order, the last
    class taking the rest."""
    n = int(cfg["n_devices"])
    names = []
    for cls, share in cfg["sensor_mix"][:-1]:
        names += [cls] * int(n * share)
    names += [cfg["sensor_mix"][-1][0]] * (n - len(names))
    return names


def job_span(cfg: dict) -> tuple:
    """``(start, end)`` of the job every device runs, in stream time."""
    job = cfg["job"]
    return (float(job["start_s"]),
            float(job["start_s"]) + sum(d for d, _ in job["phases"]))


def poll_cycle(cfg: dict, traffic: dict, seed: int) -> list:
    """One cycle of slabs: ``(dev [D], times [M], readings [D, M])``
    for a clean stream, ``(dev [K], times [K], readings [K])`` where the
    traffic has faults."""
    from repro.core import load as loads
    from repro.core.fleet_engine import SensorBank
    from faults import FaultInjector, FaultSpec

    n = int(cfg["n_devices"])
    period = float(cfg["poll_period_s"])
    cycle = float(cfg["cycle_s"])
    # seed s gives the devices the sensor seeds [s n, s n + n): runs on
    # different seeds share no device
    bank = SensorBank.from_catalog(sensor_names(cfg),
                                   seeds=np.arange(n) + int(seed) * n,
                                   backend="numpy")
    tl = loads.multi_phase_workload(
        [tuple(p) for p in cfg["job"]["phases"]])
    tl = tl.shift(job_span(cfg)[0] - tl.t_start)
    bank.attach(tl, t_end=tl.t_end + 1.0)
    slabs = list(bank.iter_poll_slabs(
        0.0, cycle, period_s=period,
        tick_s=int(traffic["ticks_per_slab"]) * period,
        chunk_devices=int(traffic["devices_per_slab"]), grid=True))
    if not traffic.get("faults"):
        return slabs
    inj = FaultInjector(FaultSpec(**traffic["faults"], seed=int(seed)), n,
                        0.0, cycle)
    out = []
    for seq, (dev, ts, vals) in enumerate(slabs):
        out.append(inj.apply(seq, np.repeat(dev, ts.size),
                             np.tile(ts, dev.size), vals.ravel()))
    held = inj.flush()
    if held[0].size:
        raise ValueError("faults with delayed samples have no cycle end")
    return out

