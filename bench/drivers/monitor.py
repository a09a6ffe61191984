"""Driver of the streaming monitor's cells.

Set-up makes the inputs (``generator.poll_cycle``), builds one
``MonitorService`` as the configuration states, and drives it through
the first cycle of the stream: that warms every shape the window uses,
since the window replays the same cycle moved in time.  The window then
hands over slab after slab (closed loop: the next goes as soon as the
last returns).

``program_outputs``, ``reference_outputs`` and ``readings`` compare what
the timed path produced with the plain reference
(``bench/reference/monitor.py``) fed the same slabs in the same order.
"""
from __future__ import annotations

import time

import numpy as np

import generator as gen
from check import compare
from reference.monitor import MonitorReference

STATE_FIELDS = ("last_t", "last_v", "has", "first_t", "n_samples", "n_dup",
                "n_late", "energy_j", "energy_corr_j", "win_j", "win_corr_j",
                "run_t", "n_changes", "ewma_w", "n_out")
COUNTERS = ("accepted", "duplicates", "late", "invalid", "rejected",
            "devices_reporting")
_CHECK_STREAM = 17


def fleet_arrays(cfg: dict) -> dict:
    """Per-device inputs of the monitor, from the configuration: the
    calibration of each device's sensor class, its label and its job
    window."""
    names = np.array(gen.sensor_names(cfg))
    n = names.size
    cal = cfg["calibrations"]

    def per(key):
        return np.array([float(cal[c][key]) for c in names])

    a, b = gen.job_span(cfg)
    calibrated = np.array([bool(cal[c]["calibrated"]) for c in names])
    return {"gain": per("gain"), "offset_w": per("offset_w"),
            "time_shift_s": per("time_shift_s"),
            "baseline_w": np.full(n, float(cfg["baseline_w"])),
            "ref_period_s": per("ref_period_s"), "calibrated": calibrated,
            "win_a": np.full(n, a), "win_b": np.full(n, b),
            "max_hold": np.full(n, np.inf), "env_lo": np.full(n, -np.inf),
            "env_hi": np.full(n, np.inf), "label": names}


def build_monitor(cfg: dict, fleet: dict):
    """The program under test, as the configuration states it."""
    from repro.core.stream import HealthPolicy, MonitorService
    from repro.core.stream.estimators import StreamCorrections
    m = cfg["monitor"]
    corr = StreamCorrections(
        gain=fleet["gain"], offset_w=fleet["offset_w"],
        time_shift_s=fleet["time_shift_s"], baseline_w=fleet["baseline_w"],
        ref_period_s=fleet["ref_period_s"], calibrated=fleet["calibrated"])
    mon = MonitorService(
        int(cfg["n_devices"]), corrections=corr,
        labels=fleet["label"].astype(object), integration=m["integration"],
        ring_slots=int(m["ring_slots"]),
        silent_after_s=float(m["silent_after_s"]),
        drift_tau_s=float(m["drift_tau_s"]), drift_rel=float(m["drift_rel"]),
        drift_abs_w=float(m["drift_abs_w"]), strict_ids=bool(m["strict_ids"]),
        health=HealthPolicy(**m["health"]),
        health_every_s=float(m["health_every_s"]), backend=m["backend"])
    mon.set_windows(fleet["win_a"], fleet["win_b"])
    return mon


class Driver:
    """One cell of the monitor: set-up in the constructor, then
    :meth:`window` and the comparison (see module doc)."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, span):
        self.cfg, self.span = cfg, span
        self.fleet = fleet_arrays(cfg)
        self.cycle_s = float(cfg["cycle_s"])
        self.slabs = gen.poll_cycle(cfg, traffic, seed)
        self.grid = not traffic.get("faults")
        self.slab_bytes = [self._bytes(x) for x in self.slabs]
        self.mon = build_monitor(cfg, self.fleet)
        self.log = []           # (cycle, slab) in the order ingested
        rng = np.random.default_rng((int(seed), _CHECK_STREAM))
        n = int(cfg["n_devices"])
        share = float(traffic["check"]["device_share"])
        self.subset = (np.arange(n) if share >= 1.0 else np.sort(
            rng.choice(n, size=max(1, int(n * share)), replace=False)))
        for i in range(len(self.slabs)):           # warm-up: cycle 0
            self._step(0, i, None)

    # -- one slab -----------------------------------------------------------
    def _slab(self, k: int, i: int):
        dev, ts, vals = self.slabs[i]
        return dev, ts + k * self.cycle_s, vals

    def _step(self, k: int, i: int, rec) -> None:
        dev, ts, vals = self._slab(k, i)
        self.log.append((k, i))
        t0 = time.perf_counter()
        with self.span("ingest"):
            if self.grid:
                self.mon.ingest_grid(dev, ts, vals)
            else:
                self.mon.ingest(dev, ts, vals)
        t1 = time.perf_counter()
        if rec is not None:
            rec["slab_s"].append(t1 - t0)
            rec["done"].append(t1)
            rec["samples"] += int(vals.size)
            rec["slabs"] += 1
            rec["ingest_bytes"] += self.slab_bytes[i]

    def _bytes(self, slab) -> int:
        from shapes import ingest_bytes
        dev, ts, vals = slab
        if self.grid:
            return ingest_bytes(vals.size, dev.size, ticks=ts.size)
        return ingest_bytes(vals.size, np.unique(dev).size, flat=True)

    # -- the measured window -----------------------------------------------
    def window(self, seconds: float) -> dict:
        """Replay cycles 1, 2, ... closed-loop until ``seconds`` have
        passed; returns what the metric readers read."""
        rec = {"slab_s": [], "done": [], "samples": 0, "slabs": 0,
               "ingest_bytes": 0}
        k, i = 1, 0
        with self.span("window"):
            rec["start"] = t_start = time.perf_counter()
            while True:
                self._step(k, i, rec)
                if rec["done"][-1] - t_start >= seconds:
                    break
                i += 1
                if i == len(self.slabs):
                    k, i = k + 1, 0
        rec["slab_s"] = np.array(rec["slab_s"])
        rec["attempted"] = rec["slabs"]
        return rec

    # -- correctness ---------------------------------------------------------
    def program_outputs(self) -> dict:
        """What the timed path produced, read off the program and copied,
        so that its state can be freed before the reference runs."""
        mon, s = self.mon, self.subset
        ring = mon.ring.sorted_view()
        out = {"state": {k: np.array(getattr(mon.state, k))[s]
                         for k in STATE_FIELDS},
               "ring": {"t": ring[0][s], "v": ring[1][s],
                        "e_raw": ring[2][s], "e_corr": ring[3][s]},
               "ring_written": np.array(mon.ring.n_written)[s],
               "health": np.array(mon.health.code)[s],
               "counters": {k: mon.counters[k] for k in COUNTERS}}
        self.mon = None
        return out

    def reference_outputs(self, dtype=np.float64) -> dict:
        """The plain reference (or, with ``float32``, the control) fed
        the slabs the program was fed, in the same order."""
        ref = MonitorReference(self.fleet, self.cfg["monitor"], dtype,
                               subset=self.subset)
        s = self.subset
        for k, i in self.log:
            dev, ts, vals = self._slab(k, i)
            if self.grid:
                dev, ts, vals = (np.repeat(dev, ts.size),
                                 np.tile(ts, dev.size), vals.ravel())
            ref.ingest(dev, ts, vals)
        ring = ref.ring_sorted()
        return {"state": {k: ref.st[k][s] for k in STATE_FIELDS},
                "ring": {k: ring[k][s] for k in ("t", "v", "e_raw",
                                                 "e_corr")},
                "ring_written": ref.ring["n_written"][s],
                "health": ref.health["code"][s],
                "counters": {k: ref.counters()[k] for k in COUNTERS}}

    def readings(self, got: dict, ref: dict, where: dict = None) -> dict:
        """The numbers compared: ``fold_err``, the largest error over the
        float fields of the state and the ring (each field against its
        own largest magnitude), and ``int_mismatch`` (integer and boolean
        entries that differ: counts, flags, counters, health codes).
        ``where``, if given, receives the worst field of ``fold_err``."""
        fold = compare({"state": got["state"], "ring": got["ring"]},
                       {"state": ref["state"], "ring": ref["ring"]})
        if where is not None:
            where["fold_err"] = fold["where"]
        ints = compare({"w": got["ring_written"], "h": got["health"],
                        "c": [np.int64(got["counters"][k])
                              for k in COUNTERS]},
                       {"w": ref["ring_written"], "h": ref["health"],
                        "c": [np.int64(ref["counters"][k])
                              for k in COUNTERS]})
        return {"fold_err": fold["float_err"],
                "int_mismatch": fold["int_mismatch"] + ints["int_mismatch"]}
