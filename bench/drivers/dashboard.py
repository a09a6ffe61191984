"""Driver of the dashboard cell.

Set-up builds one ``MonitorService`` with the history tier the
configuration states, and a ``MonitorQueryService`` over it, before it
makes any input, so that a program without the tier fails at once.  It
then makes one cycle of the stream (``generator.poll_cycle``), fills
``history_steps × history_step_s`` of stream through ``ingest_grid``
(every cycle but the last as one slab of every device, the last in the
window's slabs, each followed by a refresh: that warms every shape the
window uses), and the window replays the cycles after it.

The window is a closed loop: a slab, then one dashboard refresh, then
the next slab.  A refresh publishes a snapshot and flushes five
queries ending at the newest sample time aligned down to the history
step (see the traffic file); ``slab_s`` runs from a slab's hand-off to
the return of the refresh after it.  Every refresh's answers are
recorded.

``program_outputs``, ``reference_outputs`` and ``readings`` compare the
state, the ring, the tier and a seeded sample of the recorded refreshes
with the plain reference (``bench/reference/dashboard.py``) fed the
same slabs in the same order.
"""
from __future__ import annotations

import math
import time

import numpy as np

import generator as gen
from check import compare
from drivers.monitor import COUNTERS, STATE_FIELDS, fleet_arrays
from reference.dashboard import DashboardReference, tier_view
from shapes import ingest_bytes
from shapes_history import series_bytes

_CHECK_STREAM = 17


def build_monitor(cfg: dict, fleet: dict):
    """The program under test, as the configuration states it: the
    monitor of ``drivers/monitor.py`` with its history tier."""
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
        health_every_s=float(m["health_every_s"]),
        history_step_s=float(m["history_step_s"]),
        history_steps=int(m["history_steps"]), backend=m["backend"])
    mon.set_windows(fleet["win_a"], fleet["win_b"])
    return mon


def cycle_grid(slabs: list) -> tuple:
    """One cycle's slabs as a single rectangular slab of every device
    and every poll: ``(dev [D], ts [M], vals [D, M])``."""
    dev = np.unique(np.concatenate([d for d, _, _ in slabs]))
    ts = np.unique(np.concatenate([t for _, t, _ in slabs]))
    vals = np.full((dev.size, ts.size), np.nan)
    for d, t, v in slabs:
        vals[np.searchsorted(dev, d)[:, None],
             np.searchsorted(ts, t)[None, :]] = v
    if np.isnan(vals).any():
        raise ValueError("the cycle's slabs do not tile devices x polls")
    return dev, ts, vals


class Driver:
    """One dashboard cell: set-up in the constructor, then
    :meth:`window` and the comparison (see module doc)."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, span):
        from repro.serve.monitor_service import MonitorQueryService
        self.cfg, self.span, self.seed = cfg, span, int(seed)
        self.fleet = fleet_arrays(cfg)
        self.mon = build_monitor(cfg, self.fleet)
        self.svc = MonitorQueryService(
            self.mon, cache_size=int(cfg["serve"]["cache_size"]))
        m = cfg["monitor"]
        self.step = float(m["history_step_s"])
        self.range_s = float(traffic["panels"]["range_s"])
        self.label_s = float(traffic["panels"]["label_s"])
        self.n_check = int(traffic["check"]["refreshes"])
        self.cycle_s = float(cfg["cycle_s"])
        self.slabs = gen.poll_cycle(cfg, traffic, seed)
        self.fill = cycle_grid(self.slabs)
        self.log = []           # ("fill", k) or ("slab", k, i, end)
        self.answers = []       # recorded refreshes of the window
        self.newest = -np.inf
        n_fill = math.ceil(int(m["history_steps"]) * self.step
                           / self.cycle_s)
        for k in range(n_fill - 1):
            self._fill(k)
        for i in range(len(self.slabs)):            # warm-up: last cycle
            self._step(n_fill - 1, i, None)
        self.k0 = n_fill

    # -- the stream ----------------------------------------------------------
    def _shifted(self, slab, k: int):
        dev, ts, vals = slab
        return dev, ts + k * self.cycle_s, vals

    def _fill(self, k: int) -> None:
        dev, ts, vals = self._shifted(self.fill, k)
        self.log.append(("fill", k))
        self.mon.ingest_grid(dev, ts, vals)
        self.newest = max(self.newest, float(ts[-1]))

    def _queries(self, end: float) -> list:
        from repro.serve.monitor_service import MonitorQuery
        t0 = end - self.range_s
        return [MonitorQuery.fleet_series(t0, end, self.step, True),
                MonitorQuery.fleet_series(t0, end, self.step, False),
                MonitorQuery.by_label(end - self.label_s, end),
                MonitorQuery.fleet_energy(),
                MonitorQuery.window_energy()]

    def _step(self, k: int, i: int, rec) -> None:
        dev, ts, vals = self._shifted(self.slabs[i], k)
        self.newest = max(self.newest, float(ts[-1]))
        end = math.floor(self.newest / self.step) * self.step
        self.log.append(("slab", k, i, end))
        t0 = time.perf_counter()
        with self.span("ingest"):
            self.mon.ingest_grid(dev, ts, vals)
        with self.span("publish"):
            self.mon.snapshot()
        tickets = [self.svc.submit(q) for q in self._queries(end)]
        with self.span("refresh"):
            res = self.svc.flush()
        t1 = time.perf_counter()
        if rec is not None:
            rec["slab_s"].append(t1 - t0)
            rec["done"].append(t1)
            rec["samples"] += int(vals.size)
            rec["slabs"] += 1
            rec["ingest_bytes"] += ingest_bytes(vals.size, dev.size,
                                                ticks=ts.size)
            n_q = res[tickets[0]].t.size
            rec["series_bytes"] += series_bytes(n_q, self.mon.n_devices, 2)
            self.answers.append(record([res[t] for t in tickets]))

    # -- the measured window -----------------------------------------------
    def window(self, seconds: float) -> dict:
        """Replay cycles ``k0``, ``k0 + 1``, ... closed-loop until
        ``seconds`` have passed; returns what the metric readers
        read."""
        rec = {"slab_s": [], "done": [], "samples": 0, "slabs": 0,
               "ingest_bytes": 0, "series_bytes": 0}
        k, i = self.k0, 0
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
        rec["stats"] = self.svc.stats()
        return rec

    # -- correctness ---------------------------------------------------------
    def checked(self) -> list:
        """The recorded refreshes compared: a sample drawn from the seed,
        and the last."""
        n = len(self.answers)
        rng = np.random.default_rng((self.seed, _CHECK_STREAM))
        pick = rng.choice(n, size=min(self.n_check - 1, n), replace=False)
        return sorted(set(int(x) for x in pick) | {n - 1})

    def program_outputs(self) -> dict:
        """What the timed path produced, read off the program and copied,
        so that its state can be freed before the reference runs."""
        mon = self.mon
        ring = mon.ring.sorted_view()
        h = mon.history
        tier = {"b_first": h.b_first.copy(), "b_last": h.b_last.copy(),
                "e_raw": np.array(h.e_raw), "e_corr": np.array(h.e_corr)}
        out = {"state": {k: np.array(getattr(mon.state, k))
                         for k in STATE_FIELDS},
               "ring": {"t": ring[0], "v": ring[1], "e_raw": ring[2],
                        "e_corr": ring[3]},
               "ring_written": np.array(mon.ring.n_written),
               "health": np.array(mon.health.code),
               "counters": {k: mon.counters[k] for k in COUNTERS},
               "tier": {"b_first": tier["b_first"],
                        "b_last": tier["b_last"],
                        **tier_view(tier, h.steps)},
               "answers": [self.answers[j] for j in self.checked()]}
        self.mon = self.svc = None
        return out

    def reference_outputs(self, dtype=np.float64) -> dict:
        """The plain reference (or, with ``float32``, the control) fed
        the slabs the program was fed, in the same order, answering the
        checked refreshes where they fell."""
        ref = DashboardReference(self.fleet, self.cfg["monitor"], dtype)
        want = set(self.checked())
        answers, r = [], -1
        n_warm = len(self.log) - len(self.answers)
        for n, entry in enumerate(self.log):
            if entry[0] == "fill":
                ref.ingest_grid(*self._shifted(self.fill, entry[1]))
                continue
            _, k, i, end = entry
            ref.ingest_grid(*self._shifted(self.slabs[i], k))
            if n >= n_warm:
                r += 1
                if r in want:
                    answers.append(ref.panels(end, self.range_s,
                                              self.label_s))
        return {"state": {k: ref.st[k] for k in STATE_FIELDS},
                "ring": ref.ring_sorted(),
                "ring_written": ref.ring["n_written"],
                "health": ref.health["code"],
                "counters": {k: ref.counters()[k] for k in COUNTERS},
                "tier": {"b_first": ref.tier["b_first"],
                         "b_last": ref.tier["b_last"], **ref.tier_view()},
                "answers": answers}

    def readings(self, got: dict, ref: dict, where: dict = None) -> dict:
        """The numbers compared: ``fold_err`` (float fields of the state
        and the ring) and ``int_mismatch`` (their integer and boolean
        entries, ring write counts, health codes, counters and each
        device's written boundary range), as in the monitor cells, over
        every device; ``hist_err``, the tier's entries, each flavour
        against its own largest magnitude (nan where the two cover
        different entries); ``series_err``, every float field of the
        checked refreshes (energies, powers, sigmas, coverage, by-label
        totals and moments, stat panels) against its own largest
        magnitude; ``count_mismatch``, their counts, exactly.
        ``where``, if given, receives the worst field of each float
        reading."""
        fold = compare({"state": got["state"], "ring": got["ring"]},
                       {"state": ref["state"], "ring": ref["ring"]})
        ints = compare(
            {"w": got["ring_written"], "h": got["health"],
             "c": [np.int64(got["counters"][k]) for k in COUNTERS],
             "b": [got["tier"]["b_first"], got["tier"]["b_last"]]},
            {"w": ref["ring_written"], "h": ref["health"],
             "c": [np.int64(ref["counters"][k]) for k in COUNTERS],
             "b": [ref["tier"]["b_first"], ref["tier"]["b_last"]]})
        hist = compare({k: got["tier"][k] for k in ("e_raw", "e_corr")},
                       {k: ref["tier"][k] for k in ("e_raw", "e_corr")})
        series = compare([a["floats"] for a in got["answers"]],
                         [a["floats"] for a in ref["answers"]])
        counts = compare([a["counts"] for a in got["answers"]],
                         [a["counts"] for a in ref["answers"]])
        if where is not None:
            where.update(fold_err=fold["where"], hist_err=hist["where"],
                         series_err=series["where"])
        return {"fold_err": fold["float_err"],
                "int_mismatch": fold["int_mismatch"] + ints["int_mismatch"],
                "hist_err": hist["float_err"],
                "series_err": series["float_err"],
                "count_mismatch": (counts["int_mismatch"]
                                   + series["int_mismatch"])}


def record(results: list) -> dict:
    """One refresh's answers in the layout of
    :meth:`~reference.dashboard.DashboardReference.panels`: float fields
    under ``floats``, counts under ``counts``."""
    s_corr, s_raw, by, fe, we = results
    floats, counts = {}, {}
    for name, s in (("series_corr", s_corr), ("series_raw", s_raw)):
        floats[name] = {"total_j": s.total_j, "power_w": s.power_w,
                        "sigma_independent_j": s.sigma_independent_j,
                        "sigma_worstcase_j": s.sigma_worstcase_j,
                        "coverage": s.coverage}
        counts[name] = {"n_covered": s.n_covered,
                        "n_quarantined": s.n_quarantined,
                        "n_power": s.n_power}
    floats["by_label"] = {lb: {"total_j": np.float64(d["total_j"]),
                               "mean_j": np.float64(d["mean_j"]),
                               "std_j": np.float64(d["std_j"])}
                          for lb, d in by.items()}
    counts["by_label"] = {lb: {"n_covered": np.int64(d["n_covered"]),
                               "n_quarantined": np.int64(d["n_quarantined"])}
                          for lb, d in by.items()}
    floats["fleet"] = {"total_j": np.float64(fe.total_j),
                       "sigma_independent_j": np.float64(
                           fe.sigma_independent_j),
                       "sigma_worstcase_j": np.float64(fe.sigma_worstcase_j),
                       "coverage": np.float64(fe.coverage)}
    counts["fleet"] = {"n_quarantined": np.int64(fe.n_quarantined),
                       "n_reporting": np.int64(fe.n_reporting)}
    floats["window_total_j"] = np.float64(np.sum(we))
    return {"floats": floats, "counts": counts}
