"""Plain reference of the dashboard cell: the monitor's fold of clean
rectangular slabs, its history tier, and the panels a dashboard refresh
asks for, in numpy and in one precision throughout.  It imports nothing
of the program.

``DashboardReference(dev, mon, dtype)`` extends
:class:`~reference.monitor.MonitorReference` (state, ring, health and
counters as stated there) with:

* :meth:`ingest_grid`: the fold of one clean rectangular slab, ``D``
  distinct ascending devices sharing ``M`` strictly increasing times,
  every reading finite and every time past the device's newest one.  It
  is :meth:`MonitorReference.ingest` on the flattened slab, written on
  the ``[D, M]`` block directly (other slabs raise: the cell sends
  none);
* the history tier: when a slab takes a device's newest sample past
  boundary ``B = b · step`` (``B`` before the newest sample, not before
  the device's first), the device's running raw and corrected energy at
  ``B`` is recorded: the energy at the last sample ``j`` with
  ``t[j] <= B`` plus its density times ``min(B - t[j], max_hold)``.
  Each device keeps its newest ``steps + 1`` boundaries;
* :meth:`panels`: what one refresh answers: energy at each boundary of
  a range (the tier between a device's first and newest sample, the
  held newest sample from it on, 0 up to the first), the fleet totals,
  sigmas and power over devices covered and not quarantined, energy per
  label between two instants, and the stat panels (fleet energy and
  window energy since the start).
"""
from __future__ import annotations

import numpy as np

from reference.monitor import QUARANTINED, MonitorReference

# per-device sigma of an energy: the telemetry model's shunt tolerance,
# and the floor of a calibrated device
SHUNT_TOLERANCE = 0.05
CALIBRATED_TOLERANCE = 0.01
_NONE = np.iinfo(np.int64).min // 4
# devices per block of the fold, boundary instants per block of a series
_BLOCK = 2048
_ROWS = 16


class DashboardReference(MonitorReference):
    """The monitor with a history tier over every device (see module
    doc)."""

    def __init__(self, dev: dict, mon: dict, dtype=np.float64):
        super().__init__(dev, mon, dtype)
        self.step = self.f(mon["history_step_s"])
        self.steps = int(mon["history_steps"])
        slots = self.steps + 1
        self.labels = np.asarray(dev["label"])
        self.calibrated = np.asarray(dev["calibrated"], bool)
        self.tier = {"e_raw": np.zeros((slots, self.n), self.f),
                     "e_corr": np.zeros((slots, self.n), self.f),
                     "b_first": np.full(self.n, _NONE, np.int64),
                     "b_last": np.full(self.n, _NONE, np.int64)}

    # -- boundaries --------------------------------------------------------
    def _at(self, b):
        """The instant of boundary ``b``."""
        return np.asarray(b).astype(self.f) * self.step

    def _before(self, t):
        """Largest ``b`` with ``b · step < t``."""
        t = np.asarray(t, self.f)
        b = np.ceil(t / self.step).astype(np.int64) - 1
        b = np.where(self._at(b + 1) < t, b + 1, b)
        return np.where(self._at(b) >= t, b - 1, b)

    # -- ingest ------------------------------------------------------------
    def ingest_grid(self, dev, ts, vals) -> None:
        f, st = self.f, self.st
        dev = np.asarray(dev, np.int64)
        ts = np.asarray(ts, f)
        vals = np.asarray(vals, f)
        d, m = vals.shape
        if not (d and m and dev.min() >= 0 and dev.max() < self.n
                and np.all(np.diff(dev) > 0) and np.all(np.diff(ts) > 0)
                and np.all(np.isfinite(ts)) and np.all(np.isfinite(vals))
                and not np.any(st["has"][dev]
                               & (ts[0] <= st["last_t"][dev]))):
            raise ValueError("the dashboard reference folds clean "
                             "rectangular slabs only")
        # devices fold independently: blocks of rows keep the [D, M]
        # temporaries in cache; health runs once, after the slab
        for k in range(0, d, _BLOCK):
            self._fold_rows(dev[k:k + _BLOCK], ts, vals[k:k + _BLOCK])
        if ts[-1] >= self.next_health:
            self.next_health = ts[-1] + self.health_every
            self._health(ts[-1])

    def _fold_rows(self, dev, ts, vals) -> None:
        f, st, p = self.f, self.st, self.p
        d, m = vals.shape
        V = vals - p["baseline_w"][dev][:, None]
        had = st["has"][dev]
        PT = np.empty((d, m), f)
        PT[:, 0] = st["last_t"][dev]
        PT[:, 1:] = ts[:-1]
        PV = np.concatenate([st["last_v"][dev][:, None], V[:, :-1]], axis=1)
        HAS = np.ones((d, m), bool)
        HAS[:, 0] = had
        g, off = p["gain"][dev][:, None], p["offset_w"][dev][:, None]
        VC = (V - off) / g
        PVC = (PV - off) / g
        hold = np.minimum(ts[None, :] - PT, p["max_hold"][dev][:, None])
        inc = np.where(HAS, PV * hold, f(0))
        inc_c = np.where(HAS, PVC * hold, f(0))
        a, b = p["win_a"][dev][:, None], p["win_b"][dev][:, None]
        w = np.where(HAS & (PT >= a),
                     PV * np.maximum(np.minimum(PT + hold, b) - PT, f(0)),
                     f(0))
        pts = PT - p["time_shift_s"][dev][:, None]
        w_c = np.where(HAS & (pts >= a),
                       PVC * np.maximum(np.minimum(pts + hold, b) - pts,
                                        f(0)), f(0))
        change = HAS & (V != PV)
        out = ((VC < p["env_lo"][dev][:, None])
               | (VC > p["env_hi"][dev][:, None]))
        cum_e = np.cumsum(inc, axis=1)
        cum_ec = np.cumsum(inc_c, axis=1)

        if self.slots:
            cc = np.arange(max(m - self.slots, 0), m)
            slot = (self.ring["n_written"][dev][:, None] + cc[None, :]) \
                % self.slots
            rows = dev[:, None]
            self.ring["t"][rows, slot] = ts[cc][None, :]
            self.ring["v"][rows, slot] = V[:, cc]
            self.ring["e_raw"][rows, slot] = (st["energy_j"][dev][:, None]
                                              + cum_e[:, cc])
            self.ring["e_corr"][rows, slot] = (
                st["energy_corr_j"][dev][:, None] + cum_ec[:, cc])
        self.ring["n_written"][dev] += m

        self._record_boundaries(dev, had, ts, V, cum_e, cum_ec)

        chg_col = np.where(change, np.arange(m)[None, :], -1).max(axis=1)
        run_in = np.where(had, st["run_t"][dev], ts[0])
        old_last_t = st["last_t"][dev]
        mean_vc = VC.sum(axis=1) / f(m)
        st["first_t"][dev] = np.where(had, st["first_t"][dev], ts[0])
        st["last_t"][dev] = ts[-1]
        st["last_v"][dev] = V[:, -1]
        st["has"][dev] = True
        st["n_samples"][dev] += m
        st["energy_j"][dev] += cum_e[:, -1]
        st["energy_corr_j"][dev] += cum_ec[:, -1]
        st["win_j"][dev] += w.sum(axis=1)
        st["win_corr_j"][dev] += w_c.sum(axis=1)
        st["run_t"][dev] = np.where(chg_col >= 0,
                                    ts[np.maximum(chg_col, 0)], run_in)
        st["n_changes"][dev] += change.sum(axis=1)
        st["n_out"][dev] += out.sum(axis=1)
        alpha = np.exp(-np.maximum(ts[-1] - old_last_t, f(0)) / self.tau)
        st["ewma_w"][dev] = np.where(
            had, alpha * st["ewma_w"][dev] + (f(1) - alpha) * mean_vc,
            mean_vc)

    def _record_boundaries(self, dev, had, ts, V, cum_e, cum_ec) -> None:
        """Tier entries for the boundaries this slab takes each device
        past, from the state before the slab is folded."""
        f, st, p, tier = self.f, self.st, self.p, self.tier
        first_t = np.where(had, st["first_t"][dev], ts[0])
        hi = self._before(ts[-1])
        last = tier["b_last"][dev]
        lo = np.where(last == _NONE, self._before(first_t) + 1, last + 1)
        lo = np.maximum(lo, hi - self.steps)
        n = np.maximum(hi - lo + 1, 0)
        row = np.repeat(np.arange(dev.size), n)
        if not row.size:
            return
        b = lo[row] + (np.arange(row.size) - np.repeat(np.cumsum(n) - n, n))
        B = self._at(b)
        j = np.searchsorted(ts, B, side="right") - 1
        inside = j >= 0
        jc = np.maximum(j, 0)
        d = dev[row]
        e_raw = np.where(inside, st["energy_j"][d] + cum_e[row, jc],
                         st["energy_j"][d])
        e_corr = np.where(inside, st["energy_corr_j"][d] + cum_ec[row, jc],
                          st["energy_corr_j"][d])
        t_j = np.where(inside, ts[jc], st["last_t"][d])
        v_j = np.where(inside, V[row, jc], st["last_v"][d])
        hold = np.minimum(B - t_j, p["max_hold"][d])
        vc_j = (v_j - p["offset_w"][d]) / p["gain"][d]
        slot = b % (self.steps + 1)
        tier["e_raw"][slot, d] = e_raw + v_j * hold
        tier["e_corr"][slot, d] = e_corr + vc_j * hold
        fresh = (last == _NONE) & (n > 0)
        tier["b_first"][dev[fresh]] = lo[fresh]
        tier["b_last"][dev[n > 0]] = hi

    # -- what the comparison reads -----------------------------------------
    def tier_view(self) -> dict:
        return tier_view(self.tier, self.steps)

    def _energy_at(self, b: np.ndarray, corrected: bool):
        """``(e, covered)`` [Q, N] at boundary instants ``b · step``."""
        f, st, p, tier = self.f, self.st, self.p, self.tier
        tq = self._at(b)[:, None]
        if corrected:
            dens = (st["last_v"] - p["offset_w"]) / p["gain"]
            base, arr = st["energy_corr_j"], tier["e_corr"]
        else:
            dens, base, arr = st["last_v"], st["energy_j"], tier["e_raw"]
        has = st["has"][None, :]
        dt = tq - st["last_t"][None, :]
        live = has & (dt >= 0)
        e = np.where(live, base[None, :]
                     + dens[None, :] * np.minimum(dt, p["max_hold"][None, :]),
                     f(0))
        covered = live | ~has | (tq <= st["first_t"][None, :])
        started = has & (tq > st["first_t"][None, :])
        e = np.where(started, e, f(0))
        lo = np.maximum(tier["b_first"], tier["b_last"] - self.steps)
        written = tier["b_last"] != _NONE
        bq = b[:, None]
        held = (started & (tq < st["last_t"][None, :]) & written[None, :]
                & (bq >= lo[None, :]) & (bq <= tier["b_last"][None, :]))
        e = np.where(held, arr[b % (self.steps + 1)], e)
        covered = covered | held
        return np.where(covered, e, f(np.nan)), covered

    def _totals(self, e, covered, active):
        """Per row: included energy, counts and sigmas (quarantined
        devices left out, sigmas widened by covered over included)."""
        f = self.f
        inc = covered & active[None, :]
        e0 = np.where(inc, e, f(0))
        tol = np.where(self.calibrated, f(CALIBRATED_TOLERANCE),
                       f(SHUNT_TOLERANCE)).astype(f)
        sig = tol[None, :] * np.abs(e0)
        n_cov = covered.sum(axis=1)
        n_inc = inc.sum(axis=1)
        s2, s1 = np.sqrt((sig * sig).sum(axis=1)), sig.sum(axis=1)
        with np.errstate(divide="ignore", invalid="ignore"):
            widen = (n_cov / n_inc).astype(f)
            si = np.where(n_cov == n_inc, s2,
                          np.where(n_inc > 0, widen * s2, np.inf))
            sw = np.where(n_cov == n_inc, s1,
                          np.where(n_inc > 0, widen * s1, np.inf))
        floats = {"total_j": e0.sum(axis=1), "sigma_independent_j": si,
                  "sigma_worstcase_j": sw, "coverage": n_inc / self.n}
        return floats, {"n_covered": n_cov, "n_quarantined": n_cov - n_inc}

    def series(self, t0: float, t1: float, corrected: bool, active):
        """A ``fleet_series`` over ``[t0, t1]`` at the tier's step, in
        blocks of boundaries (the power of a block's first step reads the
        previous block's last row)."""
        f = self.f
        b = np.arange(self._before(t0) + 1, self._before(t1) + 2)
        b = b[self._at(b) <= f(t1)]
        rows, power, n_power, prev = [], [], [], None
        for k in range(0, b.size, _ROWS):
            e, c = self._energy_at(b[k:k + _ROWS], corrected)
            rows.append(self._totals(e, c, active))
            inc = c & active[None, :]
            if prev is not None:
                e2, inc2 = np.vstack([prev[0], e]), np.vstack([prev[1], inc])
            else:
                e2, inc2 = e, inc
            both = inc2[1:] & inc2[:-1]
            power.append(np.where(both, e2[1:] - e2[:-1], f(0)).sum(axis=1)
                         / self.step)
            n_power.append(both.sum(axis=1))
            prev = (e[-1:], inc[-1:])
        floats = {k: np.concatenate([r[0][k] for r in rows])
                  for k in rows[0][0]}
        counts = {k: np.concatenate([r[1][k] for r in rows])
                  for k in rows[0][1]}
        floats["power_w"] = np.concatenate(power)
        counts["n_power"] = np.concatenate(n_power)
        return floats, counts

    def panels(self, end: float, range_s: float, label_s: float) -> dict:
        """One refresh's answers (see module doc), in the layout the
        driver records the program's in."""
        f, st = self.f, self.st
        active = self.health["code"] != QUARANTINED
        out = {"floats": {}, "counts": {}}
        for name, corrected in (("series_corr", True), ("series_raw", False)):
            fl, ct = self.series(end - range_s, end, corrected, active)
            out["floats"][name], out["counts"][name] = fl, ct
        b = np.array([self._before(end - label_s) + 1,
                      self._before(end) + 1])
        e, c = self._energy_at(b, True)
        de = e[1] - e[0]
        cov = c[0] & c[1] & st["has"]
        fl, ct = {}, {}
        for label in np.unique(self.labels):
            sel = (self.labels == label) & cov
            n_q = int(np.sum(sel & ~active))
            vals = de[sel & active]
            n = vals.size
            mean = vals.mean() if n else f(np.nan)
            fl[str(label)] = {
                "total_j": vals.sum() if n else f(0),
                "mean_j": mean,
                "std_j": (np.sqrt(np.mean((vals - mean) ** 2)) if n
                          else f(np.nan))}
            ct[str(label)] = {"n_covered": np.int64(n),
                              "n_quarantined": np.int64(n_q)}
        out["floats"]["by_label"], out["counts"]["by_label"] = fl, ct
        e = st["energy_corr_j"][None, :]
        fl, ct = self._totals(e, np.ones_like(e, bool), active)
        out["floats"]["fleet"] = {k: v[0] for k, v in fl.items()}
        out["counts"]["fleet"] = {"n_quarantined": ct["n_quarantined"][0],
                                  "n_reporting": np.int64(st["has"].sum())}
        out["floats"]["window_total_j"] = st["win_corr_j"].sum()
        return out


def tier_view(tier: dict, steps: int) -> dict:
    """A tier's ``[slots, N]`` arrays with every entry that holds no
    covered boundary set to nan: slot ``s`` of a device holds the
    boundary ``b = b_last - ((b_last - s) mod slots)``, covered where
    ``b >= b_first``."""
    slots = steps + 1
    last, first = tier["b_last"][None, :], tier["b_first"][None, :]
    s = np.arange(slots)[:, None]
    b = last - (last - s) % slots
    ok = (last != _NONE) & (b >= first)
    return {k: np.where(ok, np.asarray(tier[k], np.float64), np.nan)
            for k in ("e_raw", "e_corr")}
