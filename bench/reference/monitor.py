"""Plain reference of the streaming monitor: the same operations on the
same slabs, written straight from the monitor's stated semantics, in
numpy and in one precision throughout.  It imports nothing of the
program.

``MonitorReference(cfg, dtype=np.float64)`` is the reference that
decides ``correct``; ``dtype=np.float32`` is the control, the reference
computed one precision below what the configuration states.

Semantics, slab by slab (``ingest``):

* ids outside ``[0, N)`` are rejected and counted; samples with a
  non-finite time or reading are dropped and counted as invalid;
* the rest are ordered by (device, time), keeping arrival order among
  equal keys; a repeat of the previous (device, time) in the slab, or of
  the device's newest stored time, is a duplicate; a time older than the
  newest stored one is late; both are dropped and counted per device;
* each device's accepted samples fold in order: the reading held since
  the previous sample (rectangle rule, hold capped by ``max_hold``) adds
  raw and corrected energy (``(v - offset) / gain``); window energy is
  the part of each hold that lies in ``[a, b]``, the corrected one on
  times moved back by the device's ``time_shift``; a reading that
  differs from the previous one is a change (``run_t`` is the time of
  the newest change, or of the first sample); readings outside the
  envelope count in ``n_out``; the drift EWMA takes one step per slab
  towards the slab's mean corrected reading;
* the ring keeps each device's newest ``slots`` samples with the running
  raw and corrected energy at each;
* the health machine (healthy, stale, quarantined) runs at the end of a
  slab that accepted something, at most every ``health_every_s`` of
  stream time, on the newest accepted time of that slab.
"""
from __future__ import annotations

import numpy as np

HEALTHY, STALE, QUARANTINED = 0, 1, 2


class MonitorReference:
    """The monitor's state over ``cfg['n_devices']`` devices, kept in
    ``dtype`` (see module doc)."""

    def __init__(self, dev: dict, mon: dict, dtype=np.float64,
                 subset=None):
        """``dev``: per-device arrays (``gain``, ``offset_w``,
        ``time_shift_s``, ``baseline_w``, ``win_a``, ``win_b``,
        ``max_hold``, ``env_lo``, ``env_hi``);
        ``mon``: the monitor's settings from the configuration.

        ``subset`` (device ids) folds energies, ring, changes, drift and
        health for those devices only; the counters and every device's
        newest time, which decide what is accepted and when health runs,
        are kept for all."""
        f = self.f = dtype
        n = self.n = len(dev["gain"])
        self.in_subset = np.ones(n, bool)
        if subset is not None:
            self.in_subset[:] = False
            self.in_subset[np.asarray(subset)] = True
        self.p = {k: np.asarray(v, dtype=f) for k, v in dev.items()
                  if k != "label"}
        self.slots = int(mon["ring_slots"])
        self.tau = f(mon["drift_tau_s"])
        self.drift_rel = f(mon["drift_rel"])
        self.drift_abs = f(mon["drift_abs_w"])
        self.silent_after = f(mon["silent_after_s"])
        self.policy = mon["health"]
        self.health_every = f(mon["health_every_s"])
        self.next_health = -np.inf
        z = lambda: np.zeros(n, dtype=f)             # noqa: E731
        i = lambda: np.zeros(n, dtype=np.int64)      # noqa: E731
        self.st = dict(last_t=z(), last_v=z(), has=np.zeros(n, bool),
                       first_t=z(), n_samples=i(), n_dup=i(), n_late=i(),
                       energy_j=z(), energy_corr_j=z(), win_j=z(),
                       win_corr_j=z(), run_t=z(), n_changes=i(),
                       ewma_w=z(), n_out=i())
        r = self.slots
        self.ring = dict(n_written=i(),
                         t=np.full((n, r), np.inf, dtype=f),
                         v=np.zeros((n, r), dtype=f),
                         e_raw=np.zeros((n, r), dtype=f),
                         e_corr=np.zeros((n, r), dtype=f))
        self.health = dict(code=np.zeros(n, np.int8),
                           clean_t=z(), clean=np.zeros(n, bool),
                           last_n_out=i())
        self.invalid = 0
        self.rejected = 0

    # -- ingest ----------------------------------------------------------
    def ingest(self, dev, t, v) -> None:
        f, st, p = self.f, self.st, self.p
        dev = np.asarray(dev, dtype=np.int64).ravel()
        t = np.asarray(t, dtype=f).ravel()
        v = np.asarray(v, dtype=f).ravel()
        ok = (dev >= 0) & (dev < self.n)
        self.rejected += int(np.sum(~ok))
        dev, t, v = dev[ok], t[ok], v[ok]
        if dev.size == 0:
            return
        ok = np.isfinite(t) & np.isfinite(v)
        self.invalid += int(np.sum(~ok))
        dev, t, v = dev[ok], t[ok], v[ok]
        step = np.diff(dev)
        if not (np.all(step >= 0)
                and np.all(t[1:][step == 0] >= t[:-1][step == 0])):
            order = np.lexsort((t, dev))    # stable: ties keep arrival
            dev, t, v = dev[order], t[order], v[order]

        dup = np.zeros(dev.size, bool)
        dup[1:] = (dev[1:] == dev[:-1]) & (t[1:] == t[:-1])
        had = st["has"][dev]
        late = ~dup & had & (t < st["last_t"][dev])
        dup |= ~dup & had & (t == st["last_t"][dev])
        np.add.at(st["n_dup"], dev[dup], 1)
        np.add.at(st["n_late"], dev[late], 1)
        keep = ~(dup | late)
        dev, t, v = dev[keep], t[keep], v[keep]
        if dev.size == 0:
            return
        sub = self.in_subset[dev]
        if sub.any():
            self._fold(dev[sub], t[sub], v[sub])
        if not sub.all():       # the rest only as far as counters need
            d, tt = dev[~sub], t[~sub]
            lastof = np.ones(d.size, bool)
            lastof[:-1] = d[1:] != d[:-1]
            firstof = np.ones(d.size, bool)
            firstof[1:] = d[1:] != d[:-1]
            new_d = firstof & ~st["has"][d]
            st["first_t"][d[new_d]] = tt[new_d]
            st["last_t"][d[lastof]] = tt[lastof]
            st["has"][d] = True
            np.add.at(st["n_samples"], d, 1)
        t_now = t.max()
        if t_now >= self.next_health:
            self.next_health = t_now + self.health_every
            self._health(t_now)

    def _fold(self, dev, t, v) -> None:
        """Fold accepted samples, ordered by (device, time)."""
        f, st, p = self.f, self.st, self.p
        v = v - p["baseline_w"][dev]

        # one row per device of this slab, its samples in order
        first = np.ones(dev.size, bool)
        first[1:] = dev[1:] != dev[:-1]
        start = np.flatnonzero(first)
        u = dev[start]
        row = np.cumsum(first) - 1
        col = np.arange(dev.size) - start[row]
        count = np.bincount(row)
        width = int(count.max())
        mask = np.zeros((u.size, width), bool)
        mask[row, col] = True

        def grid(x, fill=0):
            out = np.full((u.size, width), fill, dtype=np.asarray(x).dtype)
            out[row, col] = x
            return out

        T, V = grid(t), grid(v)
        had = st["has"][u]
        PT = np.concatenate([st["last_t"][u][:, None], T[:, :-1]], axis=1)
        PV = np.concatenate([st["last_v"][u][:, None], V[:, :-1]], axis=1)
        HAS = mask.copy()
        HAS[:, 0] = had

        g, off = p["gain"][u][:, None], p["offset_w"][u][:, None]
        VC = (V - off) / g
        PVC = (PV - off) / g
        hold = np.minimum(T - PT, p["max_hold"][u][:, None])
        inc = np.where(HAS, PV * hold, f(0))
        inc_c = np.where(HAS, PVC * hold, f(0))
        a, b = p["win_a"][u][:, None], p["win_b"][u][:, None]
        w = np.where(HAS & (PT >= a),
                     PV * np.maximum(np.minimum(PT + hold, b) - PT, f(0)),
                     f(0))
        pts = PT - p["time_shift_s"][u][:, None]
        w_c = np.where(HAS & (pts >= a),
                       PVC * np.maximum(np.minimum(pts + hold, b) - pts,
                                        f(0)), f(0))
        change = HAS & (V != PV)
        out = mask & ((VC < p["env_lo"][u][:, None])
                      | (VC > p["env_hi"][u][:, None]))
        cum_e = np.cumsum(inc, axis=1)
        cum_ec = np.cumsum(inc_c, axis=1)
        last = count - 1
        rows = np.arange(u.size)

        # ring: each device's newest ``slots`` samples of this slab
        if self.slots:
            rk = mask & (np.arange(width)[None, :] >= (count - self.slots)
                         [:, None])
            rr, cc = np.nonzero(rk)
            d = u[rr]
            slot = (self.ring["n_written"][d] + cc) % self.slots
            self.ring["t"][d, slot] = T[rr, cc]
            self.ring["v"][d, slot] = V[rr, cc]
            self.ring["e_raw"][d, slot] = st["energy_j"][d] + cum_e[rr, cc]
            self.ring["e_corr"][d, slot] = (st["energy_corr_j"][d]
                                            + cum_ec[rr, cc])
        self.ring["n_written"][u] += count

        chg_col = np.where(change, np.arange(width)[None, :], -1).max(axis=1)
        run_in = np.where(had, st["run_t"][u], T[:, 0])
        new_t = T[rows, last]
        old_last_t = st["last_t"][u]
        mean_vc = np.where(mask, VC, f(0)).sum(axis=1) / count.astype(f)
        st["first_t"][u] = np.where(had, st["first_t"][u], T[:, 0])
        st["last_t"][u] = new_t
        st["last_v"][u] = V[rows, last]
        st["has"][u] = True
        st["n_samples"][u] += count
        st["energy_j"][u] += cum_e[rows, last]
        st["energy_corr_j"][u] += cum_ec[rows, last]
        st["win_j"][u] += w.sum(axis=1)
        st["win_corr_j"][u] += w_c.sum(axis=1)
        st["run_t"][u] = np.where(chg_col >= 0,
                                  T[rows, np.maximum(chg_col, 0)], run_in)
        st["n_changes"][u] += change.sum(axis=1)
        st["n_out"][u] += out.sum(axis=1)
        alpha = np.exp(-np.maximum(new_t - old_last_t, f(0)) / self.tau)
        st["ewma_w"][u] = np.where(
            had, alpha * st["ewma_w"][u] + (f(1) - alpha) * mean_vc, mean_vc)

    # -- health ----------------------------------------------------------
    def _health(self, t_now) -> None:
        st, h, pol, f = self.st, self.health, self.policy, self.f
        silent = t_now - st["last_t"]
        stale = st["has"] & (silent > f(pol["stale_factor"])
                             * self.silent_after)
        dead = st["has"] & (silent > f(pol["quarantine_factor"])
                            * self.silent_after)
        anom = st["has"] & (st["n_out"] > h["last_n_out"])
        dur = st["last_t"] - st["first_t"]
        with np.errstate(invalid="ignore", divide="ignore"):
            mean_p = np.where(dur > 0, st["energy_corr_j"] / dur, np.nan)
        drift = (st["has"] & (dur > 2 * self.tau)
                 & (np.abs(st["ewma_w"] - mean_p)
                    > np.maximum(self.drift_rel * np.abs(mean_p),
                                 self.drift_abs)))
        drift = np.where(np.isfinite(mean_p), drift, False)
        bad = dead.copy()
        if pol["quarantine_anomalous"]:
            bad |= anom
        if pol["quarantine_drifting"]:
            bad |= drift
        clean = st["has"] & ~stale & ~anom & ~drift
        h["clean_t"] = np.where(clean & ~h["clean"], t_now, h["clean_t"])
        code = h["code"]
        new = code.copy()
        new[(code == HEALTHY) & stale & ~bad] = STALE
        new[bad] = QUARANTINED
        back = clean & ~bad & (
            (code == STALE)
            | ((code == QUARANTINED)
               & (t_now - h["clean_t"] >= f(pol["recover_after_s"]))))
        new[back] = HEALTHY
        h["code"] = new
        h["clean"] = clean
        h["last_n_out"] = st["n_out"].copy()

    # -- what the comparison reads -----------------------------------------
    def counters(self) -> dict:
        st, code = self.st, self.health["code"]
        return {"accepted": int(st["n_samples"].sum()),
                "duplicates": int(st["n_dup"].sum()),
                "late": int(st["n_late"].sum()),
                "invalid": self.invalid, "rejected": self.rejected,
                "devices_reporting": int(st["has"].sum()),
                "n_healthy": int(np.sum(code == HEALTHY)),
                "n_stale": int(np.sum(code == STALE)),
                "n_quarantined": int(np.sum(code == QUARANTINED))}

    def ring_sorted(self) -> dict:
        """The ring of each device, oldest sample first."""
        r, nw = self.slots, self.ring["n_written"]
        start = np.where(nw >= r, nw % r, 0)
        order = (start[:, None] + np.arange(r)[None, :]) % r
        return {k: np.take_along_axis(self.ring[k], order, axis=1)
                for k in ("t", "v", "e_raw", "e_corr")}

