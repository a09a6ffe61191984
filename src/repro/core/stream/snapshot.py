"""Immutable, epoch-tagged published views of the streaming monitor.

:class:`MonitorSnapshot` is the read side of the ingest/serve split: a
compact copy-on-write capture of everything queries need — the
:class:`~repro.core.stream.state.DeviceState` accumulators, the ring
buffer *pre-sorted* per device, the online period estimates, per-label
moments and ingestion counters — published at a slab boundary and never
mutated again (every captured array is marked read-only; writing to one
raises).  Readers therefore never touch mutable ingest state: a held
snapshot keeps answering bitwise-identically while ingestion races
ahead, and the :attr:`epoch` tag makes results cacheable by
``(query, epoch)``.

All query semantics live here (the façade
:class:`~repro.core.stream.monitor.MonitorService` delegates).  Query
edge contract, pinned by ``tests/test_serving.py``:

* ``energy_between(t0, t1)`` raises ``ValueError`` unless
  ``t0 <= t1`` (NaN endpoints included); ``t0 == t1`` is exact zero
  wherever covered.
* Instants beyond the ring horizon (older than the oldest retained
  sample of a reporting device) answer ``nan`` with ``covered=False``
  — never a silently-wrong number.
* ``by_label`` groups with no covered device report ``mean_j``/
  ``std_j`` of ``nan`` (and ``total_j`` 0.0) — including every group of
  a never-ingested monitor.

The batched entry points (:meth:`energy_at_batch`,
:meth:`window_energy_batch`) answer ``Q`` instants for all ``N``
devices as one array op — the substrate of the
:class:`~repro.serve.monitor_service.MonitorQueryService` executor —
and are elementwise-identical to the single-instant paths (the scalar
methods are the ``Q=1`` case of the same kernel).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import numpy as np

from repro.core.fleet_engine import StreamingMoments
from repro.core.stream.health import QUARANTINED, STALE
from repro.core.stream.state import DeviceState


@dataclasses.dataclass(frozen=True)
class FleetEnergy:
    """A fleet-energy query answer with uncertainty bounds.

    ``per_device_j`` is nan where ``covered`` is False (the query instant
    predates the device's ring-buffer coverage); totals and sigmas are
    over covered devices only.  Uncertainty follows the telemetry
    model: per-device sigma is the shunt tolerance of the energy
    (calibrated devices use the calibrated floor), aggregated both as
    independent (1/√N) and worst-case (correlated lot) bounds.

    Degraded-mode accounting (monitors with health tracking): devices
    quarantined by the health machine are excluded from ``total_j`` and
    the sigmas (their ``per_device_j`` rows remain visible), the sigma
    bounds are widened by the covered-but-excluded fraction
    (``× n_covered / n_included`` — the monitor's honest admission that
    it is extrapolating over silent/anomalous devices), and ``coverage``
    reports the included fraction of the fleet so a reader can tell a
    confident answer from a degraded one.  Without health tracking
    ``coverage`` is simply the covered fraction and ``n_quarantined``
    is 0.
    """

    t: Optional[float]
    corrected: bool
    per_device_j: np.ndarray
    covered: np.ndarray
    total_j: float
    n_reporting: int
    sigma_independent_j: float
    sigma_worstcase_j: float
    coverage: float = 1.0
    n_quarantined: int = 0


def _frozen(arr: np.ndarray) -> np.ndarray:
    out = arr.copy()
    out.setflags(write=False)
    return out


def _copy_moments(sm: StreamingMoments) -> StreamingMoments:
    out = StreamingMoments()
    out.n, out.mean, out.m2 = sm.n, sm.mean, sm.m2
    out.mean_abs, out.max_abs = sm.mean_abs, sm.max_abs
    return out


class MonitorSnapshot:
    """One immutable published view of a monitor (see module doc).

    Build with :meth:`publish`; the constructor is internal.
    """

    def __init__(self, *, epoch, n_devices, backend, be, state, ring_view,
                 ring_slots, period_est, moments, counters, corrections,
                 labels, win_a, win_b, max_hold, silent_after_s,
                 drift_tau_s, drift_rel, drift_abs_w, health_code=None):
        self.epoch = epoch
        self.n_devices = n_devices
        self.backend = backend
        self._be = be
        self.state = state
        self._ring_view = ring_view          # (t, v, e_raw, e_corr) or None
        self.ring_slots = ring_slots
        self._period_est = period_est
        self._moments = moments
        self._counters = counters
        self.corrections = corrections
        self.labels = labels
        self._win_a = win_a
        self._win_b = win_b
        self._max_hold = max_hold
        self.silent_after_s = silent_after_s
        self.drift_tau_s = drift_tau_s
        self.drift_rel = drift_rel
        self.drift_abs_w = drift_abs_w
        self._health_code = health_code      # [N] i1 codes or None
        self._flavor_cache: Dict[bool, tuple] = {}

    @classmethod
    def publish(cls, core) -> "MonitorSnapshot":
        """Capture a copy-on-write view of an
        :class:`~repro.core.stream.ingest.IngestCore` at its current
        epoch.  The ring is captured already sorted oldest→newest (one
        gather here instead of one per query)."""
        st = core.state
        state = DeviceState(**{
            f.name: _frozen(getattr(st, f.name))
            for f in dataclasses.fields(DeviceState)})
        ring_view = None
        if core.ring.slots:
            ring_view = tuple(_frozen(a) for a in core.ring.sorted_view())
        return cls(
            epoch=core.epoch, n_devices=core.n_devices,
            backend=core.backend, be=core._be, state=state,
            ring_view=ring_view, ring_slots=core.ring.slots,
            period_est=_frozen(core.periods.estimates()),
            moments={k: _copy_moments(v) for k, v in core._moments.items()},
            counters=dict(core.counters),
            corrections=core.corrections, labels=_frozen(core.labels),
            win_a=_frozen(core._win_a), win_b=_frozen(core._win_b),
            max_hold=_frozen(core._max_hold),
            silent_after_s=core.silent_after_s,
            drift_tau_s=core.drift_tau_s, drift_rel=core.drift_rel,
            drift_abs_w=core.drift_abs_w,
            health_code=(_frozen(core.health.code)
                         if core.health is not None else None))

    # -- batched kernels --------------------------------------------------
    def _flavor(self, corrected: bool):
        """Per-flavour (raw/corrected) tail + ring arrays for the
        snapshot-view kernel, computed once per snapshot."""
        if corrected not in self._flavor_cache:
            st, c = self.state, self.corrections
            if corrected:
                dens = (st.last_v - c.offset_w) / c.gain
                base = st.energy_corr_j
            else:
                dens = st.last_v
                base = st.energy_j
            if self._ring_view is not None:
                ts, vs, er, ec = self._ring_view
                if corrected:
                    ring_dens = (vs - c.offset_w[:, None]) / c.gain[:, None]
                    ring_base = ec
                else:
                    ring_dens, ring_base = vs, er
            else:
                ts = ring_dens = ring_base = None
            self._flavor_cache[corrected] = (dens, base, ts, ring_dens,
                                             ring_base)
        return self._flavor_cache[corrected]

    def energy_at_batch(self, tq: np.ndarray, corrected: bool = True
                        ) -> Tuple[np.ndarray, np.ndarray]:
        """Energy since first sample at instants ``tq`` [Q] for every
        device: ``(e, covered)`` [Q, N], nan where an instant predates
        ring coverage."""
        tq = np.asarray(tq, dtype=np.float64).ravel()
        st = self.state
        dens, base, ring_t, ring_dens, ring_base = self._flavor(corrected)
        return self._be.snapshot_energy_at(
            tq, st.last_t, dens, st.has, st.first_t, base, self._max_hold,
            ring_t, ring_dens, ring_base)

    def window_energy_batch(self, tq: np.ndarray, corrected: bool = True
                            ) -> np.ndarray:
        """Registered-window energy at instants ``tq`` [Q] → [Q, N]
        (same open-window semantics as :meth:`window_energy`)."""
        tq = np.asarray(tq, dtype=np.float64).ravel()
        st, c = self.state, self.corrections
        e = (st.win_corr_j if corrected else st.win_j)[None, :]
        shift = c.time_shift_s if corrected else 0.0
        t_rep = st.last_t - shift       # newest sample, reported time
        tqs = tq[:, None] - shift       # query instants, reported time
        dens = ((st.last_v - c.offset_w) / c.gain if corrected
                else st.last_v)
        lim = np.minimum(tqs, np.minimum(self._win_b,
                                         t_rep + self._max_hold)[None, :])
        tail = np.where(st.has[None, :] & (t_rep >= self._win_a)[None, :],
                        dens[None, :] * np.maximum(lim - t_rep[None, :],
                                                   0.0), 0.0)
        # accumulated-through-b is exact once the window closed; an
        # open window already streamed past tq is not reconstructible
        stale = (st.has[None, :] & (tqs < t_rep[None, :])
                 & (tqs < self._win_b[None, :]) & (tqs > self._win_a[None, :]))
        out = np.where(stale, np.nan, e + tail)
        # before the window opens the exact answer is 0, whatever has
        # accumulated since
        return np.where(st.has[None, :] & (tqs <= self._win_a[None, :]),
                        0.0, out)

    # -- result assembly (shared with the batched executor) ---------------
    @property
    def active_mask(self) -> Optional[np.ndarray]:
        """[N] bool, False where the health machine quarantined the
        device — or None when health tracking is off."""
        if self._health_code is None:
            return None
        return self._health_code != QUARANTINED

    def fleet_from_rows(self, t: Optional[float], corrected: bool,
                        e: np.ndarray, covered: np.ndarray) -> FleetEnergy:
        """Fold one [N] energy row into a :class:`FleetEnergy` (the
        reductions both the direct and the batched-executor paths use).
        See :class:`FleetEnergy` for the degraded-mode exclusion and
        sigma-widening semantics on health-tracked monitors."""
        from repro.core.telemetry import (CALIBRATED_TOLERANCE,
                                          SHUNT_TOLERANCE)
        tol = np.where(self.corrections.calibrated,
                       CALIBRATED_TOLERANCE, SHUNT_TOLERANCE)
        active = self.active_mask
        if active is None:
            include, n_q = covered, 0
        else:
            include = covered & active
            n_q = int(np.sum(covered & ~active))
        sig = np.where(include, tol * np.abs(np.nan_to_num(e)), 0.0)
        total = float(np.nansum(np.where(include, e, 0.0)))
        n_inc = int(np.sum(include))
        if n_q == 0:
            si = float(np.sqrt(np.sum(sig ** 2)))
            sw = float(np.sum(sig))
        elif n_inc:
            widen = (n_inc + n_q) / n_inc
            si = float(widen * np.sqrt(np.sum(sig ** 2)))
            sw = float(widen * np.sum(sig))
        else:               # every covered device quarantined: the
            si = sw = np.inf        # answer carries no information
        return FleetEnergy(
            t=t, corrected=corrected, per_device_j=e, covered=covered,
            total_j=total, n_reporting=int(np.sum(self.state.has)),
            sigma_independent_j=si, sigma_worstcase_j=sw,
            coverage=n_inc / self.n_devices, n_quarantined=n_q)

    @staticmethod
    def between_from_rows(e0, c0, e1, c1) -> Tuple[np.ndarray, np.ndarray]:
        covered = c0 & c1
        return np.where(covered, e1 - e0, np.nan), covered

    # -- queries ----------------------------------------------------------
    def fleet_energy(self, t: Optional[float] = None,
                     corrected: bool = True) -> FleetEnergy:
        """Running fleet energy at wall-clock ``t`` (default: each
        device's newest sample — no extrapolation), with the telemetry
        uncertainty bounds."""
        st = self.state
        if t is None:
            e = (st.energy_corr_j if corrected else st.energy_j).copy()
            covered = np.ones(self.n_devices, dtype=bool)
        else:
            em, cm = self.energy_at_batch(np.array([float(t)]), corrected)
            e, covered = em[0], cm[0]
        return self.fleet_from_rows(t, corrected, e, covered)

    def window_energy(self, t: Optional[float] = None,
                      corrected: bool = True) -> np.ndarray:
        """Per-device energy clipped to the registered §5 windows [N].

        With ``t`` given, devices whose window is still open get the live
        rectangle tail up to ``min(t, b)``; with ``t=None`` the
        accumulated value is returned as-is (exact once the stream has
        passed each window's end).  Window accumulation cannot be
        rewound: a query instant that a device's still-open window has
        already streamed past reports nan for that device rather than
        silently overstating."""
        st = self.state
        if t is None:
            return (st.win_corr_j if corrected else st.win_j).copy()
        return self.window_energy_batch(np.array([float(t)]), corrected)[0]

    def energy_between(self, t0: float, t1: float,
                       corrected: bool = True):
        """Windowed energy ``∫[t0, t1]`` per device from the ring buffer;
        returns ``(energy, covered)``.  Held-value semantics (the value
        at ``t0`` is the sample covering it); exact whenever both
        endpoints lie within ring coverage, nan otherwise.  Raises
        ``ValueError`` unless ``t0 <= t1`` (NaN endpoints included);
        ``t0 == t1`` is exactly zero wherever covered."""
        if not (t1 >= t0):
            raise ValueError(f"bad window [{t0}, {t1}]")
        em, cm = self.energy_at_batch(
            np.array([float(t0), float(t1)]), corrected)
        return self.between_from_rows(em[0], cm[0], em[1], cm[1])

    def by_label(self, t0: Optional[float] = None,
                 t1: Optional[float] = None,
                 corrected: bool = True) -> Dict[str, Dict[str, float]]:
        """Energy breakdown by workload label — over ``[t0, t1]`` (ring
        coverage permitting) or since stream start.  Each label reports
        its covered-device count, total energy and the Chan–Welford
        moments of the per-device energies; groups with no covered
        device (including every group of a never-ingested monitor)
        report nan moments.  On health-tracked monitors quarantined
        devices are excluded from every aggregate (and reported per
        label as ``n_quarantined``, 0 otherwise) — the per-label
        counterpart of :class:`FleetEnergy`'s degraded mode."""
        if (t0 is None) != (t1 is None):
            raise ValueError("pass both t0 and t1, or neither")
        st = self.state
        if t0 is None:
            e = (st.energy_corr_j if corrected else st.energy_j)
            covered = st.has.copy()
        else:
            e, covered = self.energy_between(t0, t1, corrected)
            covered = covered & st.has
        active = self.active_mask
        out: Dict[str, Dict[str, float]] = {}
        for label in np.unique(self.labels):
            sel = (self.labels == label) & covered
            n_q = 0
            if active is not None:
                n_q = int(np.sum(sel & ~active))
                sel = sel & active
            vals = e[sel]
            sm = StreamingMoments().update(vals, self._be)
            stats = sm.stats()
            n_cov = int(np.sum(sel))
            out[str(label)] = {
                "n_devices": int(np.sum(self.labels == label)),
                "n_covered": n_cov,
                "n_quarantined": n_q,
                "total_j": float(np.sum(vals)) if vals.size else 0.0,
                "mean_j": stats["mean_err"] if n_cov else float("nan"),
                "std_j": stats["std_err"] if n_cov else float("nan"),
            }
        return out

    def reading_stats(self) -> Dict[str, Dict[str, float]]:
        """Per-label corrected-reading moments accumulated at ingest
        (``StreamingMoments`` — mean/std/worst in watts)."""
        return {label: sm.stats()
                for label, sm in sorted(self._moments.items())}

    def update_period_s(self) -> np.ndarray:
        """[N] online update-period estimates (nan until a device has
        published ``min_runs`` complete runs)."""
        return self._period_est.copy()

    def flags(self, t: Optional[float] = None) -> Dict[str, np.ndarray]:
        """Per-device health flags at wall-clock ``t`` (default: the
        newest sample seen fleet-wide).

        * ``silent`` — no sample for longer than ``silent_after_s``
          (default 5× the device's update period — online estimate when
          converged, calibration reference otherwise);
        * ``anomalous`` — published readings outside the calibrated
          envelope;
        * ``drifting`` — the recent EWMA of corrected readings diverges
          from the device's lifetime mean corrected power;
        * ``reporting`` — has ever reported;
        * ``stale`` / ``quarantined`` — the health machine's current
          state codes (all-False on monitors without health tracking:
          the instantaneous flags above are always available, the
          stateful machine is opt-in).
        """
        st = self.state
        if t is None:
            t = float(np.max(st.last_t[st.has])) if np.any(st.has) else 0.0
        that = self._period_est
        ref = np.where(np.isfinite(that), that,
                       self.corrections.ref_period_s)
        after = (np.full(self.n_devices, float(self.silent_after_s))
                 if self.silent_after_s is not None else 5.0 * ref)
        silent = st.has & (t - st.last_t > after)
        dur = st.last_t - st.first_t
        with np.errstate(invalid="ignore", divide="ignore"):
            mean_p = np.where(dur > 0.0, st.energy_corr_j / dur, np.nan)
        dev = np.abs(st.ewma_w - mean_p)
        drifting = (st.has & (dur > 2.0 * self.drift_tau_s)
                    & (dev > np.maximum(self.drift_rel * np.abs(mean_p),
                                        self.drift_abs_w)))
        code = self._health_code
        return {
            "reporting": st.has.copy(),
            "silent": silent,
            "anomalous": st.n_out > 0,
            "drifting": np.where(np.isfinite(mean_p), drifting, False),
            "stale": (code == STALE if code is not None
                      else np.zeros(self.n_devices, dtype=bool)),
            "quarantined": (code == QUARANTINED if code is not None
                            else np.zeros(self.n_devices, dtype=bool)),
        }

    def health_summary(self) -> Dict[str, float]:
        """Fleet-level health digest: state-machine population counts
        plus the coverage fraction degraded-mode queries report.  On
        monitors without health tracking every device counts healthy
        and ``tracked`` is False."""
        st = self.state
        n = self.n_devices
        code = self._health_code
        n_stale = int(np.sum(code == STALE)) if code is not None else 0
        n_quar = int(np.sum(code == QUARANTINED)) if code is not None else 0
        return {
            "tracked": code is not None,
            "epoch": int(self.epoch),
            "n_devices": n,
            "n_reporting": int(np.sum(st.has)),
            "n_healthy": n - n_stale - n_quar,
            "n_stale": n_stale,
            "n_quarantined": n_quar,
            "coverage": (n - n_quar) / n,
        }

    @property
    def counters(self) -> Dict[str, int]:
        return dict(self._counters)
