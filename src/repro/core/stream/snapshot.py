"""Immutable, epoch-tagged published views of the streaming monitor.

:class:`MonitorSnapshot` is the read side of the ingest/serve split: a
compact copy-on-write capture of everything queries need — the
:class:`~repro.core.stream.state.DeviceState` accumulators, the ring
buffer (held by reference and sorted per device on first use; the
ingest core copies it before writing while a snapshot holds it), the
online period estimates, per-label moments and ingestion counters —
published at a slab boundary and never mutated again (every copied
array is marked read-only; writing to one raises).  Readers therefore
never touch mutable ingest state: a held
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
  — never a silently-wrong number.  With a history tier the same holds
  beyond the tier's horizon for boundary instants.
* ``by_label`` groups with no covered device report ``mean_j``/
  ``std_j`` of ``nan`` (and ``total_j`` 0.0) — including every group of
  a never-ingested monitor.

The batched entry points (:meth:`energy_at_batch`,
:meth:`window_energy_batch`) answer ``Q`` instants for all ``N``
devices as one array op — the substrate of the
:class:`~repro.serve.monitor_service.MonitorQueryService` executor —
and are elementwise-identical to the single-instant paths (the scalar
methods are the ``Q=1`` case of the same kernel).

On a monitor with a history tier
(:class:`~repro.core.stream.state.HistoryTier`) the snapshot holds the
tier's arrays by reference — publication copies none of them — and
every query answers an instant that is a tier boundary within the
tier's horizon from the tier (:meth:`on_tier`), any other instant from
the ring.
:meth:`fleet_series` reduces the tier over devices on the backend and
returns per-instant fleet numbers only; so does :meth:`by_label` between
two tier boundaries, per label.  The tier's kernels read one operand
record per flavour, packed and placed once per snapshot, and any number
of their calls run with one upload and one fetch (:meth:`run_tier`,
:meth:`prefetched`).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Dict, Optional, Tuple

import numpy as np

from repro.common.trace import span
from repro.core.engine_backend.pytrees import TIER_F64, TIER_I32
from repro.core.fleet_engine import StreamingMoments
from repro.core.stream.health import QUARANTINED, STALE
from repro.core.stream.state import (DeviceState, HistoryView,
                                     boundary_from)


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


@dataclasses.dataclass(frozen=True)
class FleetSeries:
    """A ``fleet_series`` answer: fleet energy at every step-aligned
    instant ``t`` [Q] of a range, and fleet power over each step [Q - 1].

    Per instant, the reductions of :class:`FleetEnergy`: ``total_j`` over
    included devices (covered and not quarantined), ``n_covered``,
    ``n_quarantined`` (covered but quarantined), ``coverage`` (included
    share of the fleet) and the two sigma bounds with the same
    degraded-mode widening.  Per step, ``power_w`` is the energy change
    over ``step_s`` of the devices included at both ends, ``n_power``
    how many they are.
    """

    t: np.ndarray
    step_s: float
    corrected: bool
    total_j: np.ndarray
    n_covered: np.ndarray
    n_quarantined: np.ndarray
    coverage: np.ndarray
    sigma_independent_j: np.ndarray
    sigma_worstcase_j: np.ndarray
    power_w: np.ndarray
    n_power: np.ndarray


def series_range(t0: float, t1: float, step_s: float) -> tuple:
    """Validate a series range: finite ``t0 <= t1`` and a finite,
    positive ``step_s``.  Returns them as floats."""
    t0, t1, step_s = float(t0), float(t1), float(step_s)
    if not (np.isfinite(t0) and np.isfinite(t1) and t1 >= t0):
        raise ValueError(f"bad series range [{t0}, {t1}]")
    if not (np.isfinite(step_s) and step_s > 0.0):
        raise ValueError(f"series step must be a positive number, "
                         f"got {step_s}")
    return t0, t1, step_s


def series_multiple(step_s: float, history_step_s: float) -> int:
    """How many history steps one series step spans; raises
    ``ValueError`` unless ``step_s`` is a whole multiple of
    ``history_step_s``."""
    m = int(round(step_s / history_step_s))
    if m < 1 or abs(m * history_step_s - step_s) > 1e-9 * step_s:
        raise ValueError(f"series step {step_s} is not a multiple of "
                         f"the history step {history_step_s}")
    return m


def _call_key(call: tuple) -> tuple:
    """What identifies a tier call's result on one snapshot: its kernel,
    flavour, instants and trailing arguments."""
    kind, corrected, tq, args = call
    return kind, corrected, tq.tobytes(), args


def _frozen(arr: np.ndarray) -> np.ndarray:
    out = arr.copy()
    out.setflags(write=False)
    return out


def _frozen_new(arr: np.ndarray) -> np.ndarray:
    """``arr``, a new array, made read-only."""
    arr.setflags(write=False)
    return arr


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
                 drift_tau_s, drift_rel, drift_abs_w, health_code=None,
                 history: Optional[HistoryView] = None, label_codes=None,
                 label_names=None):
        self.epoch = epoch
        self.n_devices = n_devices
        self.backend = backend
        self._be = be
        self.state = state
        self._ring_view = ring_view          # RingView or None
        self._ring_sorted: Optional[tuple] = None
        self.ring_slots = ring_slots
        self._period_est = period_est
        self._moments = moments
        self._counters = counters
        self.corrections = corrections
        self.labels = labels
        self._label_codes = label_codes      # [N] index into label_names
        self._label_names = label_names      # sorted distinct labels
        self._win_a = win_a
        self._win_b = win_b
        self._max_hold = max_hold
        self.silent_after_s = silent_after_s
        self.drift_tau_s = drift_tau_s
        self.drift_rel = drift_rel
        self.drift_abs_w = drift_abs_w
        self._health_code = health_code      # [N] i1 codes or None
        self._history = history
        self._flavor_cache: Dict[bool, tuple] = {}
        self._prefetch: Dict[tuple, tuple] = {}

    @classmethod
    def publish(cls, core) -> "MonitorSnapshot":
        """Capture a copy-on-write view of an
        :class:`~repro.core.stream.ingest.IngestCore` at its current
        epoch.  The ring and the history tier are held by reference, not
        copied (the ingest core copies them before a write while a
        snapshot still holds them); the ring is sorted oldest→newest on
        the first query that reads it."""
        with span("snapshot.publish"):
            return cls._publish(core)

    @classmethod
    def _publish(cls, core) -> "MonitorSnapshot":
        st = core.state
        state = DeviceState(**{
            f.name: _frozen(getattr(st, f.name))
            for f in dataclasses.fields(DeviceState)})
        ring_view = None
        if core.ring.slots:
            ring_view = core.ring.share()
        return cls(
            epoch=core.epoch, n_devices=core.n_devices,
            backend=core.backend, be=core._be, state=state,
            ring_view=ring_view, ring_slots=core.ring.slots,
            period_est=_frozen(core.periods.estimates()),
            moments={k: _copy_moments(v) for k, v in core._moments.items()},
            counters=dict(core.counters),
            corrections=core.corrections, labels=_frozen(core.labels),
            label_codes=_frozen(core._label_codes),
            label_names=list(core._label_names),
            win_a=_frozen(core._win_a), win_b=_frozen(core._win_b),
            max_hold=_frozen(core._max_hold),
            silent_after_s=core.silent_after_s,
            drift_tau_s=core.drift_tau_s, drift_rel=core.drift_rel,
            drift_abs_w=core.drift_abs_w,
            health_code=(_frozen(core.health.code)
                         if core.health is not None else None),
            history=(core.history.share() if core.history is not None
                     else None))

    # -- batched kernels --------------------------------------------------
    def _tail(self, corrected: bool):
        """Per-flavour (raw/corrected) density and running energy of
        each device's newest sample."""
        st, c = self.state, self.corrections
        if corrected:
            return (st.last_v - c.offset_w) / c.gain, st.energy_corr_j
        return st.last_v, st.energy_j

    def _flavor(self, corrected: bool):
        """Per-flavour (raw/corrected) tail + ring arrays for the
        snapshot-view kernel, computed once per snapshot."""
        if corrected not in self._flavor_cache:
            c = self.corrections
            dens, base = self._tail(corrected)
            if self._ring_view is not None:
                if self._ring_sorted is None:
                    self._ring_sorted = tuple(
                        _frozen_new(a) for a in self._ring_view.sorted_view())
                ts, vs, er, ec = self._ring_sorted
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

    def on_tier(self, tq: np.ndarray) -> np.ndarray:
        """[Q] bool: which instants the history tier answers (tier
        boundaries within its horizon); all False without a tier."""
        tq = np.asarray(tq, dtype=np.float64).ravel()
        if self._history is None:
            return np.zeros(tq.shape, dtype=bool)
        return self._history.boundary_of(tq)[1]

    @functools.cached_property
    def _tol(self) -> np.ndarray:
        """[N] per-device sigma tolerance of an energy."""
        from repro.core.telemetry import (CALIBRATED_TOLERANCE,
                                          SHUNT_TOLERANCE)
        return np.where(self.corrections.calibrated, CALIBRATED_TOLERANCE,
                        SHUNT_TOLERANCE)

    @functools.cached_property
    def _tier_ops(self) -> tuple:
        """The corrected and the raw operands of the tier kernels: every
        per-device operand packed into one float64 and one int32 host
        buffer (``TIER_F64``, ``TIER_I32``), placed by the backend once
        per snapshot."""
        st, n = self.state, self.n_devices
        f64 = np.empty((len(TIER_F64), n))
        i32 = np.empty((len(TIER_I32), n), dtype=np.int32)
        f = dict(zip(TIER_F64, f64))
        f["lo_t"][:], f["hi_t"][:] = self._history.held_times()
        f["last_t"][:], f["first_t"][:] = st.last_t, st.first_t
        f["max_hold"][:], f["tol"][:] = self._max_hold, self._tol
        f["dens_corr"][:], f["base_corr"][:] = self._tail(True)
        f["dens_raw"][:], f["base_raw"][:] = self._tail(False)
        active = self.active_mask
        i32[0], i32[2] = st.has, self._label_codes
        i32[1] = True if active is None else active
        return self._be.history_operands(f64, i32)

    def tier_call(self, kind: str, tq: np.ndarray, corrected: bool,
                  args: tuple = ()) -> tuple:
        """One call of the tier's ``kind`` kernel (``energy_at``,
        ``series`` or ``by_label``) at boundary instants ``tq``, for
        :meth:`run_tier`: ``(kind, corrected, tq, args)``, ``args`` the
        kernel's trailing arguments."""
        return kind, corrected, np.asarray(tq, dtype=np.float64), tuple(args)

    def run_tier(self, calls: list) -> list:
        """The host results of :meth:`tier_call` calls, in order: on the
        accelerated tiers one upload of their instants and one fetch of
        every result (the operands are placed on a snapshot's first
        call).  Calls :meth:`prefetched` already ran are not run
        again."""
        keys = [_call_key(c) for c in calls]
        h = self._history
        todo = [(kind, h.e_corr if corrected else h.e_raw,
                 self._tier_ops[0 if corrected else 1],
                 h.boundary_of(tq)[0] % h.slots, tq, args)
                for (kind, corrected, tq, args), k in zip(calls, keys)
                if k not in self._prefetch]
        fresh = iter(self._be.history_batch(todo) if todo else ())
        return [self._prefetch[k] if k in self._prefetch else next(fresh)
                for k in keys]

    @contextlib.contextmanager
    def prefetched(self, calls: list):
        """Run ``calls`` now, as one :meth:`run_tier`; inside the block
        the query methods that make any of them read its result instead
        of running it again.  A flush of the executor plans its tier
        calls, runs them here and answers every query through the
        direct path's methods, so the flush makes one upload and one
        fetch."""
        self._prefetch = dict(zip(map(_call_key, calls),
                                  self.run_tier(calls)))
        try:
            yield
        finally:
            self._prefetch = {}

    def transfers(self, n_tier_calls: int, n_ring_calls: int) -> tuple:
        """``(h2d, d2h)``: the host↔device buffers that ``n_tier_calls``
        tier calls in one :meth:`run_tier` and ``n_ring_calls`` ring
        energy-at calls move on this snapshot's backend."""
        moves = self._be.TRANSFERS
        steps = [moves["ring"]] * n_ring_calls
        if n_tier_calls:
            steps.append(moves["tier_batch"])
            if "_tier_ops" not in self.__dict__:
                steps.append(moves["tier_operands"])
        return (sum(h for h, _ in steps), sum(d for _, d in steps))

    def energy_at_batch(self, tq: np.ndarray, corrected: bool = True
                        ) -> Tuple[np.ndarray, np.ndarray]:
        """Energy since first sample at instants ``tq`` [Q] for every
        device: ``(e, covered)`` [Q, N], nan where an instant predates
        coverage: the history tier's for instants it answers
        (:meth:`on_tier`), the ring's for the others."""
        tq = np.asarray(tq, dtype=np.float64).ravel()
        on = self.on_tier(tq)
        if not on.any():
            return self._ring_energy_at(tq, corrected)
        (tier,) = self.run_tier([self.tier_call("energy_at", tq[on],
                                                corrected)])
        if on.all():
            return tier
        e = np.empty((tq.size, self.n_devices))
        covered = np.empty((tq.size, self.n_devices), dtype=bool)
        e[on], covered[on] = tier
        e[~on], covered[~on] = self._ring_energy_at(tq[~on], corrected)
        return e, covered

    def _ring_energy_at(self, tq: np.ndarray, corrected: bool):
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
        tol = self._tol
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

    def series_instants(self, t0: float, t1: float,
                        step_s: float) -> np.ndarray:
        """The instants of :meth:`fleet_series`: the multiples of
        ``step_s`` in ``[t0, t1]``, each formed as a boundary of the
        history tier.  Raises ``ValueError`` without a tier, for a bad
        range (:func:`series_range`), or where ``step_s`` is not a whole
        multiple of the tier's step."""
        t0, t1, step_s = series_range(t0, t1, step_s)
        h = self._history
        if h is None:
            raise ValueError("fleet_series needs a history tier "
                             "(history_steps > 0)")
        m = series_multiple(step_s, h.step_s)
        b_lo = int(boundary_from(t0, h.step_s))
        b_hi = int(boundary_from(t1, h.step_s))
        if b_hi * h.step_s > t1:
            b_hi -= 1
        return np.arange(-(-b_lo // m), b_hi // m + 1) * m * h.step_s

    def fleet_series(self, t0: float, t1: float, step_s: float,
                     corrected: bool = True) -> FleetSeries:
        """Fleet energy at every multiple of ``step_s`` in ``[t0, t1]``
        and fleet power over each step, from the history tier: the
        reductions of :meth:`fleet_from_rows` and
        :meth:`between_from_rows` (quarantined devices excluded, sigmas
        widened), done over devices by the backend, so only [Q] numbers
        reach the host.  Instants older than the tier's horizon leave
        devices that had reported by then not covered."""
        tq = self.series_instants(t0, t1, step_s)
        with span("serve.series", instants=tq.size, devices=self.n_devices):
            (raw,) = self.run_tier([self.series_call(tq, step_s,
                                                     corrected)])
        total, n_cov, n_inc, s2, s1, power, n_pow = raw
        n_cov, n_inc = n_cov.astype(np.int64), n_inc.astype(np.int64)
        n_q = n_cov - n_inc
        with np.errstate(divide="ignore", invalid="ignore"):
            widen = n_cov / n_inc
            si = np.where(n_q == 0, np.sqrt(s2),
                          np.where(n_inc > 0, widen * np.sqrt(s2), np.inf))
            sw = np.where(n_q == 0, s1,
                          np.where(n_inc > 0, widen * s1, np.inf))
        out = dict(t=tq, total_j=total, n_covered=n_cov, n_quarantined=n_q,
                   coverage=n_inc / self.n_devices, sigma_independent_j=si,
                   sigma_worstcase_j=sw, power_w=power,
                   n_power=n_pow.astype(np.int64))
        return FleetSeries(step_s=float(step_s), corrected=corrected,
                           **{k: _frozen(np.asarray(v))
                              for k, v in out.items()})

    def series_call(self, tq: np.ndarray, step_s: float,
                    corrected: bool) -> tuple:
        """The tier call of a series at instants ``tq``
        (:meth:`series_instants`)."""
        return self.tier_call("series", tq, corrected, (float(step_s),))

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
        counterpart of :class:`FleetEnergy`'s degraded mode.  Between
        two tier boundaries the backend reduces the energy per label
        (:meth:`label_call`); otherwise the host groups the rows."""
        if (t0 is None) != (t1 is None):
            raise ValueError("pass both t0 and t1, or neither")
        st = self.state
        if t0 is None:
            e = (st.energy_corr_j if corrected else st.energy_j)
            return self.by_label_rows(e, st.has.copy())
        if not (t1 >= t0):
            raise ValueError(f"bad window [{t0}, {t1}]")
        call = self.label_call(t0, t1, corrected)
        if call is not None:
            return self.labels_from(self.run_tier([call])[0])
        e, covered = self.energy_between(t0, t1, corrected)
        return self.by_label_rows(e, covered & st.has)

    def label_call(self, t0: float, t1: float,
                   corrected: bool) -> Optional[tuple]:
        """The tier call that reduces ``by_label(t0, t1)`` on the backend,
        or None where an instant is not on the tier (:meth:`on_tier`):
        the host path then groups the energy rows."""
        tq = np.array([float(t0), float(t1)])
        if not self.on_tier(tq).all():
            return None
        return self.tier_call("by_label", tq, corrected,
                              (len(self._label_names),))

    @functools.cached_property
    def _label_counts(self) -> np.ndarray:
        """[L] devices per label."""
        return np.bincount(self._label_codes,
                           minlength=len(self._label_names))

    def labels_from(self, raw: tuple) -> Dict[str, Dict[str, float]]:
        """The :meth:`by_label` answer from the by-label kernel's [L]
        results (see the backends' ``history_by_label``)."""
        n_cov, n_q, total, mean, m2 = raw[:5]
        out: Dict[str, Dict[str, float]] = {}
        for ci, label in enumerate(self._label_names):
            n = int(n_cov[ci])
            out[label] = {
                "n_devices": int(self._label_counts[ci]),
                "n_covered": n,
                "n_quarantined": int(n_q[ci]),
                "total_j": float(total[ci]) if n else 0.0,
                "mean_j": float(mean[ci]) if n else float("nan"),
                "std_j": (float(np.sqrt(max(m2[ci] / n, 0.0))) if n
                          else float("nan")),
            }
        return out

    def by_label_rows(self, e: np.ndarray,
                      covered: np.ndarray) -> Dict[str, Dict[str, float]]:
        """The by-label grouping of one [N] energy row on the host (the
        reductions :meth:`by_label` and the batched executor share where
        the tier does not answer), over the integer label codes the
        ingest core keeps."""
        active = self.active_mask
        codes = self._label_codes
        out: Dict[str, Dict[str, float]] = {}
        for ci, label in enumerate(self._label_names):
            sel = (codes == ci) & covered
            n_q = 0
            if active is not None:
                n_q = int(np.sum(sel & ~active))
                sel = sel & active
            vals = e[sel]
            sm = StreamingMoments().update(vals, self._be)
            stats = sm.stats()
            n_cov = int(np.sum(sel))
            out[label] = {
                "n_devices": int(self._label_counts[ci]),
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
