"""Online estimators and correction parameters for the streaming monitor.

:class:`OnlinePeriodEstimator` is the streaming counterpart of
:func:`repro.core.microbench.estimate_update_period`: the offline
estimator takes the median of *complete* run durations (runs of
identical consecutive readings bounded by a change on both sides) over a
finished capture; here the same complete runs arrive one at a time —
extracted by the ingest kernel with the same first/last-run-dropped rule
(see :func:`repro.core.microbench.complete_run_durations`) — and fold
into a per-device log-spaced duration histogram.  The estimate is the
mean duration inside the median bin: with run durations concentrated at
the true update period (reading noise breaks value ties, so nearly every
sensor tick is a change) this converges to the offline median as runs
accumulate, at O(bins) memory per device instead of O(runs).

:class:`StreamCorrections` stacks the paper's §5 per-device correction
parameters — calibrated gain/offset inversion, the boxcar-window
re-synchronisation shift, a host-baseline debit for module-scope
sensors — as [N] arrays consumed directly by the ingest kernel.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Sequence

import numpy as np

from repro.core.calibrate import CalibrationRecord


class OnlinePeriodEstimator:
    """Per-device streaming update-period estimate from complete runs."""

    def __init__(self, n_devices: int, lo_s: float = 1e-3,
                 hi_s: float = 100.0, n_bins: int = 24,
                 min_runs: int = 3):
        if not (0.0 < lo_s < hi_s):
            raise ValueError(f"bad histogram range [{lo_s}, {hi_s}]")
        if n_bins < 2:
            raise ValueError("need at least two histogram bins")
        self.min_runs = int(min_runs)
        # interior edges: bin 0 catches everything below lo_s, the last
        # bin everything above hi_s, so no run is ever dropped
        self.edges = np.geomspace(lo_s, hi_s, n_bins - 1)
        self.counts = np.zeros((n_devices, n_bins), dtype=np.int64)
        self.sums = np.zeros((n_devices, n_bins))
        # (counts, sums, min_runs, estimates, stale rows) of the last
        # estimates() call: record() marks the rows it changes stale
        self._est = None

    @property
    def n_bins(self) -> int:
        return self.counts.shape[1]

    def nbytes(self) -> int:
        from repro.core.stream import schema
        return schema.registry_nbytes(self, schema.PERIOD_FIELDS,
                                      "OnlinePeriodEstimator")

    def record(self, dev: np.ndarray, durations: np.ndarray) -> None:
        """Fold one slab's completed runs (device ids + durations)."""
        if len(dev) == 0:
            return
        b = np.searchsorted(self.edges, durations, side="right")
        np.add.at(self.counts, (dev, b), 1)
        np.add.at(self.sums, (dev, b), durations)
        c = self._est
        if c is not None and c[0] is self.counts:   # not since a grow
            c[4][dev] = True

    @property
    def n_runs(self) -> np.ndarray:
        return self.counts.sum(axis=1)

    def estimates(self) -> np.ndarray:
        """[N] update-period estimates; nan below ``min_runs`` complete
        runs (the offline estimator's guard against phase-biased
        short captures).  Only the rows :meth:`record` changed since
        the last call are computed again, unless they are most rows: one
        pass over every row is then cheaper than gathering them."""
        c = self._est
        if (c is None or c[0] is not self.counts or c[1] is not self.sums
                or c[2] != self.min_runs
                or 2 * np.count_nonzero(c[4]) > c[4].size):
            est = self._estimate(self.counts, self.sums)
            stale = np.zeros(self.counts.shape[0], dtype=bool)
            self._est = (self.counts, self.sums, self.min_runs, est, stale)
        else:
            est, stale = c[3], c[4]
            rows = np.flatnonzero(stale)
            if rows.size:
                est[rows] = self._estimate(self.counts[rows],
                                           self.sums[rows])
                stale[rows] = False
        return est.copy()

    def _estimate(self, counts: np.ndarray, sums: np.ndarray) -> np.ndarray:
        n = counts.sum(axis=1)
        cum = np.cumsum(counts, axis=1)
        need = (n + 1) // 2
        bstar = np.argmax(cum >= need[:, None], axis=1)
        rows = np.arange(counts.shape[0])
        cnt = counts[rows, bstar]
        est = sums[rows, bstar] / np.maximum(cnt, 1)
        return np.where((n >= self.min_runs) & (cnt > 0), est, np.nan)


@dataclasses.dataclass(frozen=True)
class StreamCorrections:
    """Per-device §5 correction parameters as stacked arrays.

    ``gain``/``offset_w`` invert the calibrated steady-state transform
    (``corrected = (reading - offset) / gain``); ``time_shift_s``
    re-synchronises reported timestamps with device activity (a reading
    at ``t`` covers ``[t - W, t]``); ``baseline_w`` is debited from every
    raw reading before anything else (module-scope sensors, §6);
    ``ref_period_s`` is the calibration's update period, the fallback
    reference when the online estimate has not converged yet;
    ``calibrated`` marks devices with a gain-calibrated record (their
    energy uncertainty uses the calibrated tolerance).
    """

    gain: np.ndarray
    offset_w: np.ndarray
    time_shift_s: np.ndarray
    baseline_w: np.ndarray
    ref_period_s: np.ndarray
    calibrated: np.ndarray

    def __post_init__(self):
        n = self.gain.shape[0]
        for fld in dataclasses.fields(self):
            a = getattr(self, fld.name)
            if a.shape != (n,):
                raise ValueError(f"{fld.name} must be [{n}], got {a.shape}")
        if np.any(self.gain == 0.0):
            raise ValueError("correction gain must be non-zero")

    @property
    def n_devices(self) -> int:
        return self.gain.shape[0]

    @classmethod
    def identity(cls, n: int,
                 baseline_w: float | np.ndarray = 0.0,
                 ref_period_s: float = 0.1) -> "StreamCorrections":
        """No-op corrections: corrected energy equals raw energy."""
        return cls(gain=np.ones(n), offset_w=np.zeros(n),
                   time_shift_s=np.zeros(n),
                   baseline_w=np.broadcast_to(
                       np.asarray(baseline_w, dtype=np.float64), (n,)).copy(),
                   ref_period_s=np.full(n, float(ref_period_s)),
                   calibrated=np.zeros(n, dtype=bool))

    @classmethod
    def from_calibrations(cls, profile_names: Sequence[str],
                          calibs: Dict[str, CalibrationRecord],
                          baseline_w: float | np.ndarray = 0.0,
                          apply_gain: bool = True,
                          time_shift: bool = True) -> "StreamCorrections":
        """Gather per-device parameters from calibration records keyed by
        profile name — the same shape ``fleet_audit`` threads its
        records through the offline §5 protocol."""
        names = list(profile_names)
        n = len(names)
        uniq = sorted(set(names))
        missing = [u for u in uniq if u not in calibs]
        if missing:
            raise KeyError("no calibration record for profile(s): "
                           + ", ".join(missing))
        rows = {u: i for i, u in enumerate(uniq)}
        code = np.array([rows[x] for x in names], dtype=np.int64)

        def field(fn, dtype=np.float64):
            return np.array([fn(calibs[u]) for u in uniq],
                            dtype=dtype)[code]

        gain = (field(lambda c: c.correction_gain) if apply_gain
                else np.ones(n))
        return cls(
            gain=gain,
            offset_w=(field(lambda c: c.correction_offset_w) if apply_gain
                      else np.zeros(n)),
            time_shift_s=(field(lambda c: c.time_shift_s) if time_shift
                          else np.zeros(n)),
            baseline_w=np.broadcast_to(
                np.asarray(baseline_w, dtype=np.float64), (n,)).copy(),
            ref_period_s=field(lambda c: c.update_period_s),
            calibrated=field(lambda c: c.gain is not None, dtype=bool))


def default_calibrations(
        profile_names: Sequence[str]) -> Dict[str, CalibrationRecord]:
    """Synthetic per-profile records from the catalog's nominal
    parameters (no gain/offset — uncalibrated): the same
    :func:`repro.core.calibrate.nominal_record` recipe
    ``fleet_audit(good_practice=True)`` builds for itself."""
    from repro.core import profiles as _profiles
    from repro.core.calibrate import nominal_record
    return {name: nominal_record("stream", _profiles.get(name))
            for name in sorted(set(profile_names))}
