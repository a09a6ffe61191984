"""The streaming fleet monitor façade: ingest core + snapshot serving.

:class:`MonitorService` keeps the one-object API the rest of the repo
(and the parity pins in ``tests/test_stream.py``) program against, but
is now a thin façade over the layered stack:

* :class:`~repro.core.stream.ingest.IngestCore` — the mutable state and
  the slab-folding hot path (correction kernels, ring writes, period
  recording, per-label moments).  ``ingest``/``ingest_grid`` delegate
  straight through; the hot path gained no indirection beyond one
  attribute hop.
* :class:`~repro.core.stream.snapshot.MonitorSnapshot` — immutable,
  epoch-tagged copy-on-write views.  Every query method here resolves
  ``self.snapshot()`` — published lazily, at most once per ingest epoch
  — and delegates, so readers never touch mutable ingest state and a
  query's answer is reproducible for as long as its snapshot is held.
* :class:`~repro.serve.monitor_service.MonitorQueryService` — the
  batched query executor for high-traffic serving (thousands of
  concurrent queries per snapshot as one vectorized op, LRU-cached by
  ``(query, epoch)``).
* :mod:`~repro.core.stream.checkpoint` — save/restore of the full
  online state (bitwise resume at any slab boundary).

Parity contract (pinned by ``tests/test_stream.py``): replaying a
fleet's poll series through ``ingest`` yields — on both execution
backends — registered-window energies equal to
``SensorBank.integrate_polled`` (and hence ``fleet_audit``'s naive
estimates) and full-span energies equal to the offline integration of
the same series, within float accumulation order (~1e-12 relative).
See ``docs/streaming.md``.
"""
from __future__ import annotations

from typing import Dict, Optional, Union

import numpy as np

from repro.core.stream.estimators import StreamCorrections
from repro.core.stream.health import HealthPolicy
from repro.core.stream.ingest import IngestCore, IngestReport
from repro.core.stream.snapshot import (FleetEnergy, FleetSeries,
                                         MonitorSnapshot)

__all__ = ["FleetEnergy", "FleetSeries", "HealthPolicy", "IngestReport",
           "MonitorService"]


class MonitorService:
    """Online fleet monitor over raw poll-sample slabs.

    Usage::

        mon = MonitorService(n_devices, corrections=corr, labels=labels)
        mon.set_windows(a, b)              # optional §5 execution windows
        for dev, t, v in sensor_bank.iter_poll_slabs(0.0, 10.0, 0.001):
            mon.ingest(dev, t, v)
        fleet = mon.fleet_energy(t=8.0)    # mid-run corrected energy
        per_label = mon.by_label(t0=6.0, t1=8.0)

    Ingestion policy (graceful by construction): slabs may arrive with
    samples in any order — they are sorted per device; exact duplicates
    and samples older than a device's newest accepted sample are dropped
    and counted (``state.n_dup`` / ``state.n_late``); non-finite samples
    are dropped and counted; devices simply absent from a slab keep
    their last reading (rectangle extrapolation, optionally capped by
    ``max_hold_s`` for gap-aware integration).

    Queries are answered from the current epoch's immutable
    :class:`~repro.core.stream.snapshot.MonitorSnapshot` (see
    :meth:`snapshot`); hold one to pin a consistent view across several
    queries while ingestion continues.

    History beyond the ring (off by default): ``history_steps > 0``
    keeps each device's running energy at every ``history_step_s``
    boundary, ``history_steps`` boundaries back
    (:class:`~repro.core.stream.state.HistoryTier`, held on the device
    by the accelerated backends).  Boundary instants within that horizon
    are then answered from it in every query, and :meth:`fleet_series`
    serves fleet energy and power over it.
    """

    def __init__(self, n_devices: int, *,
                 corrections: Optional[StreamCorrections] = None,
                 labels: Optional[np.ndarray] = None,
                 integration: str = "rectangle",
                 max_hold_s: Union[None, float, np.ndarray] = None,
                 envelope_w: Optional[tuple] = None,
                 ring_slots: int = 8,
                 period_bins: int = 24,
                 min_runs: int = 3,
                 silent_after_s: Optional[float] = None,
                 drift_tau_s: float = 30.0,
                 drift_rel: float = 0.25,
                 drift_abs_w: float = 5.0,
                 strict_ids: bool = True,
                 health: Optional[HealthPolicy] = None,
                 health_every_s: float = 0.0,
                 history_step_s: Optional[float] = None,
                 history_steps: int = 0,
                 backend: Optional[str] = None):
        self._core = IngestCore(
            n_devices, corrections=corrections, labels=labels,
            integration=integration, max_hold_s=max_hold_s,
            envelope_w=envelope_w, ring_slots=ring_slots,
            period_bins=period_bins, min_runs=min_runs,
            silent_after_s=silent_after_s, drift_tau_s=drift_tau_s,
            drift_rel=drift_rel, drift_abs_w=drift_abs_w,
            strict_ids=strict_ids, health=health,
            health_every_s=health_every_s, history_step_s=history_step_s,
            history_steps=history_steps, backend=backend)
        self._snap: Optional[MonitorSnapshot] = None

    # -- layer access ------------------------------------------------------
    @property
    def core(self) -> IngestCore:
        """The mutable ingest core (write side of the split)."""
        return self._core

    def snapshot(self) -> MonitorSnapshot:
        """The current epoch's immutable published view, created lazily
        and reused until the next slab lands — copy-on-write: the next
        slab writes the ring and the history tier in place unless a
        snapshot is still held, and then copies what it writes first, so
        a held snapshot's answers stay bitwise stable."""
        if self._snap is None or self._snap.epoch != self._core.epoch:
            self._snap = MonitorSnapshot.publish(self._core)
        return self._snap

    @property
    def epoch(self) -> int:
        """Monotonic ingest epoch (bumps on every slab that lands)."""
        return self._core.epoch

    # -- pass-through state (the pre-split attribute surface) --------------
    @property
    def n_devices(self) -> int:
        return self._core.n_devices

    @property
    def backend(self):
        return self._core.backend

    @property
    def corrections(self) -> StreamCorrections:
        return self._core.corrections

    @property
    def labels(self) -> np.ndarray:
        return self._core.labels

    @property
    def trapezoid(self) -> bool:
        return self._core.trapezoid

    @property
    def silent_after_s(self):
        return self._core.silent_after_s

    @property
    def state(self):
        """Live (mutable) per-device accumulators — ingest-side state;
        readers wanting a stable view should use :meth:`snapshot`."""
        return self._core.state

    @property
    def ring(self):
        return self._core.ring

    @property
    def periods(self):
        return self._core.periods

    @property
    def history(self):
        """The history tier (None unless ``history_steps > 0``)."""
        return self._core.history

    # -- configuration -----------------------------------------------------
    def set_windows(self, a, b) -> None:
        self._core.set_windows(a, b)

    set_windows.__doc__ = IngestCore.set_windows.__doc__

    def nbytes(self) -> int:
        return self._core.nbytes()

    nbytes.__doc__ = IngestCore.nbytes.__doc__

    def grow(self, n_new: int, *, corrections=None, labels=None) -> None:
        self._core.grow(n_new, corrections=corrections, labels=labels)

    grow.__doc__ = IngestCore.grow.__doc__

    # -- ingestion ---------------------------------------------------------
    def ingest(self, dev, t, v) -> IngestReport:
        self._snap = None       # let the slab write in place if unheld
        return self._core.ingest(dev, t, v)

    ingest.__doc__ = IngestCore.ingest.__doc__

    def ingest_grid(self, dev, ts, vals) -> IngestReport:
        self._snap = None
        return self._core.ingest_grid(dev, ts, vals)

    ingest_grid.__doc__ = IngestCore.ingest_grid.__doc__

    # -- queries (delegated to the current snapshot) -----------------------
    def fleet_energy(self, t: Optional[float] = None,
                     corrected: bool = True) -> FleetEnergy:
        return self.snapshot().fleet_energy(t, corrected)

    fleet_energy.__doc__ = MonitorSnapshot.fleet_energy.__doc__

    def window_energy(self, t: Optional[float] = None,
                      corrected: bool = True) -> np.ndarray:
        return self.snapshot().window_energy(t, corrected)

    window_energy.__doc__ = MonitorSnapshot.window_energy.__doc__

    def energy_between(self, t0: float, t1: float,
                       corrected: bool = True):
        return self.snapshot().energy_between(t0, t1, corrected)

    energy_between.__doc__ = MonitorSnapshot.energy_between.__doc__

    def by_label(self, t0: Optional[float] = None,
                 t1: Optional[float] = None,
                 corrected: bool = True) -> Dict[str, Dict[str, float]]:
        return self.snapshot().by_label(t0, t1, corrected)

    by_label.__doc__ = MonitorSnapshot.by_label.__doc__

    def fleet_series(self, t0: float, t1: float, step_s: float,
                     corrected: bool = True):
        return self.snapshot().fleet_series(t0, t1, step_s, corrected)

    fleet_series.__doc__ = MonitorSnapshot.fleet_series.__doc__

    def reading_stats(self) -> Dict[str, Dict[str, float]]:
        return self.snapshot().reading_stats()

    reading_stats.__doc__ = MonitorSnapshot.reading_stats.__doc__

    def update_period_s(self) -> np.ndarray:
        return self.snapshot().update_period_s()

    update_period_s.__doc__ = MonitorSnapshot.update_period_s.__doc__

    def flags(self, t: Optional[float] = None) -> Dict[str, np.ndarray]:
        return self.snapshot().flags(t)

    flags.__doc__ = MonitorSnapshot.flags.__doc__

    # -- health ------------------------------------------------------------
    @property
    def health(self):
        """The live :class:`~repro.core.stream.health.HealthTracker`
        (None unless constructed with a ``health=`` policy)."""
        return self._core.health

    @property
    def health_policy(self):
        return self._core.health_policy

    def update_health(self, t_now: float) -> bool:
        return self._core.update_health(t_now)

    update_health.__doc__ = IngestCore.update_health.__doc__

    def health_summary(self) -> Dict[str, float]:
        return self.snapshot().health_summary()

    @property
    def counters(self) -> Dict[str, int]:
        return self._core.counters
