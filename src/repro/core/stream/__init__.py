"""Streaming fleet monitor: online ingestion, correction, query serving.

Everything else in :mod:`repro.core` is offline — ``fleet_audit``,
``measure_*_batch`` and ``SensorBank.integrate_polled`` all need the
full workload timeline before integrating.  This package is the *live*
counterpart: raw per-device poll samples arrive tick by tick (in any
order, with duplicates and gaps) and the paper's §5 corrections are
applied as they arrive, so corrected energy queries are served while
the fleet is still running.

Layers (see ``docs/streaming.md``):

* :mod:`~repro.core.stream.state` — stacked per-device accumulators,
  the recent-sample ring buffer and the optional per-boundary history
  tier (no per-device Python objects);
* :mod:`~repro.core.stream.estimators` — the online update-period
  estimator and the stacked §5 correction parameters;
* :mod:`~repro.core.stream.ingest` — :class:`IngestCore`, the mutable
  write side: slab folding through the backend kernels
  (:mod:`repro.core.engine_backend`, one implementation per backend);
* :mod:`~repro.core.stream.snapshot` — :class:`MonitorSnapshot`,
  immutable epoch-tagged published views that serve every query;
* :mod:`~repro.core.stream.monitor` — :class:`MonitorService`, the
  one-object façade over ingest + snapshot publication;
* :mod:`~repro.core.stream.schema` — the versioned (de)serialization
  registries shared by checkpointing and ``nbytes()`` reporting;
* :mod:`~repro.core.stream.health` — the opt-in per-device health
  machine (healthy → stale → quarantined) behind degraded-mode queries;
* :mod:`~repro.core.stream.checkpoint` — bitwise monitor
  save/restore on the seed checkpoint layout, with typed corruption
  errors and last-complete-generation fallback;
* :mod:`~repro.core.stream.supervisor` — :class:`MonitorSupervisor`,
  the crash-recovery loop (auto-checkpoint, restore-then-resume,
  slab-boundary dedup);
* :mod:`~repro.core.stream.replay` — drivers that replay any
  ``SensorBank`` / ``TimelineBank`` / ``FleetScenarioSpec`` fleet as a
  live stream, pinned against the offline audit on the same schedules,
  with seeded transport-fault injection (:class:`FaultSpec`).

(The batched, cached query executor for serving lives one level up, in
:mod:`repro.serve.monitor_service`.)
"""
from repro.core.stream.checkpoint import (CheckpointError,
                                          MissingCheckpointError,
                                          restore_monitor, save_monitor)
from repro.core.stream.estimators import (OnlinePeriodEstimator,
                                          StreamCorrections,
                                          default_calibrations)
from repro.core.stream.health import (HEALTHY, QUARANTINED, STALE,
                                      HealthPolicy, HealthTracker)
from repro.core.stream.ingest import IngestCore
from repro.core.stream.monitor import (FleetEnergy, FleetSeries,
                                       IngestReport, MonitorService)
from repro.core.stream.replay import (FaultInjector, FaultSpec,
                                      InjectionLog, StreamFleetResult,
                                      replay, stream_fleet)
from repro.core.stream.schema import SCHEMA_VERSION, SchemaError
from repro.core.stream.snapshot import MonitorSnapshot
from repro.core.stream.state import (DeviceState, HistoryTier,
                                     IngestBuffer)
from repro.core.stream.supervisor import MonitorSupervisor, SupervisorReport

__all__ = [
    "DeviceState", "IngestBuffer", "HistoryTier",
    "OnlinePeriodEstimator", "StreamCorrections", "default_calibrations",
    "FleetEnergy", "FleetSeries", "IngestReport", "IngestCore",
    "MonitorService",
    "MonitorSnapshot", "SCHEMA_VERSION", "SchemaError",
    "HEALTHY", "STALE", "QUARANTINED", "HealthPolicy", "HealthTracker",
    "CheckpointError", "MissingCheckpointError",
    "save_monitor", "restore_monitor",
    "MonitorSupervisor", "SupervisorReport",
    "FaultSpec", "FaultInjector", "InjectionLog",
    "StreamFleetResult", "replay", "stream_fleet",
]
