"""Versioned (de)serialization schema for the streaming monitor state.

Everything a live monitor accumulates online — the :class:`DeviceState`
arrays, the ring buffer, the period histograms, the per-label reading
moments — has exactly one canonical flat representation, declared here
as explicit ``{field: dtype}`` registries.  Both consumers share it:

* **checkpointing** (:mod:`repro.core.stream.checkpoint`) packs the
  registry walk into the manifest+npy layout and unpacks it on restore;
* **memory reporting** (``MonitorService.nbytes()`` and the component
  ``nbytes()`` methods) sums the same walk.

The registries are *closed*: packing validates that the live object's
array attributes match the declared field set exactly, so adding a
field to :class:`DeviceState` (or the ring / estimator) without bumping
:data:`SCHEMA_VERSION` and the registry fails loudly in the first test
that touches ``nbytes()`` or a checkpoint — instead of silently writing
checkpoints that restore into a corrupted (field-dropped) monitor.

This module imports nothing from the rest of :mod:`repro.core.stream`
at module scope (the stream modules import *it*); the monitor-level
pack/unpack resolves its classes lazily.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import numpy as np

#: Bump whenever a registry below changes shape or meaning.  Restores
#: refuse manifests written under a different version.
#: v2: health-machine arrays (``health.*``, present only when the
#: monitor tracks health) + ``strict_ids``/``health``/``health_every_s``
#: /``next_health_t``/``n_rejected`` meta.
#: v3: history-tier arrays (``history.*``, present only when the monitor
#: keeps a history tier) + ``history_step_s``/``history_steps`` meta.
SCHEMA_VERSION = 3

# -- field registries (name -> expected dtype kind) -------------------------
DEVICE_STATE_FIELDS = {
    "last_t": "f8", "last_v": "f8", "has": "b1", "first_t": "f8",
    "n_samples": "i8", "n_dup": "i8", "n_late": "i8",
    "energy_j": "f8", "energy_corr_j": "f8",
    "win_j": "f8", "win_corr_j": "f8",
    "run_t": "f8", "n_changes": "i8", "ewma_w": "f8", "n_out": "i8",
}

#: ring arrays; ``t``/``v``/``e_raw``/``e_corr`` exist only when
#: ``slots > 0`` (the registry marks them optional).
RING_FIELDS = {"n_written": "i8"}
RING_SLOT_FIELDS = {"t": "f8", "v": "f8", "e_raw": "f8", "e_corr": "f8"}

PERIOD_FIELDS = {"edges": "f8", "counts": "i8", "sums": "f8"}

CORRECTION_FIELDS = {
    "gain": "f8", "offset_w": "f8", "time_shift_s": "f8",
    "baseline_w": "f8", "ref_period_s": "f8", "calibrated": "b1",
}

#: per-device monitor configuration arrays (set at construction /
#: ``set_windows`` time, immutable during ingest — checkpointed so a
#: restore needs no out-of-band config).
CONFIG_FIELDS = {
    "win_a": "f8", "win_b": "f8", "max_hold": "f8",
    "env_lo": "f8", "env_hi": "f8", "label_codes": "i8",
}

#: per-label Chan–Welford reading moments, stacked over the sorted
#: label names recorded in the manifest meta.
MOMENT_FIELDS = {"n": "i8", "mean": "f8", "m2": "f8",
                 "mean_abs": "f8", "max_abs": "f8"}

#: health state machine arrays; present only when the monitor was built
#: with a :class:`~repro.core.stream.health.HealthPolicy`.
HEALTH_FIELDS = {"code": "i1", "since_t": "f8", "clean_t": "f8",
                 "clean": "b1", "last_n_out": "i8", "n_quarantines": "i8"}


#: history tier: each device's written boundary range (host arrays) and
#: the ``[slots, N]`` running-energy arrays the backend holds (host
#: arrays on the numpy backend, hence optional in the registry walk);
#: present only when the monitor keeps a history tier.
HISTORY_FIELDS = {"b_first": "i8", "b_last": "i8"}
HISTORY_TIER_FIELDS = {"e_raw": "f8", "e_corr": "f8"}


class SchemaError(RuntimeError):
    """A live object's fields diverged from the declared registry (or a
    checkpoint was written under a different schema)."""


def _array_attrs(obj: Any) -> Dict[str, np.ndarray]:
    """The ndarray-valued attributes of a dataclass or plain object."""
    if dataclasses.is_dataclass(obj):
        items = [(f.name, getattr(obj, f.name))
                 for f in dataclasses.fields(obj)]
    else:
        items = list(vars(obj).items())
    return {k: v for k, v in items if isinstance(v, np.ndarray)}


def check_registry(obj: Any, registry: Dict[str, str], what: str,
                   optional: Optional[Dict[str, str]] = None
                   ) -> Dict[str, np.ndarray]:
    """Validate ``obj``'s array attributes against ``registry`` and
    return them as ``{field: array}``.

    Extra *or* missing arrays raise :class:`SchemaError` naming the
    offending fields — the loud failure that protects checkpoints from
    silent field drift.  ``optional`` fields may be absent (the ring's
    slot arrays with ``slots=0``) but must match dtype when present.
    """
    arrays = _array_attrs(obj)
    expected = dict(registry)
    allowed = dict(registry, **(optional or {}))
    missing = sorted(set(expected) - set(arrays))
    extra = sorted(set(arrays) - set(allowed))
    if missing or extra:
        raise SchemaError(
            f"{what} diverged from schema v{SCHEMA_VERSION}: "
            + (f"missing {missing} " if missing else "")
            + (f"undeclared {extra} " if extra else "")
            + "— update repro.core.stream.schema (and bump "
              "SCHEMA_VERSION) alongside the state change")
    for name, arr in arrays.items():
        want = allowed[name]
        if np.dtype(arr.dtype).str[1:] != want:
            raise SchemaError(f"{what}.{name}: dtype {arr.dtype} != "
                              f"declared {want}")
    return arrays


def registry_nbytes(obj: Any, registry: Dict[str, str], what: str,
                    optional: Optional[Dict[str, str]] = None) -> int:
    """Resident bytes of ``obj``'s declared arrays — the shared walk
    behind the component ``nbytes()`` methods, so memory reporting
    exercises the same schema validation as checkpointing."""
    return sum(a.nbytes
               for a in check_registry(obj, registry, what, optional).values())


# -- monitor-level pack / unpack --------------------------------------------

def pack_monitor(mon) -> Tuple[Dict[str, np.ndarray], Dict[str, Any]]:
    """Flatten a live :class:`~repro.core.stream.MonitorService` (or its
    ingest core) into ``(arrays, meta)``.

    ``arrays`` is a flat ``{"group.field": ndarray}`` dict (every value a
    copy, safe to write asynchronously); ``meta`` is the JSON-able
    configuration needed to rebuild the monitor.  :func:`unpack_monitor`
    inverts it bitwise.
    """
    core = getattr(mon, "_core", mon)
    arrays: Dict[str, np.ndarray] = {}
    for k, v in check_registry(core.state, DEVICE_STATE_FIELDS,
                               "DeviceState").items():
        arrays[f"state.{k}"] = v.copy()
    ring = check_registry(core.ring, RING_FIELDS, "IngestBuffer",
                          optional=RING_SLOT_FIELDS)
    for k, v in ring.items():
        arrays[f"ring.{k}"] = v.copy()
    for k, v in check_registry(core.periods, PERIOD_FIELDS,
                               "OnlinePeriodEstimator").items():
        arrays[f"periods.{k}"] = v.copy()
    for k in CORRECTION_FIELDS:
        arrays[f"corrections.{k}"] = np.asarray(
            getattr(core.corrections, k)).copy()
    cfg = {"win_a": core._win_a, "win_b": core._win_b,
           "max_hold": core._max_hold, "env_lo": core._env_lo,
           "env_hi": core._env_hi, "label_codes": core._label_codes}
    for k, want in CONFIG_FIELDS.items():
        arr = np.asarray(cfg[k])
        if np.dtype(arr.dtype).str[1:] != want:
            raise SchemaError(f"config.{k}: dtype {arr.dtype} != "
                              f"declared {want}")
        arrays[f"config.{k}"] = arr.copy()
    # object-dtype labels are stored as their integer codes above plus
    # the name table in meta (np.save would need pickle for objects)
    moment_labels = sorted(core._moments)
    for k in MOMENT_FIELDS:
        dtype = np.int64 if MOMENT_FIELDS[k] == "i8" else np.float64
        arrays[f"moments.{k}"] = np.array(
            [getattr(core._moments[lb], k) for lb in moment_labels],
            dtype=dtype).reshape(len(moment_labels))
    if core.health is not None:
        for k, v in check_registry(core.health, HEALTH_FIELDS,
                                   "HealthTracker").items():
            arrays[f"health.{k}"] = v.copy()
    history = core.history
    if history is not None:
        check_registry(history, HISTORY_FIELDS, "HistoryTier",
                       optional=HISTORY_TIER_FIELDS)
        for k in HISTORY_FIELDS:
            arrays[f"history.{k}"] = getattr(history, k).copy()
        for k in HISTORY_TIER_FIELDS:
            arrays[f"history.{k}"] = np.array(getattr(history, k),
                                              dtype=np.float64)
    meta = {
        "schema_version": SCHEMA_VERSION,
        "n_devices": int(core.n_devices),
        "backend": core.backend if isinstance(core.backend, str) else "numpy",
        "trapezoid": bool(core.trapezoid),
        "ring_slots": int(core.ring.slots),
        "min_runs": int(core.periods.min_runs),
        "silent_after_s": (None if core.silent_after_s is None
                           else float(core.silent_after_s)),
        "drift_tau_s": float(core.drift_tau_s),
        "drift_rel": float(core.drift_rel),
        "drift_abs_w": float(core.drift_abs_w),
        "n_invalid": int(core._n_invalid),
        "n_rejected": int(core._n_rejected),
        "strict_ids": bool(core.strict_ids),
        "health": (None if core.health_policy is None
                   else core.health_policy.to_meta()),
        "health_every_s": float(core.health_every_s),
        "history_step_s": (None if history is None
                           else float(history.step_s)),
        "history_steps": 0 if history is None else int(history.steps),
        # -inf (never evaluated) is not JSON-able; None stands in
        "next_health_t": (None if core._next_health_t == -np.inf
                          else float(core._next_health_t)),
        "epoch": int(core.epoch),
        "label_names": list(core._label_names),
        "moment_labels": moment_labels,
    }
    return arrays, meta


def expected_keys(meta: Dict[str, Any]) -> set:
    """The exact array-key set a v``meta['schema_version']`` checkpoint
    must contain (ring slot arrays only when the ring was enabled)."""
    keys = {f"state.{k}" for k in DEVICE_STATE_FIELDS}
    keys |= {f"ring.{k}" for k in RING_FIELDS}
    if int(meta.get("ring_slots", 0)) > 0:
        keys |= {f"ring.{k}" for k in RING_SLOT_FIELDS}
    keys |= {f"periods.{k}" for k in PERIOD_FIELDS}
    keys |= {f"corrections.{k}" for k in CORRECTION_FIELDS}
    keys |= {f"config.{k}" for k in CONFIG_FIELDS}
    keys |= {f"moments.{k}" for k in MOMENT_FIELDS}
    if meta.get("health") is not None:
        keys |= {f"health.{k}" for k in HEALTH_FIELDS}
    if meta.get("history_steps"):
        keys |= {f"history.{k}"
                 for k in {**HISTORY_FIELDS, **HISTORY_TIER_FIELDS}}
    return keys


def unpack_monitor(arrays: Dict[str, np.ndarray], meta: Dict[str, Any],
                   backend: Optional[str] = None):
    """Rebuild a :class:`~repro.core.stream.MonitorService` from a
    :func:`pack_monitor` flattening — bitwise: continuing the stream
    from the rebuilt monitor is indistinguishable from never stopping.

    ``backend`` overrides the checkpointed backend name (restore a
    jax-written checkpoint on a numpy-only host and vice versa; the
    state arrays are backend-agnostic float64).
    """
    from repro.core.fleet_engine import StreamingMoments
    from repro.core.stream.estimators import StreamCorrections
    from repro.core.stream.health import HealthPolicy
    from repro.core.stream.monitor import MonitorService

    version = meta.get("schema_version")
    if version != SCHEMA_VERSION:
        raise SchemaError(f"checkpoint written under monitor schema "
                          f"v{version}, this build reads v{SCHEMA_VERSION}"
                          f" — no migration path is registered")
    want = expected_keys(meta)
    got = set(arrays)
    if want - got or got - want:
        raise SchemaError(
            f"checkpoint array set diverged from schema "
            f"v{SCHEMA_VERSION}: missing {sorted(want - got)}, "
            f"undeclared {sorted(got - want)}")

    n = int(meta["n_devices"])
    corr = StreamCorrections(**{
        k: np.ascontiguousarray(arrays[f"corrections.{k}"])
        for k in CORRECTION_FIELDS})
    names = np.asarray(meta["label_names"], dtype=object)
    labels = names[arrays["config.label_codes"]]
    policy = (None if meta["health"] is None
              else HealthPolicy.from_meta(meta["health"]))
    mon = MonitorService(
        n, corrections=corr, labels=labels,
        integration="trapezoid" if meta["trapezoid"] else "rectangle",
        ring_slots=int(meta["ring_slots"]),
        min_runs=int(meta["min_runs"]),
        silent_after_s=meta["silent_after_s"],
        drift_tau_s=meta["drift_tau_s"],
        drift_rel=meta["drift_rel"],
        drift_abs_w=meta["drift_abs_w"],
        strict_ids=bool(meta["strict_ids"]),
        health=policy,
        health_every_s=float(meta["health_every_s"]),
        history_step_s=meta["history_step_s"],
        history_steps=int(meta["history_steps"]),
        backend=backend if backend is not None else meta["backend"])
    core = mon._core
    for k in DEVICE_STATE_FIELDS:
        setattr(core.state, k, arrays[f"state.{k}"].copy())
    core.ring.n_written = arrays["ring.n_written"].copy()
    if core.ring.slots:
        for k in RING_SLOT_FIELDS:
            setattr(core.ring, k, arrays[f"ring.{k}"].copy())
    for k in PERIOD_FIELDS:
        setattr(core.periods, k, arrays[f"periods.{k}"].copy())
    core._win_a = arrays["config.win_a"].copy()
    core._win_b = arrays["config.win_b"].copy()
    core._max_hold = arrays["config.max_hold"].copy()
    core._env_lo = arrays["config.env_lo"].copy()
    core._env_hi = arrays["config.env_hi"].copy()
    core._moments = {}
    for i, lb in enumerate(meta["moment_labels"]):
        sm = StreamingMoments()
        sm.n = int(arrays["moments.n"][i])
        sm.mean = float(arrays["moments.mean"][i])
        sm.m2 = float(arrays["moments.m2"][i])
        sm.mean_abs = float(arrays["moments.mean_abs"][i])
        sm.max_abs = float(arrays["moments.max_abs"][i])
        core._moments[lb] = sm
    if core.health is not None:
        for k in HEALTH_FIELDS:
            setattr(core.health, k, arrays[f"health.{k}"].copy())
    if core.history is not None:
        for k in HISTORY_FIELDS:
            setattr(core.history, k, arrays[f"history.{k}"].copy())
        for k in HISTORY_TIER_FIELDS:
            setattr(core.history, k, core._be.history_put(
                arrays[f"history.{k}"].copy()))
    core._n_invalid = int(meta["n_invalid"])
    core._n_rejected = int(meta["n_rejected"])
    core._next_health_t = (-np.inf if meta["next_health_t"] is None
                           else float(meta["next_health_t"]))
    core.epoch = int(meta["epoch"])
    return mon
