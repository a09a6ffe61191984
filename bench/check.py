"""Output comparison and compile counting, copied from ``chip_smoke.py``.

:func:`compare` holds every output field on its own: integer and boolean
fields (counts, flags, counters) must match exactly, a float field is
compared relative to its own largest finite magnitude.
:class:`CompileClock` sums XLA's backend-compile time and counts the
compiles, so a run can show that none happened inside its window.
"""
from __future__ import annotations

import dataclasses

import numpy as np


class CompileClock:
    """Seconds XLA spent compiling, and how many compiles, summed from
    jax's backend-compile events."""

    def __init__(self):
        import jax.monitoring
        self.total = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration
            self.count += 1


def fields(x, path: str = ""):
    """``(path, array)`` for every numeric field of a result or state,
    in a fixed order (dataclass fields, sorted dict keys, list
    positions)."""
    if dataclasses.is_dataclass(x):
        x = {f.name: getattr(x, f.name) for f in dataclasses.fields(x)}
    if isinstance(x, dict):
        for k in sorted(x, key=str):
            yield from fields(x[k], f"{path}.{k}")
    elif isinstance(x, (list, tuple)):
        for i, v in enumerate(x):
            yield from fields(v, f"{path}[{i}]")
    else:
        a = np.asarray(x)
        if a.dtype.kind in "biuf":
            yield path, a


def field_err(got: np.ndarray, ref: np.ndarray) -> float:
    """One float field's largest difference relative to its own largest
    finite magnitude; nan where the two disagree on shape or on which
    entries are nan or infinite."""
    if got.shape != ref.shape:
        return float("nan")
    got, ref = got.astype(np.float64), ref.astype(np.float64)
    fin = np.isfinite(ref)
    if not (np.array_equal(np.isfinite(got), fin)
            and np.array_equal(got[~fin], ref[~fin], equal_nan=True)):
        return float("nan")
    if not fin.any():
        return 0.0
    diff = float(np.max(np.abs(got[fin] - ref[fin])))
    scale = float(np.max(np.abs(ref[fin])))
    return diff / scale if scale > 0 else (0.0 if diff == 0 else np.inf)


def compare(got, ref) -> dict:
    """Compare ``got`` with ``ref`` field by field: ``float_err`` is the
    largest float-field error (see :func:`field_err`; nan when a float
    field's shape or non-finite entries differ), ``int_mismatch`` the
    number of integer and boolean entries that differ (every entry of a
    field whose shape differs), ``where`` the worst field's path."""
    g, r = list(fields(got)), list(fields(ref))
    if [p for p, _ in g] != [p for p, _ in r]:
        return {"float_err": float("nan"), "int_mismatch": -1,
                "where": "field list"}
    worst, where, mism = 0.0, "", 0
    for (path, a), (_, b) in zip(g, r):
        if b.dtype.kind in "biu" or a.dtype.kind in "biu":
            if a.shape != b.shape:
                mism += max(a.size, b.size, 1)
            else:
                mism += int(np.sum(a != b))
            continue
        e = field_err(a, b)
        if not e <= worst:      # nan or larger
            worst, where = e, path
    return {"float_err": worst, "int_mismatch": mism, "where": where}
