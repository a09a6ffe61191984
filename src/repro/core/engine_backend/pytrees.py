"""Array containers shared by every execution backend.

:class:`TimelineArrays` is the padded ``[R, S]`` form of a
:class:`~repro.core.ground_truth.TimelineBank` — a plain ``NamedTuple`` of
arrays, which makes it a JAX pytree for free: it can be passed straight
into ``jax.jit``-compiled kernels (leaves trace as ``jnp`` arrays) while
staying a zero-cost tuple of ``np.ndarray`` views on the NumPy path.
:class:`SlabOperands` is the streaming-ingest kernels' per-device operand
record in the same form; :data:`SLAB_PAD` is the one table of the values
its padding rows take.  :class:`TierOperands` is the history tier's
query kernels' per-device record, packed by dtype for one transfer
(:data:`TIER_F64`, :data:`TIER_I32`, :func:`unpack_tier_operands`).

The container carries no behaviour on purpose: backends implement the
kernels as pure functions over these arrays, so the same signature works
for NumPy, JAX, and any future array namespace.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class TimelineArrays(NamedTuple):
    """Padded piecewise-constant traces: ``R`` rows of up to ``S`` segments.

    ``edges`` is ``[R, S+1]`` (non-decreasing per row, padding repeats the
    final valid edge), ``powers`` ``[R, S]`` (padding holds the row's idle
    power), ``idle_w`` and ``n_segs`` are ``[R]``.  Invariants are
    normalised by :class:`~repro.core.ground_truth.TimelineBank`; backends
    may assume them.
    """

    edges: np.ndarray
    powers: np.ndarray
    idle_w: np.ndarray
    n_segs: np.ndarray

    @property
    def n_rows(self) -> int:
        return self.edges.shape[0]

    @property
    def t_start(self) -> np.ndarray:
        return self.edges[:, 0]

    @property
    def t_end(self) -> np.ndarray:
        return self.edges[:, -1]


class ReadingSchedule(NamedTuple):
    """A fleet's published-reading schedule as padded ``[N, M]`` arrays.

    ``ticks`` holds every device's publication instants
    (``phase + T * k``, leading/trailing slots masked rather than
    filtered); ``first``/``last`` are each device's first/last valid slot,
    ``k0`` the tick index of slot 0.  Together with ``phase`` and
    ``update_period_s`` this is everything a kernel needs to map a
    wall-clock instant to the reading slot that covers it.
    """

    ticks: np.ndarray
    first: np.ndarray
    last: np.ndarray
    k0: np.ndarray
    phase: np.ndarray
    update_period_s: np.ndarray


class PollGrid(NamedTuple):
    """A uniform ``nvidia-smi -lms``-style poll grid shared by a fleet.

    ``t0`` and ``period_s`` are scalars; ``t1`` is per-device (each scalar
    sensor's grid ends with its own trial), so device ``i`` owns poll
    indices ``0 .. floor((t1[i] - t0) / period_s) - 1``.  ``grid_offset``
    shifts the *reported* timestamps (the §5 re-synchronisation step)
    while queries still happen at the true wall-clock instant — a
    scalar, or a per-device [N] array when a fleet mixes averaging
    windows (each sensor class re-synchronises by its own window).
    """

    t0: float
    t1: np.ndarray
    period_s: float
    grid_offset: "float | np.ndarray" = 0.0


class SlabOperands(NamedTuple):
    """The per-device operands of one streaming-ingest slab, each ``[U]``:
    one row per device the slab holds.

    The device's stored state: ``prev_t``/``prev_v`` its newest sample,
    ``has_prev`` whether it has one, ``run_t`` the start of its current
    reading run (the slab's first sample time for a device with no
    history), ``n_changes`` the reading changes seen so far.  Its
    configuration: ``gain``/``offset`` invert the calibrated transform,
    ``tshift`` re-synchronises reported timestamps (a reading at ``t``
    covers ``[t - tshift, t]``), ``win_a``/``win_b`` bound its registered
    measurement window, ``max_hold`` caps how long one reading may be
    extrapolated across a sampling gap (``inf`` = plain rectangle),
    ``env_lo``/``env_hi`` are its calibrated plausibility envelope.
    """

    prev_t: np.ndarray
    prev_v: np.ndarray
    has_prev: np.ndarray
    run_t: np.ndarray
    n_changes: np.ndarray
    gain: np.ndarray
    offset: np.ndarray
    tshift: np.ndarray
    win_a: np.ndarray
    win_b: np.ndarray
    max_hold: np.ndarray
    env_lo: np.ndarray
    env_hi: np.ndarray


#: The value each operand takes in a padding row: no stored sample, no
#: hold, a unit gain (the correction stays finite), a window that never
#: opens and an open envelope, so a padding row folds to zeros.
SLAB_PAD = SlabOperands(prev_t=0.0, prev_v=0.0, has_prev=False, run_t=0.0,
                        n_changes=0, gain=1.0, offset=0.0, tshift=0.0,
                        win_a=np.inf, win_b=-np.inf, max_hold=0.0,
                        env_lo=-np.inf, env_hi=np.inf)


def pad_operands(ops: SlabOperands, n: int) -> SlabOperands:
    """``ops`` extended to ``n`` rows with :data:`SLAB_PAD`, each field
    keeping its dtype."""
    return SlabOperands(*(
        np.concatenate([x, np.full(n - len(x), pad, np.asarray(x).dtype)])
        for x, pad in zip(ops, SLAB_PAD)))


class TierOperands(NamedTuple):
    """The per-device operands of the history tier's query kernels, each
    ``[N]``, for one flavour (raw or corrected).

    ``lo_t``/``hi_t`` are the times of the oldest and newest boundary the
    device's slots hold (``+inf``/``-inf`` where it holds none);
    ``last_t``/``first_t`` its newest and first sample time, ``has``
    whether it has one, ``max_hold`` its hold cap; ``dens``/``base`` the
    flavour's density and running energy at its newest sample; ``active``
    False where health quarantined it, ``tol`` its sigma tolerance, and
    ``codes`` its label's index.
    """

    lo_t: np.ndarray
    hi_t: np.ndarray
    last_t: np.ndarray
    first_t: np.ndarray
    has: np.ndarray
    max_hold: np.ndarray
    dens: np.ndarray
    base: np.ndarray
    active: np.ndarray
    tol: np.ndarray
    codes: np.ndarray


#: The rows of a snapshot's packed float64 operand buffer [10, N]: both
#: flavours' tails, so one buffer serves every query of the snapshot.
TIER_F64 = ("lo_t", "hi_t", "last_t", "first_t", "max_hold", "tol",
            "dens_corr", "base_corr", "dens_raw", "base_raw")
#: The rows of its int32 buffer [3, N], booleans as 0 and 1.
TIER_I32 = ("has", "active", "codes")


def unpack_tier_operands(f64, i32) -> tuple:
    """The corrected and the raw :class:`TierOperands` of one packed pair
    of buffers (:data:`TIER_F64`, :data:`TIER_I32`); works on host and on
    device arrays alike."""
    f = dict(zip(TIER_F64, f64))
    i = dict(zip(TIER_I32, i32))
    common = dict(lo_t=f["lo_t"], hi_t=f["hi_t"], last_t=f["last_t"],
                  first_t=f["first_t"], has=i["has"] != 0,
                  max_hold=f["max_hold"], active=i["active"] != 0,
                  tol=f["tol"], codes=i["codes"])
    return tuple(TierOperands(dens=f["dens_" + flavour],
                              base=f["base_" + flavour], **common)
                 for flavour in ("corr", "raw"))
