"""Stacked per-device state for the streaming fleet monitor.

No per-device Python objects anywhere — the same array discipline as
:class:`~repro.core.fleet_engine.SensorBank`: every accumulator is one
[N] (or [N, R]) array, updated by scatter operations over the devices a
slab actually touched.

Three layers:

* :class:`DeviceState` — the streaming accumulators: last accepted
  sample, running raw/corrected energy, registered-window energy,
  run-tracking state for the online update-period estimator, ingestion
  counters, and the EWMA used for drift detection.
* :class:`IngestBuffer` — a ring of each device's most recent samples
  ``(t, reading, running raw energy, running corrected energy)``.  The
  energy snapshots make any *recent* instant exactly reconstructible
  (``energy_at = e[j] + v[j] · (t - t[j])``), which is what serves
  windowed mid-run queries without keeping the full history.  A
  published snapshot holds its arrays by reference (:class:`RingView`).
* :class:`HistoryTier` — the coarse tier behind minutes of history:
  each device's running raw and corrected energy at every boundary
  ``b · step_s``, the newest ``steps + 1`` boundaries deep, held by the
  backend (on the device for the accelerated tiers).
"""
from __future__ import annotations

import dataclasses
import functools
import weakref

import numpy as np

#: boundary index of a device that has written none yet
NO_BOUNDARY = np.iinfo(np.int64).min // 4


@dataclasses.dataclass
class DeviceState:
    """Streaming accumulators, one slot per device (see module doc)."""

    last_t: np.ndarray          # [N] newest accepted sample time
    last_v: np.ndarray          # [N] newest accepted (baselined) reading
    has: np.ndarray             # [N] device has reported at least once
    first_t: np.ndarray         # [N] first accepted sample time
    n_samples: np.ndarray       # [N] accepted samples
    n_dup: np.ndarray           # [N] duplicates dropped
    n_late: np.ndarray          # [N] out-of-order (late) samples dropped
    energy_j: np.ndarray        # [N] ∫ raw readings dt since first sample
    energy_corr_j: np.ndarray   # [N] ∫ corrected readings dt
    win_j: np.ndarray           # [N] raw energy clipped to the window
    win_corr_j: np.ndarray      # [N] corrected energy clipped to the window
    run_t: np.ndarray           # [N] time of the last reading change
    n_changes: np.ndarray       # [N] reading changes seen (ever)
    ewma_w: np.ndarray          # [N] EWMA of corrected readings (drift)
    n_out: np.ndarray           # [N] readings outside the envelope

    @classmethod
    def zeros(cls, n: int) -> "DeviceState":
        f = lambda: np.zeros(n)                       # noqa: E731
        i = lambda: np.zeros(n, dtype=np.int64)       # noqa: E731
        return cls(last_t=f(), last_v=f(),
                   has=np.zeros(n, dtype=bool), first_t=f(),
                   n_samples=i(), n_dup=i(), n_late=i(),
                   energy_j=f(), energy_corr_j=f(),
                   win_j=f(), win_corr_j=f(),
                   run_t=f(), n_changes=i(), ewma_w=f(), n_out=i())

    @property
    def n_devices(self) -> int:
        return self.last_t.shape[0]

    def nbytes(self) -> int:
        from repro.core.stream import schema
        return schema.registry_nbytes(self, schema.DEVICE_STATE_FIELDS,
                                      "DeviceState")


class IngestBuffer:
    """Ring of each device's ``slots`` most recent accepted samples.

    Writes happen once per ingest slab: the caller passes the slab's
    per-sample within-group ordinals, and only each group's last
    ``slots`` samples are written (earlier ones would be overwritten in
    the same slab anyway), so scatter indices never collide.

    ``slots=0`` disables the buffer — the monitor still answers live
    queries, but windowed/past queries report not-covered.

    Copy-on-write: :meth:`share` hands a published snapshot the arrays
    themselves; a write while that view is still held first replaces
    them by copies, and once no snapshot holds it writes go in place.
    """

    def __init__(self, n_devices: int, slots: int):
        if slots < 0:
            raise ValueError(f"ring slots must be >= 0, got {slots}")
        self.slots = int(slots)
        self.n_written = np.zeros(n_devices, dtype=np.int64)
        if self.slots:
            self.t = np.full((n_devices, self.slots), np.inf)
            self.v = np.zeros((n_devices, self.slots))
            self.e_raw = np.zeros((n_devices, self.slots))
            self.e_corr = np.zeros((n_devices, self.slots))
        self._held = []         # weakrefs to the views snapshots hold

    def nbytes(self) -> int:
        from repro.core.stream import schema
        return schema.registry_nbytes(self, schema.RING_FIELDS,
                                      "IngestBuffer",
                                      optional=schema.RING_SLOT_FIELDS)

    def write(self, dev: np.ndarray, ordinal: np.ndarray,
              group_count: np.ndarray, t: np.ndarray, v: np.ndarray,
              e_raw: np.ndarray, e_corr: np.ndarray,
              u_dev: np.ndarray, counts: np.ndarray) -> None:
        """Append one slab's accepted samples.

        ``dev``/``ordinal``/``group_count`` are per-sample [K] (device
        id, position within its device's group, that group's size);
        ``u_dev``/``counts`` are the slab's distinct devices and their
        sample counts [U].
        """
        self._own()
        if self.slots:
            keep = ordinal >= group_count - self.slots
            d = dev[keep]
            slot = (self.n_written[d] + ordinal[keep]) % self.slots
            self.t[d, slot] = t[keep]
            self.v[d, slot] = v[keep]
            self.e_raw[d, slot] = e_raw[keep]
            self.e_corr[d, slot] = e_corr[keep]
        self.n_written[u_dev] += counts

    def write_grid(self, dev: np.ndarray, t: np.ndarray, v: np.ndarray,
                   e_raw: np.ndarray, e_corr: np.ndarray) -> None:
        """Append one rectangular slab: ``dev`` [D] distinct devices all
        sampled at the shared, increasing times ``t`` [M]; ``v``/
        ``e_raw``/``e_corr`` are [D, M].  Equivalent to :meth:`write`
        with ordinal = column index — only each row's last ``slots``
        columns land, so scatter indices never collide."""
        m = t.shape[0]
        self._own()
        if self.slots:
            kc = min(self.slots, m)
            cols = np.arange(m - kc, m)
            rows = dev[:, None]
            slot = (self.n_written[dev][:, None] + cols[None, :]) \
                % self.slots
            self.t[rows, slot] = t[cols][None, :]
            self.v[rows, slot] = v[:, cols]
            self.e_raw[rows, slot] = e_raw[:, cols]
            self.e_corr[rows, slot] = e_corr[:, cols]
        self.n_written[dev] += m

    def sorted_view(self):
        """``(t, v, e_raw, e_corr)`` [N, R] oldest→newest per row, unused
        slots ``+inf`` — ready for row-wise binary search."""
        if not self.slots:
            raise RuntimeError("ring buffer disabled (slots=0)")
        return self.share().sorted_view()

    def share(self) -> "RingView":
        """The view a snapshot publishes: read-only views of the ring's
        arrays, which are not written again while it is held."""
        view = RingView(self.slots, *(_read_only(a) for a in (
            self.n_written, self.t, self.v, self.e_raw, self.e_corr)))
        self._held = _held_views(self._held) + [weakref.ref(view)]
        return view

    def _own(self) -> None:
        """Before a write: copy the arrays a held view still reads."""
        held, self._held = _held_views(self._held), []
        if held:
            self.n_written = self.n_written.copy()
            for k in ("t", "v", "e_raw", "e_corr"):
                setattr(self, k, getattr(self, k).copy())


def _read_only(a: np.ndarray) -> np.ndarray:
    view = a.view()
    view.setflags(write=False)
    return view


def _held_views(refs: list) -> list:
    """The weak references of ``refs`` whose view is still alive."""
    return [r for r in refs if r() is not None]


@dataclasses.dataclass(frozen=True)
class RingView:
    """What a published snapshot reads of an :class:`IngestBuffer`: its
    arrays by reference (see :meth:`IngestBuffer.share`)."""

    slots: int
    n_written: np.ndarray
    t: np.ndarray
    v: np.ndarray
    e_raw: np.ndarray
    e_corr: np.ndarray

    def sorted_view(self) -> tuple:
        """New ``(t, v, e_raw, e_corr)`` [N, R] arrays, oldest→newest
        per row, unused slots ``+inf``."""
        r = self.slots
        start = np.where(self.n_written >= r, self.n_written % r, 0)
        order = (start[:, None] + np.arange(r)[None, :]) % r
        return tuple(np.take_along_axis(a, order, axis=1)
                     for a in (self.t, self.v, self.e_raw, self.e_corr))


def boundary_before(t, step_s: float) -> np.ndarray:
    """Largest boundary index ``b`` with ``b · step_s < t``, decided on
    the float64 products themselves."""
    t = np.asarray(t, dtype=np.float64)
    b = np.ceil(t / step_s).astype(np.int64) - 1
    b = np.where((b + 1) * step_s < t, b + 1, b)
    return np.where(b * step_s >= t, b - 1, b)


def boundary_from(t, step_s: float) -> np.ndarray:
    """Smallest boundary index ``b`` with ``b · step_s >= t``."""
    return boundary_before(t, step_s) + 1


@dataclasses.dataclass(frozen=True)
class HistoryView:
    """What a published snapshot reads of a :class:`HistoryTier`: the
    backend's arrays by reference (never written again once shared) and
    a copy of each device's covered boundary range ``[b_lo, b_hi]``."""

    step_s: float
    steps: int
    e_raw: object
    e_corr: object
    b_lo: np.ndarray
    b_hi: np.ndarray

    @property
    def slots(self) -> int:
        return self.steps + 1

    @functools.cached_property
    def _oldest(self):
        """The oldest boundary any device holds, or None: a fleet-wide
        min, taken once per view."""
        held = self.b_hi >= self.b_lo
        return int(self.b_lo[held].min()) if held.any() else None

    def held_times(self) -> tuple:
        """``(lo_t, hi_t)`` [N]: the times of the oldest and newest
        boundary each device holds, ``(+inf, -inf)`` where it holds none.
        An instant ``b · step_s`` lies between them exactly when ``b``
        lies in ``[b_lo, b_hi]``."""
        empty = self.b_hi < self.b_lo
        return (np.where(empty, np.inf, self.b_lo * self.step_s),
                np.where(empty, -np.inf, self.b_hi * self.step_s))

    def boundary_of(self, tq: np.ndarray) -> tuple:
        """``(b, on_tier)`` per instant: the boundary index nearest each
        instant, and whether the tier answers it: the instant is that
        boundary, and no older than the oldest boundary a device still
        holds.  The tier then answers it for every device, a device
        whose slots do not hold it being not covered there (its ring
        can reach no further back, unless the ring spans more time than
        the tier)."""
        tq = np.asarray(tq, dtype=np.float64)
        with np.errstate(invalid="ignore"):
            b = np.rint(tq / self.step_s)
        ok = np.abs(b) < 2.0 ** 62          # nan and inf fail too
        b = np.where(ok, b, 0).astype(np.int64)
        if self._oldest is None:
            return b, np.zeros(b.shape, dtype=bool)
        return b, ok & (b * self.step_s == tq) & (b >= self._oldest)


class HistoryTier:
    """Each device's running raw and corrected energy at the boundaries
    ``b · step_s``, the newest ``steps + 1`` deep (see module doc).

    The ingest path writes a boundary once a device's newest accepted
    sample lies past it: later samples can then no longer change it
    (late and duplicate samples are dropped).  The value is the held
    value of the ring's rule, ``e[j] + dens[j] · min(B - t[j],
    max_hold)`` at the last sample ``j`` with ``t[j] <= B``.  Boundaries
    before a device's first sample are never written (the query rule
    answers them 0).  Slot ``b mod (steps + 1)`` holds boundary ``b``;
    ``b_first``/``b_last`` bound what a device has written.

    Copy-on-write: :meth:`share` hands a published snapshot the arrays
    themselves; a write while that view is still held leaves them
    untouched and makes new ones (a functional update), and once no
    snapshot holds them they are updated in place (donated, on the
    accelerated tiers).
    """

    def __init__(self, n_devices: int, step_s: float, steps: int, be):
        step_s, steps = float(step_s), int(steps)
        if not (np.isfinite(step_s) and step_s > 0.0):
            raise ValueError(f"history_step_s must be a positive number, "
                             f"got {step_s}")
        if steps < 1:
            raise ValueError(f"history_steps must be >= 1, got {steps}")
        self.step_s, self.steps = step_s, steps
        self._be = be
        self.b_first = np.full(n_devices, NO_BOUNDARY, dtype=np.int64)
        self.b_last = np.full(n_devices, NO_BOUNDARY, dtype=np.int64)
        self.e_raw = be.history_put(np.zeros((self.slots, n_devices)))
        self.e_corr = be.history_put(np.zeros((self.slots, n_devices)))
        self._held = []         # weakrefs to the views snapshots hold

    @property
    def slots(self) -> int:
        return self.steps + 1

    def nbytes(self) -> int:
        """Bytes of the boundary ranges and of the two tier arrays,
        wherever the backend holds them."""
        from repro.core.stream import schema
        host = schema.check_registry(self, schema.HISTORY_FIELDS,
                                     "HistoryTier",
                                     optional=schema.HISTORY_TIER_FIELDS)
        return (sum(host[k].nbytes for k in schema.HISTORY_FIELDS)
                + 2 * self.slots * self.b_last.shape[0] * 8)

    def plan(self, dev: np.ndarray, first_t: np.ndarray,
             t_new: np.ndarray) -> tuple:
        """The boundaries that devices ``dev`` [U] pass in a slab whose
        newest accepted time per device is ``t_new`` (``first_t``: each
        device's first accepted time, this slab's where it is new):
        ``(row, b)`` [P], ``row`` indexing ``dev``.  Only the newest
        ``steps + 1`` of a device's boundaries are planned."""
        hi = np.broadcast_to(boundary_before(t_new, self.step_s), dev.shape)
        last = self.b_last[dev]
        lo = np.where(last == NO_BOUNDARY,
                      boundary_from(first_t, self.step_s), last + 1)
        lo = np.maximum(lo, hi - self.steps)
        n = np.maximum(hi - lo + 1, 0)
        row = np.repeat(np.arange(dev.size), n)
        off = np.arange(row.size) - np.repeat(np.cumsum(n) - n, n)
        return row, lo[row] + off

    def write(self, dev: np.ndarray, b: np.ndarray, e_raw: np.ndarray,
              e_corr: np.ndarray) -> None:
        """Store ``e_raw``/``e_corr`` [P] at boundaries ``b`` of devices
        ``dev`` [P], as :meth:`plan` orders them: grouped by device, each
        device's boundaries ascending and new, at most ``steps + 1``."""
        if not b.size:
            return
        held, self._held = _held_views(self._held), []
        self.e_raw, self.e_corr = self._be.history_write(
            self.e_raw, self.e_corr, b % self.slots, dev, e_raw, e_corr,
            bool(held))
        head = np.ones(b.size, dtype=bool)
        head[1:] = dev[1:] != dev[:-1]
        tail = np.ones(b.size, dtype=bool)
        tail[:-1] = head[1:]
        fresh = head & (self.b_last[dev] == NO_BOUNDARY)
        self.b_first[dev[fresh]] = b[fresh]
        self.b_last[dev[tail]] = b[tail]

    def share(self) -> HistoryView:
        """The view a snapshot publishes; the arrays it holds are never
        written again."""
        written = self.b_last != NO_BOUNDARY
        lo = np.where(written,
                      np.maximum(self.b_first, self.b_last - self.steps),
                      NO_BOUNDARY + 1)
        b_lo, b_hi = lo, self.b_last.copy()
        b_lo.setflags(write=False)
        b_hi.setflags(write=False)
        view = HistoryView(self.step_s, self.steps, self.e_raw,
                           self.e_corr, b_lo, b_hi)
        self._held = _held_views(self._held) + [weakref.ref(view)]
        return view

    def grow(self, n_add: int) -> None:
        """Append ``n_add`` devices that have written nothing."""
        pad = np.full(n_add, NO_BOUNDARY, dtype=np.int64)
        self.b_first = np.concatenate([self.b_first, pad])
        self.b_last = np.concatenate([self.b_last, pad])
        z = np.zeros((self.slots, n_add))
        be = self._be
        self.e_raw = be.history_put(np.concatenate(
            [np.asarray(self.e_raw), z], axis=1))
        self.e_corr = be.history_put(np.concatenate(
            [np.asarray(self.e_corr), z], axis=1))
