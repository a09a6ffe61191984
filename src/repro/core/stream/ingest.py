"""The streaming monitor's ingest core: mutable state + the hot path.

:class:`IngestCore` owns everything the monitor accumulates online —
the :class:`~repro.core.stream.state.DeviceState` arrays, the recent-
sample ring, the period histograms, the per-label reading moments — and
the two slab-folding entry points (``ingest`` for arbitrary slabs,
``ingest_grid`` for the rectangular clean-stream fast path).  It serves
**no queries**: readers go through the immutable
:class:`~repro.core.stream.snapshot.MonitorSnapshot` the façade
publishes, so nothing ever reads this object's arrays concurrently with
a scatter update.

Every slab that lands bumps :attr:`epoch` — the monotonic counter the
snapshot layer and the ``(query, epoch)`` result cache key on.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Union

import numpy as np

from repro.common.trace import span
from repro.core.engine_backend import get_backend, resolve_backend
from repro.core.engine_backend.pytrees import SlabOperands
from repro.core.fleet_engine import StreamingMoments
from repro.core.stream.estimators import (OnlinePeriodEstimator,
                                          StreamCorrections)
from repro.core.stream.health import HealthPolicy, HealthTracker
from repro.core.stream.state import DeviceState, HistoryTier, IngestBuffer

_INTEGRATIONS = ("rectangle", "trapezoid")


@dataclasses.dataclass(frozen=True)
class IngestReport:
    """What one ``ingest`` call did with its slab."""

    accepted: int
    duplicates: int
    late: int
    invalid: int
    n_devices: int      # distinct devices that contributed samples
    rejected: int = 0   # out-of-range device ids (strict_ids=False only)


class IngestCore:
    """Mutable online state + slab ingestion (see module doc).

    Construction arguments are identical to
    :class:`~repro.core.stream.monitor.MonitorService`, which documents
    them — the façade forwards its ``__init__`` here verbatim.
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
        if n_devices < 1:
            raise ValueError("need at least one device")
        if integration not in _INTEGRATIONS:
            raise ValueError(f"unknown integration '{integration}'; "
                             f"known: {', '.join(_INTEGRATIONS)}")
        n = int(n_devices)
        self.n_devices = n
        self.backend = resolve_backend(backend)
        self._be = get_backend(self.backend)
        self.corrections = (corrections if corrections is not None
                            else StreamCorrections.identity(n))
        if self.corrections.n_devices != n:
            raise ValueError(
                f"corrections cover {self.corrections.n_devices} devices, "
                f"monitor has {n}")
        if labels is None:
            self.labels = np.full(n, "all", dtype=object)
        else:
            self.labels = np.asarray(labels, dtype=object)
            if self.labels.shape != (n,):
                raise ValueError(f"labels must be [{n}], "
                                 f"got {self.labels.shape}")
        # integer label codes keep object-array work off the hot path
        names, codes = np.unique(self.labels.astype(str),
                                 return_inverse=True)
        self._label_names = [str(x) for x in names]
        self._label_codes = codes.astype(np.int64)
        self.trapezoid = (integration == "trapezoid")
        if max_hold_s is None:
            self._max_hold = np.full(n, np.inf)
        else:
            self._max_hold = np.broadcast_to(
                np.asarray(max_hold_s, dtype=np.float64), (n,)).copy()
            if np.any(self._max_hold <= 0.0):
                raise ValueError("max_hold_s must be positive")
        if envelope_w is None:
            self._env_lo = np.full(n, -np.inf)
            self._env_hi = np.full(n, np.inf)
        else:
            lo, hi = envelope_w
            self._env_lo = np.broadcast_to(
                np.asarray(lo, dtype=np.float64), (n,)).copy()
            self._env_hi = np.broadcast_to(
                np.asarray(hi, dtype=np.float64), (n,)).copy()

        self.state = DeviceState.zeros(n)
        self.ring = IngestBuffer(n, ring_slots)
        if history_steps and history_step_s is None:
            raise ValueError("history_steps needs history_step_s")
        self.history = (HistoryTier(n, history_step_s, history_steps,
                                    self._be) if history_steps else None)
        self.periods = OnlinePeriodEstimator(n, n_bins=period_bins,
                                             min_runs=min_runs)
        # windows disabled until registered: [+inf, -inf] selects nothing
        self._win_a = np.full(n, np.inf)
        self._win_b = np.full(n, -np.inf)

        self.silent_after_s = silent_after_s
        self.drift_tau_s = float(drift_tau_s)
        self.drift_rel = float(drift_rel)
        self.drift_abs_w = float(drift_abs_w)
        self._moments: Dict[str, StreamingMoments] = {}
        self._n_invalid = 0
        # defensive-mode knobs: with strict_ids=False, out-of-range ids
        # are rejected and counted instead of raising (the posture for
        # streams behind a corrupting collector); with a HealthPolicy,
        # the per-device state machine runs at slab boundaries (at most
        # every health_every_s of stream time)
        self.strict_ids = bool(strict_ids)
        self.health_policy = health
        self.health = HealthTracker.zeros(n) if health is not None else None
        self.health_every_s = float(health_every_s)
        self._next_health_t = -np.inf
        self._n_rejected = 0
        # bumped on every slab that mutates state; snapshots and the
        # (query, epoch) result cache key on it
        self.epoch = 0

    # -- configuration ----------------------------------------------------
    def set_windows(self, a, b) -> None:
        """Register per-device measurement windows ``[a_i, b_i]`` (the §5
        execution windows — e.g. each device's workload span).  Window
        energy accumulates sample-by-sample, so windows must be set
        before the first sample arrives."""
        if int(np.sum(self.state.n_samples)) > 0:
            raise RuntimeError("windows must be registered before the "
                               "first ingest (accumulation is not "
                               "retroactive)")
        n = self.n_devices
        a = np.broadcast_to(np.asarray(a, dtype=np.float64), (n,)).copy()
        b = np.broadcast_to(np.asarray(b, dtype=np.float64), (n,)).copy()
        self._win_a, self._win_b = a, b
        self.epoch += 1

    def nbytes(self) -> int:
        """Approximate resident size of the monitor state (the memory
        that scales with fleet size) — summed through the same schema
        registries checkpointing serializes, so a field added to the
        state without a schema update fails here first."""
        return (self.state.nbytes() + self.ring.nbytes()
                + self.periods.nbytes()
                + (self.health.nbytes() if self.health is not None else 0)
                + (self.history.nbytes() if self.history is not None
                   else 0))

    def grow(self, n_new: int, *,
             corrections: Optional[StreamCorrections] = None,
             labels: Optional[np.ndarray] = None) -> None:
        """Widen the monitor to ``n_new`` devices mid-stream.

        The live-collector contract (:mod:`repro.collect`): a gpu_uuid
        the registry has never seen hot-adds a device, and the monitor
        must grow to match **without perturbing anything already
        accumulated** — after growth, every state array equals what a
        monitor built at the full width from the start would hold, with
        the appended rows in their pristine zero state (pinned bitwise
        in ``tests/test_collect.py``).  ``corrections``/``labels``
        cover the appended tail (``n_new - n_devices`` rows; identity
        corrections and the ``"all"`` label by default); tail windows
        start disabled, tail ``max_hold``/envelope unlimited — exactly
        a fresh monitor's defaults.  Bumps the epoch, so held snapshots
        stay valid and the next query publishes at the new width.
        """
        from repro.core.stream import schema
        n_old = self.n_devices
        n_new = int(n_new)
        if n_new < n_old:
            raise ValueError(f"cannot shrink a monitor: {n_old} -> {n_new}")
        if n_new == n_old:
            return
        n_add = n_new - n_old
        tail_corr = (corrections if corrections is not None
                     else StreamCorrections.identity(n_add))
        if tail_corr.n_devices != n_add:
            raise ValueError(f"tail corrections cover "
                             f"{tail_corr.n_devices} devices, growing "
                             f"by {n_add}")
        self.corrections = StreamCorrections(**{
            f.name: np.concatenate([getattr(self.corrections, f.name),
                                    getattr(tail_corr, f.name)])
            for f in dataclasses.fields(StreamCorrections)})
        if labels is None:
            tail_labels = np.full(n_add, "all", dtype=object)
        else:
            tail_labels = np.asarray(labels, dtype=object)
            if tail_labels.shape != (n_add,):
                raise ValueError(f"tail labels must be [{n_add}], "
                                 f"got {tail_labels.shape}")
        self.labels = np.concatenate([self.labels, tail_labels])
        names, codes = np.unique(self.labels.astype(str),
                                 return_inverse=True)
        self._label_names = [str(x) for x in names]
        self._label_codes = codes.astype(np.int64)

        # per-device state: fieldwise concat with the pristine zero rows,
        # walked through the schema registries so a state field added
        # without growth support fails loudly here
        pad = DeviceState.zeros(n_add)
        old = schema.check_registry(self.state, schema.DEVICE_STATE_FIELDS,
                                    "DeviceState")
        self.state = DeviceState(**{
            k: np.concatenate([v, getattr(pad, k)])
            for k, v in old.items()})
        ring_pad = IngestBuffer(n_add, self.ring.slots)
        for k in schema.check_registry(
                self.ring, schema.RING_FIELDS, "IngestBuffer",
                optional=schema.RING_SLOT_FIELDS):
            setattr(self.ring, k, np.concatenate(
                [getattr(self.ring, k), getattr(ring_pad, k)]))
        self.periods.counts = np.concatenate(
            [self.periods.counts,
             np.zeros((n_add, self.periods.n_bins), dtype=np.int64)])
        self.periods.sums = np.concatenate(
            [self.periods.sums, np.zeros((n_add, self.periods.n_bins))])
        if self.health is not None:
            hp = HealthTracker.zeros(n_add)
            for k in schema.check_registry(self.health,
                                           schema.HEALTH_FIELDS,
                                           "HealthTracker"):
                setattr(self.health, k, np.concatenate(
                    [getattr(self.health, k), getattr(hp, k)]))
        if self.history is not None:
            self.history.grow(n_add)

        # config vectors: tail rows take a fresh monitor's defaults
        self._max_hold = np.concatenate([self._max_hold,
                                         np.full(n_add, np.inf)])
        self._env_lo = np.concatenate([self._env_lo,
                                       np.full(n_add, -np.inf)])
        self._env_hi = np.concatenate([self._env_hi,
                                       np.full(n_add, np.inf)])
        self._win_a = np.concatenate([self._win_a, np.full(n_add, np.inf)])
        self._win_b = np.concatenate([self._win_b, np.full(n_add, -np.inf)])
        self.n_devices = n_new
        self.epoch += 1

    # -- ingestion --------------------------------------------------------
    def ingest(self, dev, t, v) -> IngestReport:
        """Fold one slab of raw poll samples into the online state.

        ``dev`` [K] int device ids, ``t`` [K] sample times, ``v`` [K]
        raw readings — any order, duplicates and late samples tolerated
        (dropped and counted).  Out-of-range device ids raise by
        default; with ``strict_ids=False`` they are rejected and counted
        instead (the defensive posture for corrupting collectors) —
        either way they never touch state.  Returns an
        :class:`IngestReport`.
        """
        # a flat slab learns its devices by grouping: its ``devices``
        # ride on the ``ingest.kernel`` span
        with span("ingest", samples=np.size(dev)):
            return self._ingest(dev, t, v)

    def _ingest(self, dev, t, v) -> IngestReport:
        st = self.state
        with span("ingest.prep"):
            dev = np.asarray(dev, dtype=np.int64).ravel()
            t = np.asarray(t, dtype=np.float64).ravel()
            v = np.asarray(v, dtype=np.float64).ravel()
            if not (dev.shape == t.shape == v.shape):
                raise ValueError(f"shape mismatch: dev {dev.shape}, "
                                 f"t {t.shape}, v {v.shape}")
            ok_id, n_rej = self._known_ids(dev, 1)
            if ok_id is not None:
                dev, t, v = dev[ok_id], t[ok_id], v[ok_id]
            k_in = dev.size
            if k_in == 0:
                if n_rej:               # counters mutated: publish fresh
                    self.epoch += 1
                return IngestReport(0, 0, 0, 0, 0, n_rej)
            # even an all-dropped slab mutates counters: publish fresh
            self.epoch += 1

            ok = np.isfinite(t) & np.isfinite(v)
            n_invalid = int(k_in - ok.sum())
            if n_invalid:
                self._n_invalid += n_invalid
                dev, t, v = dev[ok], t[ok], v[ok]

            order = np.lexsort((t, dev))
            dev, t, v = dev[order], t[order], v[order]

            # duplicates: same (device, t) — keep the first arrival
            dup = np.zeros(len(dev), dtype=bool)
            dup[1:] = (dev[1:] == dev[:-1]) & (t[1:] == t[:-1])
            # vs stored state: strictly older samples arrive late, a
            # repeat of the newest timestamp is a duplicate
            late = ~dup & st.has[dev] & (t < st.last_t[dev])
            dup_state = ~dup & st.has[dev] & (t == st.last_t[dev])
            n_dup = int(np.sum(dup | dup_state))
            n_late = int(np.sum(late))
            if n_dup:
                np.add.at(st.n_dup, dev[dup | dup_state], 1)
            if n_late:
                np.add.at(st.n_late, dev[late], 1)
            keep = ~(dup | dup_state | late)
            dev, t, v = dev[keep], t[keep], v[keep]
            k = dev.size
            if k == 0:
                return IngestReport(0, n_dup, n_late, n_invalid, 0, n_rej)

            # compact to per-slab groups (devices sorted => contiguous)
            first = np.empty(k, dtype=bool)
            first[0] = True
            first[1:] = dev[1:] != dev[:-1]
            start_idx = np.flatnonzero(first)
            end_idx = np.concatenate([start_idx[1:] - 1, [k - 1]])
            u_dev = dev[start_idx]
            seg = np.cumsum(first) - 1

        with span("ingest.gather"):
            v = v - self.corrections.baseline_w[dev]
            ops = self._operands(u_dev, t[start_idx])

        with span("ingest.kernel", samples=k, devices=len(u_dev)):
            (new_t, new_v, new_run_t, new_nchg, counts, d_e, d_ec, d_w,
             d_wc, sum_vc, n_out, cum_e, cum_ec, vc, run_dur, run_rec) = \
                self._be.stream_ingest(t, v, seg, first, start_idx, end_idx,
                                       ops, self.trapezoid)

        # ring snapshots see running totals *before* this slab is folded
        with span("ingest.ring"):
            if self.ring.slots:
                ordinal = np.arange(k) - start_idx[seg]
                self.ring.write(dev, ordinal, counts[seg], t, v,
                                st.energy_j[u_dev][seg] + cum_e,
                                st.energy_corr_j[u_dev][seg] + cum_ec,
                                u_dev, counts)
            else:
                self.ring.n_written[u_dev] += counts

        if self.history is not None:
            self._history_flat(u_dev, ops.has_prev, t, v, start_idx,
                               end_idx, cum_e, cum_ec)
        self._write_back(u_dev, ops, t[start_idx], new_t, counts, new_v,
                         new_run_t, new_nchg, d_e, d_ec, d_w, d_wc, sum_vc,
                         n_out)
        self._record_periods(dev, run_dur, run_rec)
        # label sums from the slab's corrected readings, O(K + labels)
        av = np.abs(vc)
        self._merge_moments(dev, 1, vc, vc * vc, av, av)
        self._maybe_update_health(float(np.max(new_t)))
        return IngestReport(k, n_dup, n_late, n_invalid, len(u_dev), n_rej)

    def ingest_grid(self, dev, ts, vals) -> IngestReport:
        """Fold one *rectangular* slab: ``dev`` [D] distinct ascending
        device ids, ``ts`` [M] strictly-increasing sample times shared by
        every device, ``vals`` [D, M] raw readings.

        This is the clean-stream fast path: no sorting, no per-sample
        scatter — the backend's ``stream_ingest_grid`` kernel does
        row-wise cumsums and reductions over the [D, M] slab directly.
        Slabs that violate the rectangular contract (unsorted ids or
        times, non-finite readings, samples at/behind a device's newest
        accepted sample) fall back to the general :meth:`ingest` path
        with identical semantics.
        """
        with span("ingest_grid", samples=np.size(vals),
                  devices=np.size(dev)):
            return self._ingest_grid(dev, ts, vals)

    def _ingest_grid(self, dev, ts, vals) -> IngestReport:
        st = self.state
        with span("ingest.prep"):
            dev = np.asarray(dev, dtype=np.int64).ravel()
            ts = np.asarray(ts, dtype=np.float64).ravel()
            vals = np.asarray(vals, dtype=np.float64)
            d, m = dev.size, ts.size
            if vals.shape != (d, m):
                raise ValueError(f"vals must be [{d}, {m}], "
                                 f"got {vals.shape}")
            if d == 0 or m == 0:
                return IngestReport(0, 0, 0, 0, 0)
            ok_id, n_rej = self._known_ids(dev, m)
            if ok_id is not None:
                dev, vals = dev[ok_id], vals[ok_id]
                d = dev.size
                if d == 0:
                    self.epoch += 1     # counters mutated: publish fresh
                    return IngestReport(0, 0, 0, 0, 0, n_rej)

            clean = (np.all(np.diff(dev) > 0)
                     and np.all(np.diff(ts) > 0)
                     and bool(np.all(np.isfinite(ts)))
                     and bool(np.all(np.isfinite(vals)))
                     and not np.any(st.has[dev] & (ts[0] <= st.last_t[dev])))
        if not clean:
            rep = self.ingest(np.repeat(dev, m), np.tile(ts, d),
                              vals.ravel())
            return (dataclasses.replace(rep, rejected=rep.rejected + n_rej)
                    if n_rej else rep)
        self.epoch += 1

        with span("ingest.gather"):
            v = vals - self.corrections.baseline_w[dev][:, None]
            ops = self._operands(dev, ts[0])

        with span("ingest.kernel", samples=d * m, devices=d):
            (new_v, new_run_t, new_nchg, d_e, d_ec, d_w, d_wc,
             sum_vc, sum_vc2, sum_abs_vc, max_abs_vc, n_out,
             cum_e, cum_ec, run_dur, run_rec) = \
                self._be.stream_ingest_grid(ts, v, ops, self.trapezoid)

        # ring snapshots see running totals *before* this slab is folded
        with span("ingest.ring"):
            if self.ring.slots:
                self.ring.write_grid(dev, ts, v,
                                     st.energy_j[dev][:, None] + cum_e,
                                     st.energy_corr_j[dev][:, None] + cum_ec)
            else:
                self.ring.n_written[dev] += m

        if self.history is not None:
            self._history_grid(dev, ops.has_prev, ts, v, cum_e, cum_ec)
        self._write_back(dev, ops, ts[0], ts[-1], m, new_v, new_run_t,
                         new_nchg, d_e, d_ec, d_w, d_wc, sum_vc, n_out)
        self._record_periods(dev[:, None], run_dur, run_rec)
        # label sums from the kernel's per-device sums, O(D + labels)
        self._merge_moments(dev, m, sum_vc, sum_vc2, sum_abs_vc, max_abs_vc)
        self._maybe_update_health(float(ts[-1]))
        return IngestReport(d * m, 0, 0, 0, d, n_rej)

    # -- the slab fold both entry points share -----------------------------
    def _known_ids(self, dev, per_id: int):
        """``(mask, rejected)``: the mask of ``dev``'s in-range ids, or
        None when all are, and the samples rejected with the others,
        ``per_id`` each, added to the counter.  Out-of-range ids raise
        instead under ``strict_ids``."""
        if not dev.size or (dev.min() >= 0 and dev.max() < self.n_devices):
            return None, 0
        if self.strict_ids:
            raise ValueError("device id out of range")
        ok_id = (dev >= 0) & (dev < self.n_devices)
        n_rej = int(ok_id.size - ok_id.sum()) * per_id
        self._n_rejected += n_rej
        return ok_id, n_rej

    def _operands(self, idx, t0) -> SlabOperands:
        """The kernel's operands for devices ``idx``: their stored state
        and configuration; a device with no stored sample starts its run
        at ``t0``, its first sample in the slab."""
        st, c = self.state, self.corrections
        had = st.has[idx]
        return SlabOperands(
            prev_t=st.last_t[idx], prev_v=st.last_v[idx], has_prev=had,
            run_t=np.where(had, st.run_t[idx], t0),
            n_changes=st.n_changes[idx], gain=c.gain[idx],
            offset=c.offset_w[idx], tshift=c.time_shift_s[idx],
            win_a=self._win_a[idx], win_b=self._win_b[idx],
            max_hold=self._max_hold[idx], env_lo=self._env_lo[idx],
            env_hi=self._env_hi[idx])

    def _write_back(self, idx, ops, t0, t_end, counts, new_v, new_run_t,
                    new_nchg, d_e, d_ec, d_w, d_wc, sum_vc, n_out) -> None:
        """Fold the kernel's per-device results into the state of devices
        ``idx``, whose operands were ``ops``.  ``t0``/``t_end`` are each
        device's first and last sample time in the slab and ``counts``
        its sample count: [U] on the flat path, scalars that every device
        shares on the grid path."""
        st, had = self.state, ops.has_prev
        with span("ingest.scatter"):
            st.first_t[idx] = np.where(had, st.first_t[idx], t0)
            st.last_t[idx] = t_end
            st.last_v[idx] = new_v
            st.has[idx] = True
            st.n_samples[idx] += counts
            st.energy_j[idx] += d_e
            st.energy_corr_j[idx] += d_ec
            st.win_j[idx] += d_w
            st.win_corr_j[idx] += d_wc
            st.run_t[idx] = new_run_t
            st.n_changes[idx] = new_nchg
            st.n_out[idx] += n_out

            # drift EWMA over wall time, one slab-mean step per device
            mean_vc = sum_vc / counts
            alpha = np.exp(-np.maximum(t_end - ops.prev_t, 0.0)
                           / self.drift_tau_s)
            st.ewma_w[idx] = np.where(
                had, alpha * st.ewma_w[idx] + (1.0 - alpha) * mean_vc,
                mean_vc)

    def _record_periods(self, dev, run_dur, run_rec) -> None:
        """Record the slab's complete reading runs; ``dev`` broadcasts to
        the kernel's per-sample shape."""
        with span("ingest.periods"):
            rec = np.asarray(run_rec, dtype=bool)
            if np.any(rec):
                self.periods.record(np.broadcast_to(dev, rec.shape)[rec],
                                    np.asarray(run_dur)[rec])

    def _merge_moments(self, dev, n_each, s1, s2, sa, mx) -> None:
        """Merge one slab's corrected-reading moments into the per-label
        accumulators (Chan–Welford).  Entry ``i`` covers ``n_each`` readings
        of device ``dev[i]``: their sum ``s1``, sum of squares ``s2``, sum
        of magnitudes ``sa`` and largest magnitude ``mx``.  One bincount
        pass per sum, O(entries + labels): no per-label masks, so
        per-device labels stay cheap at fleet scale."""
        with span("ingest.moments"):
            codes = self._label_codes[dev]
            nl = len(self._label_names)
            cnt = n_each * np.bincount(codes, minlength=nl)
            s1 = np.bincount(codes, weights=s1, minlength=nl)
            s2 = np.bincount(codes, weights=s2, minlength=nl)
            sa = np.bincount(codes, weights=sa, minlength=nl)
            mxl = np.zeros(nl)
            np.maximum.at(mxl, codes, mx)
            for ci in np.flatnonzero(cnt):
                nb = int(cnt[ci])
                mean = s1[ci] / nb
                m2 = max(float(s2[ci] - nb * mean * mean), 0.0)
                self._moments.setdefault(
                    self._label_names[ci], StreamingMoments()).merge(
                        nb, float(mean), m2, float(sa[ci] / nb),
                        float(mxl[ci]))

    # -- history tier ------------------------------------------------------
    # Boundaries are written before the slab's state is scattered: a
    # boundary held from the device's previous newest sample reads that
    # sample's stored energy and reading.

    def _history_grid(self, dev, had, ts, v, cum_e, cum_ec) -> None:
        """Write the boundaries a rectangular slab passes: the sample
        each boundary holds from is the same column for every device."""
        st = self.state
        row, b = self.history.plan(
            dev, np.where(had, st.first_t[dev], ts[0]), ts[-1])
        with span("ingest.history", devices=dev.size, boundaries=b.size):
            j = np.searchsorted(ts, b * self.history.step_s,
                                side="right") - 1
            jc = np.maximum(j, 0)
            self._history_record(dev[row], b, j >= 0, ts[jc], v[row, jc],
                                 cum_e[row, jc], cum_ec[row, jc])

    def _history_flat(self, u_dev, had, t, v, start_idx, end_idx, cum_e,
                      cum_ec) -> None:
        """Write the boundaries a sorted, grouped slab passes: each
        boundary's sample is found by bisection within its device's
        group."""
        st = self.state
        row, b = self.history.plan(
            u_dev, np.where(had, st.first_t[u_dev], t[start_idx]),
            t[end_idx])
        with span("ingest.history", devices=u_dev.size, boundaries=b.size):
            bt = b * self.history.step_s
            lo, hi = start_idx[row], end_idx[row] + 1
            width = int(np.max(hi - lo, initial=0))
            for _ in range(width.bit_length()):
                mid = np.minimum((lo + hi) // 2, t.size - 1)
                right = (lo < hi) & (t[mid] <= bt)
                hi = np.where((lo < hi) & ~right, mid, hi)
                lo = np.where(right, mid + 1, lo)
            j = lo - 1
            jc = np.maximum(j, 0)
            self._history_record(u_dev[row], b, j >= start_idx[row], t[jc],
                                 v[jc], cum_e[jc], cum_ec[jc])

    def _history_record(self, d, b, in_slab, t_j, v_j, cum_j, cumc_j):
        """Held energy at boundaries ``b`` of devices ``d`` [P]: from the
        slab's sample ``j`` where ``in_slab`` (its running energy is the
        stored one plus ``cum_j``), else from the device's stored newest
        sample — the ring's rule, in the same operations."""
        st, c = self.state, self.corrections
        e_raw, e_corr = st.energy_j[d], st.energy_corr_j[d]
        e_raw = np.where(in_slab, e_raw + cum_j, e_raw)
        e_corr = np.where(in_slab, e_corr + cumc_j, e_corr)
        t_j = np.where(in_slab, t_j, st.last_t[d])
        v_j = np.where(in_slab, v_j, st.last_v[d])
        hold = np.minimum(b * self.history.step_s - t_j, self._max_hold[d])
        vc = (v_j - c.offset_w[d]) / c.gain[d]
        self.history.write(d, b, e_raw + v_j * hold, e_corr + vc * hold)

    # -- health -----------------------------------------------------------
    def _maybe_update_health(self, t_now: float) -> None:
        """Run the health machine at a slab boundary, throttled to at
        most once per ``health_every_s`` of stream time.  Time going
        *backward* across slabs (chunked replays re-start the clock per
        device slab) never triggers an evaluation, so chunk order cannot
        quarantine devices that simply haven't been streamed yet."""
        if self.health is None or not np.isfinite(t_now):
            return
        if t_now < self._next_health_t:
            return
        self._next_health_t = t_now + self.health_every_s
        with span("ingest.health", devices=self.n_devices):
            self.update_health(t_now, _bump_epoch=False)

    def update_health(self, t_now: float, _bump_epoch: bool = True) -> bool:
        """Evaluate one health step at wall-clock ``t_now`` (no-op
        without a policy).  Returns True when any device changed state;
        an explicit call that changes state bumps the epoch (ingestion's
        own slab-boundary evaluations ride the slab's bump)."""
        if self.health is None:
            return False
        changed = self.health.update(
            self.state, t_now=float(t_now), policy=self.health_policy,
            period_est=self.periods.estimates(),
            ref_period_s=self.corrections.ref_period_s,
            silent_after_s=self.silent_after_s,
            drift_tau_s=self.drift_tau_s, drift_rel=self.drift_rel,
            drift_abs_w=self.drift_abs_w)
        if changed and _bump_epoch:
            self.epoch += 1
        return changed

    # -- accounting -------------------------------------------------------
    @property
    def counters(self) -> Dict[str, int]:
        st = self.state
        out = {
            "accepted": int(np.sum(st.n_samples)),
            "duplicates": int(np.sum(st.n_dup)),
            "late": int(np.sum(st.n_late)),
            "invalid": self._n_invalid,
            "rejected": self._n_rejected,
            "devices_reporting": int(np.sum(st.has)),
        }
        if self.health is not None:
            out.update(self.health.counts())
        return out
