"""Batched query serving over the streaming fleet monitor.

The serving counterpart of :mod:`repro.serve.engine`'s slot loop, for
monitor queries instead of token decoding: callers ``submit`` any mix
of ``fleet_energy`` / ``window_energy`` / ``energy_between`` /
``by_label`` / ``fleet_series`` requests, and ``flush`` executes the
whole batch against **one** immutable
:class:`~repro.core.stream.snapshot.MonitorSnapshot`:

* all distinct query instants of a flavour collapse into a single
  energy-at call ([Q, N] — one vectorized array op however many
  thousand requests are queued; the history tier's kernel for
  instants on its boundaries, ``snapshot_energy_at`` for the rest);
* each ``fleet_series`` (a dashboard panel over the history tier) is
  one series kernel call that reduces over devices on the backend, and
  each ``by_label`` over two tier boundaries one by-label kernel call;
* every history-tier call of a flush runs with one upload of their
  instants and one fetch of all their results (the snapshot's operands
  go up once, on its first flush);
* results are memoised in an LRU cache keyed ``(query, epoch)`` —
  an epoch tag in every key means a result can never be served against
  a different snapshot than the one that computed it;
* duplicate queries inside one batch are computed once and fanned out.

Results are the same objects the direct ``MonitorService`` query
methods return, produced through the same snapshot reduction helpers —
on the numpy backend the executor's answers are *bitwise* equal to the
direct path (pinned in ``tests/test_serving.py``).

Usage::

    svc = MonitorQueryService(mon)
    tickets = [svc.submit(MonitorQuery.fleet_energy(t)) for t in instants]
    results = svc.flush()               # {ticket: FleetEnergy}
    one = svc.query(MonitorQuery.energy_between(2.0, 4.0))
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.common.trace import span
from repro.core.stream.monitor import MonitorService
from repro.core.stream.snapshot import (MonitorSnapshot, series_multiple,
                                        series_range)

_KINDS = ("fleet_energy", "window_energy", "energy_between", "by_label",
          "fleet_series")


@dataclasses.dataclass(frozen=True)
class MonitorQuery:
    """One hashable monitor query (build via the factory classmethods —
    they validate the edge contract at construction, so a malformed
    query fails at submit time, not deep inside a batch)."""

    kind: str
    t: Optional[float] = None
    t0: Optional[float] = None
    t1: Optional[float] = None
    corrected: bool = True
    step: Optional[float] = None

    def __post_init__(self):
        if self.kind not in _KINDS:
            raise ValueError(f"unknown query kind '{self.kind}'; "
                             f"known: {', '.join(_KINDS)}")

    @classmethod
    def fleet_energy(cls, t: Optional[float] = None,
                     corrected: bool = True) -> "MonitorQuery":
        return cls("fleet_energy", t=None if t is None else float(t),
                   corrected=corrected)

    @classmethod
    def window_energy(cls, t: Optional[float] = None,
                      corrected: bool = True) -> "MonitorQuery":
        return cls("window_energy", t=None if t is None else float(t),
                   corrected=corrected)

    @classmethod
    def energy_between(cls, t0: float, t1: float,
                       corrected: bool = True) -> "MonitorQuery":
        t0, t1 = float(t0), float(t1)
        if not (t1 >= t0):        # also rejects NaN endpoints
            raise ValueError(f"bad window [{t0}, {t1}]")
        return cls("energy_between", t0=t0, t1=t1, corrected=corrected)

    @classmethod
    def by_label(cls, t0: Optional[float] = None,
                 t1: Optional[float] = None,
                 corrected: bool = True) -> "MonitorQuery":
        if (t0 is None) != (t1 is None):
            raise ValueError("pass both t0 and t1, or neither")
        if t0 is not None:
            t0, t1 = float(t0), float(t1)
            if not (t1 >= t0):
                raise ValueError(f"bad window [{t0}, {t1}]")
        return cls("by_label", t0=t0, t1=t1, corrected=corrected)

    @classmethod
    def fleet_series(cls, t0: float, t1: float, step: float,
                     corrected: bool = True) -> "MonitorQuery":
        """Fleet energy at each multiple of ``step`` in ``[t0, t1]`` and
        power over each step (a dashboard panel).  Raises for a
        non-finite or reversed range and a step that is not a positive
        number; a step that is not a multiple of the monitor's history
        step raises at :meth:`MonitorQueryService.submit`."""
        t0, t1, step = series_range(t0, t1, step)
        return cls("fleet_series", t0=t0, t1=t1, corrected=corrected,
                   step=step)


class MonitorQueryService:
    """Queue + batch executor + ``(query, epoch)`` LRU over one monitor.

    ``cache_size`` bounds the number of memoised results (fleet-energy
    answers carry [N] per-device arrays, so size the cache against
    ``n_devices`` — the default keeps a 100k-device monitor under
    ~250 MB worst-case).
    """

    def __init__(self, monitor: MonitorService, cache_size: int = 256):
        if cache_size < 0:
            raise ValueError("cache_size must be >= 0")
        self.monitor = monitor
        self.cache_size = int(cache_size)
        self._cache: "OrderedDict[Tuple[MonitorQuery, int], Any]" = \
            OrderedDict()
        self._pending: List[Tuple[int, MonitorQuery]] = []
        self._next_ticket = 0
        self.n_submitted = 0
        self.n_hits = 0
        self.n_misses = 0
        self.n_flushes = 0
        # where each executed instant was answered from, and series
        # kernel calls
        self.n_instants_tier = 0
        self.n_instants_ring = 0
        self.n_instants_uncovered = 0
        self.n_series_calls = 0
        # by-label answers reduced on the backend, and grouped on the host
        self.n_labels_device = 0
        self.n_labels_host = 0

    # -- request management ------------------------------------------------
    def submit(self, query: MonitorQuery) -> int:
        """Queue one query; returns the ticket that keys its result in
        the next :meth:`flush`."""
        if not isinstance(query, MonitorQuery):
            raise TypeError(f"submit takes a MonitorQuery, "
                            f"got {type(query).__name__}")
        if query.kind == "fleet_series":
            history = self.monitor.history
            if history is None:
                raise ValueError("fleet_series needs a monitor with a "
                                 "history tier (history_steps > 0)")
            series_multiple(query.step, history.step_s)
        ticket = self._next_ticket
        self._next_ticket += 1
        self.n_submitted += 1
        self._pending.append((ticket, query))
        return ticket

    def query(self, query: MonitorQuery):
        """Submit + flush a single query (convenience; batching still
        applies to whatever else is already queued)."""
        ticket = self.submit(query)
        return self.flush()[ticket]

    def query_many(self, queries: List[MonitorQuery]) -> List[Any]:
        """Submit a batch and flush once; results in input order.  The
        one-call shape the collector CLI uses for its replay summary —
        every distinct instant still collapses into one kernel call."""
        tickets = [self.submit(q) for q in queries]
        results = self.flush()
        return [results[t] for t in tickets]

    # -- execution ---------------------------------------------------------
    def flush(self) -> Dict[int, Any]:
        """Execute every pending query against the monitor's *current*
        snapshot and return ``{ticket: result}``.

        Cache hits are served without touching the snapshot arrays;
        misses are deduplicated, grouped by kind, and executed as one
        vectorized op per (kind, corrected) group.
        """
        if not self._pending:
            return {}
        snap = self.monitor.snapshot()
        epoch = snap.epoch
        self.n_flushes += 1
        pending, self._pending = self._pending, []

        # dedup: every distinct query computes once per flush
        tickets_for: "OrderedDict[MonitorQuery, List[int]]" = OrderedDict()
        for ticket, q in pending:
            tickets_for.setdefault(q, []).append(ticket)

        results: Dict[MonitorQuery, Any] = {}
        misses: List[MonitorQuery] = []
        for q in tickets_for:
            key = (q, epoch)
            if key in self._cache:
                self._cache.move_to_end(key)
                results[q] = self._cache[key]
                self.n_hits += len(tickets_for[q])
            else:
                misses.append(q)
                self.n_misses += len(tickets_for[q])

        with span("serve.flush", queries=len(pending), misses=len(misses)):
            for q, res in self._execute(snap, misses).items():
                results[q] = res
                if self.cache_size:
                    self._cache[(q, epoch)] = res
            while len(self._cache) > self.cache_size:
                self._cache.popitem(last=False)

            return {ticket: results[q]
                    for q, ts in tickets_for.items() for ticket in ts}

    def _execute(self, snap: MonitorSnapshot,
                 misses: List[MonitorQuery]) -> Dict[MonitorQuery, Any]:
        """Run the deduplicated cache misses against one snapshot: plan
        every history-tier call of the flush, run them all with one
        upload and one fetch (:meth:`MonitorSnapshot.prefetched`), then
        answer each query through the snapshot's own methods.  The span
        ``serve.execute`` carries the host↔device buffers the flush
        moves (``h2d``, ``d2h``)."""
        calls: List[tuple] = []
        # energy-at instants per corrected flavour: fleet_energy(t) needs
        # one row, energy_between(t0, t1) two, and so does a by_label
        # whose instants are not both on the tier; by_label over two tier
        # instants is reduced on the backend instead
        rows: Dict[bool, tuple] = {}
        labels: Dict[MonitorQuery, tuple] = {}
        for corrected in (True, False):
            instants: List[float] = []
            seen: Dict[float, int] = {}

            def row_of(t: float) -> int:
                if t not in seen:
                    seen[t] = len(instants)
                    instants.append(t)
                return seen[t]

            plan: List[Tuple[MonitorQuery, Tuple[int, ...]]] = []
            for q in misses:
                if q.corrected != corrected:
                    continue
                if q.kind == "fleet_energy" and q.t is not None:
                    plan.append((q, (row_of(q.t),)))
                elif q.kind in ("energy_between", "by_label") \
                        and q.t0 is not None:
                    call = (snap.label_call(q.t0, q.t1, corrected)
                            if q.kind == "by_label" else None)
                    if call is None:
                        plan.append((q, (row_of(q.t0), row_of(q.t1))))
                    else:
                        labels[q] = call
                        calls.append(call)
            if plan:
                tq = np.array(instants)
                on = snap.on_tier(tq)
                if on.any():
                    calls.append(snap.tier_call("energy_at", tq[on],
                                                corrected))
                rows[corrected] = (tq, on, plan)
        for q in misses:
            if q.kind == "fleet_series":
                calls.append(snap.series_call(
                    snap.series_instants(q.t0, q.t1, q.step), q.step,
                    q.corrected))

        n_ring = sum(not on.all() for _, on, _ in rows.values())
        h2d, d2h = snap.transfers(len(calls), n_ring)
        with span("serve.execute", h2d=h2d, d2h=d2h), \
                snap.prefetched(calls):
            return self._answer(snap, misses, rows, labels)

    def _answer(self, snap: MonitorSnapshot, misses: List[MonitorQuery],
                rows: dict, labels: dict) -> Dict[MonitorQuery, Any]:
        """Each miss's answer, planned by :meth:`_execute`, inside its
        :meth:`MonitorSnapshot.prefetched` block."""
        out: Dict[MonitorQuery, Any] = {}
        for corrected, (tq, on, plan) in rows.items():
            e, cov = snap.energy_at_batch(tq, corrected)
            self.n_instants_tier += int(np.sum(on))
            self.n_instants_ring += int(np.sum(~on))
            self.n_instants_uncovered += int(np.sum(~cov.all(axis=1)))
            for q, r in plan:
                if q.kind == "fleet_energy":
                    out[q] = snap.fleet_from_rows(
                        q.t, corrected, e[r[0]].copy(), cov[r[0]].copy())
                    continue
                de, dc = snap.between_from_rows(e[r[0]], cov[r[0]],
                                                e[r[1]], cov[r[1]])
                if q.kind == "energy_between":
                    out[q] = (de, dc)
                else:
                    out[q] = snap.by_label_rows(de, dc & snap.state.has)
                    self.n_labels_host += 1
        for q, call in labels.items():
            (raw,) = snap.run_tier([call])
            out[q] = snap.labels_from(raw)
            self.n_labels_device += 1
            self.n_instants_tier += raw[5].size
            self.n_instants_uncovered += int(np.sum(raw[5] <
                                                    snap.n_devices))
        # each series is one reduction kernel over the history tier
        for q in misses:
            if q.kind == "fleet_series":
                out[q] = res = snap.fleet_series(q.t0, q.t1, q.step,
                                                 q.corrected)
                self.n_series_calls += 1
                self.n_instants_tier += int(res.t.size)
                self.n_instants_uncovered += int(
                    np.sum(res.n_covered < snap.n_devices))

        # window_energy(t): all instants of a flavour in one broadcast
        for corrected in (True, False):
            wq = [q for q in misses
                  if q.kind == "window_energy" and q.corrected == corrected
                  and q.t is not None]
            if wq:
                wt = {t: i for i, t in enumerate(dict.fromkeys(
                    q.t for q in wq))}
                we = snap.window_energy_batch(np.array(list(wt)), corrected)
                for q in wq:
                    out[q] = we[wt[q.t]].copy()

        # the t=None / since-start variants read snapshot arrays directly
        for q in misses:
            if q in out:
                continue
            if q.kind == "fleet_energy":
                out[q] = snap.fleet_energy(None, q.corrected)
            elif q.kind == "window_energy":
                out[q] = snap.window_energy(None, q.corrected)
            elif q.kind == "by_label":
                out[q] = snap.by_label(None, None, q.corrected)
                self.n_labels_host += 1
            else:                                    # pragma: no cover
                raise AssertionError(f"unplanned query {q}")
        return out

    # -- accounting --------------------------------------------------------
    def stats(self) -> Dict[str, float]:
        """Executor counters: submissions, cache hit rate, flushes, the
        instants executed from the history tier, from the ring and with
        some device not covered, series kernel calls, and the by-label
        answers reduced on the backend (``labels_device``) and grouped
        on the host (``labels_host``)."""
        answered = self.n_hits + self.n_misses
        return {
            "n_submitted": self.n_submitted,
            "n_answered": answered,
            "n_pending": len(self._pending),
            "cache_hits": self.n_hits,
            "cache_misses": self.n_misses,
            "cache_hit_rate": (self.n_hits / answered) if answered else 0.0,
            "cache_entries": len(self._cache),
            "n_flushes": self.n_flushes,
            "instants_tier": self.n_instants_tier,
            "instants_ring": self.n_instants_ring,
            "instants_uncovered": self.n_instants_uncovered,
            "series_calls": self.n_series_calls,
            "labels_device": self.n_labels_device,
            "labels_host": self.n_labels_host,
        }
