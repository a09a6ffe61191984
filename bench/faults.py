"""The benchmark's own copy of the monitor's fault injector.

A copy of ``FaultSpec``/``InjectionLog``/``FaultInjector`` from
``repro.core.stream.replay``, so that the traffic a cell replays is made
by the benchmark and not by the code under test.  Every per-slab
decision comes from ``default_rng((seed, slab_seq))``; the plan (clock
drift and skew, dropout instants, collector restarts) from
``default_rng((seed, plan))``.  ``tests/bench_chip`` checks it against the
original on a few slabs.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

_FRACTIONS = ("dup_fraction", "drop_fraction", "delay_fraction",
              "corrupt_fraction", "dropout_fraction", "dropout_after")
# substream tags for the plan/slab rng derivations (any fixed ints work;
# they only have to differ so plan draws never alias slab draws)
_PLAN_STREAM = 101
_SLAB_STREAM = 202


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """Declarative transport/collector fault configuration.

    Legacy transport knobs (identical semantics to the old ``replay``
    keyword arguments):

    * ``shuffle`` — permute each slab's arrival order,
    * ``dup_fraction`` — re-emit that fraction of samples,
    * ``drop_fraction`` — remove samples (sampling gaps),
    * ``delay_fraction`` — hold samples back one slab (arrive late).

    Fault-domain taxonomy:

    * ``clock_drift`` / ``clock_skew_s`` — each device's reported
      timestamps become ``skew_i + (1 + rate_i) · t`` with ``rate_i``
      uniform in ``±clock_drift`` and ``skew_i`` uniform in
      ``±clock_skew_s`` (unsynchronised device/collector clocks),
    * ``restart_every_s`` — collector restarts at exponentially-spaced
      instants; every sample inside the following
      ``restart_blackout_s`` window is lost (slab stream truncated and
      resumed),
    * ``corrupt_fraction`` — that fraction of samples is garbled:
      values to NaN/inf, device ids pushed out of range, timestamps to
      NaN (all detectable, so a defensive ingest rejects and counts
      them; see ``MonitorService(strict_ids=False)``),
    * ``dropout_fraction`` — that fraction of devices dies permanently
      at a uniform instant in the last ``1 - dropout_after`` of the
      replay span and never reports again.

    Everything is seeded and composable; ``FaultInjector`` realises the
    spec with per-slab rng substreams, so any slab's faults reproduce
    independently of how many slabs came before it.
    """

    shuffle: bool = False
    dup_fraction: float = 0.0
    drop_fraction: float = 0.0
    delay_fraction: float = 0.0
    clock_drift: float = 0.0
    clock_skew_s: float = 0.0
    restart_every_s: float = 0.0
    restart_blackout_s: float = 0.05
    corrupt_fraction: float = 0.0
    dropout_fraction: float = 0.0
    dropout_after: float = 0.35
    seed: int = 0

    def __post_init__(self):
        for name in _FRACTIONS:
            f = getattr(self, name)
            if not 0.0 <= f <= 1.0:
                raise ValueError(f"{name} must be in [0, 1], got {f}")
        if not 0.0 <= self.clock_drift < 1.0:
            raise ValueError("clock_drift must be in [0, 1) — a rate "
                             "error of ±100% would reverse time")
        if self.clock_skew_s < 0.0:
            raise ValueError("clock_skew_s must be >= 0")
        if self.restart_every_s < 0.0 or self.restart_blackout_s < 0.0:
            raise ValueError("restart intervals must be >= 0")

    @property
    def any(self) -> bool:
        """Whether any fault is active (False → clean, grid-eligible)."""
        return bool(self.shuffle or self.dup_fraction or self.drop_fraction
                    or self.delay_fraction or self.clock_drift
                    or self.clock_skew_s or self.restart_every_s
                    or self.corrupt_fraction or self.dropout_fraction)

    def counts_zero(self) -> Dict[str, int]:
        """The all-zero injection-count dict (clean replays report it)."""
        return {k: 0 for k in _COUNT_KEYS}


_COUNT_KEYS = ("dropped_out", "blacked_out", "dropped", "corrupt_value",
               "corrupt_id", "corrupt_time", "duplicated", "delayed",
               "shuffled_slabs")


@dataclasses.dataclass
class InjectionLog:
    """Machine-readable record of every injection decision.

    ``counts`` aggregates per category; ``slabs`` records one dict per
    slab (seq, samples in/out, per-category counts); the plan arrays
    (``drift_rate``/``skew_s`` per device, ``dropout_t`` — ``+inf`` for
    survivors — and collector ``restarts``) fully determine the
    deterministic part.  Together with the spec, the log reproduces the
    exact faulty stream: feed the same spec/span to a fresh
    :class:`FaultInjector` and every decision repeats bit-for-bit.
    """

    spec: FaultSpec
    n_devices: int
    t0: float
    t1: float
    drift_rate: np.ndarray          # [N] per-device clock rate error
    skew_s: np.ndarray              # [N] per-device clock offset
    dropout_t: np.ndarray           # [N] death instant, +inf = never
    restarts: np.ndarray            # [R] collector restart instants
    counts: Dict[str, int] = dataclasses.field(default_factory=dict)
    slabs: list = dataclasses.field(default_factory=list)

    def summary(self) -> dict:
        """JSON-able digest (plan extremes + aggregate counts)."""
        dead = np.flatnonzero(np.isfinite(self.dropout_t))
        return {
            "seed": self.spec.seed,
            "n_devices": self.n_devices,
            "span": [self.t0, self.t1],
            "n_slabs": len(self.slabs),
            "counts": dict(self.counts),
            "restarts": [float(r) for r in self.restarts],
            "dropped_out_devices": [int(d) for d in dead],
            "dropout_t": [float(self.dropout_t[d]) for d in dead],
            "max_abs_drift": float(np.max(np.abs(self.drift_rate),
                                          initial=0.0)),
            "max_abs_skew_s": float(np.max(np.abs(self.skew_s),
                                           initial=0.0)),
        }


class FaultInjector:
    """Realise a :class:`FaultSpec` over a slab stream, deterministically.

    The device-level plan (drift rates, skews, dropout instants, restart
    schedule) is drawn once from ``default_rng((seed, plan))``; every
    per-slab decision comes from ``default_rng((seed, slab, seq))`` — so
    slab ``seq`` injects identical faults no matter how the stream is
    resumed or re-chunked upstream, which is what makes crash-recovery
    replays bitwise comparable to uninterrupted ones.

    ``apply(seq, dev, ts, vs)`` returns the faulted slab; delayed
    samples are held internally and prepended to the next ``apply``;
    call :meth:`flush` after the source is exhausted to collect any
    still-held tail.
    """

    def __init__(self, spec: FaultSpec, n_devices: int,
                 t0: float, t1: float):
        if n_devices < 1:
            raise ValueError("need at least one device")
        self.spec = spec
        self.n_devices = int(n_devices)
        plan = np.random.default_rng((spec.seed, _PLAN_STREAM))
        n = self.n_devices
        drift = (spec.clock_drift * plan.uniform(-1.0, 1.0, n)
                 if spec.clock_drift else np.zeros(n))
        skew = (spec.clock_skew_s * plan.uniform(-1.0, 1.0, n)
                if spec.clock_skew_s else np.zeros(n))
        dropout_t = np.full(n, np.inf)
        if spec.dropout_fraction:
            dead = plan.random(n) < spec.dropout_fraction
            at = plan.uniform(spec.dropout_after, 1.0, n)
            dropout_t[dead] = t0 + at[dead] * (t1 - t0)
        restarts = []
        if spec.restart_every_s:
            t = float(t0)
            while True:
                t += plan.exponential(spec.restart_every_s)
                if t >= t1:
                    break
                restarts.append(t)
        self.log = InjectionLog(
            spec=spec, n_devices=n, t0=float(t0), t1=float(t1),
            drift_rate=drift, skew_s=skew, dropout_t=dropout_t,
            restarts=np.asarray(restarts, dtype=np.float64),
            counts=spec.counts_zero())
        self._held = None

    def reset(self) -> None:
        """Drop any held (delayed) samples, e.g. before re-playing the
        stream from the top; the plan and log are kept."""
        self._held = None

    def apply(self, seq: int, dev, ts, vs):
        """Inject slab ``seq``'s faults; returns ``(dev, ts, vs)``."""
        spec = self.spec
        c = self.log.counts
        rng = np.random.default_rng((spec.seed, _SLAB_STREAM, int(seq)))
        dev = np.asarray(dev, dtype=np.int64).ravel()
        ts = np.asarray(ts, dtype=np.float64).ravel()
        vs = np.asarray(vs, dtype=np.float64).ravel()
        rec = {"seq": int(seq), "in": int(dev.size)}
        # device deaths and collector blackouts act on true (collector)
        # time, before the device clock garbles the reported timestamps
        if spec.dropout_fraction and dev.size:
            alive = ts < self.log.dropout_t[dev]
            k = int(alive.size - alive.sum())
            if k:
                dev, ts, vs = dev[alive], ts[alive], vs[alive]
                c["dropped_out"] += k
                rec["dropped_out"] = k
        if self.log.restarts.size and dev.size:
            black = np.zeros(ts.shape, dtype=bool)
            for r in self.log.restarts:
                black |= (ts >= r) & (ts < r + spec.restart_blackout_s)
            k = int(black.sum())
            if k:
                keep = ~black
                dev, ts, vs = dev[keep], ts[keep], vs[keep]
                c["blacked_out"] += k
                rec["blacked_out"] = k
        if spec.clock_drift or spec.clock_skew_s:
            ts = self.log.skew_s[dev] + (1.0 + self.log.drift_rate[dev]) * ts
        if spec.drop_fraction and dev.size:
            keep = rng.random(dev.size) >= spec.drop_fraction
            k = int(keep.size - keep.sum())
            if k:
                dev, ts, vs = dev[keep], ts[keep], vs[keep]
                c["dropped"] += k
                rec["dropped"] = k
        if spec.corrupt_fraction and dev.size:
            hit = np.flatnonzero(rng.random(dev.size) < spec.corrupt_fraction)
            if hit.size:
                cat = rng.integers(0, 4, hit.size)
                dev, ts, vs = dev.copy(), ts.copy(), vs.copy()
                vs[hit[cat == 0]] = np.nan
                vs[hit[cat == 1]] = np.inf
                dev[hit[cat == 2]] += self.n_devices    # out-of-range id
                ts[hit[cat == 3]] = np.nan
                nv = int(np.sum(cat <= 1))
                ni = int(np.sum(cat == 2))
                nt = int(np.sum(cat == 3))
                c["corrupt_value"] += nv
                c["corrupt_id"] += ni
                c["corrupt_time"] += nt
                rec["corrupt"] = nv + ni + nt
        if spec.dup_fraction and dev.size:
            extra = rng.random(dev.size) < spec.dup_fraction
            k = int(extra.sum())
            if k:
                dev = np.concatenate([dev, dev[extra]])
                ts = np.concatenate([ts, ts[extra]])
                vs = np.concatenate([vs, vs[extra]])
                c["duplicated"] += k
                rec["duplicated"] = k
        if spec.delay_fraction and dev.size:
            hold = rng.random(dev.size) < spec.delay_fraction
            new_held = (dev[hold], ts[hold], vs[hold])
            dev, ts, vs = dev[~hold], ts[~hold], vs[~hold]
            k = int(hold.sum())
            if k:
                c["delayed"] += k
                rec["delayed"] = k
        else:
            new_held = None
        if self._held is not None:
            dev = np.concatenate([self._held[0], dev])
            ts = np.concatenate([self._held[1], ts])
            vs = np.concatenate([self._held[2], vs])
        self._held = new_held
        if spec.shuffle and dev.size:
            perm = rng.permutation(dev.size)
            dev, ts, vs = dev[perm], ts[perm], vs[perm]
            c["shuffled_slabs"] += 1
        rec["out"] = int(dev.size)
        self.log.slabs.append(rec)
        return dev, ts, vs

    def flush(self):
        """Hand back any still-held delayed samples (possibly empty)."""
        held = self._held
        self._held = None
        if held is None:
            return (np.empty(0, dtype=np.int64), np.empty(0), np.empty(0))
        return held
