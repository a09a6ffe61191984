"""NumPy implementation of the fleet-engine kernels.

This module is the reference semantics: every function is a pure array
function (no hidden state, no RNG) extracted from the original
``fleet_engine`` / ``ground_truth`` hot paths.  The JAX backend
(:mod:`repro.core.engine_backend.jax_backend`) reimplements the same
signatures with ``jax.jit`` + ``vmap``; parity is pinned by
``tests/test_engine_backend.py`` to within one reporting quantum.

Kernels
-------
* :func:`searchsorted_rows`     — row-wise exact binary search
* :func:`timeline_integral`     — exact per-row ∫P dt (idle outside coverage)
* :func:`boxcar_means`          — batched trailing-window means
* :func:`estimation_means`      — activity-proxy means (boxcar × model gain)
* :func:`log_filter`            — first-order-filter segment scan
* :func:`poll_counts`           — closed-form poll counting for
  ``integrate_polled`` (how many uniform poll instants land in each
  reading interval, plus the partial final step)
* :func:`step_integrate`        — batched rectangle/trapezoid
  integration of sampled reading series (the single source of truth
  shared by ``meter._integrate_readings`` and the streaming monitor)
* :func:`stream_ingest`         — the streaming monitor's hot path:
  one slab of (device, t, reading) samples folded into per-device
  online accumulators (energy, windowed energy, run tracking)
* :func:`stream_ingest_grid`    — the rectangular fast path of
  ``stream_ingest``: D devices × one shared strictly-increasing time
  axis, all accumulators row-wise (no sorting or segmented reductions)

No module in this file imports from the rest of :mod:`repro` — backends
sit at the bottom of the dependency graph so ``ground_truth`` and
``fleet_engine`` can both build on them.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from repro.core.engine_backend.pytrees import (PollGrid, ReadingSchedule,
                                               SlabOperands, TierOperands,
                                               TimelineArrays,
                                               unpack_tier_operands)

name = "numpy"

_FAR = np.iinfo(np.int64).max // 2


def searchsorted_rows(a: np.ndarray, v: np.ndarray,
                      side: str = "right") -> np.ndarray:
    """Row-wise ``np.searchsorted``: sorted rows ``a`` [R, S] against query
    rows ``v`` [G, M], where R == G or R == 1 (row broadcast).

    A fixed-iteration vectorised binary search with *exact* comparisons —
    no offset/flattening tricks that would perturb float values — so the
    result is bitwise what ``np.searchsorted(a[i], v[i], side)`` returns
    per row.  Cost is ``ceil(log2 S)`` gather passes over [G, M].
    """
    if side not in ("left", "right"):
        raise ValueError(f"bad side '{side}'")
    a = np.asarray(a)
    v = np.asarray(v)
    r, s = a.shape
    g = v.shape[0]
    if r not in (1, g):
        raise ValueError(f"cannot broadcast {r} rows against {g} queries")
    if r == 1 and g > 1:
        a = np.broadcast_to(a, (g, s))
    lo = np.zeros(v.shape, dtype=np.int64)
    hi = np.full(v.shape, s, dtype=np.int64)
    for _ in range(int(np.ceil(np.log2(max(s, 2)))) + 1):
        active = lo < hi
        if not np.any(active):
            break
        mid = (lo + hi) >> 1
        # mid < s wherever active; the clip only feeds settled lanes
        amid = np.take_along_axis(a, np.minimum(mid, s - 1), axis=1)
        go = (amid <= v) if side == "right" else (amid < v)
        lo = np.where(active & go, mid + 1, lo)
        hi = np.where(active & ~go, mid, hi)
    return lo


def _broadcast_rows(tl: TimelineArrays, g: int) -> TimelineArrays:
    """Broadcast a single-row bank to ``g`` query rows (views, no copy)."""
    r = tl.n_rows
    if r == g:
        return tl
    if r != 1:
        raise ValueError(f"{g} query rows for {r} timeline rows")
    return TimelineArrays(
        np.broadcast_to(tl.edges, (g, tl.edges.shape[1])),
        np.broadcast_to(tl.powers, (g, tl.powers.shape[1])),
        np.broadcast_to(tl.idle_w, (g,)),
        np.broadcast_to(tl.n_segs, (g,)))


def cum_energy(tl: TimelineArrays) -> np.ndarray:
    """Per-row cumulative segment energy [R, S+1] (zero at the first edge)."""
    seg = tl.powers * np.diff(tl.edges, axis=1)
    return np.concatenate(
        [np.zeros((tl.n_rows, 1)), np.cumsum(seg, axis=1)], axis=1)


def timeline_integral(tl: TimelineArrays, t0: np.ndarray,
                      t1: np.ndarray) -> np.ndarray:
    """Exact per-row ∫P_i dt over [t0_i, t1_i] [G, M]; idle outside
    coverage.  ``tl`` has G rows, or 1 row broadcast against G."""
    t0 = np.asarray(t0, dtype=np.float64)
    t1 = np.asarray(t1, dtype=np.float64)
    g = t0.shape[0]
    cum = cum_energy(tl)            # on the R stored rows, then broadcast
    tl = _broadcast_rows(tl, g)
    e, p, idle, ns = tl
    if cum.shape[0] != g:
        cum = np.broadcast_to(cum, (g, cum.shape[1]))
    first = e[:, 0][:, None]
    last = e[:, -1][:, None]
    hi_idx = np.maximum(ns - 1, 0)[:, None]

    def eval_I(t):
        tc = np.clip(t, first, last)
        idx = np.clip(searchsorted_rows(e, tc, "right") - 1, 0, hi_idx)
        inner = (np.take_along_axis(cum, idx, axis=1)
                 + np.take_along_axis(p, idx, axis=1)
                 * (tc - np.take_along_axis(e, idx, axis=1)))
        before = np.minimum(t - first, 0.0) * idle[:, None]
        after = np.maximum(t - last, 0.0) * idle[:, None]
        return inner + before + after

    return eval_I(t1) - eval_I(t0)


def boxcar_means(tl: TimelineArrays, t0: np.ndarray,
                 t1: np.ndarray) -> np.ndarray:
    """Batched trailing-window means: ∫P dt / (t1 - t0) over [G, M]
    windows — the boxcar transient's raw reading."""
    t0 = np.asarray(t0, dtype=np.float64)
    t1 = np.asarray(t1, dtype=np.float64)
    dt = np.maximum(t1 - t0, 1e-12)
    return timeline_integral(tl, t0, t1) / dt


def estimation_means(tl: TimelineArrays, t0: np.ndarray, t1: np.ndarray,
                     model_gain: np.ndarray) -> np.ndarray:
    """Activity-proxy transient: the true period mean seen through a crude
    per-device activity model (``model_gain`` [G])."""
    return boxcar_means(tl, t0, t1) * np.asarray(model_gain)[:, None]


def log_filter(tl: TimelineArrays, ticks: np.ndarray,
               tau: np.ndarray) -> np.ndarray:
    """Batched first-order filter y' = (P - y)/tau for G devices.

    The scalar ``OnboardSensor._filtered_at`` walks the piecewise-constant
    segments in a per-device Python loop; here one scan advances a vector
    of G filter states per step.  With a shared timeline (single-row bank)
    the loop length is the number of timeline edges — independent of fleet
    size; with per-device rows the scan walks each row's own padded edge
    sequence, masking the zero-width padding steps so the state carries
    through unchanged.  Before the first real edge the state is exactly
    ``idle_w`` (the ``t_lo`` padding only ever covers idle), so readings
    are bitwise identical to the scalar filter for any padding choice.
    """
    ext_e, ext_p, dts = log_filter_segments(tl, ticks, tau)
    g, n_seg = ticks.shape[0], ext_p.shape[1]
    tau = np.asarray(tau, dtype=np.float64)
    y = np.empty((g, n_seg + 1))
    y[:, 0] = np.broadcast_to(tl.idle_w, (g,))
    for i in range(n_seg):
        dt = dts[:, i]
        sp = ext_p[:, i]
        step = sp + (y[:, i] - sp) * np.exp(-dt / tau)
        y[:, i + 1] = np.where(dt > 0, step, y[:, i])
    return log_filter_readout(y, ext_e, ext_p, ticks, tau)


def log_filter_segments(tl: TimelineArrays, ticks: np.ndarray,
                        tau: np.ndarray):
    """The filter's segment sequence: edges ``ext_e`` [R, S+3] padded by
    an idle segment on each side (wide enough that the state has settled
    at ``idle_w`` before the first real edge), their powers ``ext_p``
    [R, S+2] and widths ``dts`` [R, S+2]."""
    t_lo = (min(float(np.min(ticks)), float(np.min(tl.t_start)))
            - 5.0 * float(np.max(tau)))
    t_hi = max(float(np.max(ticks)), float(np.max(tl.t_end))) + 1e-9
    r = tl.n_rows
    ext_e = np.concatenate([np.full((r, 1), t_lo), tl.edges,
                            np.full((r, 1), t_hi)], axis=1)
    ext_p = np.concatenate([tl.idle_w[:, None], tl.powers,
                            tl.idle_w[:, None]], axis=1)
    return ext_e, ext_p, np.diff(ext_e, axis=1)


def log_filter_readout(y: np.ndarray, ext_e: np.ndarray, ext_p: np.ndarray,
                       ticks: np.ndarray, tau: np.ndarray) -> np.ndarray:
    """Readings at ``ticks`` from the filter state ``y`` [G, S+3] at each
    segment entry: decay from the entry state of the tick's segment."""
    g = ticks.shape[0]
    n_seg = ext_p.shape[1]
    idx = np.clip(searchsorted_rows(ext_e, ticks, side="right") - 1,
                  0, n_seg - 1)
    y_at = np.take_along_axis(y, idx, axis=1)
    sp_at = np.take_along_axis(np.broadcast_to(ext_p, (g, n_seg)), idx,
                               axis=1)
    e_at = np.take_along_axis(np.broadcast_to(ext_e, (g, n_seg + 1)), idx,
                              axis=1)
    return sp_at + (y_at - sp_at) * np.exp(-(ticks - e_at) / tau[:, None])


def poll_counts(sched: ReadingSchedule, grid: PollGrid, a: np.ndarray,
                b: np.ndarray) -> Tuple[np.ndarray, np.ndarray,
                                        np.ndarray, np.ndarray]:
    """Closed-form poll counting over uniform grids: the core of
    ``SensorBank.integrate_polled``.

    Because the poll grid is uniform and the published readings are a
    step function over the tick grid, the number of poll instants falling
    inside each reading interval has a closed form — no [N, n_poll]
    reading matrix is ever materialised.  Returns

    * ``counts`` [N, M]  — poll instants covered by each reading slot
      within the selected index range,
    * ``slot_b`` [N]     — the reading slot current at the final selected
      poll instant (for the partial last step),
    * ``tail_dt`` [N]    — ``b - r(j1)``, the partial step the final poll
      instant integrates over,
    * ``nonempty`` [N]   — whether any poll instant landed in [a, b].

    The caller contracts ``period · Σ_k v_k · counts_k + v_{slot_b} ·
    tail_dt`` (zeroed where empty), which matches
    ``meter._integrate_readings`` on the equivalent polled series.
    """
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    n = a.shape[0]
    period_s = grid.period_s
    # per-device poll ends reproduce each scalar sensor's finite grid
    m_i = np.floor((np.asarray(grid.t1, dtype=np.float64) - grid.t0)
                   / period_s).astype(np.int64)

    def q(idx):
        # true wall-clock query instant, same expression as poll()
        return grid.t0 + period_s * idx

    def r(idx):
        # reported (possibly re-synchronised) poll timestamp
        return (grid.t0 + period_s * idx) + grid.grid_offset

    # per-device selected index range [j0, j1] on the shared grid,
    # settling FP boundary cases against the actual grid values
    j0 = np.ceil((a - grid.grid_offset - grid.t0) / period_s).astype(np.int64)
    j1 = np.floor((b - grid.grid_offset - grid.t0) / period_s).astype(np.int64)
    for _ in range(2):
        j0 = np.where(r(j0 - 1) >= a, j0 - 1, j0)
        j0 = np.where(r(j0) < a, j0 + 1, j0)
        j1 = np.where(r(j1 + 1) <= b, j1 + 1, j1)
        j1 = np.where(r(j1) > b, j1 - 1, j1)
    j0 = np.maximum(j0, 0)
    j1 = np.minimum(j1, m_i - 1)

    ticks = sched.ticks
    m = ticks.shape[1]
    slot = np.arange(m)[None, :]
    # lo[k]: first poll index whose reading is slot k, i.e. smallest j
    # with q(j) >= tick_k (two FP settling passes, like query())
    lo = np.ceil((ticks - grid.t0) / period_s).astype(np.int64)
    for _ in range(2):
        lo = np.where(q(lo - 1) >= ticks, lo - 1, lo)
        lo = np.where(q(lo) < ticks, lo + 1, lo)
    hi = np.concatenate([lo[:, 1:] - 1, np.full((n, 1), _FAR)], axis=1)
    # query() clamps to [first, last]: the first reading extends back to
    # -inf, the last forward to +inf
    lo = np.where(slot == sched.first[:, None], np.int64(0), lo)
    hi = np.where(slot == sched.last[:, None], _FAR, hi)
    counts = (np.minimum(hi, (j1 - 1)[:, None])
              - np.maximum(lo, j0[:, None]) + 1)
    valid = (slot >= sched.first[:, None]) & (slot <= sched.last[:, None])
    counts = np.where(valid, np.maximum(counts, 0), 0)

    slot_b = query_slots(sched, q(j1.astype(np.float64))[:, None])[:, 0]
    tail_dt = b - r(j1.astype(np.float64))
    return counts, slot_b, tail_dt, j1 >= j0


def err_moments(e: np.ndarray) -> Tuple[int, float, float, float, float]:
    """One slab's error-moment reduction for the streaming fleet audit:
    ``(count, mean, M2, mean_abs, max_abs)``.  Slabs merge by Chan's
    parallel-Welford update (:class:`repro.core.fleet_engine.\
StreamingMoments`), so a chunked audit never reduces over all N errors
    at once."""
    e = np.asarray(e, dtype=np.float64)
    n = int(e.size)
    if n == 0:
        return 0, 0.0, 0.0, 0.0, 0.0
    mean = float(np.mean(e))
    m2 = float(np.sum((e - mean) ** 2))
    ae = np.abs(e)
    return n, mean, m2, float(np.mean(ae)), float(np.max(ae))


def step_integrate(ts: np.ndarray, vals: np.ndarray, t0: np.ndarray,
                   t1: np.ndarray, trapezoid: bool = False) -> np.ndarray:
    """Batched ``meter._integrate_readings``: integrate each row's sampled
    reading series over ``[t0_i, t1_i]``.

    ``ts`` is [N, M] per-row *non-decreasing* sample times — pad unused
    trailing slots with ``+inf`` — and ``vals`` [N, M] the readings.
    Samples with ``t0 <= ts <= t1`` contribute; sample ``j`` holds until
    the next sample (the last selected one holds to ``t1``), exactly the
    scalar reference's rectangle rule.  ``trapezoid=True`` replaces each
    interval's held value with the two endpoints' mean (the final partial
    step stays rectangular — there is no sample beyond it).  Rows whose
    window selects no sample integrate to 0.

    Selection is two row-wise exact binary searches, the interior sum a
    prefix-sum difference, so the whole thing is O(N·M) with no Python
    loop — this is the one rectangle/trapezoid implementation shared by
    the offline §5 protocol and the online streaming monitor.
    """
    ts = np.asarray(ts, dtype=np.float64)
    vals = np.asarray(vals, dtype=np.float64)
    t0 = np.asarray(t0, dtype=np.float64)
    t1 = np.asarray(t1, dtype=np.float64)
    n, m = ts.shape
    if m == 0:      # no samples at all: every window integrates to 0
        return np.zeros(n)
    j0 = searchsorted_rows(ts, t0[:, None], "left")[:, 0]
    j1 = searchsorted_rows(ts, t1[:, None], "right")[:, 0] - 1

    nxt_finite = np.isfinite(ts[:, 1:])
    # padding slots are +inf; mask the operands (not just the result) so
    # no inf - inf is ever evaluated
    dt = (np.where(nxt_finite, ts[:, 1:], 0.0)
          - np.where(nxt_finite, ts[:, :-1], 0.0))
    if trapezoid:
        dens = 0.5 * (vals[:, :-1] + np.where(nxt_finite, vals[:, 1:], 0.0))
    else:
        dens = vals[:, :-1]
    cum = np.concatenate([np.zeros((n, 1)), np.cumsum(dens * dt, axis=1)],
                         axis=1)

    j0c = np.clip(j0, 0, m - 1)[:, None]
    j1c = np.clip(j1, 0, m - 1)[:, None]
    core = (np.take_along_axis(cum, j1c, axis=1)
            - np.take_along_axis(cum, j0c, axis=1))[:, 0]
    tail = (np.take_along_axis(vals, j1c, axis=1)[:, 0]
            * (t1 - np.take_along_axis(ts, j1c, axis=1)[:, 0]))
    nonempty = (j1 >= j0) & (j0 < m)
    return np.where(nonempty, core + tail, 0.0)


def stream_ingest(t: np.ndarray, v: np.ndarray, seg: np.ndarray,
                  first: np.ndarray, start_idx: np.ndarray,
                  end_idx: np.ndarray, ops: SlabOperands,
                  trapezoid: bool = False) -> Tuple:
    """One slab of the streaming monitor's hot path.

    Inputs are ``K`` accepted samples sorted by (device, time) and
    compacted to ``U`` per-slab device groups: ``seg`` [K] is the group
    id (0..U-1, contiguous and ascending), ``first`` [K] marks each
    group's first sample, ``start_idx``/``end_idx`` [U] are the group
    boundary positions (host-computed so the jax twin stays static-shape).
    ``ops`` holds each group's device operands, every field [U]: its
    gathered monitor state and correction parameters
    (:class:`~repro.core.engine_backend.pytrees.SlabOperands`), with
    ``run_t`` pre-initialised to the group's first sample time for a
    device with no history.  A tier that pads the slab to a bucket fills
    the padding groups with
    :data:`~repro.core.engine_backend.pytrees.SLAB_PAD`.

    Returns, per group [U]: ``new_t, new_v, new_run_t, new_n_changes,
    counts, d_energy, d_energy_corr, d_win, d_win_corr, sum_vc, n_out``
    and, per sample [K]: ``cum_e, cum_ec`` (within-slab inclusive energy
    prefixes for ring snapshots), ``vc`` (corrected readings),
    ``run_dur, run_rec`` (completed-run durations and whether each is a
    *complete* run — bounded by a reading change on both sides — for the
    online update-period histogram).
    """
    (prev_t, prev_v, has_prev, run_t, n_changes, gain, offset, tshift,
     win_a, win_b, max_hold, env_lo, env_hi) = ops
    t = np.asarray(t, dtype=np.float64)
    v = np.asarray(v, dtype=np.float64)
    k = t.shape[0]
    u = prev_t.shape[0]
    idx = np.arange(k)

    # previous sample within the slab, or the stored state at group starts
    shift_t = np.concatenate([[0.0], t[:-1]])
    shift_v = np.concatenate([[0.0], v[:-1]])
    pt = np.where(first, prev_t[seg], shift_t)
    pv = np.where(first, prev_v[seg], shift_v)
    has = np.where(first, has_prev[seg], True)

    g = gain[seg]
    off = offset[seg]
    vc = (v - off) / g
    pvc = (pv - off) / g
    dt = t - pt
    hold = np.minimum(dt, max_hold[seg])
    dens_r = 0.5 * (pv + v) if trapezoid else pv
    dens_c = 0.5 * (pvc + vc) if trapezoid else pvc
    inc = np.where(has, dens_r * hold, 0.0)
    inc_c = np.where(has, dens_c * hold, 0.0)

    # within-group inclusive energy prefixes (global cumsum re-based at
    # each group's start), so ring snapshots see exact running totals
    cs = np.cumsum(inc)
    cum_e = cs - (cs[start_idx] - inc[start_idx])[seg]
    csc = np.cumsum(inc_c)
    cum_ec = csc - (csc[start_idx] - inc_c[start_idx])[seg]
    d_energy = cum_e[end_idx]
    d_energy_corr = cum_ec[end_idx]

    # registered measurement windows: the §5 naive/corrected protocol's
    # [a, b] clipping, sample-by-sample (corrected uses reported times,
    # i.e. raw times shifted back by the averaging window)
    a = win_a[seg]
    b = win_b[seg]
    w_inc = np.where(has & (pt >= a),
                     dens_r * np.maximum(np.minimum(pt + hold, b) - pt, 0.0),
                     0.0)
    pts = pt - tshift[seg]
    w_inc_c = np.where(has & (pts >= a),
                       dens_c * np.maximum(np.minimum(pts + hold, b) - pts,
                                           0.0),
                       0.0)
    d_win = np.bincount(seg, weights=w_inc, minlength=u)
    d_win_corr = np.bincount(seg, weights=w_inc_c, minlength=u)

    # run tracking: a reading change closes the run started at the
    # previous change; only runs bounded by changes on *both* sides are
    # recorded (microbench's complete-runs rule, online)
    change = has & (v != pv)
    ci = np.where(change, idx, -1)
    acc = np.maximum.accumulate(ci)
    acc_excl = np.concatenate([[-1], acc[:-1]])
    gstart = start_idx[seg]
    prev_chg = np.where(acc_excl >= gstart, acc_excl, -1)
    run_start = np.where(prev_chg >= 0, t[np.maximum(prev_chg, 0)],
                         run_t[seg])
    run_dur = np.where(change, t - run_start, 0.0)
    cchg = np.cumsum(change)
    chg_before_slab = cchg - (cchg[start_idx]
                              - change[start_idx])[seg] - change
    run_rec = change & (n_changes[seg] + chg_before_slab >= 1)

    new_run_t = np.where(acc[end_idx] >= start_idx,
                         t[np.maximum(acc[end_idx], 0)], run_t)
    new_n_changes = n_changes + np.bincount(
        seg, weights=change.astype(np.float64), minlength=u).astype(np.int64)

    counts = np.bincount(seg, minlength=u).astype(np.int64)
    sum_vc = np.bincount(seg, weights=vc, minlength=u)
    out = ((vc < env_lo[seg]) | (vc > env_hi[seg])).astype(np.float64)
    n_out = np.bincount(seg, weights=out, minlength=u).astype(np.int64)

    return (t[end_idx], v[end_idx], new_run_t, new_n_changes, counts,
            d_energy, d_energy_corr, d_win, d_win_corr, sum_vc, n_out,
            cum_e, cum_ec, vc, run_dur, run_rec)


def stream_ingest_grid(ts: np.ndarray, v: np.ndarray, ops: SlabOperands,
                       trapezoid: bool = False) -> Tuple:
    """Rectangular fast path of :func:`stream_ingest`: ``D`` devices share
    one strictly-increasing time axis ``ts`` [M] with readings ``v``
    [D, M] (the shape tick-grid emitters such as
    ``SensorBank.iter_poll_slabs(grid=True)`` produce natively).

    Semantically this is ``stream_ingest`` on the equivalent flattened
    device-major slab where every device contributes every tick — but
    with no sorting, no group compaction and no segmented reductions:
    every accumulator is a row-wise cumulative sum or reduction over the
    [D, M] block, so the per-sample cost is a handful of vector ops.
    ``ops``'s fields are all [D]; ``run_t`` must be pre-initialised to
    ``ts[0]`` for devices without history, exactly as the generic
    kernel's caller does.

    Returns, per device [D]: ``new_v, new_run_t, new_n_changes,
    d_energy, d_energy_corr, d_win, d_win_corr, sum_vc, sum_vc2,
    sum_abs_vc, max_abs_vc, n_out`` and, per sample [D, M]: ``cum_e,
    cum_ec, run_dur, run_rec``.  (``new_t`` is just ``ts[-1]`` and
    ``counts`` is ``M`` — the caller computes both; the extra corrected-
    reading moment sums replace the flattened ``vc`` vector, so label
    statistics merge from [D] reductions instead of [D·M] samples.)
    """
    (prev_t, prev_v, has_prev, run_t, n_changes, gain, offset, tshift,
     win_a, win_b, max_hold, env_lo, env_hi) = ops
    ts = np.asarray(ts, dtype=np.float64)
    v = np.asarray(v, dtype=np.float64)
    d, m = v.shape
    if m == 0:      # empty slab: state passes through untouched
        z = np.zeros((d, 0))
        return (prev_v.copy(), run_t.copy(), n_changes.copy(),
                np.zeros(d), np.zeros(d), np.zeros(d), np.zeros(d),
                np.zeros(d), np.zeros(d), np.zeros(d), np.zeros(d),
                np.zeros(d, dtype=np.int64), z, z, z,
                np.zeros((d, 0), dtype=bool))

    # previous sample per column: the stored state at column 0, the
    # neighbouring column elsewhere
    pt = np.empty((d, m))
    pt[:, 0] = prev_t
    pt[:, 1:] = ts[:-1][None, :]
    pv = np.concatenate([prev_v[:, None], v[:, :-1]], axis=1)
    has = np.ones((d, m), dtype=bool)
    has[:, 0] = has_prev

    g = gain[:, None]
    off = offset[:, None]
    vc = (v - off) / g
    pvc = (pv - off) / g
    dt = ts[None, :] - pt
    hold = np.minimum(dt, max_hold[:, None])
    dens_r = 0.5 * (pv + v) if trapezoid else pv
    dens_c = 0.5 * (pvc + vc) if trapezoid else pvc
    inc = np.where(has, dens_r * hold, 0.0)
    inc_c = np.where(has, dens_c * hold, 0.0)
    cum_e = np.cumsum(inc, axis=1)
    cum_ec = np.cumsum(inc_c, axis=1)

    a = win_a[:, None]
    b = win_b[:, None]
    w_inc = np.where(has & (pt >= a),
                     dens_r * np.maximum(np.minimum(pt + hold, b) - pt, 0.0),
                     0.0)
    pts = pt - tshift[:, None]
    w_inc_c = np.where(has & (pts >= a),
                       dens_c * np.maximum(np.minimum(pts + hold, b) - pts,
                                           0.0),
                       0.0)

    # run tracking, row-wise: the previous change within the row (or the
    # carried ``run_t``) opens the run a change closes
    change = has & (v != pv)
    cols = np.arange(m)[None, :]
    ci = np.where(change, cols, -1)
    acc = np.maximum.accumulate(ci, axis=1)
    acc_excl = np.concatenate([np.full((d, 1), -1), acc[:, :-1]], axis=1)
    run_start = np.where(acc_excl >= 0, ts[np.maximum(acc_excl, 0)],
                         run_t[:, None])
    run_dur = np.where(change, ts[None, :] - run_start, 0.0)
    cchg = np.cumsum(change, axis=1)
    run_rec = change & (n_changes[:, None] + (cchg - change) >= 1)

    last = acc[:, -1]
    new_run_t = np.where(last >= 0, ts[np.maximum(last, 0)], run_t)
    new_n_changes = n_changes + cchg[:, -1]

    av = np.abs(vc)
    out = (vc < env_lo[:, None]) | (vc > env_hi[:, None])
    return (v[:, -1].copy(), new_run_t, new_n_changes,
            cum_e[:, -1].copy(), cum_ec[:, -1].copy(),
            np.sum(w_inc, axis=1), np.sum(w_inc_c, axis=1),
            np.sum(vc, axis=1), np.sum(vc * vc, axis=1),
            np.sum(av, axis=1), np.max(av, axis=1),
            np.sum(out, axis=1).astype(np.int64),
            cum_e, cum_ec, run_dur, run_rec)


def query_slots(sched: ReadingSchedule, tq: np.ndarray) -> np.ndarray:
    """Reading slot current at wall-clock times ``tq`` [N, K]: the
    arithmetic index (same ``phase + T·k`` expression that built the
    grid), settled against the stored tick values and clamped to each
    device's valid range — identical to ``SensorBank.query``'s indexing.
    """
    T = sched.update_period_s[:, None]
    phase = sched.phase[:, None]
    m = sched.ticks.shape[1]
    j = np.floor((tq - phase) / T).astype(np.int64) - sched.k0[:, None]
    j = np.clip(j, 0, m - 1)
    # the arithmetic index can be off by one ulp at tick boundaries;
    # settle it against the actual stored tick values (two passes are
    # enough: the estimate is within ±1 of the true slot)
    for _ in range(2):
        tj = np.take_along_axis(sched.ticks, j, axis=1)
        j = np.where((tj > tq) & (j > 0), j - 1, j)
    for _ in range(2):
        jn = np.minimum(j + 1, m - 1)
        tn = np.take_along_axis(sched.ticks, jn, axis=1)
        j = np.where((tn <= tq) & (jn > j), jn, j)
    return np.clip(j, sched.first[:, None], sched.last[:, None])


def snapshot_energy_at(tq: np.ndarray, last_t: np.ndarray,
                       dens: np.ndarray, has: np.ndarray,
                       first_t: np.ndarray, base: np.ndarray,
                       max_hold: np.ndarray, ring_t, ring_dens, ring_base):
    """Batched snapshot-view energy query: energy since first sample at
    ``Q`` instants for all ``N`` devices at once.

    ``tq`` [Q] query instants; ``last_t``/``dens``/``has``/``first_t``/
    ``base``/``max_hold`` [N] are the published snapshot's per-device
    tail state (``dens``/``base`` already in the requested raw/corrected
    flavour); ``ring_t``/``ring_dens``/``ring_base`` [N, R] are the
    snapshot's *sorted* ring view in the same flavour, or ``None`` when
    the ring is disabled.  Returns ``(e, covered)`` [Q, N] with nan
    where an instant predates ring coverage — each row bitwise equal to
    the single-instant query path (the math is elementwise, so the Q
    broadcast changes nothing).
    """
    tq = np.asarray(tq, dtype=np.float64)[:, None]          # [Q, 1]
    dt = tq - last_t[None, :]
    hold = np.minimum(dt, max_hold[None, :])
    live = has[None, :] & (dt >= 0.0)
    e_live = np.where(live, base[None, :] + dens[None, :] * hold, 0.0)
    covered = live | ~has[None, :] | (tq <= first_t[None, :])
    started = has[None, :] & (tq > first_t[None, :])
    e = np.where(started, e_live, 0.0)
    past = started & (tq < last_t[None, :])
    if ring_t is not None and np.any(past):
        rows = np.broadcast_to(tq.T, (ring_t.shape[0], tq.shape[0]))
        j = searchsorted_rows(ring_t, rows, "right") - 1    # [N, Q]
        ok = j >= 0
        jc = np.clip(j, 0, ring_t.shape[1] - 1)
        rt = np.take_along_axis(ring_t, jc, axis=1)
        rd = np.take_along_axis(ring_dens, jc, axis=1)
        rb = np.take_along_axis(ring_base, jc, axis=1)
        hold_p = np.minimum(tq - rt.T, max_hold[None, :])
        # empty ring slots carry t=inf sentinels: 0*inf warns but the
        # result is masked out by sel below
        with np.errstate(invalid="ignore"):
            e_past = rb.T + rd.T * hold_p
        sel = past & ok.T
        e = np.where(sel, e_past, e)
        covered = covered | sel
    return np.where(covered, e, np.nan), covered


# -- the monitor's history tier (see repro.core.stream.state.HistoryTier) --

def history_put(a: np.ndarray) -> np.ndarray:
    """A tier array as this backend holds it: the host array itself."""
    return np.asarray(a, dtype=np.float64)


def history_write(e_raw, e_corr, rows: np.ndarray, cols: np.ndarray,
                  v_raw: np.ndarray, v_corr: np.ndarray, shared: bool):
    """Store ``v_raw``/``v_corr`` [P] at ``(rows, cols)`` of the tier's
    ``[slots, N]`` arrays; returns the arrays written.  ``shared``
    arrays (held by a published snapshot) are left as they are and
    copies are written."""
    if shared:
        e_raw, e_corr = e_raw.copy(), e_corr.copy()
    e_raw[rows, cols] = v_raw
    e_corr[rows, cols] = v_corr
    return e_raw, e_corr


def history_operands(f64: np.ndarray, i32: np.ndarray) -> tuple:
    """The corrected and the raw :class:`~repro.core.engine_backend.\
pytrees.TierOperands` of a snapshot's packed operand buffers
    (``TIER_F64`` rows of float64, ``TIER_I32`` rows of int32), as this
    backend reads them: host arrays."""
    return unpack_tier_operands(f64, i32)


def _history_rows(tier, rows, tq, ops: TierOperands):
    """Energy since first sample at ``Q`` boundary instants for all
    ``N`` devices: :func:`snapshot_energy_at`'s rule with the tier in
    place of the ring.  ``tier`` [S, N]; ``rows`` [Q] the slot of each
    instant, ``tq`` [Q] the instants (``b · step_s``); ``ops.lo_t``/
    ``ops.hi_t`` [N] the times of the boundaries each device's slots
    hold.  A device's instant between its first and newest sample is
    answered from its slot where the slot holds that boundary, and is
    not covered otherwise."""
    tq = tq[:, None]
    dt = tq - ops.last_t[None, :]
    hold = np.minimum(dt, ops.max_hold[None, :])
    live = ops.has[None, :] & (dt >= 0.0)
    e_live = np.where(live, ops.base[None, :] + ops.dens[None, :] * hold,
                      0.0)
    covered = live | ~ops.has[None, :] | (tq <= ops.first_t[None, :])
    started = ops.has[None, :] & (tq > ops.first_t[None, :])
    e = np.where(started, e_live, 0.0)
    held = (started & (tq < ops.last_t[None, :])
            & (tq >= ops.lo_t[None, :]) & (tq <= ops.hi_t[None, :]))
    e = np.where(held, tier[rows], e)
    covered = covered | held
    return np.where(covered, e, np.nan), covered


def history_energy_at(tier, ops: TierOperands, rows: np.ndarray,
                      tq: np.ndarray):
    """``(e, covered)`` [Q, N] at boundary instants ``tq`` (see
    :func:`_history_rows`)."""
    return _history_rows(tier, rows, np.asarray(tq, np.float64), ops)


def history_series(tier, ops: TierOperands, rows: np.ndarray,
                   tq: np.ndarray, step_s: float):
    """The fleet reductions of :func:`history_energy_at`'s rows, per
    instant [Q]: the energy of the included devices (covered and
    ``active``), the covered and included counts, the sums of squared
    and of plain per-device sigmas (``tol · |e|``); and per step
    [Q - 1]: the energy change of devices included at both ends over
    ``step_s`` (their power), and how many they are."""
    e, cov = history_energy_at(tier, ops, rows, tq)
    inc = cov & ops.active[None, :]
    e0 = np.where(inc, e, 0.0)
    sig = ops.tol[None, :] * np.abs(e0)
    both = inc[1:] & inc[:-1]
    power = np.sum(np.where(both, e[1:] - e[:-1], 0.0), axis=1) / step_s
    return (np.sum(e0, axis=1), np.sum(cov, axis=1), np.sum(inc, axis=1),
            np.sum(sig * sig, axis=1), np.sum(sig, axis=1), power,
            np.sum(both, axis=1))


def history_by_label(tier, ops: TierOperands, rows: np.ndarray,
                     tq: np.ndarray, n_labels: int):
    """The by-label reductions of the energy between two boundary
    instants ``tq`` [2]: per label [L], over its devices covered at both
    instants and reporting (``has``), the count of those ``active``
    (``n_covered``) and of those quarantined (``n_quarantined``), and
    over the active ones the total energy, its mean (nan where none) and
    the two-pass sum of squared deviations from it (``M2``); and per
    instant [2] how many devices are covered."""
    e, cov = history_energy_at(tier, ops, rows, tq)
    de = e[1] - e[0]
    covered = cov[0] & cov[1] & ops.has
    out = np.zeros((5, n_labels))
    for c in range(n_labels):
        sel = (ops.codes == c) & covered
        inc = sel & ops.active
        vals = de[inc]
        n = vals.size
        total = np.sum(vals)
        mean = total / n if n else np.nan
        out[:, c] = (n, np.sum(sel & ~ops.active), total, mean,
                     np.sum((vals - mean) ** 2) if n else np.nan)
    return (out[0].astype(np.int64), out[1].astype(np.int64), out[2],
            out[3], out[4], np.sum(cov, axis=1))


_TIER_KERNELS = {"energy_at": history_energy_at, "series": history_series,
                 "by_label": history_by_label}

#: Host↔device buffers each step of a query moves: none on this tier.
TRANSFERS = {"tier_operands": (0, 0), "tier_batch": (0, 0),
             "ring": (0, 0)}


def history_batch(calls: list) -> list:
    """The results of tier kernel calls, each ``(kind, tier, ops, rows,
    tq, args)`` with ``kind`` one of ``energy_at``, ``series`` and
    ``by_label`` and ``args`` the kernel's trailing arguments, in
    order."""
    return [_TIER_KERNELS[kind](tier, ops, rows, tq, *args)
            for kind, tier, ops, rows, tq, args in calls]
