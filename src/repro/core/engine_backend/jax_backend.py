"""JAX implementation of the fleet-engine kernels (`jax.jit` + `vmap`).

Same signatures, same semantics as
:mod:`repro.core.engine_backend.numpy_backend` — NumPy arrays in, NumPy
arrays out — with the array math dispatched through XLA:

* row-wise binary search is ``vmap(jnp.searchsorted)``;
* the logarithmic-filter recurrence ``y_{i+1} = a_i·y_i + b_i`` (affine
  per segment) runs as a ``lax.associative_scan`` over segments —
  O(log S) depth instead of the NumPy backend's sequential Python loop;
* the poll-counting closed form is one fused jitted kernel.

Everything is traced under the 64-bit policy of
:mod:`~repro.core.engine_backend.precision`, so float64 semantics match
NumPy bit-for-bit on elementwise arithmetic; only reduction/scan
association order differs, which is why the parity contract is "within
one reporting quantum", not bitwise (``tests/test_engine_backend.py``
pins it).  Compiled kernels are cached
by shape, so repeated trials of a fixed fleet re-use one compilation.
"""
from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.common.trace import span
from repro.core.engine_backend import numpy_backend as _nb
from repro.core.engine_backend import precision as _p
from repro.core.engine_backend.pytrees import (PollGrid, ReadingSchedule,
                                               SlabOperands, TierOperands,
                                               TimelineArrays, pad_operands,
                                               unpack_tier_operands)

name = "jax"

_FAR = np.iinfo(np.int64).max // 2


def pad_bucket(n: int, floor: int) -> int:
    """Padded length for an axis of ``n``: a power of two no smaller
    than ``floor``, so inputs of varying size share a few compilations
    (each costs seconds of TPU compile under emulated float64)."""
    return max(floor, 1 << max(int(n) - 1, 0).bit_length())


def _searchsorted_rows(a, v, side: str):
    g = v.shape[0]
    if a.shape[0] == 1 and g > 1:
        a = jnp.broadcast_to(a, (g, a.shape[1]))
    return jax.vmap(
        lambda ar, vr: jnp.searchsorted(ar, vr, side=side))(a, v)


def _broadcast_rows(tl: TimelineArrays, g: int) -> TimelineArrays:
    r = tl.edges.shape[0]
    if r == g:
        return tl
    if r != 1:
        raise ValueError(f"{g} query rows for {r} timeline rows")
    return TimelineArrays(
        jnp.broadcast_to(tl.edges, (g, tl.edges.shape[1])),
        jnp.broadcast_to(tl.powers, (g, tl.powers.shape[1])),
        jnp.broadcast_to(tl.idle_w, (g,)),
        jnp.broadcast_to(tl.n_segs, (g,)))


@jax.jit
def _integral_impl(tl: TimelineArrays, t0, t1):
    g = t0.shape[0]
    seg = tl.powers * jnp.diff(tl.edges, axis=1)
    cum = jnp.concatenate(
        [jnp.zeros((tl.edges.shape[0], 1)), _p.cumsum(seg, axis=1)],
        axis=1)
    tl = _broadcast_rows(tl, g)
    cum = jnp.broadcast_to(cum, (g, cum.shape[1]))
    e, p, idle, ns = tl
    first = e[:, 0][:, None]
    last = e[:, -1][:, None]
    hi_idx = jnp.maximum(ns - 1, 0)[:, None]

    def eval_I(t):
        tc = jnp.clip(t, first, last)
        idx = jnp.clip(_searchsorted_rows(e, tc, "right") - 1, 0, hi_idx)
        inner = (jnp.take_along_axis(cum, idx, axis=1)
                 + jnp.take_along_axis(p, idx, axis=1)
                 * (tc - jnp.take_along_axis(e, idx, axis=1)))
        before = jnp.minimum(t - first, 0.0) * idle[:, None]
        after = jnp.maximum(t - last, 0.0) * idle[:, None]
        return inner + before + after

    return eval_I(t1) - eval_I(t0)


@jax.jit
def _boxcar_impl(tl: TimelineArrays, t0, t1):
    dt = jnp.maximum(t1 - t0, 1e-12)
    return _integral_impl(tl, t0, t1) / dt


@jax.jit
def _estimation_impl(tl: TimelineArrays, t0, t1, model_gain):
    return _boxcar_impl(tl, t0, t1) * model_gain[:, None]


@jax.jit
def _log_filter_impl(tl: TimelineArrays, ticks, tau, t_lo, t_hi):
    g = ticks.shape[0]
    r = tl.edges.shape[0]
    ext_e = jnp.concatenate([jnp.full((r, 1), t_lo), tl.edges,
                             jnp.full((r, 1), t_hi)], axis=1)
    ext_p = jnp.concatenate([tl.idle_w[:, None], tl.powers,
                             tl.idle_w[:, None]], axis=1)
    n_seg = ext_p.shape[1]
    dts = jnp.broadcast_to(jnp.diff(ext_e, axis=1), (g, n_seg))
    sp = jnp.broadcast_to(ext_p, (g, n_seg))
    # each segment advances the filter state affinely:
    #   y_{i+1} = a_i · y_i + b_i  with  a_i = e^{-dt_i/tau},
    #   b_i = P_i (1 - a_i); zero-width padding steps are the identity map
    decay = jnp.exp(-dts / tau[:, None])
    a_seg = jnp.where(dts > 0, decay, 1.0)
    b_seg = jnp.where(dts > 0, sp * (1.0 - decay), 0.0)

    def compose(lo, hi):
        a1, b1 = lo
        a2, b2 = hi
        return (a1 * a2, b1 * a2 + b2)

    A, B = lax.associative_scan(compose, (a_seg, b_seg), axis=1)
    y0 = jnp.broadcast_to(tl.idle_w, (g,))[:, None]
    y = jnp.concatenate([y0, A * y0 + B], axis=1)          # [g, n_seg+1]

    ext_e_g = jnp.broadcast_to(ext_e, (g, n_seg + 1))
    idx = jnp.clip(_searchsorted_rows(ext_e, ticks, "right") - 1,
                   0, n_seg - 1)
    y_at = jnp.take_along_axis(y, idx, axis=1)
    sp_at = jnp.take_along_axis(sp, idx, axis=1)
    e_at = jnp.take_along_axis(ext_e_g, idx, axis=1)
    return sp_at + (y_at - sp_at) * jnp.exp(-(ticks - e_at) / tau[:, None])


@jax.jit
def _query_slots_impl(sched: ReadingSchedule, tq):
    T = sched.update_period_s[:, None]
    phase = sched.phase[:, None]
    m = sched.ticks.shape[1]
    j = jnp.floor((tq - phase) / T).astype(_p.INT) - sched.k0[:, None]
    j = jnp.clip(j, 0, m - 1)
    for _ in range(2):
        tj = jnp.take_along_axis(sched.ticks, j, axis=1)
        j = jnp.where((tj > tq) & (j > 0), j - 1, j)
    for _ in range(2):
        jn = jnp.minimum(j + 1, m - 1)
        tn = jnp.take_along_axis(sched.ticks, jn, axis=1)
        j = jnp.where((tn <= tq) & (jn > j), jn, j)
    return jnp.clip(j, sched.first[:, None], sched.last[:, None])


@jax.jit
def _poll_counts_impl(sched: ReadingSchedule, t0, t1, period_s,
                      grid_offset, a, b):
    n = a.shape[0]
    m_i = jnp.floor((t1 - t0) / period_s).astype(_p.INT)

    def q(idx):
        return t0 + period_s * idx

    def r(idx):
        return (t0 + period_s * idx) + grid_offset

    j0 = jnp.ceil((a - grid_offset - t0) / period_s).astype(_p.INT)
    j1 = jnp.floor((b - grid_offset - t0) / period_s).astype(_p.INT)
    for _ in range(2):
        j0 = jnp.where(r(j0 - 1) >= a, j0 - 1, j0)
        j0 = jnp.where(r(j0) < a, j0 + 1, j0)
        j1 = jnp.where(r(j1 + 1) <= b, j1 + 1, j1)
        j1 = jnp.where(r(j1) > b, j1 - 1, j1)
    j0 = jnp.maximum(j0, 0)
    j1 = jnp.minimum(j1, m_i - 1)

    ticks = sched.ticks
    m = ticks.shape[1]
    slot = jnp.arange(m)[None, :]
    lo = jnp.ceil((ticks - t0) / period_s).astype(_p.INT)
    for _ in range(2):
        lo = jnp.where(q(lo - 1) >= ticks, lo - 1, lo)
        lo = jnp.where(q(lo) < ticks, lo + 1, lo)
    hi = jnp.concatenate([lo[:, 1:] - 1, jnp.full((n, 1), _FAR)], axis=1)
    lo = jnp.where(slot == sched.first[:, None], _p.INT(0), lo)
    hi = jnp.where(slot == sched.last[:, None], _FAR, hi)
    counts = (jnp.minimum(hi, (j1 - 1)[:, None])
              - jnp.maximum(lo, j0[:, None]) + 1)
    valid = ((slot >= sched.first[:, None])
             & (slot <= sched.last[:, None]))
    counts = jnp.where(valid, jnp.maximum(counts, 0), 0)

    slot_b = _query_slots_impl(sched, q(j1.astype(_p.FLOAT))[:, None])
    tail_dt = b - r(j1.astype(_p.FLOAT))
    return counts, slot_b[:, 0], tail_dt, j1 >= j0


# -- public wrappers: NumPy in, NumPy out -----------------------------------

def boxcar_means(tl: TimelineArrays, t0: np.ndarray,
                 t1: np.ndarray) -> np.ndarray:
    with _p.x64():
        return np.asarray(_boxcar_impl(tl, jnp.asarray(t0, _p.FLOAT),
                                       jnp.asarray(t1, _p.FLOAT)))


def estimation_means(tl: TimelineArrays, t0: np.ndarray, t1: np.ndarray,
                     model_gain: np.ndarray) -> np.ndarray:
    with _p.x64():
        return np.asarray(_estimation_impl(
            tl, jnp.asarray(t0, _p.FLOAT), jnp.asarray(t1, _p.FLOAT),
            jnp.asarray(model_gain, _p.FLOAT)))


def timeline_integral(tl: TimelineArrays, t0: np.ndarray,
                      t1: np.ndarray) -> np.ndarray:
    with _p.x64():
        return np.asarray(_integral_impl(tl, jnp.asarray(t0, _p.FLOAT),
                                         jnp.asarray(t1, _p.FLOAT)))


def log_filter(tl: TimelineArrays, ticks: np.ndarray,
               tau: np.ndarray) -> np.ndarray:
    tau = np.asarray(tau, dtype=np.float64)
    # concrete pad bounds (cheap NumPy reductions) keep the jitted kernel
    # free of host round-trips; they only need to cover idle
    t_lo = (min(float(np.min(ticks)), float(np.min(tl.t_start)))
            - 5.0 * float(np.max(tau)))
    t_hi = max(float(np.max(ticks)), float(np.max(tl.t_end))) + 1e-9
    with _p.x64():
        return np.asarray(_log_filter_impl(
            tl, jnp.asarray(ticks, _p.FLOAT), jnp.asarray(tau),
            _p.FLOAT(t_lo), _p.FLOAT(t_hi)))


def poll_counts(sched: ReadingSchedule, grid: PollGrid, a: np.ndarray,
                b: np.ndarray) -> Tuple[np.ndarray, np.ndarray,
                                        np.ndarray, np.ndarray]:
    with _p.x64():
        counts, slot_b, tail_dt, nonempty = _poll_counts_impl(
            sched, _p.FLOAT(grid.t0),
            jnp.asarray(grid.t1, _p.FLOAT),
            _p.FLOAT(grid.period_s),
            jnp.asarray(grid.grid_offset, _p.FLOAT),
            jnp.asarray(a, _p.FLOAT), jnp.asarray(b, _p.FLOAT))
    return (np.asarray(counts), np.asarray(slot_b),
            np.asarray(tail_dt), np.asarray(nonempty))


def query_slots(sched: ReadingSchedule, tq: np.ndarray) -> np.ndarray:
    with _p.x64():
        return np.asarray(_query_slots_impl(
            sched, jnp.asarray(tq, _p.FLOAT)))


@functools.partial(jax.jit, static_argnums=(4,))
def _step_integrate_impl(ts, vals, t0, t1, trapezoid: bool):
    n, m = ts.shape
    j0 = _searchsorted_rows(ts, t0[:, None], "left")[:, 0]
    j1 = _searchsorted_rows(ts, t1[:, None], "right")[:, 0] - 1

    nxt_finite = jnp.isfinite(ts[:, 1:])
    dt = jnp.where(nxt_finite, ts[:, 1:] - ts[:, :-1], 0.0)
    if trapezoid:
        dens = 0.5 * (vals[:, :-1]
                      + jnp.where(nxt_finite, vals[:, 1:], 0.0))
    else:
        dens = vals[:, :-1]
    cum = jnp.concatenate(
        [jnp.zeros((n, 1)), _p.cumsum(dens * dt, axis=1)], axis=1)

    j0c = jnp.clip(j0, 0, m - 1)[:, None]
    j1c = jnp.clip(j1, 0, m - 1)[:, None]
    core = (jnp.take_along_axis(cum, j1c, axis=1)
            - jnp.take_along_axis(cum, j0c, axis=1))[:, 0]
    tail = (jnp.take_along_axis(vals, j1c, axis=1)[:, 0]
            * (t1 - jnp.take_along_axis(ts, j1c, axis=1)[:, 0]))
    nonempty = (j1 >= j0) & (j0 < m)
    return jnp.where(nonempty, core + tail, 0.0)


def step_integrate(ts: np.ndarray, vals: np.ndarray, t0: np.ndarray,
                   t1: np.ndarray, trapezoid: bool = False) -> np.ndarray:
    """Batched rectangle/trapezoid step integration (see the numpy
    backend's reference docstring) as one jitted kernel."""
    ts = np.asarray(ts, dtype=np.float64)
    if ts.shape[1] == 0:    # no samples at all: every window is 0
        return np.zeros(ts.shape[0])
    with _p.x64():
        return np.asarray(_step_integrate_impl(
            jnp.asarray(ts, _p.FLOAT), jnp.asarray(vals, _p.FLOAT),
            jnp.asarray(t0, _p.FLOAT), jnp.asarray(t1, _p.FLOAT),
            bool(trapezoid)))


@jax.named_scope("ingest_prev")
def ingest_prev(t, v, seg, first, ops: SlabOperands):
    """Each sample's predecessor ``(pt, pv, has)``: the previous sample
    within the slab, or the stored state at group starts."""
    shift_t = jnp.concatenate([jnp.zeros(1, t.dtype), t[:-1]])
    shift_v = jnp.concatenate([jnp.zeros(1, v.dtype), v[:-1]])
    pt = jnp.where(first, ops.prev_t[seg], shift_t)
    pv = jnp.where(first, ops.prev_v[seg], shift_v)
    has = jnp.where(first, ops.has_prev[seg], True)
    return pt, pv, has


@functools.partial(jax.jit, static_argnums=(7,))
def _stream_ingest_impl(t, v, seg, first, start_idx, end_idx,
                        ops: SlabOperands, trapezoid: bool):
    pt, pv, has = ingest_prev(t, v, seg, first, ops)
    g = ops.gain[seg]
    off = ops.offset[seg]
    vc = (v - off) / g
    pvc = (pv - off) / g
    dt = t - pt
    hold = jnp.minimum(dt, ops.max_hold[seg])
    dens_r = 0.5 * (pv + v) if trapezoid else pv
    dens_c = 0.5 * (pvc + vc) if trapezoid else pvc
    inc = jnp.where(has, dens_r * hold, 0.0)
    inc_c = jnp.where(has, dens_c * hold, 0.0)

    a = ops.win_a[seg]
    b = ops.win_b[seg]
    w_inc = jnp.where(
        has & (pt >= a),
        dens_r * jnp.maximum(jnp.minimum(pt + hold, b) - pt, 0.0), 0.0)
    pts = pt - ops.tshift[seg]
    w_inc_c = jnp.where(
        has & (pts >= a),
        dens_c * jnp.maximum(jnp.minimum(pts + hold, b) - pts, 0.0), 0.0)
    change = has & (v != pv)
    out = (vc < ops.env_lo[seg]) | (vc > ops.env_hi[seg])
    return ingest_fold(t, v, seg, start_idx, end_idx, ops.run_t,
                       ops.n_changes, inc, inc_c, w_inc, w_inc_c, vc, change,
                       out)


def _shift(x, d: int, fill):
    """``x`` moved ``d`` places along its last axis, ``fill`` in front."""
    pad = jnp.full(x.shape[:-1] + (d,), fill, x.dtype)
    return jnp.concatenate([pad, x[..., :-d]], axis=-1)


def _segmented_scan(combine, starts, xs):
    """Inclusive scan of ``xs`` (an array or a tuple of arrays, along the
    last axis) under ``combine(earlier, later)``, restarting wherever
    ``starts`` [K] is set.

    Doubling steps: after the step of width ``d``, each position holds
    the combination of the up to ``2d`` positions that end at it, cut at
    its group's first sample; ``done`` marks positions whose span has
    reached that first sample.  Each step is one elementwise pass over
    shifted copies, which a TPU streams: on a TPU v5e, over 32,768
    float64 samples, 6 us against 173 us for ``lax.associative_scan``'s
    strided slices, which also compile slower."""
    done = starts
    d = 1
    while d < starts.shape[0]:
        new = combine(jax.tree.map(lambda x: _shift(x, d, 0), xs), xs)
        xs = jax.tree.map(lambda n, x: jnp.where(done, x, n), new, xs)
        done = done | _shift(done, d, True)
        d *= 2
    return xs


def _last(earlier, later):
    """Carry the latest flagged value forward: ``(flag, value)`` pairs."""
    return (earlier[0] | later[0],
            jnp.where(later[0], later[1], earlier[1]))


@jax.named_scope("ingest_fold")
def ingest_fold(t, v, seg, start_idx, end_idx, run_t, n_changes, inc,
                inc_c, w_inc, w_inc_c, vc, change, out) -> Tuple:
    """Fold one slab's per-sample increments into per-group results (the
    second half of :func:`_stream_ingest_impl`, shared with the pallas
    tier, whose kernel computes the per-sample part).

    Groups are contiguous runs of the sorted slab, so every per-group
    result is a segmented scan over the slab read at the group's last
    sample: no scatter, which a TPU serialises update by update.  A
    group's sums hold its own increments only (all-zero sums to exactly
    0) and carry no rounding of earlier groups; counts are exact in
    int32 at slab size and widened at the end."""
    starts = jnp.concatenate([jnp.ones(1, bool), seg[1:] != seg[:-1]])
    sums = _segmented_scan(
        jnp.add, starts, jnp.stack([inc, inc_c, w_inc, w_inc_c, vc]))
    n_chg, n_out = _segmented_scan(
        jnp.add, starts, jnp.stack([change, out]).astype(_p.KINT))
    # run tracking: the latest change in the group at or before each
    # sample; the run a change closes started at the latest change before
    # it, or, for the group's first change, at the run carried in from
    # earlier slabs (``run_t``)
    any_chg, chg_t = _segmented_scan(_last, starts, (change, t))

    (new_t, new_v, d_energy, d_energy_corr, d_win, d_win_corr, sum_vc,
     last_chg_t) = jnp.concatenate(
         [jnp.stack([t, v]), sums, chg_t[None]])[:, end_idx]
    n_chg_g, n_out_g, any_chg_g = jnp.stack(
        [n_chg, n_out, any_chg.astype(_p.KINT)])[:, end_idx]
    new_run_t = jnp.where(any_chg_g != 0, last_chg_t, run_t)
    new_n_changes = n_changes + n_chg_g
    counts = (end_idx - start_idx + 1).astype(_p.INT)

    # the carried run and change count, per sample, in one two-column row
    # gather: a TPU v5e gathers 32,768 such rows in 133 us, and 32,768
    # single float64 elements in 470 us; the count is exact as a float64
    carried = jnp.stack([run_t, n_changes.astype(t.dtype)], axis=1)[seg]
    earlier = _shift(any_chg, 1, False) & ~starts  # a change before it
    run_start = jnp.where(earlier, _shift(chg_t, 1, 0.0), carried[:, 0])
    run_dur = jnp.where(change, t - run_start, 0.0)
    chg_before = n_chg - change     # earlier changes of its group in the slab
    run_rec = change & (carried[:, 1] + chg_before >= 1)

    return (new_t, new_v, new_run_t, new_n_changes, counts,
            d_energy, d_energy_corr, d_win, d_win_corr, sum_vc,
            n_out_g.astype(_p.INT), sums[0], sums[1], vc, run_dur, run_rec)


def stream_ingest(t, v, seg, first, start_idx, end_idx, ops: SlabOperands,
                  trapezoid: bool = False) -> Tuple:
    """Streaming-monitor ingest slab (see the numpy backend's reference
    docstring), fused into one jitted kernel."""
    return ingest_padded(_stream_ingest_impl, t, v, seg, first, start_idx,
                         end_idx, ops, bool(trapezoid))


def fill_rows(out: np.ndarray, cols) -> np.ndarray:
    """Each row of ``out`` [R, N] set from one ``(values, fill)`` pair of
    ``cols``: the values cast to ``out``'s dtype, then the fill.  The
    padding wrappers pack a slab's operands this way, one host buffer
    per dtype, so that the slab makes one transfer each way."""
    for row, (x, fill) in zip(out, cols):
        n = len(x)
        row[:n] = x
        row[n:] = fill
    return out


# the operands the flat slab packs as float64 rows, in the record's order;
# the other two travel in the int64 buffer
_F64_OPS = tuple(f for f in SlabOperands._fields
                 if f not in ("has_prev", "n_changes"))


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _ingest_packed(impl, fbuf, ibuf, kp: int, static: tuple):
    """``impl`` on a slab packed by dtype, its outputs packed the same way.

    ``fbuf`` (float64) holds ``t`` and ``v`` [Kp], then the float
    operands ``_F64_OPS`` [11, Up]; ``ibuf`` (int64) holds ``seg`` and
    ``first`` [Kp], then ``start_idx``, ``end_idx``, ``has_prev`` and
    ``n_changes`` [Up].  Out come the eight float per-group results
    [8, Up] and the four float per-sample ones [4, Kp] as one float64
    buffer, and the three integer per-group results [3, Up] and
    ``run_rec`` [Kp] as one int64 buffer."""
    up = (fbuf.shape[0] - 2 * kp) // len(_F64_OPS)
    t, v = fbuf[:2 * kp].reshape(2, kp)
    seg, first = ibuf[:2 * kp].reshape(2, kp)
    start_idx, end_idx, has_prev, n_changes = ibuf[2 * kp:].reshape(4, up)
    ops = SlabOperands(
        has_prev=has_prev != 0, n_changes=n_changes,
        **dict(zip(_F64_OPS, fbuf[2 * kp:].reshape(len(_F64_OPS), up))))
    (new_t, new_v, new_run_t, new_n_changes, counts, d_energy,
     d_energy_corr, d_win, d_win_corr, sum_vc, n_out, e_cum, e_cum_c, vc,
     run_dur, run_rec) = impl(t, v, seg, first != 0, start_idx, end_idx,
                              ops, *static)
    return (jnp.concatenate([new_t, new_v, new_run_t, d_energy,
                             d_energy_corr, d_win, d_win_corr, sum_vc,
                             e_cum, e_cum_c, vc, run_dur]),
            jnp.concatenate([new_n_changes, counts, n_out,
                             run_rec.astype(_p.INT)]))


def ingest_padded(impl, t, v, seg, first, start_idx, end_idx,
                  ops: SlabOperands, *static) -> Tuple:
    """Run the jitted slab kernel ``impl`` under 64-bit mode on the slab
    padded to power-of-two ``(K, U)`` buckets (the tail samples form one
    extra, inert group), so slabs of varying size share a few
    compilations; ``static`` follows the operands.  The slab goes to the
    device in one put of one float64 and one int64 buffer, and its
    results come back in one fetch of two such buffers
    (:func:`_ingest_packed`)."""
    k, u = len(t), len(start_idx)
    kp, up = pad_bucket(k, 1024), pad_bucket(u + 1, 8)
    t = np.asarray(t, dtype=np.float64)
    ops = pad_operands(ops, up)
    fbuf = np.empty(2 * kp + len(_F64_OPS) * up)
    fill_rows(fbuf[:2 * kp].reshape(2, kp),
              [(t, t[-1] if k else 0.0), (v, 0.0)])
    np.stack([getattr(ops, f) for f in _F64_OPS],
             out=fbuf[2 * kp:].reshape(len(_F64_OPS), up))
    ibuf = np.empty(2 * kp + 4 * up, np.int64)
    per_sample = fill_rows(ibuf[:2 * kp].reshape(2, kp),
                           [(seg, u), (first, 0)])
    per_sample[1, k:k + 1] = 1      # the tail samples' group starts
    per_group = ibuf[2 * kp:].reshape(4, up)
    fill_rows(per_group[:2], [(np.r_[start_idx, k], kp - 1),
                              (end_idx, kp - 1)])
    per_group[2:] = ops.has_prev, ops.n_changes
    # the float64 put stays inside x64: outside it, jax would cast the
    # buffer to float32
    with span("ingest.kernel.pad", samples=k, slots=kp, h2d=2,
              d2h=2), _p.x64():
        fo, io = jax.device_get(_ingest_packed(
            impl, *jax.device_put((fbuf, ibuf)), kp, static))
    g = fo[:8 * up].reshape(8, up)[:, :u]
    s = fo[8 * up:].reshape(4, kp)[:, :k]
    gi = io[:3 * up].reshape(3, up)[:, :u]
    return (g[0], g[1], g[2], gi[0], gi[1], g[3], g[4], g[5], g[6], g[7],
            gi[2], s[0], s[1], s[2], s[3], io[3 * up:3 * up + k] != 0)


@functools.partial(jax.jit, static_argnums=(3,))
def _stream_ingest_grid_impl(ts, v, ops: SlabOperands, trapezoid: bool):
    (prev_t, prev_v, has_prev, run_t, n_changes, gain, offset, tshift,
     win_a, win_b, max_hold, env_lo, env_hi) = ops
    d, m = v.shape
    pt = jnp.concatenate(
        [prev_t[:, None],
         jnp.broadcast_to(ts[:-1][None, :], (d, m - 1))], axis=1)
    pv = jnp.concatenate([prev_v[:, None], v[:, :-1]], axis=1)
    has = jnp.concatenate(
        [has_prev[:, None], jnp.ones((d, m - 1), dtype=bool)], axis=1)

    g = gain[:, None]
    off = offset[:, None]
    vc = (v - off) / g
    pvc = (pv - off) / g
    dt = ts[None, :] - pt
    hold = jnp.minimum(dt, max_hold[:, None])
    dens_r = 0.5 * (pv + v) if trapezoid else pv
    dens_c = 0.5 * (pvc + vc) if trapezoid else pvc
    inc = jnp.where(has, dens_r * hold, 0.0)
    inc_c = jnp.where(has, dens_c * hold, 0.0)
    cum_e = _p.cumsum(inc, axis=1)
    cum_ec = _p.cumsum(inc_c, axis=1)

    a = win_a[:, None]
    b = win_b[:, None]
    w_inc = jnp.where(
        has & (pt >= a),
        dens_r * jnp.maximum(jnp.minimum(pt + hold, b) - pt, 0.0), 0.0)
    pts = pt - tshift[:, None]
    w_inc_c = jnp.where(
        has & (pts >= a),
        dens_c * jnp.maximum(jnp.minimum(pts + hold, b) - pts, 0.0), 0.0)

    # run tracking: every row shares the slab's single tick vector, so
    # the previous change column is a plain row-wise running maximum of
    # change positions (the numpy reference's ``maximum.accumulate``) —
    # gathers from the 1-D ``ts``, no scatters (XLA CPU scatters are
    # serial and dominated this kernel's profile)
    change = has & (v != pv)
    chg_i = change.astype(_p.INT)
    cchg = _p.cumsum(chg_i, axis=1)
    tsb = jnp.broadcast_to(ts[None, :], (d, m))
    cols = lax.broadcasted_iota(_p.INT, (d, m), 1)
    ci = jnp.where(change, cols, _p.INT(-1))
    acc = _p.cummax(ci, axis=1)                  # last change ≤ col j
    acc_excl = jnp.concatenate(
        [jnp.full((d, 1), -1, dtype=_p.INT), acc[:, :-1]], axis=1)
    run_start = jnp.where(acc_excl >= 0, ts[jnp.maximum(acc_excl, 0)],
                          run_t[:, None])
    run_dur = jnp.where(change, tsb - run_start, 0.0)
    prev_ord = cchg - chg_i                       # changes strictly < j
    run_rec = change & (n_changes[:, None] + prev_ord >= 1)
    last = acc[:, -1]
    new_run_t = jnp.where(last >= 0, ts[jnp.maximum(last, 0)], run_t)
    new_n_changes = n_changes + cchg[:, -1]

    av = jnp.abs(vc)
    out = (vc < env_lo[:, None]) | (vc > env_hi[:, None])
    return (v[:, -1], new_run_t, new_n_changes,
            cum_e[:, -1], cum_ec[:, -1],
            jnp.sum(w_inc, axis=1), jnp.sum(w_inc_c, axis=1),
            jnp.sum(vc, axis=1), jnp.sum(vc * vc, axis=1),
            jnp.sum(av, axis=1), jnp.max(av, axis=1),
            jnp.sum(out, axis=1).astype(_p.INT),
            cum_e, cum_ec, run_dur, run_rec)


def stream_ingest_grid(ts, v, ops: SlabOperands,
                       trapezoid: bool = False) -> Tuple:
    """Rectangular-slab streaming ingest (see the numpy backend's
    reference docstring) fused into one jitted kernel; compiled once per
    (D, M) slab shape, so a fixed-tick replay reuses one compilation."""
    ts = np.asarray(ts, dtype=np.float64)
    v = np.asarray(v, dtype=np.float64)
    if v.shape[1] == 0:     # empty slab: state passes through untouched
        return _nb.stream_ingest_grid(ts, v, ops, trapezoid)
    with _p.x64():
        outs = _stream_ingest_grid_impl(
            jnp.asarray(ts, _p.FLOAT), jnp.asarray(v, _p.FLOAT),
            jax.tree.map(jnp.asarray, ops), bool(trapezoid))
    return tuple(np.asarray(o) for o in outs)


@jax.jit
def _err_moments_impl(e):
    mean = jnp.mean(e)
    ae = jnp.abs(e)
    return mean, jnp.sum((e - mean) ** 2), jnp.mean(ae), jnp.max(ae)


def err_moments(e: np.ndarray):
    """One slab's ``(count, mean, M2, mean_abs, max_abs)`` reduction (see
    the numpy backend) as a fused jitted kernel."""
    e = np.asarray(e, dtype=np.float64)
    if e.size == 0:
        return 0, 0.0, 0.0, 0.0, 0.0
    with _p.x64():
        mean, m2, mean_abs, max_abs = jax.device_get(_err_moments_impl(
            jnp.asarray(e, _p.FLOAT)))
    return (int(e.size), float(mean), float(m2), float(mean_abs),
            float(max_abs))


@functools.partial(jax.jit, static_argnums=(10,))
def _snapshot_energy_at_impl(tq, last_t, dens, has, first_t, base,
                             max_hold, ring_t, ring_dens, ring_base,
                             with_ring: bool):
    tqc = tq[:, None]                                       # [Q, 1]
    dt = tqc - last_t[None, :]
    hold = jnp.minimum(dt, max_hold[None, :])
    live = has[None, :] & (dt >= 0.0)
    e_live = jnp.where(live, base[None, :] + dens[None, :] * hold, 0.0)
    covered = live | ~has[None, :] | (tqc <= first_t[None, :])
    started = has[None, :] & (tqc > first_t[None, :])
    e = jnp.where(started, e_live, 0.0)
    past = started & (tqc < last_t[None, :])
    if with_ring:
        j = jax.vmap(
            lambda row: jnp.searchsorted(row, tq, side="right"))(ring_t) - 1
        ok = j >= 0                                         # [N, Q]
        jc = jnp.clip(j, 0, ring_t.shape[1] - 1)
        rt = jnp.take_along_axis(ring_t, jc, axis=1)
        rd = jnp.take_along_axis(ring_dens, jc, axis=1)
        rb = jnp.take_along_axis(ring_base, jc, axis=1)
        hold_p = jnp.minimum(tqc - rt.T, max_hold[None, :])
        e_past = rb.T + rd.T * hold_p
        sel = past & ok.T
        e = jnp.where(sel, e_past, e)
        covered = covered | sel
    return jnp.where(covered, e, jnp.nan), covered


def snapshot_energy_at(tq: np.ndarray, last_t: np.ndarray,
                       dens: np.ndarray, has: np.ndarray,
                       first_t: np.ndarray, base: np.ndarray,
                       max_hold: np.ndarray, ring_t, ring_dens, ring_base):
    """Batched snapshot-view energy query (see the numpy backend's
    reference docstring) as one jitted [Q, N] kernel."""
    with_ring = ring_t is not None
    if not with_ring:
        r = np.zeros((last_t.shape[0], 0))
        ring_t = ring_dens = ring_base = r
    tq = np.asarray(tq, dtype=np.float64)
    q = tq.shape[0]
    tq = np.concatenate([tq, np.full(pad_bucket(q, 8) - q,
                                     tq[-1] if q else 0.0)])
    with _p.x64():
        e, covered = _snapshot_energy_at_impl(
            jnp.asarray(tq, _p.FLOAT), jnp.asarray(last_t, _p.FLOAT),
            jnp.asarray(dens, _p.FLOAT), jnp.asarray(has, jnp.bool_),
            jnp.asarray(first_t, _p.FLOAT), jnp.asarray(base, _p.FLOAT),
            jnp.asarray(max_hold, _p.FLOAT),
            jnp.asarray(ring_t, _p.FLOAT),
            jnp.asarray(ring_dens, _p.FLOAT),
            jnp.asarray(ring_base, _p.FLOAT), with_ring)
    return np.asarray(e)[:q], np.asarray(covered)[:q]


# -- the monitor's history tier (see repro.core.stream.state.HistoryTier) --
# The tier lives on the device as two float64 [slots, N] arrays.  Writes
# are functional updates: donated (in place) unless a published snapshot
# holds the arrays, whose bits then never change.

def history_put(a: np.ndarray):
    """A host tier array placed on the device (float64)."""
    with _p.x64():
        return jnp.asarray(np.asarray(a, dtype=np.float64), _p.FLOAT)


def _history_write_impl(e_raw, e_corr, rows, cols, v_raw, v_corr):
    with jax.named_scope("history_write"):
        return (e_raw.at[rows, cols].set(v_raw, mode="drop"),
                e_corr.at[rows, cols].set(v_corr, mode="drop"))


_history_write_shared = jax.jit(_history_write_impl)
_history_write_owned = jax.jit(_history_write_impl, donate_argnums=(0, 1))


def history_write(e_raw, e_corr, rows: np.ndarray, cols: np.ndarray,
                  v_raw: np.ndarray, v_corr: np.ndarray, shared: bool):
    """Store ``v_raw``/``v_corr`` [P] at ``(rows, cols)`` of the tier
    (see the numpy backend).  Pairs are padded to a power of two with
    out-of-range rows, which the scatter drops."""
    p = rows.shape[0]
    pp = pad_bucket(p, 1024)

    def pad(x, fill, dtype):
        out = np.full(pp, fill, dtype=dtype)
        out[:p] = x
        return out

    fn = _history_write_shared if shared else _history_write_owned
    with _p.x64():
        return fn(e_raw, e_corr,
                  jnp.asarray(pad(rows, e_raw.shape[0], np.int32)),
                  jnp.asarray(pad(cols, 0, np.int32)),
                  jnp.asarray(pad(v_raw, 0.0, np.float64), _p.FLOAT),
                  jnp.asarray(pad(v_corr, 0.0, np.float64), _p.FLOAT))


_history_unpack = jax.jit(unpack_tier_operands)


def history_operands(f64: np.ndarray, i32: np.ndarray) -> tuple:
    """The corrected and the raw :class:`~repro.core.engine_backend.\
pytrees.TierOperands` of a snapshot's packed operand buffers, placed on
    the device with one explicit put of the two buffers and unpacked
    there (a snapshot reuses them for every query)."""
    with _p.x64():
        return _history_unpack(*jax.device_put((f64, i32)))


def _history_rows(tier, rows, tq, ops: TierOperands):
    tqc = tq[:, None]
    dt = tqc - ops.last_t[None, :]
    hold = jnp.minimum(dt, ops.max_hold[None, :])
    live = ops.has[None, :] & (dt >= 0.0)
    e_live = jnp.where(live, ops.base[None, :] + ops.dens[None, :] * hold,
                       0.0)
    covered = live | ~ops.has[None, :] | (tqc <= ops.first_t[None, :])
    started = ops.has[None, :] & (tqc > ops.first_t[None, :])
    e = jnp.where(started, e_live, 0.0)
    held = (started & (tqc < ops.last_t[None, :])
            & (tqc >= ops.lo_t[None, :]) & (tqc <= ops.hi_t[None, :]))
    e = jnp.where(held, tier[rows], e)
    covered = covered | held
    return jnp.where(covered, e, jnp.nan), covered


def _count(m):
    return jnp.sum(m.astype(jnp.int32), axis=-1)


def _energy_at(tier, rows, tq, ops: TierOperands):
    with jax.named_scope("history_energy_at"):
        return _history_rows(tier, rows, tq, ops)


def _series(tier, rows, tq, step_s, ops: TierOperands):
    with jax.named_scope("history_series"):
        e, cov = _history_rows(tier, rows, tq, ops)
        inc = cov & ops.active[None, :]
        e0 = jnp.where(inc, e, 0.0)
        sig = ops.tol[None, :] * jnp.abs(e0)
        both = inc[1:] & inc[:-1]
        power = jnp.sum(jnp.where(both, e[1:] - e[:-1], 0.0),
                        axis=1) / step_s
        return (jnp.sum(e0, axis=1), _count(cov), _count(inc),
                jnp.sum(sig * sig, axis=1), jnp.sum(sig, axis=1), power,
                _count(both))


def _by_label(tier, rows, tq, n_labels: int, ops: TierOperands):
    # masked sums over a static [L, N]: no scatter, no segment sum
    with jax.named_scope("history_by_label"):
        e, cov = _history_rows(tier, rows, tq, ops)
        covered = cov[0] & cov[1] & ops.has
        de = jnp.where(covered, e[1] - e[0], 0.0)[None, :]
        sel = (ops.codes[None, :] == jnp.arange(n_labels)[:, None]) \
            & covered[None, :]
        inc = sel & ops.active[None, :]
        n = _count(inc)
        total = jnp.sum(jnp.where(inc, de, 0.0), axis=1)
        mean = jnp.where(n > 0, total / jnp.maximum(n, 1), jnp.nan)
        m2 = jnp.sum(jnp.where(inc, (de - mean[:, None]) ** 2, 0.0),
                     axis=1)
        return (n, _count(sel & ~ops.active[None, :]), total, mean,
                jnp.where(n > 0, m2, jnp.nan), _count(cov))


def _flush_results(tiers, ops, qbuf, layout: tuple) -> list:
    """Every tier call of a flush.  ``layout`` holds per call ``(kind,
    tier index, Q, static)``: its instants ``tq`` [Q], slots [Q] and one
    trailing number (a series' step) lie in turn in the float64 ``qbuf``;
    ``static`` is a by-label's label count."""
    out, at = [], 0
    for kind, k, q, static in layout:
        tq, rows = qbuf[at:at + q], qbuf[at + q:at + 2 * q]
        rows, arg = rows.astype(jnp.int32), qbuf[at + 2 * q]
        at += 2 * q + 1
        if kind == "energy_at":
            out.append(_energy_at(tiers[k], rows, tq, ops[k]))
        elif kind == "series":
            out.append(_series(tiers[k], rows, tq, arg, ops[k]))
        else:
            out.append(_by_label(tiers[k], rows, tq, static, ops[k]))
    return out


@functools.partial(jax.jit, static_argnums=(3,))
def _history_flush(tiers, ops, qbuf, layout: tuple):
    """:func:`_flush_results` as one program, its results in one float64
    buffer (counts and flags are exact in it).  A tier that several
    calls read is one operand, split once."""
    out = _flush_results(tiers, ops, qbuf, layout)
    with jax.named_scope("history_fetch"):
        return jnp.concatenate([jnp.ravel(x).astype(_p.FLOAT)
                                for x in jax.tree.leaves(out)])


@functools.lru_cache(maxsize=256)
def _result_shapes(layout: tuple, slots: int, n: int) -> list:
    """Per call of ``layout``, each result's ``(shape, dtype)``."""
    f64 = jax.ShapeDtypeStruct((n,), _p.FLOAT)
    flag = jax.ShapeDtypeStruct((n,), jnp.bool_)
    ops = TierOperands(lo_t=f64, hi_t=f64, last_t=f64, first_t=f64,
                       has=flag, max_hold=f64, dens=f64, base=f64,
                       active=flag, tol=f64,
                       codes=jax.ShapeDtypeStruct((n,), jnp.int32))
    k = 1 + max(c[1] for c in layout)
    tiers = (jax.ShapeDtypeStruct((slots, n), _p.FLOAT),) * k
    qbuf = jax.ShapeDtypeStruct((sum(2 * c[2] + 1 for c in layout),),
                                _p.FLOAT)
    with _p.x64():
        out = jax.eval_shape(lambda t, o, b: _flush_results(t, o, b, layout),
                             tiers, (ops,) * k, qbuf)
    return [[(x.shape, np.dtype(x.dtype)) for x in c] for c in out]


#: Host↔device buffers moved by each step of a query: placing a
#: snapshot's operands, one batch of tier calls, one ring energy-at call.
TRANSFERS = {"tier_operands": (2, 0), "tier_batch": (1, 1),
             "ring": (10, 2)}


def history_batch(calls: list) -> list:
    """The results of tier kernel calls (see the numpy backend), as one
    program with one upload and one fetch: every call's instants, slots
    and trailing argument go up in one float64 buffer, and every result
    comes back in one float64 buffer, cast back on the host by the
    results' shapes and dtypes.  Energy-at instants are padded to a
    power of two."""
    tiers, ops, index = [], [], {}
    layout, parts = [], []
    for kind, tier, op, rows, tq, args in calls:
        if id(tier) not in index:
            index[id(tier)] = len(tiers)
            tiers.append(tier)
            ops.append(op)
        q = len(tq)
        qp = pad_bucket(q, 8) if kind == "energy_at" else q
        pad = lambda x: np.concatenate(  # noqa: E731
            [x, np.repeat(x[-1:], qp - q)])
        parts += [pad(np.asarray(tq, np.float64)),
                  pad(np.asarray(rows, np.float64)),
                  [float(args[0]) if kind == "series" else 0.0]]
        layout.append((kind, index[id(tier)], qp,
                       int(args[0]) if kind == "by_label" else 0))
    layout = tuple(layout)
    with _p.x64():
        flat = jax.device_get(_history_flush(
            tuple(tiers), tuple(ops), jax.device_put(np.concatenate(parts)),
            layout))
    res, at = [], 0
    shapes = _result_shapes(layout, *tiers[0].shape)
    for (kind, _, _, _, tq, _), out in zip(calls, shapes):
        host = []
        for shape, dtype in out:
            size = int(np.prod(shape))
            host.append(flat[at:at + size].reshape(shape).astype(dtype))
            at += size
        if kind == "energy_at":
            host = [h[:len(tq)] for h in host]
        res.append(tuple(host))
    return res


def history_energy_at(tier, ops: TierOperands, rows: np.ndarray,
                      tq: np.ndarray):
    """``(e, covered)`` [Q, N] at boundary instants (see the numpy
    backend), as one jitted kernel."""
    return history_batch([("energy_at", tier, ops, rows, tq, ())])[0]


def history_series(tier, ops: TierOperands, rows: np.ndarray,
                   tq: np.ndarray, step_s: float):
    """The fleet reductions over the tier's rows (see the numpy
    backend), done on the device: only [Q] numbers come back."""
    return history_batch([("series", tier, ops, rows, tq, (step_s,))])[0]


def history_by_label(tier, ops: TierOperands, rows: np.ndarray,
                     tq: np.ndarray, n_labels: int):
    """The by-label reductions between two boundary instants (see the
    numpy backend), done on the device over a static number of labels:
    only [L] numbers come back."""
    return history_batch([("by_label", tier, ops, rows, tq,
                           (n_labels,))])[0]
