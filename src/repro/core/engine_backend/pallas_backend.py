"""Pallas implementation of the streaming hot-loop kernels.

Same signatures, same semantics as
:mod:`repro.core.engine_backend.numpy_backend` — NumPy arrays in, NumPy
arrays out — with the per-sample arithmetic of four kernels in
``pl.pallas_call``\\ s that compile for the TPU:

* ``stream_ingest_grid`` — the rectangular fast path.  Devices lie on
  the vector lanes (``[M, D/128, 128]`` tiles) and the kernel walks the
  shared tick axis in a loop, so the running energies, the run tracking
  (a carried run-start time and last-change column) and the per-device
  moments are loop carries: no in-kernel scan, gather or cross-lane
  reduction.  Long slabs are split along the tick axis, with the carries
  kept in VMEM scratch and in the resident per-device output blocks.
* ``stream_ingest`` — the general (sorted, segmented) path: one fused
  elementwise kernel over ``[K/128, 128]`` tiles (correction, hold,
  window clipping, change and envelope flags) inside a float64 jit that
  forms each sample's predecessor, gap and window openings before it
  and runs the jax tier's fold (group-re-based prefix sums, segment
  sums, run tracking) after it, all on the device.
* ``step_integrate`` — row-blocked masked sums over the sample axis; the
  window edges come from an exact float64 search on the host.
* ``log_filter`` — the affine recurrence ``y_{i+1} = a_i·y_i + b_i`` as
  a blocked sequential scan over segment chunks (the grid walks the
  segment axis innermost; VMEM scratch carries the filter state).

Kernels run in 32 bits (:mod:`~repro.core.engine_backend.precision`):
times enter relative to a float64 per-slab anchor, gaps are formed in
float64 before the cast, kernels return per-slab increments, and these
are re-based into float64 totals and counts widened to int64 outside
the ``pallas_call`` (on the host, or for ``stream_ingest`` in the
float64 jit around it).
Where the platform is the CPU the same kernels run in the Pallas
interpreter.  Gather-bound kernels with no streaming inner loop
(``boxcar_means``, ``poll_counts``, ``snapshot_energy_at``, the history
tier's ``history_*``, …) are the jax tier's.
"""
from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common.trace import span
from repro.core.engine_backend import jax_backend as _jb
from repro.core.engine_backend import numpy_backend as _nb
from repro.core.engine_backend import precision as _p
from repro.core.engine_backend.pytrees import SlabOperands, pad_operands

name = "pallas"

_LANES = 128
# grid ingest: devices per block are _GRID_SUBLANES·128, ticks per block
# at most _GRID_TICKS (longer slabs loop over tick blocks)
_GRID_SUBLANES = 8
_GRID_TICKS = 64
# flat ingest: rows of 128 samples per block
_FLAT_ROWS = 256
_STEP_ROWS = 256
_SCAN_CHUNK = 64
_SCAN_LANES = 512

# gather-bound kernels: same jitted jax implementations, re-exported
boxcar_means = _jb.boxcar_means
estimation_means = _jb.estimation_means
timeline_integral = _jb.timeline_integral
poll_counts = _jb.poll_counts
query_slots = _jb.query_slots
err_moments = _jb.err_moments
snapshot_energy_at = _jb.snapshot_energy_at
history_put = _jb.history_put
history_write = _jb.history_write
history_operands = _jb.history_operands
history_energy_at = _jb.history_energy_at
history_series = _jb.history_series
history_by_label = _jb.history_by_label
history_batch = _jb.history_batch
TRANSFERS = _jb.TRANSFERS


def _interpret() -> bool:
    """Kernels run in the Pallas interpreter exactly when jax's platform
    is the CPU."""
    return jax.default_backend() == "cpu"


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _k32(x, n: int, fill, dtype=_p.KFLOAT) -> np.ndarray:
    """``x`` cast to a kernel dtype and padded with ``fill`` to ``n``
    along the first axis."""
    x = np.asarray(x)
    out = np.full((n,) + x.shape[1:], fill, dtype=dtype)
    out[:x.shape[0]] = x
    return out


def _first_at_least(ts: np.ndarray, a: np.ndarray, shift) -> np.ndarray:
    """Per row, the first index ``i`` with ``ts[i] - shift >= a`` (``len(ts)``
    if none), decided in float64 by a vectorised binary search on that
    exact predicate — a window edge that coincides with a poll instant
    must fall on the same side as in the float64 reference."""
    m = ts.shape[0]
    shift = np.broadcast_to(shift, a.shape)
    lo = np.zeros(a.shape, dtype=np.int64)
    hi = np.full(a.shape, m, dtype=np.int64)
    for _ in range(max(m, 1).bit_length()):
        mid = np.minimum((lo + hi) // 2, m - 1)
        ok = ts[mid] - shift >= a
        live = lo < hi
        hi = np.where(live & ok, mid, hi)
        lo = np.where(live & ~ok, mid + 1, lo)
    return lo


# -- stream_ingest_grid: devices on lanes, loop over ticks ------------------

def _grid_kernel(ts_ref, dts_ref, v_ref, pt0_ref, dt0_ref, pv0_ref,
                 has0_ref, rt_ref, nchp_ref, g_ref, off_ref, tsh_ref,
                 ja_ref, jac_ref, wb_ref, mh_ref, el_ref, eh_ref,
                 ce_ref, cec_ref, rd_ref, rr_ref,
                 de_ref, dec_ref, dw_ref, dwc_ref, sv_ref, sv2_ref, sa_ref,
                 mx_ref, no_ref, last_ref, nchg_ref,
                 pv_s, rs_s, *, trapezoid: bool, m_real: int, tm: int):
    mi = pl.program_id(1)
    f32, i32 = _p.KFLOAT, _p.KINT
    sums = (de_ref, dec_ref, dw_ref, dwc_ref, sv_ref, sv2_ref, sa_ref,
            mx_ref)

    @pl.when(mi == 0)
    def _init():
        pv_s[...] = pv0_ref[...]
        rs_s[...] = rt_ref[...]
        for r in sums:
            r[...] = jnp.zeros(r.shape, f32)
        no_ref[...] = jnp.zeros(no_ref.shape, i32)
        nchg_ref[...] = jnp.zeros(nchg_ref.shape, i32)
        last_ref[...] = jnp.full(last_ref.shape, -1, i32)

    g = g_ref[...]
    off = off_ref[...]
    tsh = tsh_ref[...]
    ja = ja_ref[...]
    jac = jac_ref[...]
    b = wb_ref[...]
    mh = mh_ref[...]
    el = el_ref[...]
    eh = eh_ref[...]
    nch_pos = nchp_ref[...] != 0
    pt0 = pt0_ref[...]
    dt0 = dt0_ref[...]
    has0 = has0_ref[...] != 0

    def body(jj, c):
        pv, rs, ce, cec, dw, dwc, sv, sv2, sa, mx, no, last, nchg = c
        j = mi * tm + jj
        first = j == 0
        valid = j < m_real
        v = v_ref[jj]
        t_j = ts_ref[j]
        pt = jnp.where(first, pt0, ts_ref[jnp.maximum(j - 1, 0)])
        dt = jnp.where(first, dt0, dts_ref[j])
        has = (has0 | (j != 0)) & valid

        vc = (v - off) / g
        pvc = (pv - off) / g
        hold = jnp.minimum(dt, mh)
        dens_r = 0.5 * (pv + v) if trapezoid else pv
        dens_c = 0.5 * (pvc + vc) if trapezoid else pvc
        ce = ce + jnp.where(has, dens_r * hold, 0.0)
        cec = cec + jnp.where(has, dens_c * hold, 0.0)
        ce_ref[jj] = ce
        cec_ref[jj] = cec
        dw = dw + jnp.where(
            has & (j >= ja),
            dens_r * jnp.maximum(jnp.minimum(hold, b - pt), 0.0), 0.0)
        pts = pt - tsh
        dwc = dwc + jnp.where(
            has & (j >= jac),
            dens_c * jnp.maximum(jnp.minimum(hold, b - pts), 0.0), 0.0)

        change = has & (v != pv)
        rd_ref[jj] = jnp.where(change, t_j - rs, 0.0)
        rr_ref[jj] = (change & (nch_pos | (nchg >= 1))).astype(i32)
        nchg = nchg + change.astype(i32)
        last = jnp.where(change, j, last)
        rs = jnp.where(change, t_j, rs)

        av = jnp.abs(vc)
        sv = sv + jnp.where(valid, vc, 0.0)
        sv2 = sv2 + jnp.where(valid, vc * vc, 0.0)
        sa = sa + jnp.where(valid, av, 0.0)
        mx = jnp.where(valid, jnp.maximum(mx, av), mx)
        no = no + (valid & ((vc < el) | (vc > eh))).astype(i32)
        pv = jnp.where(valid, v, pv)
        return pv, rs, ce, cec, dw, dwc, sv, sv2, sa, mx, no, last, nchg

    carry = (pv_s[...], rs_s[...], de_ref[...], dec_ref[...], dw_ref[...],
             dwc_ref[...], sv_ref[...], sv2_ref[...], sa_ref[...],
             mx_ref[...], no_ref[...], last_ref[...], nchg_ref[...])
    (pv, rs, ce, cec, dw, dwc, sv, sv2, sa, mx, no, last,
     nchg) = lax.fori_loop(0, tm, body, carry)
    pv_s[...] = pv
    rs_s[...] = rs
    for r, x in zip(sums, (ce, cec, dw, dwc, sv, sv2, sa, mx)):
        r[...] = x
    no_ref[...] = no
    last_ref[...] = last
    nchg_ref[...] = nchg


def _grid_ticks(m: int) -> int:
    """Padded tick count of an ``m``-tick slab."""
    return _ceil_to(m, min(m, _GRID_TICKS))


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _grid_call(fbuf, ibuf, trapezoid: bool, m_real: int, interpret: bool):
    """The slab's operands packed by dtype → its results packed by dtype.

    Every buffer is [rows of 128 device lanes], tick-major as the kernel
    reads and writes it, so packing costs the device no transposes.
    ``fbuf`` (float32) holds the readings [Mp, Dp], the 11 per-device
    float operands [11, Dp], then ``ts`` and ``dts``, each padded to
    whole rows; ``ibuf`` (int32) holds ``has0``, ``nchp``, ``ja`` and
    ``jac`` [4, Dp].  Out come the ``ce``, ``cec`` and ``rd`` ticks
    [3, Mp, Dp] and the 8 float sums [8, Dp] as one float32 buffer, and
    the ``rr`` ticks [Mp, Dp] then ``no``, ``last`` and ``nchg`` [3, Dp]
    as one int32 buffer."""
    rows, mp = ibuf.shape[0] // 4, _grid_ticks(m_real)
    vt = fbuf[:mp * rows].reshape(mp, rows, _LANES)
    pt0, dt0, pv0, rt, g, off, tsh, wb, mh, el, eh = (
        fbuf[mp * rows:(mp + 11) * rows].reshape(11, rows, _LANES))
    ts, dts = fbuf[(mp + 11) * rows:].reshape(2, -1)[:, :mp]
    has0, nchp, ja, jac = ibuf.reshape(4, rows, _LANES)
    tm = min(mp, _GRID_TICKS)
    bs = min(_GRID_SUBLANES, rows)
    per_dev = [pt0, dt0, pv0, has0, rt, nchp, g, off, tsh, ja, jac, wb, mh,
               el, eh]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    tick = pl.BlockSpec((tm, bs, _LANES), lambda i, m: (m, i, 0))
    row = pl.BlockSpec((bs, _LANES), lambda i, m: (i, 0))
    f32, i32 = _p.KFLOAT, _p.KINT
    slab = lambda dt: jax.ShapeDtypeStruct((mp, rows, _LANES), dt)
    vec = lambda dt: jax.ShapeDtypeStruct((rows, _LANES), dt)
    outs = pl.pallas_call(
        functools.partial(_grid_kernel, trapezoid=trapezoid,
                          m_real=m_real, tm=tm),
        name="ingest_grid",
        grid=(rows // bs, mp // tm),
        in_specs=[smem, smem, tick] + [row] * 15,
        out_specs=[tick] * 4 + [row] * 11,
        out_shape=[slab(f32)] * 3 + [slab(i32)] + [vec(f32)] * 8
        + [vec(i32)] * 3,
        scratch_shapes=[pltpu.VMEM((bs, _LANES), f32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(ts, dts, vt, *per_dev)
    pack = lambda xs: jnp.concatenate([x.reshape(-1, _LANES) for x in xs])
    return pack(outs[:3] + outs[4:12]), pack(outs[3:4] + outs[12:])


def stream_ingest_grid(ts, v, ops: SlabOperands,
                       trapezoid: bool = False) -> Tuple:
    """Rectangular-slab streaming ingest (see the numpy backend's
    reference docstring) as one Pallas kernel over device-lane tiles."""
    ts = np.asarray(ts, dtype=np.float64)
    v = np.asarray(v, dtype=np.float64)
    d, m = v.shape
    if m == 0 or d == 0:
        return _nb.stream_ingest_grid(ts, v, ops, trapezoid)
    anchor = ts[0]
    dp = _ceil_to(d, _LANES * min(_GRID_SUBLANES, -(-d // _LANES)))
    mp = _grid_ticks(m)
    # padding lanes hold SLAB_PAD and zero readings, padded ticks repeat
    # the last reading; all of it is sliced off below
    o = pad_operands(ops, dp)
    # window openings as tick columns, decided in float64: column j >= 1
    # starts its hold at ts[j-1], column 0 at the stored prev_t
    ja = 1 + _first_at_least(ts, o.win_a, 0.0)
    jac = 1 + _first_at_least(ts, o.win_a, o.tshift)
    ja = np.where(o.has_prev & (o.prev_t >= o.win_a), 0, ja)
    jac = np.where(o.has_prev & (o.prev_t - o.tshift >= o.win_a), 0, jac)
    # the operands packed by dtype (layout: _grid_call)
    rows, tr = dp // _LANES, -(-mp // _LANES)
    fbuf = np.empty(((mp + 11) * rows + 2 * tr, _LANES), _p.KFLOAT)
    fv = fbuf[:mp * rows].reshape(mp, dp)
    fv[:m, :d] = v.T
    fv[m:, :d] = v[:, -1]
    fv[:, d:] = 0.0
    np.stack([o.prev_t - anchor, np.where(o.has_prev, ts[0] - o.prev_t, 0.0),
              o.prev_v, o.run_t - anchor, o.gain, o.offset, o.tshift,
              o.win_b - anchor, o.max_hold, o.env_lo, o.env_hi],
             out=fbuf[mp * rows:(mp + 11) * rows].reshape(11, dp))
    _jb.fill_rows(fbuf[(mp + 11) * rows:].reshape(2, tr * _LANES),
                  [(ts - anchor, 0.0), (np.r_[0.0, np.diff(ts)], 0.0)])
    ibuf = np.array([o.has_prev, o.n_changes >= 1, ja, jac], _p.KINT)
    with span("ingest.kernel.pad", samples=d * m, slots=dp * mp, h2d=2,
              d2h=2), _p.x32():
        fo, io = jax.device_get(_grid_call(
            *jax.device_put((fbuf, ibuf.reshape(4 * rows, _LANES))),
            bool(trapezoid), m, _interpret()))
    fo, io = fo.reshape(-1, dp), io.reshape(-1, dp)
    # the ticks come back tick-major: one transposing copy each, on the
    # host, into the [D, M] the callers read
    ticks = lambda x, dt: np.ascontiguousarray(x[:m, :d].T, dtype=dt)
    ce, cec, rd = (ticks(fo[i * mp:], np.float64) for i in range(3))
    de, dec, dw, dwc, sv, sv2, sa, mx = fo[3 * mp:, :d].astype(np.float64)
    no, last, nchg = io[mp:, :d]
    new_run_t = np.where(last >= 0, ts[np.maximum(last, 0)], ops.run_t)
    new_n_changes = np.asarray(ops.n_changes, dtype=np.int64) + nchg
    return (v[:, -1].copy(), new_run_t, new_n_changes, de, dec, dw, dwc, sv,
            sv2, sa, mx, no.astype(np.int64), ce, cec, rd,
            ticks(io, io.dtype) != 0)


# -- stream_ingest: fused elementwise kernel, float64 fold on the device ---

def _flat_kernel(v_ref, pv_ref, dt_ref, has_ref, pt_ref, win_ref, winc_ref,
                 g_ref, off_ref, tsh_ref, wb_ref, mh_ref, el_ref, eh_ref,
                 inc_ref, incc_ref, wi_ref, wic_ref, vc_ref, chg_ref,
                 out_ref, *, trapezoid: bool):
    v = v_ref[...]
    pv = pv_ref[...]
    pt = pt_ref[...]
    has = has_ref[...] != 0
    g = g_ref[...]
    off = off_ref[...]
    b = wb_ref[...]

    vc = (v - off) / g
    pvc = (pv - off) / g
    hold = jnp.minimum(dt_ref[...], mh_ref[...])
    dens_r = 0.5 * (pv + v) if trapezoid else pv
    dens_c = 0.5 * (pvc + vc) if trapezoid else pvc
    inc_ref[...] = jnp.where(has, dens_r * hold, 0.0)
    incc_ref[...] = jnp.where(has, dens_c * hold, 0.0)
    wi_ref[...] = jnp.where(
        win_ref[...] != 0,
        dens_r * jnp.maximum(jnp.minimum(hold, b - pt), 0.0), 0.0)
    pts = pt - tsh_ref[...]
    wic_ref[...] = jnp.where(
        winc_ref[...] != 0,
        dens_c * jnp.maximum(jnp.minimum(hold, b - pts), 0.0), 0.0)
    vc_ref[...] = vc
    chg_ref[...] = (has & (v != pv)).astype(_p.KINT)
    out_ref[...] = ((vc < el_ref[...]) | (vc > eh_ref[...])).astype(_p.KINT)


@functools.partial(jax.jit, static_argnums=(14, 15))
def _flat_call(v, pv, dt, has, pt, win, winc, g, off, tsh, wb, mh, el, eh,
               trapezoid: bool, interpret: bool):
    """Fourteen per-sample [Kp] 32-bit inputs → seven per-sample [Kp]
    outputs."""
    kp = v.shape[0]
    rows = kp // _LANES
    br = min(_FLAT_ROWS, rows)
    spec = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    shape = lambda dt: jax.ShapeDtypeStruct((rows, _LANES), dt)
    outs = pl.pallas_call(
        functools.partial(_flat_kernel, trapezoid=trapezoid),
        name="ingest_flat",
        grid=(rows // br,),
        in_specs=[spec] * 14,
        out_specs=[spec] * 7,
        out_shape=[shape(_p.KFLOAT)] * 5 + [shape(_p.KINT)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*(x.reshape(rows, _LANES) for x in (v, pv, dt, has, pt, win, winc,
                                          g, off, tsh, wb, mh, el, eh)))
    return tuple(o.reshape(kp) for o in outs)


@functools.partial(jax.jit, static_argnums=(7, 8))
def _flat_impl(t, v, seg, first, start_idx, end_idx, ops: SlabOperands,
               trapezoid: bool, interpret: bool):
    """The slab in float64 on the device: predecessors, gaps and window
    openings before the cast, the Pallas kernel on slab-relative 32-bit
    values, then the jax tier's group fold of the widened increments."""
    pt, pv, has = _jb.ingest_prev(t, v, seg, first, ops)
    anchor = t[0]
    a = ops.win_a[seg]
    f32 = lambda x: x.astype(_p.KFLOAT)
    i32 = lambda x: x.astype(_p.KINT)
    args = (f32(v), f32(pv), f32(jnp.where(has, t - pt, 0.0)), i32(has),
            f32(pt - anchor), i32(has & (pt >= a)),
            i32(has & (pt - ops.tshift[seg] >= a)), f32(ops.gain[seg]),
            f32(ops.offset[seg]), f32(ops.tshift[seg]),
            f32(ops.win_b[seg] - anchor), f32(ops.max_hold[seg]),
            f32(ops.env_lo[seg]), f32(ops.env_hi[seg]))
    with _p.x32():
        outs = _flat_call(*args, trapezoid, interpret)
    inc, inc_c, w_inc, w_inc_c, vc = (o.astype(_p.FLOAT) for o in outs[:5])
    change, out = (o != 0 for o in outs[5:])
    return _jb.ingest_fold(t, v, seg, start_idx, end_idx, ops.run_t,
                           ops.n_changes, inc, inc_c, w_inc, w_inc_c, vc,
                           change, out)


def stream_ingest(t, v, seg, first, start_idx, end_idx, ops: SlabOperands,
                  trapezoid: bool = False) -> Tuple:
    """Streaming-monitor ingest slab (see the numpy backend's reference
    docstring): the per-sample arithmetic as one Pallas kernel, the
    group fold in float64, both on the device."""
    return _jb.ingest_padded(_flat_impl, t, v, seg, first, start_idx,
                             end_idx, ops, bool(trapezoid), _interpret())


# -- step_integrate: row-blocked masked sums --------------------------------

def _step_kernel(vals_ref, nxt_ref, dt_ref, tail_ref, j0_ref, j1_ref,
                 o_ref, *, trapezoid: bool, m: int):
    vals = vals_ref[...]
    j0 = j0_ref[...]
    j1 = j1_ref[...]
    col = lax.broadcasted_iota(_p.KINT, vals.shape, 1)
    dens = 0.5 * (vals + nxt_ref[...]) if trapezoid else vals
    core = jnp.sum(jnp.where((col >= j0) & (col < j1),
                             dens * dt_ref[...], 0.0), axis=1,
                   keepdims=True)
    tail = jnp.sum(jnp.where(col == j1, vals * tail_ref[...], 0.0),
                   axis=1, keepdims=True)
    o_ref[...] = jnp.where((j1 >= j0) & (j0 < m), core + tail, 0.0)


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _step_call(vals, nxt, dt, tail, j0, j1, trapezoid: bool, m: int,
               interpret: bool):
    n, mp = vals.shape
    bn = min(_STEP_ROWS, n)
    mat = pl.BlockSpec((bn, mp), lambda i: (i, 0))
    col = pl.BlockSpec((bn, 1), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_step_kernel, trapezoid=trapezoid, m=m),
        grid=(n // bn,),
        in_specs=[mat] * 4 + [col] * 2,
        out_specs=col,
        out_shape=jax.ShapeDtypeStruct((n, 1), _p.KFLOAT),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(vals, nxt, dt, tail, j0, j1)[:, 0]


def step_integrate(ts: np.ndarray, vals: np.ndarray, t0: np.ndarray,
                   t1: np.ndarray, trapezoid: bool = False) -> np.ndarray:
    """Batched rectangle/trapezoid step integration (see the numpy
    backend's reference docstring): exact float64 window search and
    gaps on the host, the masked sums in a row-blocked Pallas kernel."""
    ts = np.asarray(ts, dtype=np.float64)
    vals = np.asarray(vals, dtype=np.float64)
    t0 = np.asarray(t0, dtype=np.float64)
    t1 = np.asarray(t1, dtype=np.float64)
    n, m = ts.shape
    if m == 0 or n == 0:    # no samples at all: every window is 0
        return np.zeros(n)
    j0 = _nb.searchsorted_rows(ts, t0[:, None], "left")
    j1 = _nb.searchsorted_rows(ts, t1[:, None], "right") - 1
    fin = np.isfinite(ts)
    nxt_fin = np.zeros_like(fin)
    nxt_fin[:, :-1] = fin[:, 1:]
    dt = np.zeros_like(ts)
    dt[:, :-1] = np.where(nxt_fin[:, :-1], np.diff(np.where(fin, ts, 0.0),
                                                   axis=1), 0.0)
    nxt = np.zeros_like(vals)
    nxt[:, :-1] = np.where(nxt_fin[:, :-1], vals[:, 1:], 0.0)
    tail = np.where(fin, t1[:, None] - np.where(fin, ts, 0.0), 0.0)
    np_ = _ceil_to(n, 8) if n <= _STEP_ROWS else _ceil_to(n, _STEP_ROWS)
    with _p.x32():
        out = _step_call(_k32(vals, np_, 0.0), _k32(nxt, np_, 0.0),
                         _k32(dt, np_, 0.0), _k32(tail, np_, 0.0),
                         _k32(j0, np_, 0, _p.KINT),
                         _k32(j1, np_, -1, _p.KINT), bool(trapezoid), m,
                         _interpret())
    return np.asarray(out)[:n].astype(np.float64)


# -- log_filter: blocked sequential scan over segments ----------------------

def _scan_kernel(a_ref, b_ref, y0_ref, o_ref, carry):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        carry[...] = y0_ref[...]

    def step(i, y):
        y = a_ref[pl.ds(i, 1), :] * y + b_ref[pl.ds(i, 1), :]
        o_ref[pl.ds(i, 1), :] = y
        return y

    carry[...] = lax.fori_loop(0, a_ref.shape[0], step, carry[...])


@functools.partial(jax.jit, static_argnums=(3,))
def _scan_call(aT, bT, y0, interpret: bool):
    sp_n, gp = aT.shape
    ch = min(_SCAN_CHUNK, sp_n)
    bg = min(_SCAN_LANES, gp)
    tile = pl.BlockSpec((ch, bg), lambda gi, si: (si, gi))
    return pl.pallas_call(
        _scan_kernel,
        grid=(gp // bg, sp_n // ch),
        in_specs=[tile, tile,
                  pl.BlockSpec((1, bg), lambda gi, si: (0, gi))],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((sp_n, gp), _p.KFLOAT),
        scratch_shapes=[pltpu.VMEM((1, bg), _p.KFLOAT)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(aT, bT, y0)


def log_filter(tl, ticks: np.ndarray, tau: np.ndarray) -> np.ndarray:
    """Logarithmic-filter readings (see the numpy backend's reference
    docstring): float64 segment coefficients and readout on the host,
    the per-segment affine recurrence as a blocked sequential Pallas
    scan with the filter state carried in VMEM."""
    ticks = np.asarray(ticks, dtype=np.float64)
    tau = np.asarray(tau, dtype=np.float64)
    ext_e, ext_p, dts = _nb.log_filter_segments(tl, ticks, tau)
    g, n_seg = ticks.shape[0], ext_p.shape[1]
    decay = np.exp(-dts / tau[:, None])
    a_seg = np.where(dts > 0, decay, 1.0)
    b_seg = np.where(dts > 0, ext_p * (1.0 - decay), 0.0)
    # [segments, rows], padded with identity steps (a=1, b=0) along the
    # segment axis and zero columns along the row axis
    ch = min(_SCAN_CHUNK, _ceil_to(n_seg, 8))
    sp_n = _ceil_to(n_seg, ch)
    gp = _ceil_to(g, _LANES) if g <= _SCAN_LANES else _ceil_to(
        g, _SCAN_LANES)
    aT = np.ones((sp_n, gp), dtype=_p.KFLOAT)
    bT = np.zeros((sp_n, gp), dtype=_p.KFLOAT)
    aT[:n_seg, :g] = np.broadcast_to(a_seg, (g, n_seg)).T
    bT[:n_seg, :g] = np.broadcast_to(b_seg, (g, n_seg)).T
    idle = np.broadcast_to(tl.idle_w, (g,))
    with _p.x32():
        yT = np.asarray(_scan_call(aT, bT, _k32(idle, gp, 0.0)[None, :],
                                   _interpret()))
    y = np.concatenate([idle[:, None], yT[:n_seg, :g].T.astype(np.float64)],
                       axis=1)
    return _nb.log_filter_readout(y, ext_e, ext_p, ticks, tau)
