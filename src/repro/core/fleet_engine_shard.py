"""Mesh-sharded fleet audits: ``shard_map`` the audit kernels over devices.

The chunked :func:`~repro.core.fleet_engine.fleet_audit` streams device
slabs through one host; every kernel call — the transient responses in
:meth:`SensorBank.attach`, the closed-form poll counting behind
``integrate_polled``, the ``err_moments`` reductions — is embarrassingly
parallel across device *rows*.  This module puts those rows on a jax
mesh:

* :class:`ShardedBackend` wraps the jax backend's jitted kernel impls in
  ``shard_map`` over a 1-D ``("data",)`` mesh
  (:func:`repro.launch.mesh.data_mesh`).  It exposes the standard
  backend kernel surface, so ``SensorBank(..., backend=ShardedBackend(mesh))``
  and ``fleet_audit(..., mesh=mesh)`` work unchanged — row counts are
  padded to a multiple of the axis size (padding replicates the last
  row) and results sliced back.
* ``err_moments`` becomes an **on-device tree reduction**: each shard
  reduces its rows to one Chan moment block ``(count, mean, M2,
  mean_abs, max_abs)`` inside the mapped kernel (padded rows masked by
  global index), and the per-shard blocks merge on device through a
  log-depth binary tree of Chan parallel-Welford combines
  (:func:`tree_merge_moments`) — no sequential host-side folding.
  Tree-order invariance of the merge is property-tested in
  ``tests/test_fleet_engine.py``.
* :func:`fleet_audit_sharded` is the entry point: it builds the mesh,
  sizes super-slabs as ``n_shards x shard_chunk`` rows so every mesh
  device audits one slab-worth per step, and double-buffers workload
  synthesis (``prefetch_workloads=True`` — vecrng streams are jump-based
  so per-slab substreams are deterministic regardless of which thread
  synthesises them).

Determinism: per-device results are row-independent math, so a sharded
audit matches the single-process jax audit at the same super-slab
chunking to float-accumulation order (≲1e-12 relative; the only
reordering is each shard's padded reading width).  The single-shard path
is untouched — ``fleet_audit`` without ``mesh=`` never imports this
module.  See ``docs/scaling.md`` for the
``XLA_FLAGS=--xla_force_host_platform_device_count`` recipe.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.engine_backend import jax_backend as _jb
from repro.core.engine_backend import precision as _p
from repro.core.engine_backend.pytrees import (PollGrid, ReadingSchedule,
                                               TimelineArrays)

__all__ = ["ShardedBackend", "fleet_audit_sharded", "tree_merge_moments"]


# ---------------------------------------------------------------------------
# On-device Chan tree reduction
# ---------------------------------------------------------------------------

def _chan_pair(a, b):
    """Merge moment blocks pairwise: ``a``/``b`` are ``[k, 5]`` stacks of
    ``(count, mean, M2, mean_abs, max_abs)``; returns the ``[k, 5]`` Chan
    parallel-Welford combination.  Empty blocks (count 0) are identity
    elements on either side, so padding a tree with zero blocks is
    exact."""
    na, nb = a[:, 0], b[:, 0]
    tot = na + nb
    safe = jnp.maximum(tot, 1.0)
    delta = b[:, 1] - a[:, 1]
    mean = a[:, 1] + delta * nb / safe
    m2 = a[:, 2] + b[:, 2] + delta * delta * na * nb / safe
    mean_abs = a[:, 3] + (b[:, 3] - a[:, 3]) * nb / safe
    max_abs = jnp.maximum(a[:, 4], b[:, 4])
    merged = jnp.stack([tot, mean, m2, mean_abs, max_abs], axis=1)
    merged = jnp.where((nb == 0)[:, None], a, merged)
    return jnp.where((na == 0)[:, None], b, merged)


@jax.jit
def _tree_merge_impl(blocks):
    k = blocks.shape[0]
    p = 1 << max(k - 1, 0).bit_length()
    if p > k:
        blocks = jnp.concatenate(
            [blocks, jnp.zeros((p - k, 5), blocks.dtype)], axis=0)
    while blocks.shape[0] > 1:
        blocks = _chan_pair(blocks[0::2], blocks[1::2])
    return blocks[0]


def tree_merge_moments(blocks) -> np.ndarray:
    """Fold ``[k, 5]`` Chan moment blocks to one ``[5]`` block through a
    log-depth binary tree (``blocks[0::2]`` ⊕ ``blocks[1::2]`` per
    level).  ``k`` is padded to a power of two with empty blocks — exact
    identities under :func:`_chan_pair` — so any shard count works.  The
    tree is unrolled at trace time (k is static); for the shard counts
    this module sees (≤ dozens) that is a handful of fused combines."""
    with _p.x64():
        return np.asarray(
            _tree_merge_impl(jnp.asarray(blocks, _p.FLOAT)))


def _local_moments_impl(e, n_true):
    """Per-shard moment block over the locally-held error rows.  Rows at
    global index >= ``n_true`` are padding and masked out; runs *inside*
    ``shard_map``, so ``lax.axis_index`` supplies the shard's offset."""
    c = e.shape[0]
    i0 = lax.axis_index("data") * c
    valid = (i0 + jnp.arange(c)) < n_true
    cnt = jnp.sum(valid.astype(e.dtype))
    safe = jnp.maximum(cnt, 1.0)
    mean = jnp.sum(jnp.where(valid, e, 0.0)) / safe
    m2 = jnp.sum(jnp.where(valid, (e - mean) ** 2, 0.0))
    ae = jnp.where(valid, jnp.abs(e), 0.0)
    mean_abs = jnp.sum(ae) / safe
    max_abs = jnp.max(ae, initial=0.0)
    zero = cnt == 0
    mean = jnp.where(zero, 0.0, mean)
    mean_abs = jnp.where(zero, 0.0, mean_abs)
    return jnp.stack([cnt, mean, m2, mean_abs, max_abs])[None, :]


# ---------------------------------------------------------------------------
# The sharded backend
# ---------------------------------------------------------------------------

def _pad_rows(a: np.ndarray, rows: int) -> np.ndarray:
    """Pad axis 0 to ``rows`` by replicating the final row — always valid
    device data, so padded lanes trace the same math and never produce
    non-finite values (their outputs are sliced away)."""
    n = a.shape[0]
    if n == rows:
        return a
    reps = np.broadcast_to(a[-1:], (rows - n,) + a.shape[1:])
    return np.concatenate([np.asarray(a), reps], axis=0)


class ShardedBackend:
    """The jax kernel set ``shard_map``-ed over a ``("data",)`` mesh.

    Drop-in for a named backend module anywhere the engine takes
    ``backend=`` (``SensorBank``, ``fleet_audit``, ``StreamingMoments
    .update``): each kernel splits its row axis across the mesh devices,
    runs the jax backend's jitted impl per shard, and reassembles.
    Scalars and shared (1-row) timelines are replicated.  Kernels not on
    the audit hot path delegate to the plain jax module via attribute
    fallthrough.

    ``err_moments`` does NOT return per-row output: each shard reduces
    locally and the per-shard blocks merge through the on-device Chan
    tree (:func:`tree_merge_moments`), so a 10M-row error reduction
    ships 5 floats to the host.
    """

    def __init__(self, mesh, base: str = "jax"):
        if "data" not in mesh.axis_names:
            raise ValueError(
                f"mesh axes {mesh.axis_names} lack the 'data' axis; build "
                "one with repro.launch.mesh.data_mesh(n_shards)")
        if base not in ("jax", "auto"):
            raise ValueError(
                "ShardedBackend shards the jax kernel impls; "
                f"base='{base}' is not supported (use 'jax')")
        self.mesh = mesh
        self.n_shards = int(mesh.shape["data"])
        self.name = f"shard({self.n_shards})"

        def smap(fn, in_specs, out_specs=P("data")):
            return jax.jit(jax.shard_map(
                fn, mesh=self.mesh, in_specs=in_specs,
                out_specs=out_specs, check_vma=False))

        D, R = P("data"), P()
        # two variants per timeline kernel: per-device timelines shard
        # with the query rows; a shared 1-row timeline replicates
        self._boxcar = {True: smap(_jb._boxcar_impl, (D, D, D)),
                        False: smap(_jb._boxcar_impl, (R, D, D))}
        self._estimation = {
            True: smap(_jb._estimation_impl, (D, D, D, D)),
            False: smap(_jb._estimation_impl, (R, D, D, D))}
        self._log_filter = {
            True: smap(_jb._log_filter_impl, (D, D, D, R, R)),
            False: smap(_jb._log_filter_impl, (R, D, D, R, R))}
        self._query_slots = smap(_jb._query_slots_impl, (D, D))
        self._poll_counts = smap(
            _jb._poll_counts_impl, (D, R, D, R, D, D, D),
            out_specs=(D, D, D, D))
        self._local_moments = smap(
            _local_moments_impl, (D, R), out_specs=D)

    # -- row plumbing ------------------------------------------------------

    def _rows(self, n: int) -> int:
        return self.n_shards * max(math.ceil(n / self.n_shards), 1)

    def _pad_tree(self, tree, rows: int):
        return type(tree)(*(_pad_rows(np.asarray(leaf), rows)
                            for leaf in tree))

    # -- kernel surface ----------------------------------------------------

    def boxcar_means(self, tl: TimelineArrays, t0, t1) -> np.ndarray:
        n = t0.shape[0]
        rows = self._rows(n)
        per_dev = tl.n_rows != 1
        if per_dev:
            tl = self._pad_tree(tl, rows)
        with _p.x64():
            out = self._boxcar[per_dev](
                tl, jnp.asarray(_pad_rows(t0, rows), _p.FLOAT),
                jnp.asarray(_pad_rows(t1, rows), _p.FLOAT))
        return np.asarray(out)[:n]

    def estimation_means(self, tl: TimelineArrays, t0, t1,
                         model_gain) -> np.ndarray:
        n = t0.shape[0]
        rows = self._rows(n)
        per_dev = tl.n_rows != 1
        if per_dev:
            tl = self._pad_tree(tl, rows)
        with _p.x64():
            out = self._estimation[per_dev](
                tl, jnp.asarray(_pad_rows(t0, rows), _p.FLOAT),
                jnp.asarray(_pad_rows(t1, rows), _p.FLOAT),
                jnp.asarray(_pad_rows(np.asarray(model_gain), rows),
                            _p.FLOAT))
        return np.asarray(out)[:n]

    def log_filter(self, tl: TimelineArrays, ticks, tau) -> np.ndarray:
        n = ticks.shape[0]
        rows = self._rows(n)
        tau = np.asarray(tau, dtype=np.float64)
        # concrete pad bounds exactly as the jax wrapper computes them
        t_lo = (min(float(np.min(ticks)), float(np.min(tl.t_start)))
                - 5.0 * float(np.max(tau)))
        t_hi = max(float(np.max(ticks)), float(np.max(tl.t_end))) + 1e-9
        per_dev = tl.n_rows != 1
        if per_dev:
            tl = self._pad_tree(tl, rows)
        with _p.x64():
            out = self._log_filter[per_dev](
                tl, jnp.asarray(_pad_rows(ticks, rows), _p.FLOAT),
                jnp.asarray(_pad_rows(tau, rows), _p.FLOAT),
                _p.FLOAT(t_lo), _p.FLOAT(t_hi))
        return np.asarray(out)[:n]

    def query_slots(self, sched: ReadingSchedule, tq) -> np.ndarray:
        n = tq.shape[0]
        rows = self._rows(n)
        sched = self._pad_tree(sched, rows)
        with _p.x64():
            out = self._query_slots(
                sched, jnp.asarray(_pad_rows(np.asarray(tq), rows),
                                   _p.FLOAT))
        return np.asarray(out)[:n]

    def poll_counts(self, sched: ReadingSchedule, grid: PollGrid, a, b):
        n = np.asarray(a).shape[0]
        rows = self._rows(n)
        sched = self._pad_tree(sched, rows)
        t1 = _pad_rows(np.asarray(grid.t1, dtype=np.float64), rows)
        off = _pad_rows(
            np.broadcast_to(np.asarray(grid.grid_offset, np.float64),
                            (n,)), rows)
        with _p.x64():
            counts, slot_b, tail_dt, nonempty = self._poll_counts(
                sched, _p.FLOAT(grid.t0), jnp.asarray(t1, _p.FLOAT),
                _p.FLOAT(grid.period_s), jnp.asarray(off, _p.FLOAT),
                jnp.asarray(_pad_rows(np.asarray(a, np.float64), rows),
                            _p.FLOAT),
                jnp.asarray(_pad_rows(np.asarray(b, np.float64), rows),
                            _p.FLOAT))
        return (np.asarray(counts)[:n], np.asarray(slot_b)[:n],
                np.asarray(tail_dt)[:n], np.asarray(nonempty)[:n])

    def err_moments(self, e: np.ndarray):
        """Sharded error-moment reduction: per-shard local blocks, then
        the on-device Chan tree.  Same contract as the module backends:
        ``(count, mean, M2, mean_abs, max_abs)``."""
        e = np.asarray(e, dtype=np.float64).ravel()
        n = e.size
        if n == 0:
            return 0, 0.0, 0.0, 0.0, 0.0
        rows = self._rows(n)
        padded = np.zeros(rows) if rows != n else e
        if rows != n:
            padded[:n] = e
        with _p.x64():
            blocks = self._local_moments(jnp.asarray(padded, _p.FLOAT),
                                         _p.FLOAT(n))
            merged = np.asarray(_tree_merge_impl(blocks))
        return (int(merged[0]), float(merged[1]), float(merged[2]),
                float(merged[3]), float(merged[4]))

    def __getattr__(self, item):
        # off-hot-path kernels (step_integrate, stream ingest, ...) run
        # on the plain jax tier
        return getattr(_jb, item)


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def fleet_audit_sharded(n_devices: int,
                        profile: Union[str, Sequence[str]] = "a100",
                        workload=None, seed: int = 0,
                        good_practice: bool = False, n_trials: int = 2,
                        n_shards: Optional[int] = None, mesh=None,
                        shard_chunk: Optional[int] = None,
                        prefetch_workloads: bool = True):
    """A :func:`~repro.core.fleet_engine.fleet_audit` whose kernels run
    ``shard_map``-ed over ``n_shards`` mesh devices.

    Super-slabs of ``n_shards x shard_chunk`` rows stream through the
    audit loop, so every mesh device processes ``shard_chunk`` rows per
    step and peak memory stays one slab per device; workload synthesis
    for slab *k+1* overlaps slab *k*'s audit
    (``prefetch_workloads=True``).  ``mesh`` may be supplied directly
    (any mesh with a ``"data"`` axis); otherwise
    :func:`repro.launch.mesh.data_mesh` builds one over the first
    ``n_shards`` visible devices.  Results match the single-process
    audit within the chunked-audit tolerance (``docs/scaling.md``).
    """
    from repro.core.fleet_engine import fleet_audit
    if mesh is None:
        from repro.launch.mesh import data_mesh
        mesh = data_mesh(n_shards)
    k = int(mesh.shape["data"])
    if shard_chunk is None:
        shard_chunk = min(max(math.ceil(n_devices / k), 1), 25_000)
    chunk = min(int(shard_chunk) * k, max(n_devices, 1))
    return fleet_audit(
        n_devices, profile=profile, workload=workload, seed=seed,
        good_practice=good_practice, n_trials=n_trials,
        backend=ShardedBackend(mesh), chunk_devices=chunk,
        prefetch_workloads=prefetch_workloads)
