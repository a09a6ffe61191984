"""Precision policy of the accelerated tiers, in one module.

Two policies, one per tier:

* **jax and shard tiers** trace and run under 64-bit mode
  (:func:`x64`) with :data:`FLOAT`/:data:`INT` arrays, so elementwise
  arithmetic matches the numpy float64 reference bit for bit and only
  reduction order differs.  A TPU has no native float64: XLA emulates
  it, correctly but slowly to compile, which is why the tier's prefix
  scans go through :func:`cumsum`/:func:`cummax` below.
* **pallas tier** kernels run in 32 bits (:data:`KFLOAT`/:data:`KINT`,
  under :func:`x32`), because Mosaic has no 64-bit element types.  The
  wrappers subtract a float64 per-slab anchor before the cast (times
  become slab-relative; gaps are formed in float64 on the host), kernels
  return per-slab increments, and the host re-bases them into the
  float64 running totals and widens counts to int64.

:data:`KERNEL_RTOL` is the agreement the pallas tier holds with the
numpy reference, argued from what the sensor can resolve at all.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

FLOAT = jnp.float64
INT = jnp.int64
KFLOAT = jnp.float32
KINT = jnp.int32

# nvidia-smi prints power.draw with two decimals: a 0.01 W reporting
# quantum.  The lowest draw a supported GPU reports is its idle floor,
# tens of watts, so one quantum is >= 2e-4 of any reading.  A 32-bit
# kernel on slab-relative inputs rounds each reading, gap and product at
# 6e-8 relative and sums at most one slab row per float32 accumulator,
# which stays more than ten times below that quantum: 1e-5 relative.
KERNEL_RTOL = 1e-5


def x64():
    """Context manager under which the jax and shard tiers run."""
    return jax.enable_x64(True)


def x32():
    """Context manager under which the pallas tier's kernels run (their
    index maps and scalars must stay 32-bit whatever the global flag)."""
    return jax.enable_x64(False)


def cumsum(x, axis: int = 0):
    """Inclusive prefix sum for 64-bit arrays.

    ``jnp.cumsum`` lowers to a ``reduce_window`` on TPU, which XLA's
    emulated float64 takes minutes to compile at slab sizes; the
    log-depth associative scan compiles in seconds."""
    return lax.associative_scan(jnp.add, x, axis=axis)


def cummax(x, axis: int = 0):
    """Inclusive running maximum, for the same reason as :func:`cumsum`."""
    return lax.associative_scan(jnp.maximum, x, axis=axis)
