"""Parity tolerance of each accelerated tier against the numpy reference.

The jax tier runs in float64 and keeps the tolerance each test states.
The pallas tier's kernels run in 32 bits
(:mod:`repro.core.engine_backend.precision`): every value carries the
float32 rounding of the readings, gaps and slab-relative times that
formed it, and window clipping subtracts nearby times, so the error
scales with the magnitude of the output, not with each entry.  The
pallas bound is therefore ``KERNEL_RTOL`` relative to each entry and to
the largest finite entry of the output.  ``KERNEL_RTOL`` (1e-5) is ten
times below nvidia-smi's 0.01 W reporting quantum over the idle floor of
any supported GPU: a difference the sensor cannot show.  Booleans and
counts stay exact under it.
"""
import numpy as np

from repro.core.engine_backend.precision import KERNEL_RTOL


def tier_tol(backend, desired, rtol, atol=0.0):
    """``(rtol, atol)`` for comparing ``backend``'s output to the numpy
    reference ``desired``: as stated for float64 tiers, widened to
    :data:`KERNEL_RTOL` of the output's magnitude for the pallas tier."""
    if backend != "pallas":
        return rtol, atol
    d = np.asarray(desired, dtype=np.float64)
    fin = np.abs(d[np.isfinite(d)])
    scale = float(fin.max()) if fin.size else 0.0
    return max(rtol, KERNEL_RTOL), max(atol, KERNEL_RTOL * scale)


def assert_tier_close(actual, desired, backend, rtol, atol=0.0, **kw):
    rtol, atol = tier_tol(backend, desired, rtol, atol)
    np.testing.assert_allclose(np.asarray(actual, dtype=np.float64),
                               np.asarray(desired, dtype=np.float64),
                               rtol=rtol, atol=atol, **kw)
