"""Multi-backend execution layer for the fleet engine.

The batched sensor simulation reduces to four pure array kernels — the
three transient responses (trailing boxcar, first-order "logarithmic"
filter, estimation proxy) and the closed-form poll counting behind
``SensorBank.integrate_polled``.  This package holds one implementation
per array backend:

* :mod:`~repro.core.engine_backend.numpy_backend` — the reference
  semantics; always available.
* :mod:`~repro.core.engine_backend.jax_backend` — ``jax.jit`` + ``vmap``
  kernels (``lax.associative_scan`` for the filter recurrence), traced
  under x64 so results stay within one reporting quantum of NumPy.
* :mod:`~repro.core.engine_backend.pallas_backend` — fused Pallas
  kernels for the streaming hot loops (``stream_ingest``,
  ``stream_ingest_grid``, ``step_integrate``, ``log_filter``) in 32
  bits, run by the Pallas interpreter where the platform is the CPU;
  gather-bound kernels delegate to the jax tier.

Backends are plain modules sharing one function signature set over the
pytree containers in :mod:`~repro.core.engine_backend.pytrees`
(``TimelineArrays``, ``ReadingSchedule``, ``PollGrid``).  Select one with
``SensorBank(..., backend="jax")`` / ``fleet_audit(..., backend="auto")``
or grab it directly via :func:`get_backend`.  See ``docs/backends.md``.

The package also hosts :mod:`~repro.core.engine_backend.vecrng` — N
lock-step per-seed RNG streams, bitwise-compatible with
``np.random.default_rng`` — the substrate of the array-native workload
synthesis and the engine's vectorized noise/jitter draws
(``docs/scaling.md``).
"""
from __future__ import annotations

import importlib
import importlib.util
import os
from typing import Optional, Tuple

from repro.core.engine_backend import numpy_backend
from repro.core.engine_backend.pytrees import (PollGrid, ReadingSchedule,
                                               TimelineArrays)

__all__ = ["available_backends", "get_backend", "has_jax",
           "resolve_backend", "use_compile_cache", "PollGrid",
           "ReadingSchedule", "TimelineArrays", "numpy_backend"]

_BACKENDS = {"numpy": numpy_backend}
_CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         *[os.pardir] * 4))
_KNOWN = ("numpy", "jax", "pallas")


_HAS_JAX: Optional[bool] = None


def has_jax() -> bool:
    """Whether jax is installed.  An installed jax is imported here, and
    a broken install (jax without a matching jaxlib, say) raises instead
    of reading as absent, so ``backend="auto"`` never falls back to numpy
    on a host that was meant to use the accelerator.  The result is
    cached."""
    global _HAS_JAX
    if _HAS_JAX is None:
        _HAS_JAX = importlib.util.find_spec("jax") is not None
        if _HAS_JAX:
            importlib.import_module("jax")
    return _HAS_JAX


def use_compile_cache() -> Optional[str]:
    """Keep jax's persistent compilation cache in one fixed directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already uses it and
    nothing else is set.  Otherwise the cache goes to ``.jax_cache/`` at
    the root of the checkout: a fixed path, since the path is part of the
    cache key.  Entry points call this (``chip_smoke.py``, the
    benchmarks, ``python -m repro.collect``); the tests do not.  Returns
    the directory, or None without jax."""
    if not has_jax():
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def available_backends() -> Tuple[str, ...]:
    """Names accepted by :func:`get_backend`, in preference order.

    The pallas tier rides on the same jax install (on a CPU platform its
    kernels run in the Pallas interpreter), so both accelerated tiers
    appear whenever jax is installed."""
    return ("numpy", "jax", "pallas") if has_jax() else ("numpy",)


# the minimum kernel surface a backend *object* must expose to stand in
# for a named backend module (the audit path's working set)
_KERNEL_SURFACE = ("boxcar_means", "estimation_means", "log_filter",
                   "query_slots", "poll_counts", "err_moments")


def resolve_backend(name):
    """Normalise a backend selector: ``None`` → ``"numpy"`` (the default
    and reference), ``"auto"`` → ``"jax"`` when importable else
    ``"numpy"``.  Asking for ``"jax"`` without jax installed raises.

    A non-string *backend object* (module-like: anything exposing the
    kernel signature set, e.g. a
    :class:`~repro.core.fleet_engine_shard.ShardedBackend`) passes
    through unchanged — that is how composed tiers plug into
    ``SensorBank``/``fleet_audit`` without registering a global name."""
    if name is None:
        return "numpy"
    if not isinstance(name, str):
        missing = [k for k in _KERNEL_SURFACE if not hasattr(name, k)]
        if missing:
            raise ValueError(
                f"backend object {name!r} lacks kernel(s) "
                f"{', '.join(missing)}; a backend must expose "
                f"{', '.join(_KERNEL_SURFACE)}")
        return name
    if name == "auto":
        return "jax" if has_jax() else "numpy"
    if name not in _KNOWN:
        raise ValueError(
            f"unknown backend '{name}'; known: {', '.join(_KNOWN)}")
    if name in ("jax", "pallas") and not has_jax():
        raise ValueError(f"backend '{name}' requested but jax is not "
                         "installed; use backend='numpy' or 'auto'")
    return name


def get_backend(name=None):
    """The backend module (or passed-through backend object) for ``name``
    (see :func:`resolve_backend`)."""
    name = resolve_backend(name)
    if not isinstance(name, str):
        return name
    if name not in _BACKENDS:
        _BACKENDS[name] = importlib.import_module(
            f"repro.core.engine_backend.{name}_backend")
    return _BACKENDS[name]
