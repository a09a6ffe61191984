"""Production mesh construction (see brief: MULTI-POD DRY-RUN §1).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state.  The dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; everything else (tests, benches) sees the real single device.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Optional[Sequence[str]] = None):
    """Arbitrary mesh for tests (e.g. (2,2) on 4 forced host devices)."""
    shape = tuple(shape)
    if axes is None:
        axes = ("pod", "data", "model")[-len(shape):] if len(shape) <= 3 \
            else tuple(f"ax{i}" for i in range(len(shape)))
    return jax.make_mesh(shape, tuple(axes))


def data_mesh(n_shards: Optional[int] = None):
    """1-D ``("data",)`` mesh over the first ``n_shards`` local devices —
    the fleet-audit sharding axis (see ``core/fleet_engine_shard``).

    Unlike :func:`make_mesh` this may use a *subset* of the visible
    devices, so a 4-way audit mesh works on an 8-device host.  Defaults
    to every visible device.  On CPU hosts, set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=<n>`` before the
    first jax import to expose n devices (``docs/scaling.md``)."""
    n = jax.device_count() if n_shards is None else int(n_shards)
    if n < 1:
        raise ValueError(f"n_shards must be >= 1, got {n}")
    require_devices(n)
    devs = np.asarray(jax.devices()[:n], dtype=object)
    return jax.sharding.Mesh(devs, ("data",))


def n_chips(mesh) -> int:
    return int(np.prod(mesh.devices.shape))


def require_devices(n: int) -> None:
    have = jax.device_count()
    if have < n:
        if jax.default_backend() != "cpu":
            raise RuntimeError(
                f"mesh needs {n} devices but this {jax.default_backend()} "
                f"host has {have}; run on a host with {n} chips")
        raise RuntimeError(
            f"mesh needs {n} devices but the backend exposes {have}. "
            "On a CPU host (rehearsal only), set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=<n> before "
            "any jax import (see launch/dryrun.py). That flag does not "
            "apply to an accelerator: there the mesh spans real chips, "
            "all driven from one process.")
