"""TPU v5e compiles of the main path's kernels, without a chip.

Each test lowers and compiles one kernel for a described (not attached)
v5e chip at the width a deployment runs — a 10⁵-device fleet × 20 poll
ticks, 10⁵-sample flat slabs, 10⁴-device query batches — so a kernel
the TPU compiler would refuse (an unaligned tile, an unsupported
in-kernel op, too much VMEM) fails here, at no chip time.  Nothing runs:
these tests say nothing about results or speed.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library at a time,
and every test worker imports every test file.
"""
import importlib.util
import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,  # noqa: E402
                          SingleDeviceSharding)

from repro.core.engine_backend import jax_backend as jb  # noqa: E402
from repro.core.engine_backend import pallas_backend as pb  # noqa: E402
from repro.core.engine_backend import precision  # noqa: E402
from repro.core.engine_backend.pytrees import (  # noqa: E402
    TIER_F64, TIER_I32, SlabOperands, TierOperands, TimelineArrays)

D, M = 102_400, 20          # fleet × poll ticks per slab (padded to 128s)
K = 131_072                 # flat slab: samples (a power-of-two bucket)
F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2.  Skips only where the TPU library is not
    installed; a library that is present but fails is an error."""
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu is not installed")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args):
    compiled = fn.lower(*args).compile()
    return compiled.as_text()


def _kernel_in(text):
    assert "tpu_custom_call" in text


def test_pallas_stream_ingest_grid_compiles(one_chip):
    s = lambda shape, dt=F32: jax.ShapeDtypeStruct(shape, dt,
                                                   sharding=one_chip)
    # the packed operands in rows of 128 lanes: the readings, 11
    # per-device rows and one row each of ts and dts in float32, 4
    # per-device rows in int32
    rows = D // 128
    with precision.x32():
        text = _compile(pb._grid_call, s(((M + 11) * rows + 2, 128)),
                        s((4 * rows, 128), I32), False, M, False)
    _kernel_in(text)


def test_pallas_stream_ingest_compiles(one_chip):
    s = lambda shape, dt=F32: jax.ShapeDtypeStruct(shape, dt,
                                                   sharding=one_chip)
    k, ki = s((K,)), s((K,), I32)
    with precision.x32():
        text = _compile(pb._flat_call, k, k, k, ki, k, ki, ki, k, k, k, k,
                        k, k, k, True, False)
    _kernel_in(text)


def test_pallas_stream_ingest_packed_slab_compiles(one_chip):
    """The whole flat slab program: the packed float64 and int64 operands
    unpacked, the float64 predecessors and fold around the kernel, the
    results packed again."""
    u = 16_384
    s = lambda n, dt: jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)
    with precision.x64():
        text = _compile(jb._ingest_packed, pb._flat_impl,
                        s(2 * K + 11 * u, jnp.float64),
                        s(2 * K + 4 * u, jnp.int64), K, (False, False))
    _kernel_in(text)


def test_pallas_step_integrate_compiles(one_chip):
    s = lambda shape, dt=F32: jax.ShapeDtypeStruct(shape, dt,
                                                   sharding=one_chip)
    mat = s((D, M))
    with precision.x32():
        text = _compile(pb._step_call, mat, mat, mat, mat, s((D, 1), I32),
                        s((D, 1), I32), True, M, False)
    _kernel_in(text)


def test_pallas_log_filter_compiles(one_chip):
    s = lambda shape: jax.ShapeDtypeStruct(shape, F32, sharding=one_chip)
    with precision.x32():
        text = _compile(pb._scan_call, s((64, D)), s((64, D)), s((1, D)),
                        False)
    _kernel_in(text)


def test_jax_stream_ingest_grid_compiles(one_chip):
    s = lambda shape, dt=jnp.float64: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    r = s((D,))
    ops = SlabOperands(r, r, s((D,), jnp.bool_), r, s((D,), jnp.int64), r,
                       r, r, r, r, r, r, r)
    with precision.x64():
        _compile(jb._stream_ingest_grid_impl, s((M,)), s((D, M)), ops,
                 False)


def test_jax_snapshot_energy_at_compiles(one_chip):
    q, n, ring = 8, 10_000, 16
    s = lambda shape, dt=jnp.float64: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    v = s((n,))
    with precision.x64():
        _compile(jb._snapshot_energy_at_impl, s((q,)), v, v,
                 s((n,), jnp.bool_), v, v, v, s((n, ring)), s((n, ring)),
                 s((n, ring)), True)


def test_sharded_audit_kernel_compiles_on_2x2_mesh(topo):
    from repro.core.fleet_engine_shard import ShardedBackend
    mesh = Mesh(topo.devices, ("data",))
    sb = ShardedBackend(mesh)
    rows, segs = D, 16
    data = NamedSharding(mesh, PartitionSpec("data"))
    s = lambda shape, dt=jnp.float64: jax.ShapeDtypeStruct(
        shape, dt, sharding=data)
    tl = TimelineArrays(s((rows, segs + 1)), s((rows, segs)), s((rows,)),
                        s((rows,), jnp.int64))
    with precision.x64():
        text = _compile(sb._boxcar[True], tl, s((rows, M)), s((rows, M)))
    assert "all-gather" not in text and "all-reduce" not in text


def test_jax_history_kernels_compile(one_chip):
    """The history tier's write kernel, its operand unpack, and one
    flush's program (two series, a by-label reduction and energy-at rows
    over two tiers) at the dashboard deployment's width: 301 boundaries
    × 10⁵ devices.  The program splits each float64 tier once, however
    many of its calls read it."""
    slots, n, q, pairs = 301, 100_000, 301, 16_384
    s = lambda shape, dt=jnp.float64: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    v, b = s((n,)), s((n,), jnp.bool_)
    ops = TierOperands(lo_t=v, hi_t=v, last_t=v, first_t=v, has=b,
                       max_hold=v, dens=v, base=v, active=b, tol=v,
                       codes=s((n,), I32))
    tier = s((slots, n))
    layout = (("series", 0, q, 0), ("series", 1, q, 0),
              ("by_label", 0, 2, 3), ("energy_at", 1, 8, 0))
    with precision.x64():
        flush = _compile(jb._history_flush, (tier, tier), (ops, ops),
                         s((sum(2 * c[2] + 1 for c in layout),)), layout)
        write = _compile(jb._history_write_shared, tier, tier,
                         s((pairs,), I32), s((pairs,), I32), s((pairs,)),
                         s((pairs,)))
        _compile(jb._history_unpack, s((len(TIER_F64), n)),
                 s((len(TIER_I32), n), I32))
    # each kernel's operations carry its scope in their op names, which a
    # device trace reports as their ``tf_op``
    for scope in ("history_series", "history_energy_at", "history_by_label",
                  "history_fetch"):
        assert f"/{scope}/" in flush, scope
    assert "/history_write/" in write
    splits = [line for line in flush.splitlines()
              if "X64SplitHigh" in line and f"[{slots},{n}]" in line]
    assert len(splits) == 2, splits
