"""The ingest kernel call's transfer contract.

Each slab moves its operands to the device in one explicit
``jax.device_put`` of one packed buffer per dtype, and brings its
results back in one ``jax.device_get`` of the same kind.  Under
``jax.transfer_guard("disallow")`` an implicit upload (a NumPy array
passed to a jitted function) raises.  The guard cannot see fetches on
the CPU, where ``np.asarray`` of a device array copies nothing, so the
calls of ``jax.device_put`` and ``jax.device_get`` are counted too.  The
results are bitwise those of the same call without the guard.  Small
shapes; the Pallas kernels run in the interpreter on the CPU.
"""
import numpy as np
import pytest

from repro.core.engine_backend import get_backend, has_jax
from repro.core.engine_backend.pytrees import SlabOperands

pytestmark = pytest.mark.skipif(not has_jax(), reason="jax not installed")


def _grid_args(rng, d=37, m=7):
    """A [D, M] slab and its devices' state: 37 devices pad to one
    1,024-lane block, so padding rows travel too."""
    ts = 2.0 + np.cumsum(rng.uniform(0.05, 0.15, m))
    v = np.round(rng.uniform(60.0, 250.0, (d, m)) / 25.0) * 25.0
    has_prev = rng.random(d) > 0.3
    prev_t = rng.uniform(1.0, 2.0, d)
    return (ts, v, SlabOperands(
        prev_t, rng.uniform(60.0, 250.0, d), has_prev,
        np.where(has_prev, prev_t, ts[0]), rng.integers(0, 4, d),
        rng.uniform(0.95, 1.05, d), rng.uniform(-3.0, 3.0, d),
        np.full(d, 0.025), np.full(d, 2.2), np.full(d, 2.6),
        np.where(rng.random(d) < 0.5, np.inf, 0.05), np.full(d, 0.0),
        np.full(d, 240.0)), True)


def _flat_args(rng, k=300, u=11):
    """A sorted, grouped slab of ``k`` samples over ``u`` devices."""
    seg = np.sort(rng.integers(0, u, k))
    seg = np.unique(seg, return_inverse=True)[1]
    u = int(seg.max()) + 1
    t = np.concatenate([np.sort(rng.uniform(0.0, 5.0, n))
                        for n in np.bincount(seg)])
    v = np.round(rng.uniform(60.0, 250.0, k) / 25.0) * 25.0
    first = np.r_[True, seg[1:] != seg[:-1]]
    start_idx = np.flatnonzero(first)
    end_idx = np.r_[start_idx[1:] - 1, k - 1]
    has_prev = rng.random(u) > 0.3
    prev_t = rng.uniform(-1.0, 0.0, u)
    return (t, v, seg, first, start_idx, end_idx, SlabOperands(
        prev_t, rng.uniform(60.0, 250.0, u), has_prev,
        np.where(has_prev, prev_t, t[start_idx]), rng.integers(0, 4, u),
        rng.uniform(0.95, 1.05, u), rng.uniform(-3.0, 3.0, u),
        np.full(u, 0.025), np.full(u, 1.0), np.full(u, 4.0),
        np.where(rng.random(u) < 0.5, np.inf, 0.5), np.full(u, 0.0),
        np.full(u, 240.0)), False)


def _count_calls(monkeypatch, jax):
    """Count calls of ``jax.device_put`` and ``jax.device_get``."""
    calls = {"device_put": 0, "device_get": 0}
    for name in calls:
        real = getattr(jax, name)

        def counted(*a, _real=real, _name=name, **kw):
            calls[_name] += 1
            return _real(*a, **kw)
        monkeypatch.setattr(jax, name, counted)
    return calls


@pytest.mark.parametrize("tier,entry", [
    ("pallas", "stream_ingest_grid"),
    ("pallas", "stream_ingest"),
    ("jax", "stream_ingest"),
])
def test_ingest_call_makes_one_put_and_one_fetch(tier, entry, monkeypatch):
    import jax
    fn = getattr(get_backend(tier), entry)
    make = _grid_args if entry == "stream_ingest_grid" else _flat_args
    args = make(np.random.default_rng(5))
    want = fn(*args)        # compiles, unguarded and uncounted
    calls = _count_calls(monkeypatch, jax)
    with jax.transfer_guard("disallow"):
        got = fn(*args)
    assert calls == {"device_put": 1, "device_get": 1}
    assert len(got) == len(want) == 16
    for i, (a, b) in enumerate(zip(got, want)):
        assert isinstance(a, np.ndarray), i
        assert a.dtype == b.dtype and a.shape == b.shape, i
        np.testing.assert_array_equal(a, b, err_msg=f"output {i}")
