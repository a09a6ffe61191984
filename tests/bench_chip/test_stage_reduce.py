"""CPU tests of the stage reduction (``bench/stage_reduce.py``): a small
synthetic trace with known answers, and one tiny traced run of a cell.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench_chip
"""
from __future__ import annotations

import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
for _p in (BENCH, os.path.join(ROOT, "src"), os.path.dirname(__file__)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import stage_reduce as sr  # noqa: E402
import trace_reduce  # noqa: E402

MS = 1_000_000
T = ("/host:CPU", 0)


def _span(name, s, e, **args):
    return (T, name, s * MS, e * MS, args)


# two slabs in a 95-ms window: a clean grid slab, and a grid slab that
# fell back to the general path and ran the health machine
SPANS = [
    _span("ingest_grid", 10, 40, samples=100, devices=10),
    _span("ingest.prep", 10, 12),
    _span("ingest.gather", 12, 13),
    _span("ingest.kernel", 13, 25, samples=100, devices=10),
    _span("ingest.kernel.pad", 14, 20, samples=100, slots=128),
    _span("ingest.ring", 25, 31),
    _span("ingest.scatter", 31, 32),
    _span("ingest.periods", 32, 35),
    _span("ingest.moments", 35, 37),
    _span("ingest_grid", 45, 80, samples=50, devices=5),
    _span("ingest.prep", 45, 46),
    _span("ingest", 47, 79, samples=50),
    _span("ingest.prep", 47, 50),
    _span("ingest.kernel", 52, 60, samples=40, devices=5),
    _span("ingest.kernel.pad", 53, 59, samples=40, slots=1024),
    _span("ingest.health", 60, 79, devices=100),
]
OPS = {"/device:TPU:0": [("ingest_grid", 16 * MS, 18 * MS),
                         ("unnamed", 21 * MS, 22 * MS),
                         ("ingest_fold", 54 * MS, 56 * MS)]}
WINDOW = (0, 95 * MS)


def test_stage_reduction_on_a_synthetic_trace():
    r = sr.reduce_stages(OPS, SPANS, WINDOW)
    st = r["stages"]
    assert (r["slabs"], r["fallbacks"]) == (2, 1)
    # prep three times: 2 + 1 (the grid root's clean test) + 3 ms
    assert st["ingest.prep"]["count"] == 3
    assert st["ingest.prep"]["self_s"] == pytest.approx(0.006)
    # the kernel less the device time under it; the pad span is part of
    # it, not a child
    assert st["ingest.kernel"]["wall_s"] == pytest.approx(0.020)
    assert st["ingest.kernel"]["device_s"] == pytest.approx(0.005)
    assert st["ingest.kernel"]["self_s"] == pytest.approx(0.015)
    assert st["ingest.kernel"]["idle_s"] == pytest.approx(0.015)
    assert "ingest.kernel.pad" not in st
    # the roots' own remainder: 37-40, 46-47 and 79-80 in the grid roots,
    # 50-52 in the nested ``ingest``
    assert st["unattributed"]["self_s"] == pytest.approx(0.007)
    assert st["ingest.health"]["wall_s"] == pytest.approx(0.019)
    # idle under no program span: 0-10, 40-45, 80-95
    assert st["harness"]["idle_s"] == pytest.approx(0.030)
    assert (r["samples"], r["slots"]) == (140, 1152)

    # gaps named by the innermost span covering most of each
    assert r["idle_gaps_by_stage"] == [
        ["ingest.health", pytest.approx(0.039)],
        ["ingest.ring", pytest.approx(0.032)],
        ["harness", pytest.approx(0.016)],
        ["ingest.kernel", pytest.approx(0.003)]]

    tail = r["tail_slabs"]
    assert (tail["slabs"], tail["health"]) == (1, 1)
    assert tail["p95_ms"] == pytest.approx(34.75)
    assert tail["ms"]["ingest.health"] == pytest.approx(19.0)
    assert tail["ms"]["unattributed"] == pytest.approx(4.0)
    assert sum(tail["ms"].values()) == pytest.approx(35.0)
    assert tail["all_ms"]["ingest.kernel"] == pytest.approx(10.0)
    assert r["device_scopes"] == {"ingest_grid": pytest.approx(0.002),
                                  "ingest_fold": pytest.approx(0.002),
                                  "unnamed": pytest.approx(0.001)}

    m = sr.metrics(r)
    assert m["ingest_prep_ms"] == pytest.approx(3.0)
    assert m["ingest_pack_ms"] == pytest.approx(7.5)
    assert m["ingest_ring_ms"] == pytest.approx(3.0)
    assert m["unattributed_ms"] == pytest.approx(3.5)
    assert m["health_ms"] == pytest.approx(19.0)
    assert m["ingest_pad_share"] == pytest.approx(100 * (1 - 140 / 1152))

    bench_spans = [("window", 0, 95 * MS), ("ingest", 10 * MS, 40 * MS),
                   ("ingest", 45 * MS, 80 * MS)]
    red = trace_reduce.reduce_trace(
        {k: [("op", s, e) for _, s, e in v] for k, v in OPS.items()},
        bench_spans)
    c = sr.coverage(r, red)
    assert c["unattributed_share"] == pytest.approx(7 / 60)
    assert c["kernel_device_share"] == pytest.approx(1.0)
    assert c["stage_idle_share"] == pytest.approx(53 / 90)
    assert c["named_scope_share"] == pytest.approx(0.8)


def test_no_device_plane_gives_no_metrics():
    r = sr.reduce_stages({}, SPANS, WINDOW)
    assert r["devices"] == 0 and r["slabs"] == 2
    assert sr.metrics(r) == {}
    assert r["device_scopes"] == {}


def test_a_trace_without_program_spans():
    """The parent of this change marks no stages: nothing to reduce, and
    nothing raises."""
    r = sr.reduce_stages(OPS, [], WINDOW)
    assert (r["slabs"], r["samples"], r["slots"]) == (0, 0, 0)
    assert r["tail_slabs"] == {}
    assert sr.metrics(r) == {}
    assert {g[0] for g in r["idle_gaps_by_stage"]} == {"harness"}


@pytest.mark.parametrize("name,stats,scope", [
    ("fusion.58", [("tf_op", "jit(_flat_impl)/ingest_fold/add")],
     "ingest_fold"),
    ("ingest_flat.3", [], "ingest_flat"),
    ("fusion.9", [("tf_op", "jit(f)/ingest_grid/ingest_fold/mul")],
     "ingest_fold"),
    ("fusion.2", [("long_name", "jit(_stream_ingest_grid_impl)/mul")],
     "unnamed"),
    ("copy.1", [("flops", 0)], "unnamed")])
def test_scope_of_a_device_operation(name, stats, scope):
    assert sr.scope_of(name, stats) == scope


def _pb(*fields):
    """A protobuf message from ``(number, value)``: ints as varints,
    bytes and str length-delimited."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def test_op_names_from_event_metadata(tmp_path):
    """A device operation's op name sits in a ``tf_op`` stat on its event
    metadata, as a string or as a reference to a stat metadata's name;
    host planes are not read."""
    def event(i, name, *stats):
        return (4, _pb((1, i), (2, _pb((1, i), (2, name), *stats))))

    def stat_name(i, name):
        return (5, _pb((1, i), (2, _pb((1, i), (2, name)))))
    tpu = _pb((2, "/device:TPU:0"), stat_name(7, "tf_op"),
              stat_name(8, "flops"),
              stat_name(9, "jit(_flat_impl)/ingest_prev/gather:"),
              event(1, "%fusion.58 = f32[4096] fusion()",
                    (5, _pb((1, 8), (3, 12))),
                    (5, _pb((1, 7), (5, "jit(_flat_impl)/ingest_fold/add:")))),
              event(2, "%fusion.5 = pred[32768] fusion()",
                    (5, _pb((1, 7), (7, 9)))),
              event(3, "%copy.1 = f32[8] copy()"))
    host = _pb((2, "/host:CPU"), stat_name(7, "tf_op"),
               event(1, "repro.ingest", (5, _pb((1, 7), (5, "x")))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb((1, tpu), (1, host)))
    names = sr.op_names(str(path))
    assert names == {
        "%fusion.58 = f32[4096] fusion()": "jit(_flat_impl)/ingest_fold/add:",
        "%fusion.5 = pred[32768] fusion()":
            "jit(_flat_impl)/ingest_prev/gather:"}
    assert [sr.scope_of(n, [("tf_op", t)]) for n, t in names.items()] == [
        "ingest_fold", "ingest_prev"]


def test_stage_run_reads_a_real_trace(tmp_path, monkeypatch):
    """The whole tool on a tiny copy of the benchmark, its chip check
    switched off: the trace's program spans come back as stages, and no
    device metric is given without a device plane."""
    import run
    from test_bench_chip import tiny_root
    monkeypatch.setattr(run, "chip_error", lambda devices, chips: None)
    root = tiny_root(tmp_path)
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = sr.main(["--workload", "monitor_grid", "--seed",
                      str(2**31 + 5)], root=root)
    assert rc == 0
    out = json.loads(buf.getvalue().strip().splitlines()[-1])
    st = out["stages"]
    assert st["slabs"] > 0 and st["fallbacks"] == 0
    assert {"ingest.prep", "ingest.gather", "ingest.kernel", "ingest.ring",
            "ingest.scatter", "ingest.periods",
            "ingest.moments"} <= set(st["stages"])
    assert st["samples"] == st["slots"]
    assert out["metrics"] == {}
    assert out["window"]["samples_per_s"] > 0
