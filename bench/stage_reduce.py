"""Reduction of the program's own spans to stages, against the device
intervals of the same trace.

    python3 bench/stage_reduce.py --workload <name> --seed <n>

runs the cell's set-up, traces a window of its traffic's
``trace_seconds`` and prints one JSON line: the window's numbers as the
benchmark's readers give them, the stage metrics (:func:`metrics`), how
much of the window the stages explain (:func:`coverage`) and the whole
reduction.  It refuses to run without a TPU, as ``run.py`` does.

The program marks its stages with ``repro.*`` spans
(``src/repro/common/trace.py``): a root per slab (``ingest`` or
``ingest_grid``; an ``ingest`` inside an ``ingest_grid`` is a grid slab
that fell back to the general path) and the stages inside it
(``ingest.prep``, ``ingest.gather``, ``ingest.kernel``, ``ingest.ring``,
``ingest.scatter``, ``ingest.periods``, ``ingest.moments``,
``ingest.health``).  The backend's ``ingest.kernel.pad`` span is part
of its ``ingest.kernel`` and only lends it the ``slots`` argument: the
sample slots the device computes over, padding included.

:func:`read_xplane` turns the ``.xplane.pb`` that ``jax.profiler.trace``
writes into plain lists; :func:`reduce_stages` works on those lists
only, so it can be checked on a small synthetic trace.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

import numpy as np

from trace_reduce import _DEVICE_PLANE, _OP_LINES, _union

SPAN_PREFIX = "repro."
WINDOW = "bench.window"
ROOTS = ("ingest", "ingest_grid")
KERNEL, PAD, HEALTH = "ingest.kernel", "ingest.kernel.pad", "ingest.health"
# the program's kernel names (``pallas_call(name=...)``) and named scopes
# (``jax.named_scope``); a device operation carries one in its name or in
# a stat such as its op name
SCOPES = ("ingest_grid", "ingest_flat", "ingest_prev", "ingest_fold")
_SCOPE = re.compile(r"(?<![A-Za-z0-9_])(%s)(?![A-Za-z0-9_])"
                    % "|".join(SCOPES))
UNNAMED = "unnamed"
HARNESS, UNATTRIBUTED = "harness", "unattributed"


def scope_of(name: str, stats=()) -> str:
    """The program scope a device operation belongs to: the innermost
    of :data:`SCOPES` in its name or, failing that, in one of its string
    stats (such as its op name, ``jit(f)/scope/op``)."""
    for text in [name] + [v for _, v in stats if isinstance(v, str)]:
        found = _SCOPE.findall(text)
        if found:
            return found[-1]
    return UNNAMED


def _fields(buf):
    """``(field number, value)`` of each field of one protobuf message:
    an int for a varint, a memoryview for anything length-delimited."""
    i, n = 0, len(buf)

    def varint(i):
        out = shift = 0
        while True:
            c = buf[i]
            i += 1
            out |= (c & 0x7F) << shift
            shift += 7
            if c < 0x80:
                return out, i
    while i < n:
        key, i = varint(i)
        kind = key & 7
        if kind == 0:
            value, i = varint(i)
        elif kind == 2:
            size, i = varint(i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, value


def op_names(path: str) -> Dict[str, str]:
    """Each device operation's op name (the ``tf_op`` stat, which carries
    the jit and named scopes), by the operation's event name.

    The stat sits on the event's metadata in the ``.xplane.pb``, which
    ``ProfileData`` does not expose, so the XSpace is read here at the
    wire level: planes (1) with their name (2), event metadata (4: name
    2, stats 5) and stat metadata (5: name 2); a stat holds its
    metadata id (1) and a string (5) or a reference to one (7)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, str] = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        parts: Dict[int, list] = {2: [], 4: [], 5: []}
        for k, v in _fields(plane):
            if k in parts:
                parts[k].append(v)
        plane_name = bytes(parts[2][0]).decode() if parts[2] else ""
        if not _DEVICE_PLANE.match(plane_name):
            continue
        names = {}
        for entry in parts[5]:
            meta = dict(_fields(dict(_fields(entry))[2]))
            names[meta.get(1)] = bytes(meta.get(2, b"")).decode()
        tf_op = [i for i, name in names.items() if name == "tf_op"]
        for entry in parts[4]:
            meta = list(_fields(dict(_fields(entry))[2]))
            name = [bytes(v).decode() for k, v in meta if k == 2]
            for k, v in meta:
                stat = dict(_fields(v)) if k == 5 else {}
                if name and stat.get(1) in tf_op:
                    text = (bytes(stat[5]).decode() if 5 in stat
                            else names.get(stat.get(7), ""))
                    out[name[0]] = text
    return out


def read_xplane(trace_dir: str) -> Tuple[Dict[str, list], list, tuple]:
    """``(device_ops, spans, window)`` from the newest trace under
    ``trace_dir``: ``device_ops`` maps each device plane to its
    operations ``[(scope, start_ns, end_ns)]``; ``spans`` is the
    program's host spans ``[(thread, name, start_ns, end_ns, args)]``,
    the ``repro.`` prefix dropped; ``window`` is the benchmark's window
    span ``(start_ns, end_ns)``."""
    import jax
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    tf_op = op_names(paths[-1])
    device_ops: Dict[str, list] = {}
    spans, window = [], None
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            device_ops[plane.name] = [
                (scope_of(ev.name, [("tf_op", tf_op.get(ev.name, ""))]),
                 ev.start_ns, ev.start_ns + ev.duration_ns)
                for line in plane.lines if line.name in _OP_LINES
                for ev in line.events]
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    end = ev.start_ns + ev.duration_ns
                    if ev.name == WINDOW:
                        window = (ev.start_ns, end)
                    elif ev.name.startswith(SPAN_PREFIX):
                        spans.append(((plane.name, i),
                                      ev.name[len(SPAN_PREFIX):],
                                      ev.start_ns, end, dict(ev.stats)))
    if window is None:
        raise ValueError("trace has no bench.window span")
    return device_ops, spans, window


class _Busy:
    """Device-busy time of one device inside the window, as a function
    of time: ``before(x)`` is the busy time before ``x``."""

    def __init__(self, ops: list, w0: float, w1: float):
        iv = np.array([(max(s, w0), min(e, w1)) for _, s, e in ops
                       if e > w0 and s < w1], dtype=np.float64)
        self.iv = _union(iv.reshape(-1, 2))
        lengths = self.iv[:, 1] - self.iv[:, 0]
        self.cum = np.concatenate([[0.0], np.cumsum(lengths)])

    def before(self, x):
        x = np.asarray(x, dtype=np.float64)
        if not len(self.iv):
            return np.zeros_like(x)
        i = np.searchsorted(self.iv[:, 0], x, side="right") - 1
        ic = np.maximum(i, 0)
        part = np.clip(x - self.iv[ic, 0], 0.0,
                       self.iv[ic, 1] - self.iv[ic, 0])
        return np.where(i >= 0, self.cum[ic] + part, 0.0)

    def within(self, iv: np.ndarray) -> float:
        """Busy time inside the disjoint intervals ``iv`` [n, 2]."""
        if not len(iv):
            return 0.0
        return float(np.sum(self.before(iv[:, 1]) - self.before(iv[:, 0])))


def _nest(spans: list) -> List[int]:
    """Each span's parent (an index, or -1): the innermost span on its
    thread that contains it."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][0], spans[i][2], -spans[i][3]))
    parent = [-1] * len(spans)
    stack: List[int] = []
    for i in order:
        th, _, s, e, _ = spans[i]
        while stack and not (spans[stack[-1]][0] == th
                             and spans[stack[-1]][2] <= s
                             and e <= spans[stack[-1]][3]):
            stack.pop()
        parent[i] = stack[-1] if stack else -1
        stack.append(i)
    return parent


def _subtract(s: float, e: float, holes: list) -> np.ndarray:
    """``[s, e]`` less the disjoint, sorted intervals ``holes``."""
    out, at = [], s
    for hs, he in holes:
        hs, he = max(hs, s), min(he, e)
        if he <= hs:
            continue
        if hs > at:
            out.append((at, hs))
        at = max(at, he)
    if e > at:
        out.append((at, e))
    return np.array(out, dtype=np.float64).reshape(-1, 2)


def reduce_stages(device_ops: Dict[str, list], spans: list, window: tuple,
                  top: int = 10) -> dict:
    """Reduce one traced window (times in ns) to the program's stages.

    Returns ``slabs`` (root spans, one per slab), ``fallbacks`` (grid
    slabs that took the general path), ``stages`` (per stage: ``count``,
    ``wall_s``, ``self_s`` — host time in the stage's own interval, its
    child spans and the device-busy time under it left out —,
    ``device_s`` and ``idle_s``, the window's device-idle time in the
    stage's own interval, which equals ``self_s`` while every span lies
    in the window; ``unattributed`` for the roots' own remainder,
    ``harness`` for idle time under no program span), ``samples`` and
    ``slots`` (summed over the ``ingest.kernel`` spans),
    ``idle_gaps_by_stage`` (the ``top`` longest idle gaps, each named by
    the innermost program span covering most of it), ``tail_slabs``
    (the slabs at or above the window's p95 slab time: mean ms per stage
    there and over every slab, and how many ran ``ingest.health``) and
    ``device_scopes`` (device seconds per kernel name or scope).  Device
    times are averaged over the devices."""
    w0, w1 = float(window[0]), float(window[1])
    busy = [_Busy(ops, w0, w1) for ops in device_ops.values()]
    n_dev = max(len(busy), 1)
    parent = _nest(spans)

    # the pad span lends its arguments to its kernel span and leaves
    keep, args = [], [dict(sp[4]) for sp in spans]
    for i, sp in enumerate(spans):
        if sp[1] == PAD and parent[i] >= 0 and spans[parent[i]][1] == KERNEL:
            args[parent[i]].update(sp[4])
        else:
            keep.append(i)
    kept = set(keep)

    def up(i):                  # nearest kept ancestor
        p = parent[i]
        while p >= 0 and p not in kept:
            p = parent[p]
        return p

    children: Dict[int, list] = {i: [] for i in keep}
    for i in keep:
        if up(i) >= 0:
            children[up(i)].append(i)

    def slab_of(i):
        while up(i) >= 0:
            i = up(i)
        return i

    def stage(i):
        return UNATTRIBUTED if spans[i][1] in ROOTS else spans[i][1]

    own, rows = {}, {}
    for i in keep:
        _, name, s, e, _ = spans[i]
        s, e = max(float(s), w0), min(float(e), w1)
        holes = sorted((float(spans[c][2]), float(spans[c][3]))
                       for c in children[i])
        own[i] = _subtract(s, e, holes) if e > s else np.zeros((0, 2))
        length = float(np.sum(own[i][:, 1] - own[i][:, 0]))
        dev = sum(b.within(own[i]) for b in busy) / n_dev
        r = rows.setdefault(stage(i), {"count": 0, "wall_s": 0.0,
                                       "self_s": 0.0, "device_s": 0.0,
                                       "idle_s": 0.0})
        r["count"] += 1
        r["wall_s"] += (float(spans[i][3]) - float(spans[i][2])) * 1e-9
        r["self_s"] += (length - dev) * 1e-9
        r["device_s"] += dev * 1e-9
        r["idle_s"] += (length - dev) * 1e-9

    window_idle = ((w1 - w0) - sum(b.within(np.array([[w0, w1]]))
                                   for b in busy) / n_dev) * 1e-9
    named_idle = sum(r["idle_s"] for r in rows.values())
    rows[HARNESS] = {"idle_s": max(window_idle - named_idle, 0.0)}

    roots = [i for i in keep if up(i) < 0 and spans[i][1] in ROOTS]
    fallbacks = sum(1 for i in keep if spans[i][1] == "ingest"
                    and up(i) >= 0 and spans[up(i)][1] == "ingest_grid")
    kern = [args[i] for i in keep if spans[i][1] == KERNEL]
    samples = sum(int(a.get("samples", 0)) for a in kern)
    slots = sum(int(a.get("slots", a.get("samples", 0))) for a in kern)

    # ms per stage in each slab: the stage's own interval, device time
    # included, so that a slab's stages add up to its wall time
    per_slab: Dict[int, Dict[str, float]] = {i: {} for i in roots}
    for i in keep:
        slab = slab_of(i)
        if slab in per_slab:
            ms = float(np.sum(own[i][:, 1] - own[i][:, 0])) * 1e-6
            d = per_slab[slab]
            d[stage(i)] = d.get(stage(i), 0.0) + ms
    tail = {}
    if roots:
        wall = np.array([(spans[i][3] - spans[i][2]) * 1e-6 for i in roots])
        p95 = float(np.percentile(wall, 95))
        hot = [i for i, w in zip(roots, wall) if w >= p95]
        names = sorted({k for d in per_slab.values() for k in d})

        def mean(sel):
            return {k: sum(per_slab[i].get(k, 0.0) for i in sel) / len(sel)
                    for k in names}
        tail = {"p95_ms": p95, "slabs": len(hot),
                "health": sum(1 for i in hot if HEALTH in per_slab[i]),
                "ms": mean(hot), "all_ms": mean(roots)}

    # idle gaps, each named by the span whose own interval covers most
    owners = [(stage(i) if stage(i) != UNATTRIBUTED else spans[i][1], own[i])
              for i in keep]
    gaps = []
    for b in busy:
        edges = np.concatenate([[w0], b.iv.ravel(), [w1]]).reshape(-1, 2)
        gaps += [(s, e) for s, e in edges if e > s]
    gaps.sort(key=lambda g: g[0] - g[1])
    named_gaps = []
    for s, e in gaps[:top]:
        cover: Dict[str, float] = {}
        for name, iv in owners:
            c = float(np.sum(np.clip(np.minimum(iv[:, 1], e)
                                     - np.maximum(iv[:, 0], s), 0.0, None)))
            if c > 0:
                cover[name] = cover.get(name, 0.0) + c
        cover[HARNESS] = (e - s) - sum(cover.values())
        named_gaps.append([max(cover, key=cover.get), (e - s) * 1e-9])

    scopes: Dict[str, float] = {}
    for ops in device_ops.values():
        for scope, s, e in ops:
            s, e = max(s, w0), min(e, w1)
            if e > s:
                scopes[scope] = scopes.get(scope, 0.0) + (e - s) * 1e-9 / n_dev

    return {"devices": len(device_ops), "window_s": (w1 - w0) * 1e-9,
            "slabs": len(roots), "fallbacks": fallbacks,
            "stages": rows, "samples": samples, "slots": slots,
            "idle_gaps_by_stage": named_gaps, "tail_slabs": tail,
            "device_scopes": dict(sorted(scopes.items(),
                                         key=lambda kv: -kv[1]))}


def metrics(st: dict) -> dict:
    """The per-layer numbers the stages give: each stage's self host ms
    per slab (``ingest.kernel``'s is ``ingest_pack_ms``: packing,
    padding, dispatch, fetch and widening), ``health_ms`` per evaluation
    of the health machine, and ``ingest_pad_share``, the share of the
    kernel's sample slots that are padding.  Empty where the trace has
    no device plane or no program spans."""
    if not st["devices"] or not st["slabs"]:
        return {}

    def per_slab(stage):
        return (st["stages"].get(stage, {}).get("self_s", 0.0)
                / st["slabs"] * 1e3)

    out = {f"ingest_{k}_ms": per_slab(f"ingest.{k}")
           for k in ("prep", "gather")}
    out["ingest_pack_ms"] = per_slab(KERNEL)
    out.update((f"ingest_{k}_ms", per_slab(f"ingest.{k}"))
               for k in ("ring", "scatter", "periods", "moments"))
    out["unattributed_ms"] = per_slab(UNATTRIBUTED)
    h = st["stages"].get(HEALTH)
    if h:
        out["health_ms"] = h["wall_s"] / h["count"] * 1e3
    if st["slots"]:
        out["ingest_pad_share"] = 100.0 * (1.0 - st["samples"] / st["slots"])
    return out


def coverage(st: dict, red: dict) -> dict:
    """How much of what ``trace_reduce`` sees the program's spans explain
    (``red`` is its reduction of the same trace): the roots' unattributed
    remainder over the ``bench.ingest`` host time, the device time under
    ``ingest.kernel`` over that under ``bench.ingest``, the window's idle
    time that falls in ``ingest.*`` stages, and the device time that
    bears one of the program's kernel names or scopes."""
    out = {}
    n = red["span_count"].get("ingest")
    rows = st["stages"]
    if n:
        host = red["span_s"]["ingest"] - red["device_s"]["ingest"]
        if host > 0:
            out["unattributed_share"] = (
                rows.get(UNATTRIBUTED, {}).get("self_s", 0.0) / host)
        if red["device_s"]["ingest"] > 0:
            out["kernel_device_share"] = (
                rows.get(KERNEL, {}).get("device_s", 0.0)
                / red["device_s"]["ingest"])
    idle = red["window_s"] - red["busy_s"]
    if idle > 0:
        out["stage_idle_share"] = sum(
            r["idle_s"] for k, r in rows.items()
            if k.startswith("ingest.")) / idle
    total = sum(st["device_scopes"].values())
    if total > 0:
        out["named_scope_share"] = 1.0 - (
            st["device_scopes"].get(UNNAMED, 0.0) / total)
    return out


def main(argv=None, root: str = None) -> int:
    """Run one cell's set-up, trace a window of its traffic's
    ``trace_seconds`` and print the stage reduction as one JSON line."""
    import argparse
    import json
    import shutil
    import sys
    import tempfile
    here = os.path.dirname(os.path.abspath(__file__))
    root = root or os.path.dirname(here)
    for p in (here, os.path.join(root, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    ap = argparse.ArgumentParser(description=main.__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    import jax
    import registry
    import run
    import trace_reduce as tr
    bench = registry.Bench(root)
    cell = bench.cell(args.workload)
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    devices = jax.devices()
    err = run.chip_error(devices, int(cell["chips"]))
    if err:
        print(err, file=sys.stderr)
        return 2
    from repro.core.engine_backend import use_compile_cache
    use_compile_cache()
    span = lambda name: jax.profiler.TraceAnnotation(  # noqa: E731
        "bench." + name)
    driver = bench.driver(cfg["driver"]).Driver(cfg, traffic, args.seed,
                                                span)
    tdir = tempfile.mkdtemp(prefix="bench_stages_")
    try:
        jax.profiler.start_trace(tdir,
                                 profiler_options=tr.profile_options())
        try:
            rec = driver.window(float(traffic["trace_seconds"]))
        finally:
            jax.profiler.stop_trace()
        red = tr.reduce_trace(*tr.read_xplane(tdir))
        st = reduce_stages(*read_xplane(tdir))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    ctx = {"rec": rec, "trace": red, "peaks": None, "setup_s": None}
    window = {k: bench.reader(k)(ctx) for k in (
        "samples_per_s", "slab_p95_ms", "ingest_host_ms",
        "ingest_device_ms", "idle_share.monitor")}
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind},
        "window": window, "metrics": metrics(st),
        "coverage": coverage(st, red), "stages": st}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
