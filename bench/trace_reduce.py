"""Reduction of a profiler trace to device busy time, device time per
host span, the heaviest device operations and the longest idle gaps.

The benchmark records its own host spans (``jax.profiler.TraceAnnotation``
named ``bench.<layer>``) around its calls into each layer.  Device time
is given to a layer by the span it falls under, not by kernel name, so a
refactor that renames a kernel keeps its layer.

:func:`read_xplane` turns the ``.xplane.pb`` that ``jax.profiler.trace``
writes into plain lists; :func:`reduce_trace` works on those lists only,
so it can be checked on a small synthetic trace.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Tuple

import numpy as np

SPAN_PREFIX = "bench."
# accelerator planes (``/device:TPU:0``); other ``/device:`` planes, such
# as ``/device:CUSTOM:Megascale Trace``, hold no operations of a chip
_DEVICE_PLANE = re.compile(r"/device:(TPU|GPU):\d+$")
# the line of a device plane that holds one event per executed operation
_OP_LINES = ("XLA Ops",)


def profile_options():
    """Profiler options for a traced window: host annotations only (no
    Python function tracer, which would slow every call it records)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def read_xplane(trace_dir: str) -> Tuple[Dict[str, list], list]:
    """``(device_ops, spans)`` from the newest trace under ``trace_dir``:
    ``device_ops`` maps each device plane's name to its operations
    ``[(name, start_ns, end_ns)]``; ``spans`` is the benchmark's host
    spans ``[(layer, start_ns, end_ns)]``, the ``bench.`` prefix
    dropped."""
    import jax
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    device_ops: Dict[str, list] = {}
    spans = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name in _OP_LINES:
                    ops += [(ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns)
                            for ev in line.events]
            device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name[len(SPAN_PREFIX):],
                                      ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return device_ops, spans


def _union(iv: np.ndarray) -> np.ndarray:
    """Disjoint, sorted union of ``[start, end]`` rows."""
    if iv.size == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    idx = np.flatnonzero(new)
    stops = np.maximum.reduceat(ends, idx)
    return np.stack([starts, stops], axis=1)


def _overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Length of the intersection of two disjoint sorted interval
    sets."""
    total, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j, 1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k, 0] < e:
            total += min(e, b[k, 1]) - max(s, b[k, 0])
            k += 1
    return total


def reduce_trace(device_ops: Dict[str, list], spans: list,
                 top: int = 10) -> dict:
    """Reduce one traced window (times in ns).

    The window is the span named ``window``; the other spans are layers.
    Returns ``busy_s`` (union of operation intervals inside the window,
    averaged over the devices), ``window_s``, ``span_s`` (host seconds
    per layer), ``span_count``, ``device_s`` (device-busy seconds under
    each layer's spans, averaged over devices), ``device_ops`` (the
    ``top`` operations by summed time) and ``idle_gaps`` (the ``top``
    longest gaps between operations, each named by the layer whose
    spans cover most of it, ``harness`` where the benchmark's own loop
    does)."""
    win = [(s, e) for n, s, e in spans if n == "window"]
    spans = [x for x in spans if x[0] != "window"]
    if len(win) != 1 or not spans:
        raise ValueError("trace needs one window span and layer spans")
    w0, w1 = float(win[0][0]), float(win[0][1])
    names = sorted({n for n, _, _ in spans})
    by_layer = {n: _union(np.array([(s, e) for m, s, e in spans if m == n],
                                   dtype=np.float64)) for n in names}
    n_dev = max(len(device_ops), 1)
    busy = 0.0
    dev_s = {n: 0.0 for n in names}
    op_time: Dict[str, float] = {}
    gaps = []
    for ops in device_ops.values():
        inside = [(name, max(s, w0), min(e, w1)) for name, s, e in ops
                  if e > w0 and s < w1]
        for name, s, e in inside:
            op_time[name] = op_time.get(name, 0.0) + (e - s) * 1e-9
        u = _union(np.array([(s, e) for _, s, e in inside],
                            dtype=np.float64).reshape(-1, 2))
        busy += float(np.sum(u[:, 1] - u[:, 0])) * 1e-9
        for n in names:
            dev_s[n] += _overlap(u, by_layer[n]) * 1e-9
        edges = np.concatenate([[w0], u.ravel(), [w1]]).reshape(-1, 2)
        gaps += [(s, e) for s, e in edges if e > s]
    gaps.sort(key=lambda g: g[0] - g[1])

    def layer_of(s: float, e: float) -> str:
        """The layer whose spans cover most of ``[s, e]``; ``harness``
        where the part no span covers is larger."""
        gap = np.array([[s, e]])
        cover = {n: _overlap(gap, by_layer[n]) for n in names}
        cover["harness"] = (e - s) - sum(cover.values())
        return max(cover, key=cover.get)

    return {
        "devices": len(device_ops),
        "busy_s": busy / n_dev,
        "window_s": (w1 - w0) * 1e-9,
        "span_s": {n: float(np.sum(by_layer[n][:, 1] - by_layer[n][:, 0]))
                   * 1e-9 for n in names},
        "span_count": {n: sum(1 for m, _, _ in spans if m == n)
                       for n in names},
        "device_s": {n: v / n_dev for n, v in dev_s.items()},
        "device_ops": [[k, v / n_dev] for k, v in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[layer_of(s, e), (e - s) * 1e-9]
                      for s, e in gaps[:top]],
    }
