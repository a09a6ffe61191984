"""The program's own spans (``repro.common.trace``).

The ingest path marks a root span per slab and a span per stage; these
tests trace slabs through ``jax.profiler.trace`` on the CPU and read the
``repro.*`` events back from the profiler's host plane, and check that
the stream core still imports and ingests without jax.
"""
import glob
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.common import trace
from repro.core.stream import HealthPolicy, IngestCore

STAGES = ["ingest.prep", "ingest.gather", "ingest.kernel", "ingest.ring",
          "ingest.scatter", "ingest.periods", "ingest.moments"]


def _events(trace_dir):
    """The ``repro.*`` host events as ``(name, start, end, args)``, each
    with its parent's index (-1 for none) by nesting on its thread."""
    import jax
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted(((ev.name[len(trace.PREFIX):], ev.start_ns,
                           ev.start_ns + ev.duration_ns, dict(ev.stats))
                          for ev in line.events
                          if ev.name.startswith(trace.PREFIX)),
                         key=lambda e: (e[1], -e[2]))
            stack = []
            for ev in evs:
                while stack and not ev[2] <= out[stack[-1]][0][2]:
                    stack.pop()
                out.append((ev, stack[-1] if stack else -1))
                stack.append(len(out) - 1)
    return out


def _children(events, i):
    return [e[0][0] for e in events if e[1] == i]


def _core(n, backend):
    return IngestCore(n, ring_slots=4, health=HealthPolicy(),
                      health_every_s=0.5, backend=backend)


def test_ingest_spans_nest_by_stage(tmp_path):
    import jax
    n, m = 200, 5
    core = _core(n, "pallas")
    rng = np.random.default_rng(3)
    dev = np.arange(0, n, 2)
    ts = 0.1 * np.arange(m)
    # warm the kernels outside the trace: a compile adds no span
    core.ingest_grid(dev, ts, rng.uniform(100, 200, (dev.size, m)))
    with jax.profiler.trace(str(tmp_path)):
        # a clean grid slab, no health (within health_every_s)
        core.ingest_grid(dev, ts + 0.45, rng.uniform(100, 200,
                                                     (dev.size, m)))
        # a grid slab that falls back: a non-finite reading
        bad = rng.uniform(100, 200, (dev.size, m))
        bad[0, 0] = np.nan
        core.ingest_grid(dev, ts + 0.9, bad)
        # a flat slab, duplicates and disorder included; health runs
        fd = rng.integers(0, n, 300)
        ft = 2.0 + rng.uniform(0, 0.5, 300)
        core.ingest(np.concatenate([fd, fd[:10]]),
                    np.concatenate([ft, ft[:10]]),
                    rng.uniform(100, 200, 310))
    ev = _events(str(tmp_path))
    roots = [i for i, (e, p) in enumerate(ev) if p == -1]
    assert [ev[i][0][0] for i in roots] == ["ingest_grid", "ingest_grid",
                                            "ingest"]
    clean, fell, flat = roots

    assert ev[clean][0][3] == {"samples": dev.size * m,
                               "devices": dev.size}
    assert _children(ev, clean) == STAGES
    kern = [i for i, (e, p) in enumerate(ev)
            if p == clean and e[0] == "ingest.kernel"][0]
    assert ev[kern][0][3] == {"samples": dev.size * m, "devices": dev.size}
    # the pallas grid kernel computes over 128-lane device tiles
    pad = [e for e, p in ev if p == kern]
    assert [e[0] for e in pad] == ["ingest.kernel.pad"]
    # ... and moves the slab in one put and one fetch of two buffers each
    assert pad[0][3] == {"samples": dev.size * m, "slots": 128 * m,
                         "h2d": 2, "d2h": 2}

    # the fallback: an ``ingest`` nested in the grid root, its stages
    # inside it
    assert _children(ev, fell) == ["ingest.prep", "ingest"]
    inner = [i for i, (e, p) in enumerate(ev)
             if p == fell and e[0] == "ingest"][0]
    assert ev[inner][0][3] == {"samples": dev.size * m}
    assert _children(ev, inner) == STAGES + ["ingest.health"]

    assert _children(ev, flat) == STAGES + ["ingest.health"]
    health = [e for e, p in ev if p == flat and e[0] == "ingest.health"]
    assert health[0][3] == {"devices": n}
    fk = [i for i, (e, p) in enumerate(ev)
          if p == flat and e[0] == "ingest.kernel"][0]
    k = ev[fk][0][3]["samples"]
    assert k < 310                      # duplicates dropped in prep
    assert [e[3] for e, p in ev if p == fk] == [{"samples": k,
                                                 "slots": 1024, "h2d": 2,
                                                 "d2h": 2}]


def test_span_is_a_trace_annotation_once_jax_is_imported():
    """Outside a profiler session the spans record nothing and the
    ingest path runs as before."""
    import jax
    core = _core(50, "numpy")
    rep = core.ingest(np.arange(50), np.full(50, 1.0),
                      np.full(50, 150.0))
    assert rep.accepted == 50
    s = trace.span("ingest.prep", samples=3)
    assert isinstance(s, jax.profiler.TraceAnnotation)
    with s:
        pass


def test_stream_core_ingests_without_jax():
    """With jax unimportable, the core imports, ingests through the
    numpy tier and every span is the shared no-op."""
    code = textwrap.dedent("""
        import sys
        class NoJax:
            def find_spec(self, name, path=None, target=None):
                if name == "jax" or name.startswith("jax.") or \\
                        name.startswith("jaxlib"):
                    raise ImportError("no jax here")
        sys.meta_path.insert(0, NoJax())
        import numpy as np
        from repro.common import trace
        from repro.core.stream import HealthPolicy, IngestCore
        core = IngestCore(8, health=HealthPolicy(), backend="numpy")
        a = core.ingest(np.repeat(np.arange(8), 3),
                        np.tile([0.1, 0.2, 0.3], 8), np.full(24, 100.0))
        b = core.ingest_grid(np.arange(8), np.array([0.4, 0.5]),
                             np.full((8, 2), 120.0))
        assert (a.accepted, b.accepted) == (24, 16), (a, b)
        assert trace.span("ingest", samples=1) is trace._OFF
        assert "jax" not in sys.modules
        print("ok")
    """)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, PYTHONPATH=src)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "ok"


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_kernel_span_without_padding_has_no_pad_child(tmp_path, backend):
    """Tiers that compute over the slab as it is (numpy, the jax grid
    kernel) open no ``ingest.kernel.pad`` span: its slots are its
    samples."""
    import jax
    core = _core(64, backend)
    dev, ts = np.arange(64), 0.1 * np.arange(3)
    core.ingest_grid(dev, ts, np.full((64, 3), 150.0))
    with jax.profiler.trace(str(tmp_path)):
        core.ingest_grid(dev, ts + 0.3, np.full((64, 3), 160.0))
    ev = _events(str(tmp_path))
    kern = [i for i, (e, p) in enumerate(ev) if e[0] == "ingest.kernel"]
    assert len(kern) == 1
    assert ev[kern[0]][0][3] == {"samples": 192, "devices": 64}
    assert _children(ev, kern[0]) == []
