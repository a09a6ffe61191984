#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: set-up (inputs from the seed, the program built as the
cell's configuration states, every shape the window uses warmed), then a
window of ``--seconds`` (with ``--trace 1`` a traced window of the
traffic's ``trace_seconds``), then the comparison with the plain
reference.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``:
each number compared with its limit.  The same numbers end standard
error.  Exits non-zero, printing no result, where jax finds no TPU or
fewer chips than the cell asks for.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def chip_error(devices, chips: int):
    """Why these devices cannot run the cell, or None."""
    if not devices or devices[0].platform != "tpu":
        return (f"no TPU: jax platform is "
                f"{devices[0].platform if devices else None!r}")
    if len(devices) < chips:
        return f"cell needs {chips} TPU chips, found {len(devices)}"
    return None


def _num(x):
    """A JSON-safe number: non-finite values as strings."""
    x = float(x)
    return x if math.isfinite(x) else str(x)


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import numpy as np
    import registry
    bench = registry.Bench(root)
    cell = bench.cell(args.workload)
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])

    import jax
    devices = jax.devices()
    err = chip_error(devices, int(cell["chips"]))
    if err:
        print(err, file=sys.stderr)
        return 2
    dev0 = devices[0]
    from peaks import peaks
    peak = peaks(dev0.device_kind) if dev0.platform != "cpu" else None
    from repro.core.engine_backend import use_compile_cache
    use_compile_cache()
    # cache every program, however quick its compile, so that a run
    # whose cache is warm compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from check import CompileClock
    clock = CompileClock()

    tracing = bool(args.trace)
    if tracing:
        span = lambda name: jax.profiler.TraceAnnotation(  # noqa: E731
            "bench." + name)
    else:
        span = lambda name: contextlib.nullcontext()  # noqa: E731
    driver = bench.driver(cfg["driver"]).Driver(cfg, traffic, args.seed,
                                                span)
    setup_s = time.perf_counter() - T0
    compiles0, compile_s = clock.count, clock.total

    red, tdir = None, None
    if tracing:
        import trace_reduce as tr
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tdir, profiler_options=tr.profile_options())
        try:
            rec = driver.window(min(args.seconds,
                                    float(traffic["trace_seconds"])))
        finally:
            jax.profiler.stop_trace()
    else:
        rec = driver.window(args.seconds)
    compiles_in_window = clock.count - compiles0
    stats = dev0.memory_stats() or {}
    mem_peak = stats.get("peak_bytes_in_use")

    t_ref = time.perf_counter()
    got = driver.program_outputs()
    ref = driver.reference_outputs(np.float64)
    readings = driver.readings(got, ref)
    ref_s = time.perf_counter() - t_ref
    limits = traffic["check"]["limits"]
    checks = {k: (v, float(limits[k])) for k, v in readings.items()}
    correct = all(v <= lim for v, lim in checks.values())

    if tracing:
        try:
            red = tr.reduce_trace(*tr.read_xplane(tdir))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
    ctx = {"rec": rec, "trace": red, "peaks": peak, "setup_s": setup_s}
    metrics = {}
    for m in bench.metrics(args.workload, per_layer=tracing):
        v = bench.reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": _num(v), "unit": m["unit"]}

    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    out = {"correct": bool(correct), "attempted": int(rec["attempted"]),
           "failed": 0, "metrics": metrics, "device": device}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    out["setup_compile_s"] = compile_s
    out["compiles_in_window"] = compiles_in_window
    out["reference_s"] = ref_s
    out["checks"] = {k: {"value": _num(v), "limit": lim}
                     for k, (v, lim) in checks.items()}
    if compiles_in_window:
        print(f"warning: {compiles_in_window} compiles inside the window",
              file=sys.stderr)
    for k, (v, lim) in checks.items():
        print(f"check {k} {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
