#!/usr/bin/env python3
"""Readings that the limits in a configuration's ``limits`` are set from.

    python3 bench/control.py --workload <name> --seconds <s> --seeds 1 2 3 ...

For each seed, in one process: the cell's set-up and a window of
``--seconds``, then the numbers that decide ``correct`` read twice
against the float64 reference: once for the program (the lower
readings: the largest over sound runs) and once for the control, the
same reference computed in float32, one precision below what the
configuration states, put in the program's place (the upper readings).
Prints one JSON line per seed, with the worst field of each reading and
the window's end-to-end metrics.  The benchmark's own runs never run
this.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def readings(bench, workload: str, seed: int, seconds: float) -> dict:
    """``{"program": {...}, "control": {...}, "where": {...},
    "metrics": {...}}`` for one seed."""
    import contextlib
    import numpy as np
    t0 = time.perf_counter()
    cell = bench.cell(workload)
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    driver = bench.driver(cfg["driver"]).Driver(
        cfg, traffic, seed, lambda name: contextlib.nullcontext())
    ctx = {"setup_s": time.perf_counter() - t0, "trace": None,
           "peaks": None, "rec": driver.window(seconds)}
    metrics = {m["name"]: bench.reader(m["name"])(ctx)
               for m in bench.metrics(workload, per_layer=False)}
    got = driver.program_outputs()
    ref = driver.reference_outputs(np.float64)
    ctl = driver.reference_outputs(np.float32)
    where = {"program": {}, "control": {}}
    return {"program": driver.readings(got, ref, where["program"]),
            "control": driver.readings(ctl, ref, where["control"]),
            "where": where, "metrics": metrics,
            "attempted": ctx["rec"]["attempted"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import registry
    from repro.core.engine_backend import use_compile_cache
    use_compile_cache()
    bench = registry.Bench(ROOT)
    for seed in args.seeds:
        t0 = time.perf_counter()
        r = readings(bench, args.workload, seed, args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "s": time.perf_counter() - t0, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
