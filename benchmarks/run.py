"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (harness contract).

Modules:
  fig5   load_linearity   — FMA-chain duration linearity (benchmark load)
  fig6   update_period    — power-update-period recovery
  fig7   transient        — four transient-response classes
  fig8/9 steady_state     — proportional gain error, per-card population
  fig10-14 boxcar         — averaging-window fits + sampled fractions
  fig15-17 energy_cases   — reps vs error for W==T / W>T / W<T
  fig18  workloads        — nine workloads, naive vs good practice
  §6     module_scope     — GH200 whole-module `instant` reading
  $1M    fleet            — data-centre projection + fleet telemetry
  §Roofline roofline_report — per-cell terms from dry-run artifacts
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.core.engine_backend import use_compile_cache
    use_compile_cache()
    from benchmarks import (boxcar, energy_cases, fleet, load_linearity,
                            module_scope, profile_sweep, roofline_report,
                            steady_state, transient, update_period,
                            workloads)
    modules = [
        ("load_linearity", load_linearity),
        ("update_period", update_period),
        ("transient", transient),
        ("steady_state", steady_state),
        ("boxcar", boxcar),
        ("profile_sweep", profile_sweep),
        ("energy_cases", energy_cases),
        ("workloads", workloads),
        ("module_scope", module_scope),
        ("fleet", fleet),
        ("roofline_report", roofline_report),
    ]
    failed = []
    for name, mod in modules:
        try:
            mod.run()
        except Exception:      # noqa: BLE001 — keep the sweep going
            traceback.print_exc()
            failed.append(name)
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
