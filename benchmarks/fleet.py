"""Data-centre projection + fleet telemetry (the paper's $1M/yr headline
and the 1/√N vs worst-case uncertainty scaling), driven through the
batched engine two ways: the shared-timeline audit (one workload × 10k
seeds) and the heterogeneous mixed-scenario audit (every device its own
timeline via the `TimelineBank` substrate), with per-scenario error
breakdowns and a machine-readable ``BENCH_fleet.json`` so the perf
trajectory has data points.

Backend comparison (ISSUE 3): the same heterogeneous naive audit is
timed under every selected execution backend
(:mod:`repro.core.engine_backend`), then the jax backend runs a
fleet-scale audit (100k devices by default).

Array-native synthesis + streaming audits (ISSUE 4): workload
generation uses the bank-native samplers (`mixed_fleet_workloads(...,
as_bank=True)`), timed against the per-device object path; the
``--mega-devices`` run audits a million-device heterogeneous fleet in
bounded-memory slabs (`fleet_audit(workload=FleetScenarioSpec(...),
chunk_devices=...)`).  CLI::

Streaming monitor (ISSUE 5): the heterogeneous fleet is also replayed
as a *live* poll-sample stream through
:class:`repro.core.stream.MonitorService` (per backend, pinned against
the offline audit), and ``--stream-devices`` runs a scale replay with
spec-synthesised device slabs at bounded memory.

Pallas kernel tier (ISSUE 6): ``--backend both`` now also times the
fused-kernel ``pallas`` tier; ``tools/bench_guard.py`` dominance rules
pin the accelerated tiers' streaming ingest above the numpy reference
at both the main and ``--stream-devices`` scales.  CLI::

    python benchmarks/fleet.py --backend both --n-devices 10000 \
        --scale-devices 100000 --mega-devices 1000000 \
        --stream-devices 100000

Mesh-sharded audits (ISSUE 7): ``--shard-devices`` sweeps the
``shard_map``-sharded audit over forced host-device counts
(``--shard-counts``), each in a subprocess (the XLA flag must precede
the first jax import), and ``--shard-mega-devices`` records the
ten-million-device bounded-memory run::

    python benchmarks/fleet.py --n-devices 2000 --shard-devices 200000 \
        --shard-counts 1,2,4 --shard-mega-devices 10000000

Snapshot serving (ISSUE 8): the ``serving`` block interleaves slab
ingestion with batched query flushes through
:class:`repro.serve.monitor_service.MonitorQueryService` — sustained
queries/sec while ingesting, per-flush p50/p99 latency, cache hit rate.
``--serving-devices`` adds the 100k-device scale run;
``--serving-only`` reruns just this block (merging into an existing
``BENCH_fleet.json``)::

    python benchmarks/fleet.py --serving-only --serving-devices 100000

Fault-domain resilience (ISSUE 9): the ``chaos`` block streams the same
fleet through the full transport-fault taxonomy
(:class:`repro.core.stream.FaultSpec` — clock drift/skew, collector
blackouts, corrupt slabs, permanent dropouts) into a hardened
health-tracked monitor, recording degraded-mode ingest throughput
against the clean strict path, then kills the run mid-stream and times
the supervisor's restore-then-resume cycle (checking the recovered
monitor is *bitwise* the uninterrupted one).  ``--chaos-only`` reruns
just this block (merging into an existing ``BENCH_fleet.json``)::

    python benchmarks/fleet.py --chaos-only --backend numpy

Live collector (ISSUE 10): the ``collect`` block times the wire-format
parsers (nvidia-smi csv + daemon per-row csv, rows/sec) on a synthetic
capture and the full file→monitor replay path
(:class:`repro.collect.CollectorPipeline`), and records the committed
fixtures' parse accounting.  ``--collect-only`` reruns just this block
(merging into an existing ``BENCH_fleet.json``)::

    python benchmarks/fleet.py --collect-only
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from benchmarks.common import emit
from repro.core import load as loads
from repro.core.engine_backend import available_backends, use_compile_cache
from repro.core.fleet_engine import SensorBank, fleet_audit
from repro.core.ledger import EnergyLedger
from repro.core.meter import WorkloadSet
from repro.core.telemetry import FleetLedger, datacenter_projection

N_DEVICES = 10_000
SCALE_DEVICES = 100_000
MEGA_CHUNK = 100_000
JSON_PATH = os.environ.get("BENCH_FLEET_JSON", "BENCH_fleet.json")


def _emit_err(name: str, us_per_dev: float, st: dict) -> None:
    emit(name, us_per_dev,
         f"mean_abs={st['mean_abs_err']:.4f};std={st['std_err']:.4f};"
         f"p50={st['p50_abs']:.4f};p90={st['p90_abs']:.4f};"
         f"p99={st['p99_abs']:.4f};worst={st['worst_abs']:.4f}")


def _profile_names(n: int) -> list:
    return (["a100"] * (n // 2) + ["h100_instant"] * (n // 4)
            + ["v100"] * (n - n // 2 - n // 4))


def _parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--backend",
                    choices=("numpy", "jax", "pallas", "both", "auto"),
                    default="both",
                    help="execution backend(s) to benchmark; 'both'/'auto' "
                         "run every available tier (numpy + jax + pallas) "
                         "and degrade to numpy-only when jax is missing")
    ap.add_argument("--n-devices", type=int, default=N_DEVICES,
                    help="fleet size for the main audits "
                         f"(default {N_DEVICES})")
    ap.add_argument("--scale-devices", type=int, default=SCALE_DEVICES,
                    help="fleet size for the jax-backend scale audit "
                         f"(default {SCALE_DEVICES}; 0 disables)")
    ap.add_argument("--mega-devices", type=int, default=0,
                    help="fleet size for the chunked streaming audit "
                         "(default 0 = disabled; the committed "
                         "BENCH_fleet.json uses 1000000)")
    ap.add_argument("--mega-chunk", type=int, default=MEGA_CHUNK,
                    help=f"device slab size for --mega-devices "
                         f"(default {MEGA_CHUNK})")
    ap.add_argument("--stream-devices", type=int, default=0,
                    help="fleet size for the scale streaming-monitor "
                         "replay (default 0 = disabled; the committed "
                         "BENCH_fleet.json uses 100000)")
    ap.add_argument("--stream-chunk", type=int, default=20_000,
                    help="device slab size for --stream-devices "
                         "(default 20000)")
    ap.add_argument("--shard-devices", type=int, default=0,
                    help="fleet size for the mesh-sharded scaling sweep "
                         "(default 0 = disabled); each shard count runs "
                         "in a subprocess with "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=<k> (ISSUE 7)")
    ap.add_argument("--shard-counts", default="1,2,4,8",
                    help="comma-separated forced-host shard counts for "
                         "the scaling sweep (default 1,2,4,8)")
    ap.add_argument("--shard-chunk", type=int, default=25_000,
                    help="device rows per shard per super-slab in the "
                         "sharded runs (default 25000)")
    ap.add_argument("--shard-mega-devices", type=int, default=0,
                    help="fleet size for the sharded mega audit "
                         "(default 0 = disabled; the committed "
                         "BENCH_fleet.json uses 10000000)")
    ap.add_argument("--shard-mega-shards", type=int, default=4,
                    help="forced-host shard count for the sharded mega "
                         "audit (default 4)")
    ap.add_argument("--serving-devices", type=int, default=0,
                    help="fleet size for the scale serving bench "
                         "(default 0 = disabled; the committed "
                         "BENCH_fleet.json uses 100000)")
    ap.add_argument("--serving-only", action="store_true",
                    help="run only the snapshot-serving bench and merge "
                         "its block into an existing BENCH_fleet.json")
    ap.add_argument("--chaos-devices", type=int, default=2000,
                    help="fleet size for the fault-injection/recovery "
                         "bench (default 2000; 0 disables the block)")
    ap.add_argument("--chaos-only", action="store_true",
                    help="run only the chaos (fault-injection + "
                         "kill/recover) bench and merge its block into "
                         "an existing BENCH_fleet.json")
    ap.add_argument("--collect-rows", type=int, default=120_000,
                    help="synthetic capture size (rows) for the "
                         "collector parse/replay bench (default 120000; "
                         "0 disables the block)")
    ap.add_argument("--collect-only", action="store_true",
                    help="run only the collector (wire parse + replay) "
                         "bench and merge its block into an existing "
                         "BENCH_fleet.json")
    return ap.parse_args(argv)


def _run_shard_worker(n_devices, n_shards, shard_chunk, repeat=1,
                      parity_devices=0):
    """One shard-count measurement in a fresh interpreter: the forced
    host-device flag only takes effect before jax first imports, which
    in this process happened long ago."""
    import subprocess
    import sys as _sys

    import jax
    if jax.default_backend() != "cpu":
        raise SystemExit(
            "the shard sweep runs each shard count in a child process on "
            "forced host-CPU devices, a CPU rehearsal only; on this "
            f"{jax.default_backend()} host the chips belong to this "
            "process, so run the sharded audit in-process instead: "
            "fleet_audit(..., mesh=data_mesh(k)) or chip_smoke.py "
            "--four-chips")
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "--xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n_shards}")
    env["XLA_FLAGS"] = " ".join(flags)
    env.setdefault("JAX_PLATFORMS", "cpu")   # forced host devices are CPU
    src = os.path.join(os.path.dirname(here), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [_sys.executable, os.path.join(here, "shard_worker.py"),
           "--n-devices", str(n_devices), "--n-shards", str(n_shards),
           "--shard-chunk", str(shard_chunk), "--repeat", str(repeat)]
    if parity_devices:
        cmd += ["--parity-devices", str(parity_devices)]
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env)
    if proc.returncode != 0:
        raise RuntimeError(
            f"shard_worker failed (k={n_shards}): {proc.stderr.strip()}")
    return json.loads(proc.stdout)


def _shard_blocks(args) -> tuple:
    """The ``sharded`` BENCH block: devices/sec per forced-host shard
    count (+ parallel efficiency at 4 shards when measured), and the
    sharded mega audit.  ``host_cpu_count`` is recorded so the
    bench_guard scaling rule can tell real parallelism from time-sliced
    forced devices on small machines."""
    counts = sorted({int(c) for c in args.shard_counts.split(",") if c})
    block = {
        "n_devices": args.shard_devices,
        "shard_chunk": args.shard_chunk,
        "host_cpu_count": os.cpu_count(),
        "scaling": {},
    }
    for k in counts:
        r = _run_shard_worker(args.shard_devices, k, args.shard_chunk,
                              repeat=2,
                              parity_devices=min(args.shard_devices,
                                                 10_000) if k == counts[-1]
                              else 0)
        block["scaling"][str(k)] = r
        emit(f"fleet_audit/sharded_{args.shard_devices}_k{k}",
             r["wall_s"] * 1e6 / args.shard_devices,
             f"devices_per_sec={r['devices_per_sec']};"
             f"wall_s={r['wall_s']};peak_rss_mb={r['peak_rss_mb']}")
    if "1" in block["scaling"] and "4" in block["scaling"]:
        d1 = block["scaling"]["1"]["devices_per_sec"]
        d4 = block["scaling"]["4"]["devices_per_sec"]
        block["efficiency_4"] = round(d4 / (4.0 * d1), 3)
        emit("fleet_audit/sharded_efficiency_4", 0.0,
             f"efficiency={block['efficiency_4']};"
             f"host_cpu_count={block['host_cpu_count']}")

    mega = None
    if args.shard_mega_devices > 0:
        r = _run_shard_worker(args.shard_mega_devices,
                              args.shard_mega_shards, args.shard_chunk)
        mega = r
        emit(f"fleet_audit/sharded_mega_{args.shard_mega_devices}",
             r["wall_s"] * 1e6 / args.shard_mega_devices,
             f"devices_per_sec={r['devices_per_sec']};"
             f"wall_s={r['wall_s']};chunks={r['n_chunks']};"
             f"peak_rss_mb={r['peak_rss_mb']}")
    return block, mega


def _selected_backends(choice: str) -> list:
    avail = available_backends()
    if choice in ("both", "auto"):
        return list(avail)
    if choice in ("jax", "pallas") and choice not in avail:
        raise SystemExit(f"--backend {choice} requested but jax is not "
                         f"installed")
    return [choice]


def _materialize_grid_slabs(n, names, ws, seed, period_s=0.001,
                            tick_s=0.5, chunk_devices=None,
                            start_offset_s=0.3):
    """Pre-generate the clean rectangular poll slabs ``stream_fleet``
    would emit (same banks, seeds and attach geometry), so the monitor's
    ingest hot loop can be timed with no sensor simulation inside the
    timed region."""
    spec = ws if isinstance(ws, loads.FleetScenarioSpec) else None
    if chunk_devices is None:
        chunks = [(0, n)]
    else:
        chunks = [(lo, min(lo + chunk_devices, n))
                  for lo in range(0, n, chunk_devices)]
    slabs = []
    for lo, hi in chunks:
        wsc = (spec.workload_set(lo, hi) if spec is not None
               else (ws if len(chunks) == 1 else ws.rows(lo, hi)))
        bank = SensorBank.from_catalog(names[lo:hi],
                                       seeds=np.arange(lo, hi) + seed)
        tlb = wsc.timeline_bank
        tlb = tlb.shift(start_offset_s - tlb.t_start)
        bank.attach(tlb, t_end=tlb.t_end + 1.0)
        t1 = float(np.max(tlb.t_end) + 0.5)
        for dev, ts, vals in bank.iter_poll_slabs(
                0.0, t1, period_s=period_s, tick_s=tick_s,
                device_base=lo, grid=True):
            if len(ts):
                slabs.append((dev, ts, vals))
    return slabs


def _ingest_throughput(slabs, n, backend):
    """Time a pure ingest pass over pre-materialised slabs (one untimed
    warm-up pass first, so jit compilation is not billed to the tier)."""
    from repro.core.stream import MonitorService

    def one_pass():
        mon = MonitorService(n, backend=backend)
        mon.set_windows(np.full(n, 0.3), np.full(n, 1.0))
        for dev, ts, vals in slabs:
            mon.ingest_grid(dev, ts, vals)

    one_pass()
    t0 = time.perf_counter()
    one_pass()
    wall = time.perf_counter() - t0
    return sum(v.size for _, _, v in slabs), wall


def _serving_throughput(slabs, n, backend, *, queries_per_flush=512,
                        flushes_per_slab=4, hot_instants=24,
                        cache_size=512, seed=0):
    """Interleave slab ingestion with batched query flushes: after each
    slab lands, ``flushes_per_slab`` request batches hit the monitor's
    fresh snapshot — each batch many concurrent clients asking a small
    pool of hot dashboard instants (dedup folds repeats inside a flush,
    the ``(query, epoch)`` cache serves later flushes at the same
    epoch), plus the since-start/window/between/by-label staples.

    Returns the bench entry: sustained queries/sec and concurrent
    ingest samples/sec over the same wall clock, per-flush latency
    percentiles, cache hit rate.  One untimed warm-up pass first, so
    jit compilation is not billed to the tier.
    """
    from repro.core.stream import MonitorService
    from repro.serve.monitor_service import (MonitorQuery,
                                             MonitorQueryService)

    def one_pass():
        mon = MonitorService(n, backend=backend)
        mon.set_windows(np.full(n, 0.3), np.full(n, 1.0))
        svc = MonitorQueryService(mon, cache_size=cache_size)
        rng = np.random.default_rng(seed)
        lat, n_q, n_samp, t_hi = [], 0, 0, 0.0
        t_all = time.perf_counter()
        for dev, ts, vals in slabs:
            mon.ingest_grid(dev, ts, vals)
            n_samp += vals.size
            t_hi = max(t_hi, float(np.max(ts)))
            pool = np.round(rng.uniform(0.0, t_hi, hot_instants), 2)
            for _ in range(flushes_per_slab):
                picks = rng.choice(pool, queries_per_flush - 4)
                t0 = time.perf_counter()
                for t in picks:
                    svc.submit(MonitorQuery.fleet_energy(float(t)))
                svc.submit(MonitorQuery.fleet_energy())
                svc.submit(MonitorQuery.window_energy())
                svc.submit(MonitorQuery.energy_between(
                    float(pool.min()), float(pool.max())))
                svc.submit(MonitorQuery.by_label())
                svc.flush()
                lat.append(time.perf_counter() - t0)
                n_q += queries_per_flush
        wall = time.perf_counter() - t_all
        return mon, svc, wall, lat, n_q, n_samp

    one_pass()
    mon, svc, wall, lat, n_q, n_samp = one_pass()
    lat_ms = 1e3 * np.asarray(lat)
    st = svc.stats()
    return {
        "queries_per_flush": queries_per_flush,
        "flushes_per_slab": flushes_per_slab,
        "n_queries": n_q,
        "n_samples": int(n_samp),
        "wall_s": round(wall, 4),
        "queries_per_sec": round(n_q / wall, 1),
        "ingest_samples_per_sec_concurrent": round(n_samp / wall, 1),
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
        "cache_hit_rate": round(st["cache_hit_rate"], 4),
        "epochs": int(mon.epoch),
    }


def _serving_blocks(args, backends, slabs, n):
    """The ``serving`` BENCH block: per backend at the main size (on the
    already-materialised slabs), plus the ``--serving-devices`` scale
    run on spec-synthesised slabs."""
    block = {"n_devices": n}
    for be in backends:
        entry = _serving_throughput(slabs, n, be)
        block[be] = entry
        emit(f"serving/backend_{be}_{n}", 0.0,
             f"queries_per_sec={entry['queries_per_sec']};"
             f"ingest_samples_per_sec_concurrent="
             f"{entry['ingest_samples_per_sec_concurrent']};"
             f"p50_ms={entry['p50_ms']};p99_ms={entry['p99_ms']};"
             f"cache_hit_rate={entry['cache_hit_rate']}")
    if args.serving_devices > 0:
        ns = args.serving_devices
        spec = loads.FleetScenarioSpec(n=ns, seed=7)
        slabs_sv = _materialize_grid_slabs(
            ns, _profile_names(ns), spec, seed=7, period_s=0.01,
            chunk_devices=min(args.stream_chunk, ns))
        scale = {"n_devices": ns, "period_s": 0.01}
        for be in backends:
            # at fleet scale a flush's kernel cost is amortised over a
            # deeper request queue (more concurrent clients, same small
            # pool of hot dashboard instants)
            entry = _serving_throughput(slabs_sv, ns, be,
                                        queries_per_flush=4096)
            scale[be] = entry
            emit(f"serving/scale_{be}_{ns}", 0.0,
                 f"queries_per_sec={entry['queries_per_sec']};"
                 f"ingest_samples_per_sec_concurrent="
                 f"{entry['ingest_samples_per_sec_concurrent']};"
                 f"p50_ms={entry['p50_ms']};p99_ms={entry['p99_ms']};"
                 f"cache_hit_rate={entry['cache_hit_rate']}")
        del slabs_sv
        block["scale"] = scale
    return block


def _chaos_slabs(n, n_slabs=16, seed=3):
    """Deterministic messy poll slabs (0.5 s of stream each) — the raw
    pre-fault stream the chaos bench injects into."""
    rng = np.random.default_rng(seed)
    out, t0 = [], 0.0
    for _ in range(n_slabs):
        k = int(rng.integers(8 * n, 12 * n))
        dev = rng.integers(0, n, k).astype(np.int64)
        t = t0 + np.sort(rng.uniform(0.0, 0.5, k))
        v = 80.0 + 40.0 * rng.random(k)
        out.append((dev, t, v))
        t0 += 0.5
    return out


def _chaos_block(args, backends):
    """The ``chaos`` BENCH block: degraded-mode ingest throughput under
    the full fault taxonomy vs the clean strict path, plus a
    kill-mid-stream → restore → resume cycle timed end to end (and
    checked bitwise against the uninterrupted faulty run)."""
    import dataclasses
    import tempfile

    from repro.core.stream import (FaultInjector, FaultSpec, HealthPolicy,
                                   MonitorService, MonitorSupervisor,
                                   restore_monitor)

    n = args.chaos_devices
    raw = _chaos_slabs(n)
    t1 = 0.5 * len(raw)
    n_samples = sum(v.size for _, _, v in raw)
    spec = FaultSpec(shuffle=True, dup_fraction=0.05, drop_fraction=0.05,
                     delay_fraction=0.05, clock_drift=0.005,
                     clock_skew_s=0.01, restart_every_s=2.0,
                     corrupt_fraction=0.02, dropout_fraction=0.10,
                     seed=11)

    def faulted():
        inj = FaultInjector(spec, n, 0.0, t1)
        for seq, (dev, ts, vs) in enumerate(raw):
            dev, ts, vs = inj.apply(seq, dev, ts, vs)
            if dev.size:
                yield seq, dev, ts, vs

    def hardened(be):
        return MonitorService(n, strict_ids=False, health=HealthPolicy(),
                              health_every_s=0.5, silent_after_s=1.0,
                              backend=be)

    block = {"n_devices": n, "n_samples": int(n_samples),
             "fault_spec": dataclasses.asdict(spec)}
    for be in backends:
        def clean_pass():
            mon = MonitorService(n, backend=be)
            for dev, ts, vs in raw:
                mon.ingest(dev, ts, vs)
            return mon

        def degraded_pass():
            mon = hardened(be)
            for _, dev, ts, vs in faulted():
                mon.ingest(dev, ts, vs)
            return mon

        clean_pass()                       # untimed warm-up (jit etc.)
        t0 = time.perf_counter()
        clean_pass()
        wall_clean = time.perf_counter() - t0
        degraded_pass()
        t0 = time.perf_counter()
        ref = degraded_pass()
        wall_deg = time.perf_counter() - t0

        crash = {"armed": True}

        def crashing():
            for i, slab in enumerate(faulted()):
                if crash["armed"] and i == len(raw) // 2:
                    crash["armed"] = False
                    raise RuntimeError("chaos kill")
                yield slab

        with tempfile.TemporaryDirectory() as root:
            sup = MonitorSupervisor(lambda: hardened(be), root,
                                    checkpoint_every=4)
            t0 = time.perf_counter()
            rep = sup.run(crashing)
            wall_rec = time.perf_counter() - t0
            # the restore step alone (what a restarted collector pays
            # before its first ingest)
            t0 = time.perf_counter()
            restore_monitor(root, fallback=True)
            restore_s = time.perf_counter() - t0
        bitwise = bool(
            np.array_equal(sup.monitor.state.energy_corr_j,
                           ref.state.energy_corr_j)
            and np.array_equal(sup.monitor.health.code, ref.health.code))
        entry = {
            "ingest_samples_per_sec_clean": round(n_samples / wall_clean, 1),
            "ingest_samples_per_sec_degraded": round(n_samples / wall_deg, 1),
            "degraded_over_clean_wall": round(wall_deg / wall_clean, 3),
            "n_rejected": int(ref.counters["rejected"]),
            "n_quarantined": int(ref.counters["n_quarantined"]),
            "recovery_run_wall_s": round(wall_rec, 4),
            "restore_s": round(restore_s, 4),
            "n_restores": int(rep.n_restores),
            "n_checkpoints": int(rep.n_checkpoints),
            "recovered_bitwise": bitwise,
        }
        block[be] = entry
        emit(f"chaos/backend_{be}_{n}", 0.0,
             f"ingest_samples_per_sec_degraded="
             f"{entry['ingest_samples_per_sec_degraded']};"
             f"degraded_over_clean={entry['degraded_over_clean_wall']};"
             f"restore_s={entry['restore_s']};"
             f"recovered_bitwise={entry['recovered_bitwise']}")
        if not bitwise:
            raise SystemExit("chaos bench: recovered monitor diverged "
                             "from the uninterrupted run")
    return block


def _collect_block(args) -> dict:
    """The ``collect`` BENCH block: wire-parse throughput per format
    (rows/sec) on a synthetic capture, the full file→monitor replay
    path through :class:`repro.collect.CollectorPipeline` (numpy
    backend — the parse side is pure python, the same on every tier),
    and the committed fixtures' parse accounting so the bench JSON
    records what CI smoke-replays."""
    import tempfile

    from repro.collect import CollectorPipeline
    from repro.collect import wire as cwire

    n_dev = 16
    polls = max(args.collect_rows // n_dev, 1)
    rng = np.random.default_rng(9)
    uuids = np.asarray([f"GPU-bench-{i:04d}" for i in range(n_dev)],
                       dtype=object)
    batch = cwire.SampleBatch(
        uuid=np.tile(uuids, polls),
        t=1.7e9 + np.repeat(np.arange(polls) * 0.1, n_dev),
        power_w=80.0 + 40.0 * rng.random(polls * n_dev),
        util=rng.uniform(0.0, 100.0, polls * n_dev))
    block = {"n_rows": len(batch), "n_devices": n_dev}
    with tempfile.TemporaryDirectory() as d:
        writers = (("daemon", lambda b: cwire.format_daemon(b, precision=3)),
                   ("smi", cwire.format_query_gpu))
        for fmt, writer in writers:
            path = os.path.join(d, f"log_{fmt}.csv")
            with open(path, "w") as fh:
                fh.write(writer(batch))
            t0 = time.perf_counter()
            _, c = cwire.parse_log(path, fmt=fmt)
            wall = time.perf_counter() - t0
            assert c.samples == len(batch)
            block[f"{fmt}_parse_rows_per_sec"] = round(c.rows / wall, 1)
            block[f"{fmt}_wall_s"] = round(wall, 4)

        path = os.path.join(d, "log_daemon.csv")
        t0 = time.perf_counter()
        pipe = CollectorPipeline(backend="numpy", now=0.0)
        counters = cwire.WireCounters()
        for b in cwire.iter_batches(path, fmt="daemon", counters=counters):
            pipe.feed(b)
        mon = pipe.finish()
        wall = time.perf_counter() - t0
        block["replay_rows_per_sec"] = round(counters.rows / wall, 1)
        block["replay_wall_s"] = round(wall, 4)
        block["replay_accepted"] = int(mon.counters["accepted"])

    data = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "..", "tests", "data")
    fixtures = {}
    for name in ("daemon_sample.csv", "smi_sample.csv"):
        p = os.path.join(data, name)
        if os.path.exists(p):
            _, c = cwire.parse_log(p)
            fixtures[name] = c.as_dict()
    block["fixtures"] = fixtures

    emit(f"collect/parse_{block['n_rows']}", 0.0,
         f"daemon_rows_per_sec={block['daemon_parse_rows_per_sec']};"
         f"smi_rows_per_sec={block['smi_parse_rows_per_sec']}")
    emit(f"collect/replay_{block['n_rows']}", 0.0,
         f"replay_rows_per_sec={block['replay_rows_per_sec']};"
         f"accepted={block['replay_accepted']}")
    return block


def _audit_stats(n, names, ws, backend):
    """One timed heterogeneous naive audit; returns (wall_s, result)."""
    t0 = time.perf_counter()
    res = fleet_audit(n, profile=names, workload=ws, good_practice=False,
                      backend=backend)
    return time.perf_counter() - t0, res


def run(argv=None) -> None:
    # programmatic callers (benchmarks/run.py) get the defaults; the CLI
    # passes sys.argv[1:] explicitly
    args = _parse_args(argv if argv is not None else [])
    use_compile_cache()
    n = args.n_devices
    backends = _selected_backends(args.backend)

    if args.serving_only:
        names = _profile_names(n)
        ws = loads.mixed_fleet_workloads(n, seed=7, as_bank=True)
        slabs = _materialize_grid_slabs(n, names, ws, seed=7)
        serving = _serving_blocks(args, backends, slabs, n)
        payload = {}
        if os.path.exists(JSON_PATH):
            with open(JSON_PATH) as fh:
                payload = json.load(fh)
        payload["serving"] = serving
        with open(JSON_PATH, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
            fh.write("\n")
        emit("fleet_audit/bench_json", 0.0, f"path={JSON_PATH}")
        return

    if args.collect_only:
        collect = _collect_block(args)
        payload = {}
        if os.path.exists(JSON_PATH):
            with open(JSON_PATH) as fh:
                payload = json.load(fh)
        payload["collect"] = collect
        with open(JSON_PATH, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
            fh.write("\n")
        emit("fleet_audit/bench_json", 0.0, f"path={JSON_PATH}")
        return

    if args.chaos_only:
        chaos = _chaos_block(args, backends)
        payload = {}
        if os.path.exists(JSON_PATH):
            with open(JSON_PATH) as fh:
                payload = json.load(fh)
        payload["chaos"] = chaos
        with open(JSON_PATH, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
            fh.write("\n")
        emit("fleet_audit/bench_json", 0.0, f"path={JSON_PATH}")
        return

    proj = datacenter_projection(n_gpus=10_000, tdp_w=700.0, gain_tol=0.05)
    emit("headline_datacenter/10k_h100", 0.0,
         f"per_gpu_err_w={proj['per_gpu_err_w']:.0f};"
         f"annual_err_usd={proj['annual_err_usd']:.0f}")

    # object path (reference): a small pod of per-device ledgers
    fleet = FleetLedger()
    for i in range(256):
        led = EnergyLedger(device_id=f"chip{i}")
        for s in range(20):
            led.append(s, s * 1.0, s + 1.0, 205.0, 200.0, 10.0)
        fleet.register(led)
    s = fleet.summary()
    emit("fleet_telemetry/pod256", 0.0,
         f"total_kwh={s.kwh:.4f};sigma_ind_pct="
         f"{s.sigma_independent_j/s.total_j*100:.2f};sigma_wc_pct="
         f"{s.sigma_worstcase_j/s.total_j*100:.2f};"
         f"mean_power_w={s.mean_power_w:.0f}")

    # shared-timeline path: n heterogeneous devices, one workload,
    # naive + good practice (the paper's Fig. 18 at fleet scale)
    names = _profile_names(n)
    # time the two protocols separately: the naive-only pass first, then
    # the full audit (same seeds → identical naive results), so each
    # metric's us-per-device reflects only its own protocol's cost
    t0 = time.perf_counter()
    fleet_audit(n, profile=names, good_practice=False)
    wall_naive = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = fleet_audit(n, profile=names, good_practice=True, n_trials=2)
    wall_shared = time.perf_counter() - t0
    wall_gp = max(wall_shared - wall_naive, 0.0)
    st = res.stats()
    gp = res.stats(res.gp_err)
    _emit_err(f"fleet_audit/naive_err_{n}", wall_naive * 1e6 / n, st)
    _emit_err(f"fleet_audit/goodpractice_err_{n}", wall_gp * 1e6 / n, gp)

    unc = res.uncertainty()
    big = FleetLedger()
    big.register_batch(res.gp_j, duration_s=0.2)
    bs = big.summary()
    emit(f"fleet_audit/uncertainty_{n}", wall_shared * 1e6 / n,
         f"n={bs.n_devices};sigma_ind_pct="
         f"{unc['sigma_independent_rel']*100:.3f};"
         f"sigma_wc_pct={unc['sigma_worstcase_rel']*100:.3f};"
         f"wall_s={wall_shared:.2f}")

    # heterogeneous path: every device its own timeline (mixed scenarios:
    # training pods, Poisson inference serving, idle/maintenance, diurnal)
    # — synthesised array-natively (ISSUE 4), timed against the
    # per-device-object path it replaced (same timelines bitwise)
    t0 = time.perf_counter()
    ws_obj = WorkloadSet(loads.mixed_fleet_workloads(n, seed=7))
    ws_obj.timeline_bank  # stack the [N, S] substrate outside the audits
    wall_gen_obj = time.perf_counter() - t0
    t0 = time.perf_counter()
    ws = loads.mixed_fleet_workloads(n, seed=7, as_bank=True)
    wall_gen = time.perf_counter() - t0
    emit(f"fleet_audit/workload_gen_{n}", wall_gen * 1e6 / n,
         f"bank_s={wall_gen:.3f};objects_s={wall_gen_obj:.3f};"
         f"speedup={wall_gen_obj / max(wall_gen, 1e-9):.1f}x")
    # naive-only pass first (same seeds → identical naive results), so
    # each metric's us-per-device reflects only its own protocol's cost
    t0 = time.perf_counter()
    fleet_audit(n, profile=names, workload=ws, good_practice=False)
    wall_naive_h = time.perf_counter() - t0
    t0 = time.perf_counter()
    res_h = fleet_audit(n, profile=names, workload=ws,
                        good_practice=True, n_trials=2)
    wall_hetero = time.perf_counter() - t0
    wall_gp_h = max(wall_hetero - wall_naive_h, 0.0)
    sth = res_h.stats()
    gph = res_h.stats(res_h.gp_err)
    _emit_err(f"fleet_audit/hetero_naive_err_{n}", wall_naive_h * 1e6 / n,
              sth)
    _emit_err(f"fleet_audit/hetero_goodpractice_err_{n}",
              wall_gp_h * 1e6 / n, gph)
    by_naive = res_h.by_scenario()
    by_gp = res_h.by_scenario(res_h.gp_err)
    for label in sorted(by_naive):
        emit(f"fleet_audit/scenario_{label}", 0.0,
             f"n={by_naive[label]['n_devices']};"
             f"naive_mean_abs={by_naive[label]['mean_abs_err']:.4f};"
             f"gp_mean_abs={by_gp[label]['mean_abs_err']:.4f}")
    ratio = wall_hetero / max(wall_shared, 1e-9)
    emit("fleet_audit/hetero_over_shared", 0.0,
         f"wall_shared_s={wall_shared:.2f};wall_hetero_s={wall_hetero:.2f};"
         f"ratio={ratio:.2f}")

    # -- backend comparison (ISSUE 3): the same heterogeneous naive audit
    # timed per backend, cold (first call pays jax compilation) and warm
    backend_stats = {}
    ref_naive = None
    for be in backends:
        wall_cold, res_be = _audit_stats(n, names, ws, be)
        wall_warm, res_be = _audit_stats(n, names, ws, be)
        entry = {
            "n_devices": n,
            "wall_s_cold": round(wall_cold, 4),
            "wall_s": round(wall_warm, 4),
            "devices_per_sec": round(n / wall_warm, 1),
        }
        if ref_naive is None:
            ref_naive = res_be.naive_j
        else:
            entry["max_abs_dev_j_vs_numpy"] = float(
                np.max(np.abs(res_be.naive_j - ref_naive)))
        backend_stats[be] = entry
        emit(f"fleet_audit/backend_{be}_{n}", wall_warm * 1e6 / n,
             f"devices_per_sec={entry['devices_per_sec']};"
             f"wall_s_cold={wall_cold:.2f}")

    # -- jax at fleet scale: the ROADMAP's 100k-device heterogeneous audit
    scale_stats = None
    if "jax" in backends and args.scale_devices > 0:
        ns = args.scale_devices
        t0 = time.perf_counter()
        ws_scale = loads.mixed_fleet_workloads(ns, seed=7, as_bank=True)
        wall_gen_s = time.perf_counter() - t0
        # the object path this replaced, for the ISSUE 4 ≥10× criterion
        t0 = time.perf_counter()
        WorkloadSet(loads.mixed_fleet_workloads(ns, seed=7)).timeline_bank
        wall_gen_obj_s = time.perf_counter() - t0
        wall_scale, res_scale = _audit_stats(
            ns, _profile_names(ns), ws_scale, "jax")
        scale_stats = {
            "n_devices": ns,
            "wall_s_workload_gen": round(wall_gen_s, 4),
            "wall_s_workload_gen_objects": round(wall_gen_obj_s, 4),
            "workload_gen_speedup": round(
                wall_gen_obj_s / max(wall_gen_s, 1e-9), 1),
            "wall_s": round(wall_scale, 4),
            "devices_per_sec": round(ns / wall_scale, 1),
            "naive_mean_abs_err": res_scale.stats()["mean_abs_err"],
        }
        backend_stats["jax"]["scale"] = scale_stats
        emit(f"fleet_audit/backend_jax_scale_{ns}", wall_scale * 1e6 / ns,
             f"devices_per_sec={round(ns / wall_scale, 1)};"
             f"wall_s={wall_scale:.2f};"
             f"gen_speedup={scale_stats['workload_gen_speedup']}x")

        # chunked-vs-unchunked consistency at a reduced size (streaming
        # moments merge across ragged slabs; per-device within float
        # accumulation of the padded grids)
        nc = min(ns, 10_000)
        spec_c = loads.FleetScenarioSpec(n=nc, seed=7)
        ref_c = fleet_audit(nc, profile=_profile_names(nc), workload=spec_c)
        t0 = time.perf_counter()
        got_c = fleet_audit(nc, profile=_profile_names(nc), workload=spec_c,
                            chunk_devices=max(nc // 8, 1))
        wall_chunked = time.perf_counter() - t0
        dev = float(np.max(np.abs(got_c.naive_j - ref_c.naive_j)
                           / np.abs(ref_c.naive_j)))
        sm_delta = abs(got_c.streamed["naive"]["overall"]["mean_abs_err"]
                       - got_c.stats()["mean_abs_err"])
        emit(f"fleet_audit/chunked_consistency_{nc}",
             wall_chunked * 1e6 / nc,
             f"max_rel_dev_vs_unchunked={dev:.3e};"
             f"streamed_vs_exact_mean_abs={sm_delta:.3e}")
        chunk_block = {
            "n_devices": nc,
            "chunk_devices": max(nc // 8, 1),
            "wall_s": round(wall_chunked, 4),
            "max_rel_dev_vs_unchunked": dev,
            "streamed_vs_exact_mean_abs": sm_delta,
        }
    else:
        chunk_block = None

    # -- streaming monitor (ISSUE 5): replay the heterogeneous fleet as a
    # live poll stream through MonitorService, per backend, and pin the
    # stream-ingested window energies against the offline audit
    from repro.core.stream import stream_fleet
    stream_block = {"n_devices": n, "period_s": 0.001}
    slabs = _materialize_grid_slabs(n, names, ws, seed=7)
    for be in backends:
        # replay_samples_per_sec times the whole live pipeline (sensor
        # simulation + ingest); ingest_samples_per_sec isolates the
        # monitor's ingest hot loop on pre-materialised slabs — the
        # ISSUE 6 metric the accelerated tiers must dominate
        t0 = time.perf_counter()
        res_s = stream_fleet(n, profile=names, workload=ws, seed=7,
                             backend=be)
        wall_s = time.perf_counter() - t0
        n_ing, wall_ing = _ingest_throughput(slabs, n, be)
        entry = {
            "n_samples": int(res_s.n_samples),
            "wall_s": round(wall_s, 4),
            "samples_per_sec": round(res_s.n_samples / wall_s, 1),
            "wall_s_ingest": round(wall_ing, 4),
            "ingest_samples_per_sec": round(n_ing / wall_ing, 1),
            "monitor_state_mb": round(res_s.monitor.nbytes() / 1e6, 2),
        }
        stream_block[be] = entry
        emit(f"stream_monitor/backend_{be}_{n}", wall_s * 1e6 / n,
             f"samples_per_sec={entry['samples_per_sec']};"
             f"ingest_samples_per_sec={entry['ingest_samples_per_sec']};"
             f"n_samples={entry['n_samples']};"
             f"state_mb={entry['monitor_state_mb']}")

    # -- snapshot serving (ISSUE 8): batched query executor under
    # concurrent ingest, reusing the materialised slabs
    serving_block = _serving_blocks(args, backends, slabs, n)
    del slabs
    # untimed stream↔offline parity pin at a reduced size
    nc = min(n, 2000)
    res_p = stream_fleet(nc, profile=_profile_names(nc),
                         workload=loads.mixed_fleet_workloads(
                             nc, seed=7, as_bank=True),
                         seed=7, compare=True)
    stream_block["parity_n_devices"] = nc
    stream_block["parity_max_rel_dev"] = float(np.max(
        np.abs(res_p.naive_stream_j - res_p.naive_offline_j)
        / np.abs(res_p.naive_offline_j)))
    emit(f"stream_monitor/parity_{nc}", 0.0,
         f"max_rel_dev={stream_block['parity_max_rel_dev']:.3e}")

    # scale streaming replay: spec-synthesised slabs, bounded memory —
    # per backend, so the ISSUE 6 ordering (accelerated tiers dominate
    # numpy on ingest) is recorded at scale too
    if args.stream_devices > 0:
        import resource
        ns = args.stream_devices
        spec = loads.FleetScenarioSpec(n=ns, seed=7)
        scale_stream = {
            "n_devices": ns,
            "chunk_devices": min(args.stream_chunk, ns),
            "period_s": 0.01,
        }
        slabs_sc = _materialize_grid_slabs(
            ns, _profile_names(ns), spec, seed=7, period_s=0.01,
            chunk_devices=min(args.stream_chunk, ns))
        for be in backends:
            rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
            t0 = time.perf_counter()
            res_sc = stream_fleet(
                ns, profile=_profile_names(ns), workload=spec, seed=7,
                chunk_devices=min(args.stream_chunk, ns), period_s=0.01,
                backend=be, monitor_kwargs=dict(ring_slots=4))
            wall_sc = time.perf_counter() - t0
            rss1 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
            n_ing, wall_ing = _ingest_throughput(slabs_sc, ns, be)
            scale_stream[be] = {
                "n_samples": int(res_sc.n_samples),
                "wall_s": round(wall_sc, 2),
                "samples_per_sec": round(res_sc.n_samples / wall_sc, 1),
                "wall_s_ingest": round(wall_ing, 4),
                "ingest_samples_per_sec": round(n_ing / wall_ing, 1),
                "devices_per_sec": round(ns / wall_sc, 1),
                "monitor_state_mb": round(res_sc.monitor.nbytes() / 1e6,
                                          1),
                "peak_rss_mb": round(rss1 / 1024.0, 1),
                "peak_rss_before_mb": round(rss0 / 1024.0, 1),
            }
            emit(f"stream_monitor/scale_{be}_{ns}", wall_sc * 1e6 / ns,
                 f"samples_per_sec={scale_stream[be]['samples_per_sec']};"
                 f"ingest_samples_per_sec="
                 f"{scale_stream[be]['ingest_samples_per_sec']};"
                 f"wall_s={wall_sc:.1f};"
                 f"state_mb={scale_stream[be]['monitor_state_mb']};"
                 f"peak_rss_mb={scale_stream[be]['peak_rss_mb']}")
        del slabs_sc
        stream_block["scale"] = scale_stream

    # -- streaming million-device audit: FleetScenarioSpec slabs keep
    # peak memory bounded regardless of fleet size (ISSUE 4)
    mega_block = None
    if args.mega_devices > 0:
        import resource      # Unix-only; needed for this block alone
        nm = args.mega_devices
        chunk = min(args.mega_chunk, nm)
        # cyclic profile mix keeps every slab heterogeneous
        pattern = ["a100", "a100", "h100_instant", "v100"]
        names_m = [pattern[i % 4] for i in range(nm)]
        spec = loads.FleetScenarioSpec(n=nm, seed=7)
        rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        t0 = time.perf_counter()
        res_m = fleet_audit(nm, profile=names_m, workload=spec,
                            chunk_devices=chunk)
        wall_m = time.perf_counter() - t0
        rss1 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        st_m = res_m.stats()
        mega_block = {
            "n_devices": nm,
            "chunk_devices": chunk,
            "n_chunks": (nm + chunk - 1) // chunk,
            "wall_s": round(wall_m, 2),
            "devices_per_sec": round(nm / wall_m, 1),
            "peak_rss_mb": round(rss1 / 1024.0, 1),
            "peak_rss_before_mb": round(rss0 / 1024.0, 1),
            "naive": st_m,
            "by_scenario_streamed":
                res_m.streamed["naive"]["by_scenario"],
        }
        emit(f"fleet_audit/mega_{nm}", wall_m * 1e6 / nm,
             f"devices_per_sec={round(nm / wall_m, 1)};"
             f"wall_s={wall_m:.1f};chunks={mega_block['n_chunks']};"
             f"peak_rss_mb={mega_block['peak_rss_mb']}")

    payload = {
        "n_devices": n,
        "profiles": {"a100": n // 2, "h100_instant": n // 4,
                     "v100": n - n // 2 - n // 4},
        "backends": backend_stats,
        "shared": {
            "wall_s_naive": round(wall_naive, 4),
            "wall_s_total": round(wall_shared, 4),
            "devices_per_sec": round(n / wall_shared, 1),
            "naive": st,
            "good_practice": gp,
        },
        "heterogeneous": {
            "wall_s_workload_gen": round(wall_gen, 4),
            "wall_s_workload_gen_objects": round(wall_gen_obj, 4),
            "workload_gen_speedup": round(
                wall_gen_obj / max(wall_gen, 1e-9), 1),
            "wall_s_naive": round(wall_naive_h, 4),
            "wall_s_total": round(wall_hetero, 4),
            "devices_per_sec": round(n / wall_hetero, 1),
            "naive": sth,
            "good_practice": gph,
            "by_scenario": {k: {"n_devices": by_naive[k]["n_devices"],
                                "naive_mean_abs":
                                    by_naive[k]["mean_abs_err"],
                                "gp_mean_abs": by_gp[k]["mean_abs_err"]}
                            for k in sorted(by_naive)},
        },
        "hetero_over_shared_wall": round(ratio, 3),
        "streaming": stream_block,
        "serving": serving_block,
    }
    if chunk_block is not None:
        payload["chunked"] = chunk_block
    if mega_block is not None:
        payload["mega"] = mega_block
    if args.shard_devices > 0:
        shard_block, shard_mega = _shard_blocks(args)
        if shard_mega is not None:
            shard_block["mega"] = shard_mega
        payload["sharded"] = shard_block
    if args.chaos_devices > 0:
        payload["chaos"] = _chaos_block(args, backends)
    if args.collect_rows > 0:
        payload["collect"] = _collect_block(args)
    with open(JSON_PATH, "w") as fh:
        json.dump(payload, fh, indent=2, sort_keys=True)
        fh.write("\n")
    emit("fleet_audit/bench_json", 0.0, f"path={JSON_PATH}")


if __name__ == "__main__":
    import sys
    run(sys.argv[1:])
