"""Live collector subsystem (ISSUE 10).

Five groups:

* wire parsing — cell parsers (units, failure cells, timestamp
  formats), writer↔parser round-trips (daemon lossless, smi within its
  quantisation), and exact parse-accounting pins on the committed
  fixtures in ``tests/data/``;
* device registry — first-seen-order ids, hot-add stamping, frozen
  (reject-and-count) and strict (raise) policies;
* monitor growth — ``MonitorService.grow`` pinned *bitwise* against
  building the full width up front, through checkpoints, and growing
  under the collector pipeline;
* calibration artifacts — the versioned :class:`ArtifactStore`
  lifecycle (save/activate/rollback/deactivate/age-out/gc), schema
  drift in both directions, and the ``resolve_corrections`` fallback
  ladder;
* end to end — the ``python -m repro.collect replay`` path over the
  committed fixture with an activated store record applied, pinned
  bitwise (numpy backend) against the equivalent direct construction,
  and the CLI as a subprocess.
"""
import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.collect import (CollectorPipeline, DeviceRegistry, SampleBatch,
                           SimulatedSampler, SlabAssembler,
                           UnknownDeviceError, wire)
from repro.collect.cli import main as cli_main
from repro.core import load as loads
from repro.core import profiles
from repro.core.calibrate import CalibrationRecord, nominal_record
from repro.core.calibrate_store import (ArtifactStore, StoreError,
                                        record_stamp, resolve_corrections)
from repro.core.fleet_engine import SensorBank
from repro.core.stream import MonitorService, StreamCorrections, replay
from repro.core.stream.checkpoint import restore_monitor, save_monitor

DATA = os.path.join(os.path.dirname(__file__), "data")
DAEMON_FIXTURE = os.path.join(DATA, "daemon_sample.csv")
SMI_FIXTURE = os.path.join(DATA, "smi_sample.csv")

# exact parse accounting of the committed fixtures — regenerate with
# tools/gen_collect_fixture.py and update here if the fixtures change
FIXTURE_EXPECT = {
    "daemon_sample.csv": {"rows": 1306, "samples": 1302, "headers": 2,
                          "blank": 1, "malformed": 2, "not_available": 0,
                          "error_cells": 0},
    "smi_sample.csv": {"rows": 962, "samples": 957, "headers": 2,
                       "blank": 0, "malformed": 0, "not_available": 1,
                       "error_cells": 2},
}


# ---------------------------------------------------------------------------
# wire: cell parsers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell,watts,status", [
    ("68.84 W", 68.84, "ok"),
    ("68840 mW", 68.84, "ok"),
    ("0.25 kW", 250.0, "ok"),
    ("132.5", 132.5, "ok"),               # csv,nounits
    ("  99.0 w ", 99.0, "ok"),
    ("[N/A]", None, "na"),
    ("N/A", None, "na"),
    ("[Unknown Error]", None, "error"),
    ("ERR!", None, "error"),
    ("[Unsupported]", None, "error"),
    ("12 parsecs", None, "malformed"),
    ("watts 12", None, "malformed"),
    ("", None, "malformed"),
])
def test_power_cell(cell, watts, status):
    w, s = wire.parse_power_cell(cell)
    assert s == status
    if watts is None:
        assert np.isnan(w)
    else:
        assert w == pytest.approx(watts, rel=1e-12)


def test_timestamp_cell_formats():
    assert wire.parse_timestamp_cell("1700000000.25") == 1700000000.25
    # nvidia-smi's format, with and without milliseconds — taken as UTC
    t = wire.parse_timestamp_cell("2023/11/14 22:13:20.500")
    assert t == 1700000000.5
    assert wire.parse_timestamp_cell("2023/11/14 22:13:20") == 1700000000.0
    assert wire.parse_timestamp_cell("2023-11-14T22:13:20") == 1700000000.0
    assert wire.parse_timestamp_cell("2023-11-14 22:13:20.250") \
        == 1700000000.25
    assert np.isnan(wire.parse_timestamp_cell("yesterday"))


def test_util_cell():
    assert wire.parse_util_cell(" 85 % ") == 85.0
    assert wire.parse_util_cell("85") == 85.0
    assert np.isnan(wire.parse_util_cell("[N/A]"))
    assert np.isnan(wire.parse_util_cell(""))


# ---------------------------------------------------------------------------
# wire: round-trips and fixture pins
# ---------------------------------------------------------------------------

def _random_batch(n=257, seed=0):
    rng = np.random.default_rng(seed)
    uuids = np.asarray([f"GPU-{rng.integers(0, 8):x}" for _ in range(n)],
                       dtype=object)
    t = 1.7e9 + np.sort(rng.uniform(0.0, 60.0, n))
    p = rng.uniform(30.0, 700.0, n)
    u = rng.uniform(0.0, 100.0, n)
    u[rng.random(n) < 0.1] = np.nan       # wire had no utilisation
    return SampleBatch(uuid=uuids, t=t, power_w=p, util=u)


def test_daemon_round_trip_is_lossless():
    """repr-precision daemon CSV → parser → the same batch, bitwise."""
    batch = _random_batch()
    text = wire.format_daemon(batch, precision=None)
    back, c = wire.parse_daemon(text)
    assert c.samples == len(batch) and c.malformed == 0
    np.testing.assert_array_equal(back.uuid, batch.uuid)
    np.testing.assert_array_equal(back.t, batch.t)
    np.testing.assert_array_equal(back.power_w, batch.power_w)
    np.testing.assert_array_equal(back.util, batch.util)


@pytest.mark.parametrize("nounits", [False, True])
def test_smi_round_trip_within_quantisation(nounits):
    """The smi writer is lossy by design (ms timestamps, 2-decimal
    watts); the parser recovers it to exactly that quantisation."""
    batch = _random_batch(seed=3)
    text = wire.format_query_gpu(batch, nounits=nounits)
    back, c = wire.parse_query_gpu(text)
    assert c.samples == len(batch) and c.headers == 1
    np.testing.assert_array_equal(back.uuid, batch.uuid)
    np.testing.assert_allclose(back.t, batch.t, atol=1.0e-3)
    np.testing.assert_allclose(back.power_w, batch.power_w, atol=0.005)


@pytest.mark.parametrize("name,path", [
    ("daemon_sample.csv", DAEMON_FIXTURE),
    ("smi_sample.csv", SMI_FIXTURE),
])
def test_fixture_parse_accounting_pinned(name, path):
    batch, c = wire.parse_log(path)
    assert c.as_dict() == FIXTURE_EXPECT[name]
    assert len(batch) == FIXTURE_EXPECT[name]["samples"]
    # every row lands in exactly one bucket
    assert c.rows == (c.samples + c.headers + c.malformed
                      + c.not_available + c.error_cells)


def test_fixture_sniffing():
    with open(DAEMON_FIXTURE) as f:
        assert wire.sniff_format([next(f) for _ in range(3)]) == "daemon"
    with open(SMI_FIXTURE) as f:
        assert wire.sniff_format([next(f) for _ in range(3)]) == "smi"


@pytest.mark.parametrize("batch_rows", [7, 100, 10_000])
def test_iter_batches_chunking_invariant(batch_rows):
    """Streaming a fixture in any chunk size reproduces the one-shot
    parse bitwise — headers carried across chunk boundaries included."""
    whole, cw = wire.parse_log(DAEMON_FIXTURE)
    c = wire.WireCounters()
    parts = list(wire.iter_batches(DAEMON_FIXTURE, batch_rows=batch_rows,
                                   counters=c))
    got = parts[0]
    for b in parts[1:]:
        got = got.concat(b)
    np.testing.assert_array_equal(got.uuid, whole.uuid)
    np.testing.assert_array_equal(got.t, whole.t)
    np.testing.assert_array_equal(got.power_w, whole.power_w)
    assert c.as_dict() == cw.as_dict()


def test_smi_fixture_chunking_carries_headers():
    whole, cw = wire.parse_log(SMI_FIXTURE)
    c = wire.WireCounters()
    parts = list(wire.iter_batches(SMI_FIXTURE, batch_rows=13, counters=c))
    got = parts[0]
    for b in parts[1:]:
        got = got.concat(b)
    np.testing.assert_array_equal(got.power_w, whole.power_w)
    assert c.as_dict() == cw.as_dict()


# ---------------------------------------------------------------------------
# device registry
# ---------------------------------------------------------------------------

def test_registry_first_seen_order_and_stamping():
    reg = DeviceRegistry()
    ids = reg.resolve(np.asarray(["b", "a", "b", "c"], dtype=object),
                      t=np.asarray([5.0, 6.0, 7.0, 8.0]))
    np.testing.assert_array_equal(ids, [0, 1, 0, 2])
    assert reg.uuids == ["b", "a", "c"]
    assert reg.first_seen_t == [5.0, 6.0, 8.0]
    # idempotent adds keep ids stable
    assert reg.add("a") == 1 and reg.n_devices == 3


def test_registry_reject_policy_counts():
    reg = DeviceRegistry(["a", "b"], on_unknown="reject")
    ids = reg.resolve(np.asarray(["a", "x", "b", "y"], dtype=object))
    np.testing.assert_array_equal(ids, [0, -1, 1, -1])
    assert reg.n_rejected == 2 and reg.n_devices == 2


def test_registry_raise_policy():
    reg = DeviceRegistry(["a"], on_unknown="raise")
    with pytest.raises(UnknownDeviceError):
        reg.resolve(np.asarray(["a", "nope"], dtype=object))
    with pytest.raises(ValueError):
        DeviceRegistry(on_unknown="explode")


# ---------------------------------------------------------------------------
# monitor growth
# ---------------------------------------------------------------------------

def _stream_rows(n_all=4, late_at=100, polls=300, seed=2):
    """A synthetic sample stream where devices n_all-2.. join late."""
    rng = np.random.default_rng(seed)
    uuids = [f"GPU-{i}" for i in range(n_all)]
    rows = []
    for k in range(polls):
        fleet = uuids[:2] if k < late_at else uuids
        for u in fleet:
            rows.append((u, 0.01 * k, 50.0 + rng.standard_normal()))
    return uuids, SampleBatch.from_rows([r[0] for r in rows],
                                        [r[1] for r in rows],
                                        [r[2] for r in rows])


def _chunks(batch, size):
    for i in range(0, len(batch), size):
        yield SampleBatch(uuid=batch.uuid[i:i + size],
                          t=batch.t[i:i + size],
                          power_w=batch.power_w[i:i + size],
                          util=batch.util[i:i + size])


def _assert_monitor_equal(a, b):
    np.testing.assert_array_equal(a.state.energy_j, b.state.energy_j)
    np.testing.assert_array_equal(a.state.win_corr_j, b.state.win_corr_j)
    np.testing.assert_array_equal(a.ring.t, b.ring.t)
    np.testing.assert_array_equal(a.ring.e_corr, b.ring.e_corr)
    fa, fb = a.fleet_energy(), b.fleet_energy()
    np.testing.assert_array_equal(fa.per_device_j, fb.per_device_j)
    assert fa.total_j == fb.total_j


def test_grow_bitwise_equals_upfront_construction():
    """Hot-adding devices mid-stream (lenient registry + grow) yields
    the *same bits* as knowing the full fleet from the start."""
    uuids, batch = _stream_rows()
    pipe = CollectorPipeline(slab_samples=128, now=0.0)
    for chunk in _chunks(batch, 37):
        pipe.feed(chunk)
    grown = pipe.finish()
    assert grown.n_devices == 4

    reg = DeviceRegistry(uuids)
    asm = SlabAssembler(reg, slab_samples=128)
    upfront = MonitorService(4, strict_ids=False, backend="numpy")
    for chunk in _chunks(batch, 37):
        for dev, t, v in asm.push(chunk):
            upfront.ingest(dev, t, v)
    for dev, t, v in asm.flush():
        upfront.ingest(dev, t, v)
    _assert_monitor_equal(grown, upfront)


def test_grow_with_history_bitwise_equals_upfront_construction():
    """Hot-adding devices to a monitor with a history tier widens the
    tier: the grown monitor's tier and series equal, bit for bit, those
    of a monitor built at the full width from the start."""
    hist = {"history_step_s": 0.25, "history_steps": 8}
    uuids, batch = _stream_rows()
    pipe = CollectorPipeline(slab_samples=128, now=0.0, monitor_kwargs=hist)
    for chunk in _chunks(batch, 37):
        pipe.feed(chunk)
    grown = pipe.finish()
    assert grown.n_devices == 4

    reg = DeviceRegistry(uuids)
    asm = SlabAssembler(reg, slab_samples=128)
    upfront = MonitorService(4, strict_ids=False, backend="numpy", **hist)
    for chunk in _chunks(batch, 37):
        for dev, t, v in asm.push(chunk):
            upfront.ingest(dev, t, v)
    for dev, t, v in asm.flush():
        upfront.ingest(dev, t, v)
    _assert_monitor_equal(grown, upfront)
    for arr in ("b_first", "b_last", "e_raw", "e_corr"):
        np.testing.assert_array_equal(getattr(grown.history, arr),
                                      getattr(upfront.history, arr), arr)
    a, b = grown.fleet_series(1.0, 2.75, 0.25), upfront.fleet_series(
        1.0, 2.75, 0.25)
    np.testing.assert_array_equal(a.total_j, b.total_j)
    np.testing.assert_array_equal(a.n_covered, b.n_covered)


def test_slab_boundaries_independent_of_feed_chunking():
    """Pipeline state depends on (stream, slab_samples) only — not on
    how the file reader chunked its batches."""
    _, batch = _stream_rows(late_at=10_000)   # no hot-add: pure assembly
    monitors = []
    for feed in (11, 97, 1200):
        pipe = CollectorPipeline(slab_samples=256, now=0.0)
        for chunk in _chunks(batch, feed):
            pipe.feed(chunk)
        monitors.append(pipe.finish())
        assert pipe.assembler.n_slabs == len(batch) // 256 + \
            (1 if len(batch) % 256 else 0)
    _assert_monitor_equal(monitors[0], monitors[1])
    _assert_monitor_equal(monitors[0], monitors[2])


def test_grow_validation():
    mon = MonitorService(4, backend="numpy")
    with pytest.raises(ValueError):
        mon.grow(2)                       # shrink is not a thing
    corr = StreamCorrections.identity(3)  # wrong tail width
    with pytest.raises(ValueError):
        mon.grow(6, corrections=corr)


def test_grow_checkpoint_round_trip(tmp_path):
    """A grown monitor checkpoints and restores bitwise — growth leaves
    no state the schema registries don't know about."""
    uuids, batch = _stream_rows()
    pipe = CollectorPipeline(slab_samples=128, now=0.0)
    for chunk in _chunks(batch, 50):
        pipe.feed(chunk)
    mon = pipe.finish()
    save_monitor(mon, str(tmp_path), step=1)
    back = restore_monitor(str(tmp_path))
    _assert_monitor_equal(mon, back)
    assert back.n_devices == 4


def test_grow_epoch_bumps_and_serves_fresh():
    """Growth invalidates serving caches via the epoch tag: a cached
    pre-growth answer is never replayed at the new width."""
    from repro.serve.monitor_service import MonitorQuery, MonitorQueryService
    mon = MonitorService(2, backend="numpy")
    mon.ingest(np.array([0, 1]), np.array([0.0, 0.0]),
               np.array([100.0, 100.0]))
    mon.ingest(np.array([0, 1]), np.array([1.0, 1.0]),
               np.array([100.0, 100.0]))
    svc = MonitorQueryService(mon)
    q = MonitorQuery.fleet_energy(t=1.0)
    before = svc.query(q)
    assert before.per_device_j.shape == (2,)
    epoch0 = mon.epoch
    mon.grow(3)
    assert mon.epoch == epoch0 + 1
    mon.ingest(np.array([2, 2]), np.array([0.0, 1.0]),
               np.array([50.0, 50.0]))
    after = svc.query(q)
    assert after.per_device_j.shape == (3,)
    assert after.total_j == pytest.approx(before.total_j + 50.0)


def test_sampler_pipeline_matches_replay_bitwise():
    """The full collector path (SimulatedSampler → registry → assembler
    → monitor) reproduces the simulation-fed ``replay`` driver bitwise
    when slab boundaries align (one slab per replay tick)."""
    n = 6
    bank = SensorBank.from_catalog(["a100"] * n, seeds=np.arange(n) + 3)
    tl = loads.multi_phase_workload([(0.130, 215.0), (0.070, 165.0)])
    bank.attach(tl, t_end=2.0)

    ref = MonitorService(n, backend="numpy")
    replay(bank, ref, 0.0, 1.0, period_s=0.001, grid=False)

    sampler = SimulatedSampler(bank, t0=0.0, period_s=0.001)
    # replay's tick_s=0.5 at 1 ms → 500 polls × n devices per slab
    pipe = CollectorPipeline(slab_samples=500 * n, now=0.0,
                             monitor_kwargs={"backend": "numpy"})
    for batch in sampler.run(1000):
        pipe.feed(batch)
    mon = pipe.finish()
    assert mon.n_devices == n
    np.testing.assert_array_equal(mon.state.energy_j, ref.state.energy_j)
    np.testing.assert_array_equal(mon.state.win_corr_j,
                                  ref.state.win_corr_j)


def test_sampler_uuid_stability():
    bank = SensorBank.from_catalog(["a100"] * 3, seeds=[11, 12, 13])
    a = SimulatedSampler(bank)
    b = SimulatedSampler(bank)
    np.testing.assert_array_equal(a.uuids, b.uuids)
    assert len(set(a.uuids)) == 3
    with pytest.raises(ValueError):
        SimulatedSampler(bank, uuids=["x", "x", "y"])


# ---------------------------------------------------------------------------
# calibration artifacts
# ---------------------------------------------------------------------------

def _rec(device_id="GPU-a", gain=1.05, fitted_at=None, **kw):
    base = nominal_record(device_id, profiles.get("a100"))
    return dataclasses.replace(base, gain=gain, offset_w=-2.0,
                               fitted_at=fitted_at, **kw)


def test_store_versions_are_append_only(tmp_path):
    store = ArtifactStore(str(tmp_path))
    assert store.save(_rec(gain=1.01)) == 1
    assert store.save(_rec(gain=1.02), activate=True) == 2
    assert store.save(_rec(gain=1.03)) == 3
    assert store.active_version("GPU-a") == 2
    assert store.active("GPU-a").gain == 1.02
    infos = store.versions("GPU-a")
    assert [i.version for i in infos] == [1, 2, 3]
    assert [i.active for i in infos] == [False, True, False]
    # rollback is just activation of an older version
    store.activate("GPU-a", 1)
    assert store.active("GPU-a").gain == 1.01


def test_store_activate_phantom_raises(tmp_path):
    store = ArtifactStore(str(tmp_path))
    store.save(_rec())
    with pytest.raises(StoreError):
        store.activate("GPU-a", 99)
    with pytest.raises(StoreError):
        store.load("GPU-a", 99)


def test_store_deactivate(tmp_path):
    store = ArtifactStore(str(tmp_path))
    store.save(_rec(), activate=True)
    assert store.deactivate("GPU-a") is True
    assert store.active("GPU-a") is None
    assert store.deactivate("GPU-a") is False


def test_store_age_out(tmp_path):
    store = ArtifactStore(str(tmp_path))
    store.save(_rec(fitted_at=1000.0), activate=True)
    assert store.active("GPU-a", max_age_s=500.0, now=1400.0) is not None
    assert store.active("GPU-a", max_age_s=500.0, now=1600.0) is None
    # records with no provenance stamp never age out
    store.save(_rec(device_id="GPU-b", fitted_at=None), activate=True)
    assert record_stamp(store.active("GPU-b")) == 0.0
    assert store.active("GPU-b", max_age_s=1.0, now=1e12) is not None


def test_store_gc(tmp_path):
    store = ArtifactStore(str(tmp_path))
    store.save(_rec(fitted_at=100.0))                  # v1 stale
    store.save(_rec(fitted_at=200.0), activate=True)   # v2 stale but active
    store.save(_rec(fitted_at=900.0))                  # v3 fresh
    dry = store.gc(max_age_s=300.0, now=1000.0, dry_run=True)
    assert len(dry) == 1 and "v0001" in dry[0]
    assert len(store.versions("GPU-a")) == 3           # dry run removed nothing
    removed = store.gc(max_age_s=300.0, now=1000.0)
    assert [os.path.basename(p) for p in removed] == ["v0001.json"]
    assert [i.version for i in store.versions("GPU-a")] == [2, 3]
    # keep_active=False collects the stale active artifact too
    removed = store.gc(max_age_s=300.0, now=1000.0, keep_active=False)
    assert [os.path.basename(p) for p in removed] == ["v0002.json"]


def test_store_schema_drift_both_directions(tmp_path):
    """Artifacts written by older code (missing the provenance fields)
    and newer code (unknown extra fields) both still load."""
    store = ArtifactStore(str(tmp_path))
    store.save(_rec(), activate=True)
    path = store.versions("GPU-a")[0].path
    data = json.loads(open(path).read())
    for f in ("fitted_at", "source", "note"):
        data.pop(f)                       # "older writer" artifact
    data["flux_capacitance"] = 1.21       # "newer writer" field
    with open(path, "w") as f:
        json.dump(data, f)
    rec = store.active("GPU-a")
    assert rec.fitted_at is None and rec.source == "" and rec.note == ""
    assert rec.gain == 1.05
    with pytest.raises(ValueError):
        CalibrationRecord.from_json(json.dumps({"device_id": "x"}))
    with pytest.raises(ValueError):
        CalibrationRecord.from_json("[1, 2]")


def test_calibration_record_metadata_round_trip():
    rec = _rec(fitted_at=123.0, source="bench", note="rack 7")
    back = CalibrationRecord.from_json(rec.to_json())
    assert back == rec
    assert record_stamp(back) == 123.0
    # fitted_at takes precedence over created_at for ageing
    assert record_stamp(dataclasses.replace(rec, fitted_at=None,
                                            created_at=77.0)) == 77.0


def test_resolve_corrections_fallback_ladder(tmp_path):
    store = ArtifactStore(str(tmp_path))
    store.save(_rec(device_id="GPU-0", gain=1.10), activate=True)
    default = _rec(device_id="*", gain=1.25)
    corr, labels, n_active = resolve_corrections(
        ["GPU-0", "GPU-1"], store=store, default=default)
    assert n_active == 1
    np.testing.assert_allclose(corr.gain, [1.10, 1.25])
    assert list(labels) == ["a100", "a100"]
    # no default → identity, honestly labelled
    corr, labels, n_active = resolve_corrections(["GPU-0", "GPU-1"],
                                                 store=store)
    assert n_active == 1
    np.testing.assert_allclose(corr.gain, [1.10, 1.0])
    np.testing.assert_array_equal(corr.calibrated, [True, False])
    assert list(labels) == ["a100", "uncalibrated"]


# ---------------------------------------------------------------------------
# end to end: the committed fixture through the CLI path
# ---------------------------------------------------------------------------

FIXTURE_UUIDS = [f"GPU-f1xt-{i:04d}" for i in range(5)]


def _fixture_store(root):
    store = ArtifactStore(root)
    store.save(_rec(device_id=FIXTURE_UUIDS[0], gain=1.08,
                    fitted_at=1.7e9), activate=True)
    return store


def test_fixture_replay_matches_direct_construction(tmp_path):
    """The acceptance pin: the committed daemon log replayed through the
    CLI entry point (hot-add growth, store-resolved corrections) equals
    the equivalent direct full-width construction bitwise on numpy."""
    _fixture_store(str(tmp_path / "store"))
    out_json = str(tmp_path / "out.json")
    rc = cli_main(["replay", DAEMON_FIXTURE,
                   "--store", str(tmp_path / "store"),
                   "--default-profile", "a100",
                   "--backend", "numpy", "--slab-samples", "512",
                   "--now", "1.7e9", "--json", out_json])
    assert rc == 0
    got = json.loads(open(out_json).read())
    assert got["wire"] == FIXTURE_EXPECT["daemon_sample.csv"]
    assert got["registry"]["uuids"] == FIXTURE_UUIDS
    assert got["pipeline"]["n_active_records"] == 1

    # direct: full width up front, same store resolution, same slabs
    store = ArtifactStore(str(tmp_path / "store"))
    default = nominal_record("*", profiles.get("a100"))
    corr, labels, _ = resolve_corrections(FIXTURE_UUIDS, store=store,
                                          default=default, now=1.7e9)
    mon = MonitorService(5, corrections=corr, labels=labels,
                         strict_ids=False, backend="numpy")
    reg = DeviceRegistry(FIXTURE_UUIDS)
    asm = SlabAssembler(reg, slab_samples=512)
    counters = wire.WireCounters()
    for batch in wire.iter_batches(DAEMON_FIXTURE, counters=counters):
        for dev, t, v in asm.push(batch):
            mon.ingest(dev, t, v)
    for dev, t, v in asm.flush():
        mon.ingest(dev, t, v)

    fleet = mon.fleet_energy()
    assert got["fleet_energy"]["corrected_j"] == fleet.total_j
    assert got["fleet_energy"]["raw_j"] == mon.fleet_energy(
        corrected=False).total_j
    assert got["fleet_energy"]["n_reporting"] == fleet.n_reporting
    # the applied record actually moved the answer
    assert got["fleet_energy"]["corrected_j"] != \
        got["fleet_energy"]["raw_j"]
    # ingest accounting survived the trip too (duplicate + stale rows
    # in the fixture are dropped-and-counted identically)
    assert got["pipeline"]["ingest"] == dict(mon.counters)


def test_fixture_replay_frozen_fleet_rejects(tmp_path):
    """--frozen pins the fleet: the late joiner's samples are counted,
    not absorbed."""
    out_json = str(tmp_path / "out.json")
    rc = cli_main(["replay", DAEMON_FIXTURE,
                   "--frozen", *FIXTURE_UUIDS[:4],
                   "--backend", "numpy", "--json", out_json])
    assert rc == 0
    got = json.loads(open(out_json).read())
    assert got["registry"]["n_devices"] == 4
    assert got["registry"]["n_rejected"] == 100      # 100 late-joiner rows
    assert got["pipeline"]["ingest"]["rejected"] == 100


def test_smi_fixture_replays_end_to_end(tmp_path):
    out_json = str(tmp_path / "out.json")
    rc = cli_main(["replay", SMI_FIXTURE, "--backend", "numpy",
                   "--rebase", "--json", out_json])
    assert rc == 0
    got = json.loads(open(out_json).read())
    assert got["wire"] == FIXTURE_EXPECT["smi_sample.csv"]
    assert got["registry"]["n_devices"] == 4
    assert got["fleet_energy"]["n_reporting"] == 4
    assert got["fleet_energy"]["raw_j"] > 0


def test_cli_calibrate_lifecycle(tmp_path, capsys):
    store_dir = str(tmp_path / "store")
    assert cli_main(["calibrate", "save", "--store", store_dir,
                     "--device", "GPU-a", "--profile", "a100",
                     "--gain", "1.1", "--activate"]) == 0
    assert cli_main(["calibrate", "save", "--store", store_dir,
                     "--device", "GPU-a", "--profile", "a100",
                     "--gain", "1.2"]) == 0
    capsys.readouterr()
    assert cli_main(["calibrate", "list", "--store", store_dir]) == 0
    listed = json.loads(capsys.readouterr().out)
    assert [a["version"] for a in listed["artifacts"]] == [1, 2]
    assert [a["active"] for a in listed["artifacts"]] == [True, False]
    assert cli_main(["calibrate", "activate", "--store", store_dir,
                     "--device", "GPU-a", "--version", "2"]) == 0
    assert ArtifactStore(store_dir).active("GPU-a").gain == 1.2
    # activating a phantom version fails loudly but cleanly
    assert cli_main(["calibrate", "activate", "--store", store_dir,
                     "--device", "GPU-a", "--version", "9"]) == 2
    assert cli_main(["calibrate", "deactivate", "--store", store_dir,
                     "--device", "GPU-a"]) == 0
    assert ArtifactStore(store_dir).active("GPU-a") is None


def test_cli_smoke_subprocess():
    """``python -m repro.collect`` works as an actual subprocess (the CI
    smoke invocation) and prints machine-readable JSON."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.collect", "replay", DAEMON_FIXTURE,
         "--backend", "numpy"],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr
    got = json.loads(proc.stdout)
    assert got["wire"]["samples"] == \
        FIXTURE_EXPECT["daemon_sample.csv"]["samples"]
    assert got["fleet_energy"]["n_reporting"] == 5
