"""Device telemetry plane + flight recorder (round 10).

Host-side units: TEL_LAYOUT decode, the pack's lane order, the
router's telemetry absorption (synthetic blocks — the device-true
bit-exactness is testing/telemetry_smoke.py's gate leg), and the
flight recorder's ring/dump/merge contract."""

import json

import numpy as np
import pytest


class _StubTracer:
    """Records (event, value, tags) calls — enough surface for
    _absorb_telemetry and FlightRecorder.dump."""

    def __init__(self):
        self.observed = []
        self.counted = []

    def observe(self, event, value, **tags):
        self.observed.append((str(event), value, tags))

    def count(self, event, value=1, **tags):
        self.counted.append((str(event), value, tags))

    def span(self, event, **tags):
        import contextlib

        return contextlib.nullcontext()


def _mk_tel(rows):
    """rows: list of per-prepare dicts keyed by TEL_LAYOUT name ->
    [1, W, TEL_WORDS] u32 block (single shard)."""
    from tigerbeetle_tpu.parallel.partitioned import TEL_LAYOUT

    arr = np.zeros((1, len(rows), len(TEL_LAYOUT)), np.uint32)
    for w, row in enumerate(rows):
        for k, v in row.items():
            arr[0, w, TEL_LAYOUT.index(k)] = v
    return arr


# --------------------------------------------------------------- decode


def test_decode_telemetry_layout_roundtrip():
    from tigerbeetle_tpu.parallel.partitioned import (
        TEL_LAYOUT, TEL_WORDS, decode_telemetry)

    rng = np.random.default_rng(3)
    tel = rng.integers(0, 1 << 16, (2, 3, TEL_WORDS), dtype=np.uint32)
    d = decode_telemetry(tel)
    assert set(d) == set(TEL_LAYOUT)
    for i, name in enumerate(TEL_LAYOUT):
        np.testing.assert_array_equal(d[name], tel[..., i])


def test_telemetry_pack_preserves_word_order():
    from tigerbeetle_tpu.parallel.partitioned import (
        TEL_WORDS, _telemetry_pack)

    out = np.asarray(_telemetry_pack(*range(TEL_WORDS)))
    np.testing.assert_array_equal(out, np.arange(TEL_WORDS))
    assert out.dtype == np.uint32


def test_tel_causes_cover_fallback_causes():
    # Every kernel fb_cause (plus the two exchange breaches and the
    # scan's transitive poison) must be encodable — a new cause key
    # must be added to TEL_CAUSES or the decode reads code_<n>.
    from tigerbeetle_tpu.parallel.partitioned import TEL_CAUSES

    for name in ("e1_hard_flags", "e2_collision", "e3_limit",
                 "e4_overflow", "e5_void_closing", "closing",
                 "capacity", "forced", "shard_capacity",
                 "exchange_overflow"):
        assert name in TEL_CAUSES


# ------------------------------------------------------- router absorb


def _router(telemetry=True, tracer=None):
    import jax
    from jax.sharding import Mesh

    from tigerbeetle_tpu.parallel.partitioned import PartitionedRouter

    mesh = Mesh(np.array(jax.devices()[:1]), ("batch",))
    return PartitionedRouter(mesh, telemetry=telemetry, tracer=tracer)


def test_absorb_telemetry_aggregates_and_summary():
    from tigerbeetle_tpu.parallel.partitioned import TEL_CAUSES

    tracer = _StubTracer()
    rt = _router(tracer=tracer)
    tel = _mk_tel([
        dict(fix_rounds=0, poison_cause=0, xchg1_occupancy=4,
             xchg1_capacity=16, xchg2_occupancy=8, xchg2_capacity=32,
             cross_shard_transfers=3, ring_occupancy=7,
             writeback_transfers=7, events_owned=8),
        dict(fix_rounds=2, poison_cause=TEL_CAUSES.index("e3_limit") + 1,
             xchg1_occupancy=8, xchg1_capacity=16, xchg2_occupancy=16,
             xchg2_capacity=32, ring_occupancy=7, events_owned=9,
             shard_capacity_hit=1),
    ])
    s = rt._absorb_telemetry(tel)
    assert s["prepares"] == 2
    assert s["fix_rounds"] == [0, 2]
    assert s["poison_causes"] == [None, "e3_limit"]
    assert s["exchange_occupancy_pct"] == [25.0, 25.0, 50.0, 50.0]
    assert s["cross_shard_transfers"] == 3
    assert s["writeback_rows"] == 7
    assert s["events_owned"] == [17]
    assert s["ring_occupancy"] == [7]
    assert s["shard_capacity_hits"] == 1
    assert rt.device_poison_causes == {"e3_limit": 1}
    assert rt.writeback_rows == 7
    assert rt.shard_capacity_hits == 1
    assert rt._tel_rounds.count == 2
    assert rt._tel_hist.count == 4
    events = {e for e, _, _ in tracer.observed} | \
        {e for e, _, _ in tracer.counted}
    for name in ("device_fixpoint_rounds", "device_exchange_occupancy",
                 "device_ring_occupancy", "device_poison_cause",
                 "device_writeback_rows"):
        assert any(name in e for e in events), (name, events)


def test_absorb_telemetry_empty_and_2d():
    rt = _router()
    assert rt._absorb_telemetry(np.zeros((1, 0, 12), np.uint32)) is None
    s = rt._absorb_telemetry(np.zeros((1, 12), np.uint32))
    assert s["prepares"] == 1


def test_stats_telemetry_section_toggle():
    rt = _router()
    tel = rt.stats()["telemetry"]
    for key in ("device_poison_causes", "writeback_rows",
                "shard_capacity_hits", "exchange_occupancy",
                "fixpoint_rounds", "flight_windows", "flight_dumps"):
        assert key in tel
    assert _router(telemetry=False).stats()["telemetry"] is None


# ------------------------------------------------------ flight recorder


def test_flight_ring_bounded():
    from tigerbeetle_tpu.trace import FlightRecorder

    fr = FlightRecorder(capacity=4)
    for w in range(10):
        fr.record(window=w, route="partitioned_chain")
    assert fr.seq == 10
    recs = fr.records
    assert [r["window"] for r in recs] == [6, 7, 8, 9]
    assert [r["seq"] for r in recs] == [6, 7, 8, 9]


def test_flight_dump_artifact_and_histograms(tmp_path):
    from tigerbeetle_tpu.trace import FlightRecorder

    tracer = _StubTracer()
    fr = FlightRecorder(capacity=8, pid=3, tracer=tracer,
                        out_dir=str(tmp_path))
    fr.record(window=0, route="partitioned_chain",
              telemetry={"fix_rounds": [0, 2],
                         "exchange_occupancy_pct": [25.0, 50.0]},
              prepares=2)
    fr.record(window=1, route="epoch_verified", epoch_digest="abc123")
    path = fr.dump("unit_test")
    assert path and path.endswith("FLIGHT_3_unit_test_000002.json")
    assert fr.last_dump_path == path
    assert fr.dumps == 1
    with open(path) as f:
        doc = json.load(f)
    assert doc["reason"] == "unit_test"
    assert doc["pid"] == 3
    assert doc["windows_recorded"] == 2
    assert len(doc["records"]) == 2
    assert doc["records"][0]["telemetry"]["fix_rounds"] == [0, 2]
    assert doc["records"][1]["epoch_digest"] == "abc123"
    assert doc["histograms"]["fix_rounds"]["count"] == 2
    assert doc["histograms"]["exchange_occupancy_pct"]["count"] == 2
    assert any("flight_recorder_dump" in e for e, _, t in tracer.counted
               if t.get("reason") == "unit_test")


def test_flight_dump_never_raises_on_io_failure():
    from tigerbeetle_tpu.trace import FlightRecorder

    fr = FlightRecorder()
    fr.record(window=0, route="x")
    path = fr.dump("io_fail",
                   path="/nonexistent_dir_tb_tpu/flight.json")
    assert path == ""
    assert fr.dumps == 1
    assert fr.last_dump_path is None


def test_flight_merge_lossless(tmp_path):
    from tigerbeetle_tpu.trace import FlightRecorder, Histogram
    from tigerbeetle_tpu.trace.flight_recorder import merge_flight_records

    paths = []
    for pid, rounds in ((0, [1.0, 2.0]), (1, [3.0, 4.0, 5.0])):
        fr = FlightRecorder(pid=pid, out_dir=str(tmp_path))
        for w, r in enumerate(rounds):
            fr.record(window=w, route="partitioned_chain",
                      telemetry={"fix_rounds": [r],
                                 "exchange_occupancy_pct": []})
        paths.append(fr.dump("mirror_divergence"))
    merged = merge_flight_records(paths)
    assert merged["replicas"] == [0, 1]
    assert merged["reasons"] == ["mirror_divergence"]
    assert [(r["pid"], r["seq"]) for r in merged["records"]] == \
        [(0, 0), (0, 1), (1, 0), (1, 1), (1, 2)]
    h = Histogram.from_dict(merged["histograms"]["fix_rounds"])
    assert h.count == 5
    # The merged histogram equals one built from the union of samples.
    ref = Histogram()
    for v in (1.0, 2.0, 3.0, 4.0, 5.0):
        ref.record(v)
    assert h.to_dict() == ref.to_dict()
    # Merge accepts pre-loaded dicts too.
    docs = [json.load(open(p)) for p in paths]
    assert merge_flight_records(docs)["records"] == merged["records"]


def test_new_catalog_events_registered():
    from tigerbeetle_tpu.trace import Event

    for name in ("device_fixpoint_rounds", "device_poison_cause",
                 "device_exchange_occupancy", "device_ring_occupancy",
                 "device_writeback_rows", "flight_recorder_dump"):
        assert hasattr(Event, name), name


def test_serving_stats_expose_flight():
    # ServingSupervisor wires a recorder by default and surfaces its
    # counters; constructing one must not require a device ledger.
    from tigerbeetle_tpu.trace import FlightRecorder

    fr = FlightRecorder(capacity=2)
    fr.record(window=0, route="recovery", cause="dispatch_exhausted")
    assert fr.records[0]["detail"]["cause"] == "dispatch_exhausted"
