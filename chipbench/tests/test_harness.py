"""Tests of the benchmark's own arithmetic. Run by hand on the CPU (not
in tier 1):

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q -p no:cacheprovider
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import check, kernel_bytes, peaks, trace_reduce, wire  # noqa: E402
from chipbench.traffic import Deployment  # noqa: E402
from chipbench.window import StoreBudget  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(ROOT, "chipbench", "configs")


def config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["tb_bench_default_1r",
                                  "tb_twophase_limits_1r"])
def test_same_seed_same_bytes(name):
    """The same --seed gives byte-identical request bodies, whatever
    order they are asked for in; another seed gives others."""
    a = Deployment(config(name), 4000000123)
    b = Deployment(config(name), 4000000123)
    c = Deployment(config(name), 4000000124)
    for stream, k in [(0, 0), (3, 7), (0, 1), (1, 0)]:
        ra = a.transfer_request(stream, k, 8189)
        assert ra.payload == b.transfer_request(stream, k, 8189).payload
        assert ra.payload != c.transfer_request(stream, k, 8189).payload
        assert len(ra.payload) == 8189 * 128
    assert [r.payload for r in a.account_requests(8189)] == \
        [r.payload for r in b.account_requests(8189)]
    assert [r.payload for r in a.funding_requests(8189)] == \
        [r.payload for r in b.funding_requests(8189)]


def test_no_id_repeats_and_both_limbs():
    d = Deployment(config("tb_twophase_limits_1r"), 7)
    ids = np.concatenate([d.transfer_request(s, k, 1024).ids
                          for s in range(4) for k in range(4)]
                         + [r.ids for r in d.funding_requests(8189)])
    assert len(np.unique(ids, axis=0)) == len(ids)
    assert (ids[:, 1] != 0).all() and (ids[:, 0] != 0).all()
    assert len(set(d.account_ids())) == d.n + 1  # the cascade account
    assert any(i >> 64 for i in d.account_ids())


def test_two_phase_resolves_the_request_before():
    d = Deployment(config("tb_twophase_limits_1r"), 11)
    pend = np.frombuffer(d.transfer_request(0, 4, 8189).payload, wire.TRANSFER)
    res = np.frombuffer(d.transfer_request(0, 5, 8189).payload, wire.TRANSFER)
    assert (pend["flags"] == 2).all()
    assert (res["pending_lo"] == pend["id_lo"]).all()
    post = res["flags"] == 4
    assert set(np.unique(res["flags"])) == {4, 8}
    assert 0.85 < post.mean() < 0.95
    assert (res["amount_lo"][post] == pend["amount_lo"][post]).all()


def test_cascade_leads_the_first_untimed_request_only():
    from chipbench.traffic import STREAM_WARM
    d = Deployment(config("tb_twophase_limits_1r"), 5)
    first = np.frombuffer(d.transfer_request(STREAM_WARM, 0, 8189).payload,
                          wire.TRANSFER)
    assert list(first["amount_lo"][:12]) == [600, 600, 300, 300, 80, 80,
                                             15, 15, 4, 4, 1, 1]
    assert (first["debit_lo"][:12] == d.id_lo[d.n]).all()
    for stream, k in [(STREAM_WARM, 2), (0, 0)]:
        other = np.frombuffer(d.transfer_request(stream, k, 8189).payload,
                              wire.TRANSFER)
        assert (other["debit_lo"] != d.id_lo[d.n]).all()
    assert Deployment(config("tb_bench_default_1r"), 5).cascade is None


def test_least_bytes_hand_worked():
    """Per event: 128 in + 16 out + 2 x (64 + 2 x 128) account and
    balance rows + 160 + 200 rows out + 4 x 24 hash slots = 1240 B."""
    assert kernel_bytes.create_transfers_bytes_per_event() == 1240
    assert kernel_bytes.create_transfers_least_bytes(1024) == 1_269_760
    assert kernel_bytes.create_transfers_least_bytes(8189) == 10_154_360


def test_unknown_device_kind_is_an_error():
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v9")


def test_store_budget_ends_a_window_before_the_store_fills():
    b = StoreBudget(capacity=10_000, request_events=1024)
    sent = 0
    while b.reserve(1024):
        b.settle(1024, 1024)
        sent += 1
    assert b.exhausted
    assert b.created == sent * 1024 <= 10_000 - 1024
    # In-flight requests count as if every event were created.
    b2 = StoreBudget(capacity=4096, request_events=1024)
    assert b2.reserve(1024) and b2.reserve(1024) and b2.reserve(1024)
    assert not b2.reserve(1024)


def test_wire_trailer():
    body = wire.encode_one(b"x" * 256, 128)
    assert len(body) == 256 + 128 and body[-2:] == b"\x01\x00"
    assert wire.decode_one(wire.encode_one(b"y" * 32, 16), 16) == b"y" * 32
    with pytest.raises(ValueError):
        wire.decode_one(b"\x00" * 32, 16)


def test_commit_order_sees_real_time_contradictions():
    class S:
        def __init__(self, ts, t_send, t_reply):
            self.ts, self.t_send, self.t_reply = ts, t_send, t_reply
    good = [S(1, 0.0, 1.0), S(2, 0.5, 2.0), S(3, 2.1, 3.0)]
    assert check.commit_order(good)[1] == 0
    bad = [S(2, 0.0, 1.0), S(1, 1.5, 2.0)]  # replied first, ordered second
    assert check.commit_order(bad)[1] == 1
    assert check.commit_order([S(1, 0, 1), S(1, 0, 1)])[1] == 1


def test_prepare_timestamp_is_exact_at_1e18():
    n = 1024
    ts = 1_790_000_000_123_456_789
    rec = np.zeros(n, dtype=wire.RESULT)
    rec["timestamp"] = ts - n + 1 + np.arange(n)
    rec["timestamp"][5] = 7  # one wrong event does not move it
    assert check.prepare_timestamp(rec) == ts


# ----------------------------------------------------- trace reduction

def test_union_and_gaps():
    start = np.array([0.0, 5e8, 4e8, 2e9])
    dur = np.array([1e8, 2e8, 2e8, 1e9])
    seconds, merged = trace_reduce.union_seconds(start, dur)
    assert seconds == pytest.approx(0.1 + 0.3 + 1.0)
    assert merged == [(0.0, 1e8), (4e8, 7e8), (2e9, 3e9)]
    spans = {"commit_checkpoint": (np.array([100.7]), np.array([1.3]))}
    gaps = trace_reduce.idle_gaps(merged, 0.0, 3.5e9, 0.0, 100.0, spans)
    # gaps: .1-.4 none, .7-2.0 checkpoint, 3.0-3.5 none
    assert gaps["commit_checkpoint"] == pytest.approx(1.3)
    assert gaps["none"] == pytest.approx(0.3 + 0.5)


RECORDED = os.path.join(HERE, "recorded")


@pytest.mark.skipif(not os.path.exists(os.path.join(RECORDED, "expected.json")),
                    reason="no recorded trace")
def test_recorded_trace_reduces_to_the_recorded_numbers():
    """A small trace recorded on the chip (TPU v5 lite), reduced by
    today's code, gives the numbers written down when it was recorded."""
    import gzip
    import shutil
    import tempfile

    with open(os.path.join(RECORDED, "expected.json")) as f:
        want = json.load(f)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "recorded.xplane.pb")
        with gzip.open(os.path.join(RECORDED, "trace.xplane.pb.gz")) as src, \
                open(path, "wb") as dst:
            shutil.copyfileobj(src, dst)
        xp = trace_reduce.reduce_xplane(path)
    assert xp["anchor_ns"] is not None
    assert len(xp["devices"]) == want["devices"]
    summary = trace_reduce.device_summary(xp, trace_reduce.KERNEL_MODULES)
    assert summary["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert len(summary["dispatch_seconds"]) == want["dispatches"]
    assert summary["module_counts"]["jit_create_transfers_fast"] == 16
    assert sum(summary["dispatch_seconds"]) == pytest.approx(
        want["dispatch_seconds_sum"], rel=1e-9)
    # A second witness: the `XLA Modules` line's own durations bound the
    # union of the ops under them, and here lie within 1% of it.
    assert sum(summary["dispatch_seconds"]) <= want["module_seconds_sum"]
    assert sum(summary["dispatch_seconds"]) == pytest.approx(
        want["module_seconds_sum"], rel=0.01)
    assert xp["anchor_ns"] == want["anchor_ns"]
    spans = trace_reduce.load_spans(os.path.join(RECORDED, "spans.json"))
    assert spans["dropped_events"] == 0
    for name, (count, total) in want["spans"].items():
        start, dur = spans["spans"][name]
        assert len(dur) == count
        assert float(dur.sum()) == pytest.approx(total, rel=1e-9)
