"""Aux subsystem tests: EWAH, Marzullo clock, tracer/statsd, AOF, CDC,
multiversion, clock sampling in the cluster."""

import json
import random

import pytest

from tigerbeetle_tpu import ewah
from tigerbeetle_tpu.aof import AOF, recover as aof_recover
from tigerbeetle_tpu.cdc import CDCRunner, CallbackSink, JsonlSink
from tigerbeetle_tpu.multiversion import RELEASE, ReleaseTracker
from tigerbeetle_tpu.state_machine import StateMachine
from tigerbeetle_tpu.trace import NullTracer, StatsD, Tracer
from tigerbeetle_tpu.types import Account, ChangeEventsFilter, Operation, Transfer
from tigerbeetle_tpu.vsr.clock import Clock, Interval, marzullo
from tigerbeetle_tpu.vsr.header import Command, Header, Message


class TestEwah:
    def test_roundtrip_random(self):
        rng = random.Random(7)
        for _ in range(20):
            words = []
            for _ in range(rng.randrange(0, 50)):
                roll = rng.random()
                if roll < 0.4:
                    words.extend([0] * rng.randrange(1, 20))
                elif roll < 0.6:
                    words.extend([(1 << 64) - 1] * rng.randrange(1, 20))
                else:
                    words.append(rng.getrandbits(64) | 1)
            assert ewah.decode(ewah.encode(words)) == words

    def test_compression_and_bitset(self):
        words = [0] * 1000 + [0xDEADBEEF] + [(1 << 64) - 1] * 1000
        blob = ewah.encode(words)
        assert len(blob) < len(words) * 8 // 100  # >100x on runs
        bits = [i % 7 == 0 for i in range(1000)]
        assert ewah.decode_bitset(ewah.encode_bitset(bits)) == bits


class TestMarzullo:
    def test_overlap(self):
        best = marzullo([Interval(0, 10), Interval(5, 15), Interval(8, 12),
                         Interval(100, 110)])
        assert best.lo == 8 and best.hi == 10

    def test_disjoint_majority(self):
        best = marzullo([Interval(0, 1), Interval(0, 2), Interval(10, 11)])
        assert best.lo == 0 and best.hi == 1

    def test_clock_learn(self):
        class T:
            def realtime(self):
                return 1000

            def monotonic(self):
                return 1000

        clock = Clock(0, 3, T())
        assert clock.offset() is None  # no quorum yet
        # rtt 100 -> offset 40 +- 50: interval [-10, 90] OVERLAPS our own
        # zero-offset interval, so 2 of 3 sources agree = quorum.
        clock.learn(1, 900, 990, 1000)
        iv = clock.offset()
        assert iv is not None
        # Own [0,0] against peer [-10,90]: the overlap is exactly [0,0].
        assert iv.lo <= 0 <= iv.hi
        assert clock.realtime_synchronized() is not None
        # A peer sample DISJOINT from every other source is not
        # agreement, even though two sources were sampled (reference
        # clock.zig: the smallest interval must be consistent with a
        # replica quorum).
        lonely = Clock(0, 3, T())
        lonely.learn(1, 900, 1040, 1000)  # offset 90 +- 50: [40, 140]
        assert lonely.offset() is None


class TestTracer:
    def test_spans_and_chrome_dump(self, tmp_path):
        from tigerbeetle_tpu.trace import Event

        tracer = Tracer()
        with tracer.span(Event.commit_execute, op=1, operation=2,
                         window=1):
            pass
        tracer.count(Event.commits)
        tracer.count(Event.commits, 2)
        tracer.gauge(Event.bus_pool_used, 3)
        assert tracer.counters["commits"] == 3
        assert tracer.gauges["bus_pool_used"] == 3
        path = tmp_path / "trace.json"
        tracer.dump_chrome_trace(str(path))
        doc = json.loads(path.read_text())
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert spans[0]["name"] == "commit_execute"

    def test_statsd_datagram_format(self):
        captured = []

        class FakeSock:
            def sendto(self, data, addr):
                captured.append(data.decode())

            def setblocking(self, flag):
                pass

            def close(self):
                pass

        statsd = StatsD()
        statsd.sock = FakeSock()
        statsd.count("commits", 2, replica=1)
        statsd.timing("commit", 1.5)
        assert captured[0] == "tb_tpu.commits:2|c|#replica:1"
        assert captured[1] == "tb_tpu.commit:1.5|ms"

    def test_null_tracer_is_silent(self):
        tracer = NullTracer()
        with tracer.span("anything"):
            pass
        tracer.count("x")


def _prepare(op, operation, body, ts):
    header = Header(command=Command.prepare, cluster=1, op=op,
                    operation=int(operation), timestamp=ts)
    return Message(header.finalize(body), body=body)


class TestAOF:
    def test_append_iterate_recover(self, tmp_path):
        from tigerbeetle_tpu import multi_batch

        path = str(tmp_path / "a.aof")
        aof = AOF(path)
        sm = StateMachine()
        ts = 10**13
        body1 = multi_batch.encode(
            [b"".join(Account(id=i, ledger=1, code=1).pack() for i in (1, 2))],
            128)
        sm.commit(Operation.create_accounts, body1, ts)
        aof.append(_prepare(1, Operation.create_accounts, body1, ts))
        body2 = multi_batch.encode(
            [Transfer(id=9, debit_account_id=1, credit_account_id=2,
                      amount=5, ledger=1, code=1).pack()], 128)
        sm.commit(Operation.create_transfers, body2, ts + 100)
        aof.append(_prepare(2, Operation.create_transfers, body2, ts + 100))
        aof.close()

        msgs = list(AOF.iterate(path))
        assert [m.header.op for m in msgs] == [1, 2]

        recovered = StateMachine()
        applied = aof_recover(path, recovered)
        assert applied == 2
        assert recovered.state.accounts == sm.state.accounts
        assert recovered.state.transfers == sm.state.transfers

    def test_torn_tail_stops_iteration(self, tmp_path):
        path = str(tmp_path / "torn.aof")
        aof = AOF(path)
        body = b""
        aof.append(_prepare(1, Operation.pulse, b"", 10**13))
        aof.close()
        with open(path, "ab") as f:
            f.write(b"TBTPUAOF\xff\xff")  # torn frame
        assert len(list(AOF.iterate(path))) == 1


class TestCDC:
    def test_runner_watermark(self, tmp_path):
        from tigerbeetle_tpu import multi_batch

        sm = StateMachine()
        ts = 10**13
        sm.create_accounts([Account(id=1, ledger=1, code=1),
                            Account(id=2, ledger=1, code=1)], ts)
        sm.create_transfers(
            [Transfer(id=i, debit_account_id=1, credit_account_id=2,
                      amount=i, ledger=1, code=1) for i in (1, 2, 3)],
            ts + 100)
        seen = []
        runner = CDCRunner(sm, CallbackSink(seen.append), batch_limit=2)
        assert runner.run_until_idle() == 3
        assert [e.transfer_id for e in seen] == [1, 2, 3]
        # New events after the watermark only.
        sm.create_transfers(
            [Transfer(id=4, debit_account_id=2, credit_account_id=1,
                      amount=9, ledger=1, code=1)], ts + 200)
        assert runner.poll() == 1
        assert seen[-1].transfer_id == 4

        jsonl = tmp_path / "events.jsonl"
        sink = JsonlSink(str(jsonl))
        runner2 = CDCRunner(sm, sink)
        assert runner2.run_until_idle() == 4
        sink.close()
        lines = [json.loads(l) for l in jsonl.read_text().splitlines()]
        assert len(lines) == 4 and lines[0]["transfer_id"] == 1
        assert lines[0]["type"] == "single_phase"


class TestAOFContiguity:
    def test_reopen_dedupes_and_blocks_gaps(self, tmp_path):
        path = str(tmp_path / "c.aof")
        aof = AOF(path)
        aof.append(_prepare(1, Operation.pulse, b"", 10**13))
        aof.append(_prepare(2, Operation.pulse, b"", 10**13 + 1))
        aof.close()
        # Reopen: last_op recovered; duplicate appends are no-ops.
        aof2 = AOF(path)
        assert aof2.last_op == 2
        aof2.append(_prepare(2, Operation.pulse, b"", 10**13 + 1))
        aof2.append(_prepare(3, Operation.pulse, b"", 10**13 + 2))
        with pytest.raises(RuntimeError):
            aof2.append(_prepare(7, Operation.pulse, b"", 10**13 + 9))
        aof2.close()
        assert [m.header.op for m in AOF.iterate(path)] == [1, 2, 3]

    def test_recover_rejects_gapped_aof(self, tmp_path):
        path = str(tmp_path / "gap.aof")
        aof = AOF(path)
        aof.append(_prepare(1, Operation.pulse, b"", 10**13))
        aof.last_op = 4  # simulate a gap on disk
        aof.append(_prepare(5, Operation.pulse, b"", 10**13 + 9))
        aof.close()
        with pytest.raises(ValueError):
            aof_recover(path, StateMachine())


class TestCDCCrashResume:
    def _sm(self, n=7):
        sm = StateMachine()
        ts = 10**13
        sm.create_accounts([Account(id=1, ledger=1, code=1),
                            Account(id=2, ledger=1, code=1)], ts)
        for i in range(1, n + 1):
            sm.create_transfers(
                [Transfer(id=i, debit_account_id=1, credit_account_id=2,
                          amount=i, ledger=1, code=1)], ts + 100 * i)
        return sm

    def test_file_progress_resumes_after_crash(self, tmp_path):
        """Kill the runner mid-stream; a fresh runner recovers the
        durable watermark and resumes without losing events (reference:
        cdc/runner.zig progress-queue recovery)."""
        from tigerbeetle_tpu.cdc import FileProgress

        sm = self._sm(7)
        progress = FileProgress(str(tmp_path / "cdc.progress"))
        seen_a = []
        runner_a = CDCRunner(sm, CallbackSink(seen_a.append),
                             batch_limit=2, progress=progress,
                             pipeline=False)
        assert runner_a.recover() == 0
        runner_a.poll()  # one batch: events 1,2 — then "crash"
        assert [e.transfer_id for e in seen_a] == [1, 2]
        del runner_a

        seen_b = []
        runner_b = CDCRunner(sm, CallbackSink(seen_b.append),
                             batch_limit=2,
                             progress=FileProgress(
                                 str(tmp_path / "cdc.progress")))
        runner_b.recover()
        assert runner_b.run_until_idle() == 5
        runner_b.close()
        assert [e.transfer_id for e in seen_b] == [3, 4, 5, 6, 7]

    def test_crash_after_flush_before_store_duplicates_not_skips(
            self, tmp_path):
        """A crash BETWEEN sink flush and watermark store must replay the
        batch (at-least-once: duplicates allowed, gaps never)."""
        from tigerbeetle_tpu.cdc import FileProgress

        sm = self._sm(4)

        class StoreCrash(FileProgress):
            def __init__(self, path):
                super().__init__(path)
                self.crash = True

            def store(self, timestamp):
                if self.crash:
                    raise RuntimeError("crashed before progress store")
                super().store(timestamp)

        progress = StoreCrash(str(tmp_path / "cdc.progress"))
        seen = []
        runner = CDCRunner(sm, CallbackSink(seen.append), batch_limit=2,
                           progress=progress, pipeline=False)
        with pytest.raises(RuntimeError):
            runner.poll()
        assert [e.transfer_id for e in seen] == [1, 2]  # published...
        # ...but the durable watermark never moved:
        runner2 = CDCRunner(sm, CallbackSink(seen.append), batch_limit=2,
                            progress=FileProgress(
                                str(tmp_path / "cdc.progress")))
        runner2.recover()
        assert runner2.run_until_idle() == 4
        runner2.close()
        # 1,2 delivered twice (at-least-once), 3,4 once; no gaps.
        assert [e.transfer_id for e in seen] == [1, 2, 1, 2, 3, 4]

    def test_pipelined_matches_serial(self, tmp_path):
        """The dual-buffer overlap must deliver the identical ordered
        stream the serial pump does."""
        sm = self._sm(9)
        serial, piped = [], []
        r1 = CDCRunner(sm, CallbackSink(serial.append), batch_limit=2,
                       pipeline=False)
        assert r1.run_until_idle() == 9
        r2 = CDCRunner(sm, CallbackSink(piped.append), batch_limit=2,
                       pipeline=True)
        assert r2.run_until_idle() == 9
        r2.close()
        assert [e.transfer_id for e in piped] == \
            [e.transfer_id for e in serial]
        assert r2.timestamp_processed == r1.timestamp_processed

    def test_pipelined_flush_failure_holds_watermark(self):
        sm = self._sm(4)

        class FlakySink:
            def __init__(self):
                self.fail = True
                self.events = []

            def publish(self, event):
                self.events.append(event)

            def flush(self):
                if self.fail:
                    self.fail = False
                    raise OSError("broker down")

        sink = FlakySink()
        runner = CDCRunner(sm, sink, batch_limit=2, pipeline=True)
        with pytest.raises(OSError):
            runner.run_until_idle()
        assert runner.timestamp_processed == 0
        assert runner.run_until_idle() == 4  # full replay from watermark
        runner.close()


class TestCDCFlushFailure:
    def test_watermark_holds_until_flush_succeeds(self):
        sm = StateMachine()
        ts = 10**13
        sm.create_accounts([Account(id=1, ledger=1, code=1),
                            Account(id=2, ledger=1, code=1)], ts)
        sm.create_transfers(
            [Transfer(id=1, debit_account_id=1, credit_account_id=2,
                      amount=1, ledger=1, code=1)], ts + 100)

        class FlakySink:
            def __init__(self):
                self.fail = True
                self.events = []

            def publish(self, event):
                self.events.append(event)

            def flush(self):
                if self.fail:
                    self.fail = False
                    raise OSError("disk full")

        sink = FlakySink()
        runner = CDCRunner(sm, sink)
        with pytest.raises(OSError):
            runner.poll()
        assert runner.timestamp_processed == 0  # watermark held
        assert runner.poll() == 1  # re-read and delivered
        assert runner.timestamp_processed > 0


def test_release_gating_enforced_at_open():
    from tigerbeetle_tpu.testing.cluster import Cluster
    from tigerbeetle_tpu.vsr.superblock import SuperBlock

    cluster = Cluster(seed=6, replica_count=1)
    cluster.run(50)
    storage = cluster.storages[0]
    sb = SuperBlock.load(storage)
    sb.release = RELEASE + 1  # written by a future release
    sb.store(storage)
    cluster.crash(0)
    with pytest.raises(RuntimeError, match="release"):
        cluster.restart(0)


def test_clock_samples_expire():
    class T:
        def __init__(self):
            self.now = 10**12

        def realtime(self):
            return self.now

        def monotonic(self):
            return self.now

    t = T()
    clock = Clock(0, 3, t)
    # offset 0 +- 50 (agrees with our own zero interval).
    clock.learn(1, t.now - 100, t.now - 50, t.now)
    assert clock.offset() is not None
    t.now += clock.window_ns + 1
    assert clock.offset() is None  # stale sample no longer counts


class TestMultiversion:
    def test_release_gating(self):
        tracker = ReleaseTracker()
        tracker.observe(1, RELEASE)
        tracker.observe(2, RELEASE + 1)
        assert tracker.cluster_min == RELEASE
        assert tracker.compatible(RELEASE)
        assert not tracker.compatible(RELEASE + 1)


def test_cluster_clock_and_release_sampling():
    """Pings flow in the simulator: clocks learn offsets, releases spread."""
    from tigerbeetle_tpu.testing.cluster import Cluster

    cluster = Cluster(seed=5, replica_count=3)
    cluster.run(200)
    for r in cluster.replicas:
        assert r.releases.peers, "release observations missing"
        assert r.clock.samples, "clock samples missing"
        assert r.clock.realtime_synchronized() is not None


class TestJaxhound:
    def test_report_accounts_kernel(self):
        import re

        from tigerbeetle_tpu.jaxhound import report

        lines = report("create_accounts_fast")
        header = next(line for line in lines if "HLO instructions" in line)
        count = int(re.search(r"(\d+) HLO instructions", header).group(1))
        assert count > 50  # the kernel is large; 0 means the parser broke
        assert any("stablehlo." in line for line in lines)  # histogram rows


class TestMultiversionCli:
    def test_compatible_data_file(self, tmp_path):
        from tigerbeetle_tpu.main import main

        path = str(tmp_path / "r0.tb")
        assert main(["format", "--cluster=1", "--replica=0",
                     "--replica-count=1", "--small", path]) == 0
        assert main(["multiversion", "--small", path]) == 0


class TestClusterConfigEnforcement:
    def test_mismatched_fingerprint_peer_is_dropped(self):
        """reference: ConfigCluster must match across the cluster
        (src/config.zig:153-163); pings carry a fingerprint and a
        mismatched peer's traffic is refused."""
        from tests.test_nack import _FakeTime, _CaptureBus, _mk_replica
        from tigerbeetle_tpu.vsr.header import Command, Header, Message

        r, bus, _ = _mk_replica(0, replica_count=3)
        fp = r._config_fp
        good = Header(command=Command.ping, cluster=0xABCD01, replica=1,
                      view=0, timestamp=123, context=fp)
        r.on_message(Message(good.finalize()))
        assert bus.of(Command.pong), "matching peer must get a pong"
        bus.sent.clear()
        bad = Header(command=Command.ping, cluster=0xABCD01, replica=2,
                     view=0, timestamp=124, context=fp ^ 0x1)
        r.on_message(Message(bad.finalize()))
        assert not bus.of(Command.pong), "mismatched peer must be dropped"
        # Fingerprint-less pings (legacy / handshake hello) stay accepted
        # for unflagged peers...
        legacy = Header(command=Command.ping, cluster=0xABCD01, replica=1,
                        view=0, timestamp=125)
        r.on_message(Message(legacy.finalize()))
        assert bus.of(Command.pong)
        # ...but must NOT un-gate a flagged peer (reconnect handshake
        # would otherwise reopen the gate every connection churn).
        bus.sent.clear()
        hello = Header(command=Command.ping, cluster=0xABCD01, replica=2,
                       view=0, timestamp=126)
        r.on_message(Message(hello.finalize()))
        assert not bus.of(Command.pong)
        assert 2 in r._config_mismatch

    def test_mismatched_peer_consensus_traffic_gated(self):
        """The mismatch flag gates ALL replica traffic (prepare etc.),
        not just pongs — and a matching ping clears it."""
        from tests.test_nack import _mk_replica, _prepare_msg
        from tigerbeetle_tpu.vsr.header import Command, Header, Message

        r, bus, _ = _mk_replica(1, replica_count=3)
        r.status = "normal"
        fp = r._config_fp
        bad_ping = Header(command=Command.ping, cluster=0xABCD01, replica=0,
                          view=0, timestamp=1, context=fp ^ 0x2)
        r.on_message(Message(bad_ping.finalize()))
        assert 0 in r._config_mismatch
        # A prepare from the flagged primary is dropped.
        m = _prepare_msg(1)
        r.on_message(m)
        assert r.op == 0 and r.journal.read_prepare(1) is None
        # The peer upgrades (matching ping): flag clears, traffic flows.
        good_ping = Header(command=Command.ping, cluster=0xABCD01, replica=0,
                           view=0, timestamp=2, context=fp)
        r.on_message(Message(good_ping.finalize()))
        assert 0 not in r._config_mismatch
        r.on_message(m)
        assert r.op == 1 and r.journal.read_prepare(1) is not None


class TestCommitMetrics:
    def test_per_op_timing_from_commit_execute_spans(self):
        """reference: per-op timings recorded at commit
        (src/state_machine.zig:729-780, :2637-2667). Here the replica's
        `commit_execute` span is the timer: it carries `operation`, so
        count and duration per operation read off the recorded spans."""
        from tigerbeetle_tpu import multi_batch
        from tigerbeetle_tpu.state_machine import StateMachine
        from tigerbeetle_tpu.testing.cluster import Cluster
        from tigerbeetle_tpu.trace import Tracer
        from tigerbeetle_tpu.types import Account, Operation

        tracer = Tracer()
        cluster = Cluster(
            seed=11, replica_count=1, tracer_factory=lambda i: tracer,
            state_machine_factory=lambda: StateMachine(engine="oracle"))
        client = cluster.client(5)

        def drive(op, body):
            client.request(op, body)
            assert cluster.run(4000, until=lambda: client.idle), \
                cluster.debug_status()

        drive(Operation.create_accounts, multi_batch.encode(
            [b"".join(Account(id=i, ledger=1, code=1).pack()
                      for i in (1, 2))], 128))
        lookup = multi_batch.encode([(1).to_bytes(16, "little")], 16)
        drive(Operation.lookup_accounts, lookup)
        drive(Operation.lookup_accounts, lookup)
        m: dict = {}
        for e in tracer.events:
            if e["name"] == "commit_execute":
                name = Operation(e["args"]["operation"]).name
                row = m.setdefault(name, {"count": 0, "total_us": 0.0,
                                          "max_us": 0.0})
                row["count"] += 1
                row["total_us"] += e["dur"]
                row["max_us"] = max(row["max_us"], e["dur"])
        assert m["create_accounts"]["count"] == 1
        assert m["lookup_accounts"]["count"] == 2
        assert m["lookup_accounts"]["total_us"] >= \
            m["lookup_accounts"]["max_us"] > 0
        assert not hasattr(cluster.replicas[0].state_machine, "metrics")


