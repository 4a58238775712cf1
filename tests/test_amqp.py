"""AMQP 0.9.1 protocol + CDC AMQP sink against an in-process mini broker.

reference: src/amqp.zig + src/amqp/{protocol,spec}.zig (own protocol
implementation, no client library) and src/cdc/runner.zig (publish with
confirms). The broker here implements the server side of the same subset,
so both directions of the codec are exercised honestly over a real socket.
"""

import os
import json
import socket
import struct
import threading

from tigerbeetle_tpu import amqp
from tigerbeetle_tpu.amqp import (
    BASIC_ACK,
    BASIC_GET,
    BASIC_GET_EMPTY,
    BASIC_GET_OK,
    BASIC_PUBLISH,
    CHANNEL_OPEN,
    CHANNEL_OPEN_OK,
    CONFIRM_SELECT,
    CONFIRM_SELECT_OK,
    CONNECTION_CLOSE,
    CONNECTION_CLOSE_OK,
    CONNECTION_OPEN,
    CONNECTION_OPEN_OK,
    CONNECTION_START,
    CONNECTION_START_OK,
    CONNECTION_TUNE,
    CONNECTION_TUNE_OK,
    EXCHANGE_DECLARE,
    EXCHANGE_DECLARE_OK,
    FRAME_BODY,
    FRAME_HEADER,
    PROTOCOL_HEADER,
    QUEUE_BIND,
    QUEUE_BIND_OK,
    QUEUE_DECLARE,
    QUEUE_DECLARE_OK,
    RESOURCE_LOCKED,
    Frame,
    content_frames,
    field_table,
    longstr,
    method_frame,
    shortstr,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class MiniBroker:
    """Multi-connection AMQP 0.9.1 server: handshake, declarations
    (incl. exclusive queues), publishes (stored + routed to queues via
    the default exchange), confirms, basic.get/ack, purge — the server
    half of everything the CDC runner speaks."""

    def __init__(self):
        self.listener = socket.socket()
        self.listener.bind(("127.0.0.1", 0))
        self.listener.listen(8)
        self.port = self.listener.getsockname()[1]
        self.lock = threading.Lock()
        self.messages = []  # every publish: (exchange, routing_key, body)
        # queue name -> list of (delivery_tag, body); unacked get-issued
        # messages by tag.
        self.queues: dict[str, list] = {}
        self.unacked: dict[int, tuple[str, bytes]] = {}
        self.exclusive: dict[str, int] = {}  # queue -> owner conn id
        self.declared_exchanges = []
        self.declared_queues = []
        self.bindings = []
        self.auth = None
        self.next_tag = 0
        self._conn_seq = 0
        self.thread = threading.Thread(target=self._accept, daemon=True)
        self.thread.start()

    def _accept(self):
        while True:
            try:
                sock, _ = self.listener.accept()
            except OSError:
                return
            self._conn_seq += 1
            threading.Thread(target=self._serve,
                             args=(sock, self._conn_seq),
                             daemon=True).start()

    def _route(self, exchange, routing_key, body):
        with self.lock:
            self.messages.append((exchange, routing_key, body))
            if exchange == "" and routing_key in self.queues:
                self.queues[routing_key].append(body)

    def _serve(self, sock, conn_id):
        rx = bytearray()

        def recv_frame():
            while True:
                got = Frame.parse(rx)
                if got is not None:
                    return got
                try:
                    chunk = sock.recv(64 * 1024)
                except OSError:
                    return None
                if not chunk:
                    return None
                rx.extend(chunk)

        try:
            header = b""
            while len(header) < 8:
                got = sock.recv(8 - len(header))
                if not got:
                    return
                header += got
            assert header == PROTOCOL_HEADER, header
            sock.sendall(method_frame(
                0, CONNECTION_START,
                struct.pack(">BB", 0, 9) + field_table({"product": "mini"})
                + longstr(b"PLAIN") + longstr(b"en_US")))

            delivery_tag = 0
            pending = None
            body_size = 0
            body = b""
            while True:
                got = recv_frame()
                if got is None:
                    break
                method = got.method
                if method == CONNECTION_START_OK:
                    args = got.args()
                    args.table()
                    mechanism = args.shortstr()
                    response = args.longstr()
                    self.auth = (mechanism, response)
                    sock.sendall(method_frame(
                        0, CONNECTION_TUNE,
                        struct.pack(">HIH", 0, 128 * 1024, 0)))
                elif method == CONNECTION_TUNE_OK:
                    pass
                elif method == CONNECTION_OPEN:
                    sock.sendall(method_frame(0, CONNECTION_OPEN_OK,
                                              b"\x00"))
                elif method == CHANNEL_OPEN:
                    sock.sendall(method_frame(
                        got.channel, CHANNEL_OPEN_OK, longstr(b"")))
                elif method == EXCHANGE_DECLARE:
                    args = got.args()
                    args.u16()
                    self.declared_exchanges.append(
                        (args.shortstr(), args.shortstr()))
                    sock.sendall(method_frame(got.channel,
                                              EXCHANGE_DECLARE_OK))
                elif method == QUEUE_DECLARE:
                    args = got.args()
                    args.u16()
                    name = args.shortstr()
                    flags = args.u8()
                    exclusive = bool(flags & 0b100)
                    with self.lock:
                        owner = self.exclusive.get(name)
                        if owner is not None and owner != conn_id:
                            sock.sendall(method_frame(
                                0, CONNECTION_CLOSE,
                                struct.pack(">H", RESOURCE_LOCKED)
                                + shortstr("RESOURCE_LOCKED")
                                + struct.pack(">HH", *QUEUE_DECLARE)))
                            break
                        if exclusive:
                            self.exclusive[name] = conn_id
                        self.declared_queues.append(name)
                        self.queues.setdefault(name, [])
                    sock.sendall(method_frame(
                        got.channel, QUEUE_DECLARE_OK,
                        shortstr(name) + struct.pack(">II", 0, 0)))
                elif method == QUEUE_BIND:
                    args = got.args()
                    args.u16()
                    self.bindings.append(
                        (args.shortstr(), args.shortstr(),
                         args.shortstr()))
                    sock.sendall(method_frame(got.channel, QUEUE_BIND_OK))
                elif method == CONFIRM_SELECT:
                    sock.sendall(method_frame(got.channel,
                                              CONFIRM_SELECT_OK))
                elif method == BASIC_GET:
                    args = got.args()
                    args.u16()
                    name = args.shortstr()
                    with self.lock:
                        store = self.queues.get(name, [])
                        if store:
                            msg = store.pop(0)
                            self.next_tag += 1
                            tag = self.next_tag
                            self.unacked[tag] = (name, msg, conn_id)
                        else:
                            msg = None
                    if msg is None:
                        sock.sendall(method_frame(
                            got.channel, BASIC_GET_EMPTY, shortstr("")))
                    else:
                        sock.sendall(
                            method_frame(
                                got.channel, BASIC_GET_OK,
                                struct.pack(">QB", tag, 0)
                                + shortstr("") + shortstr(name)
                                + struct.pack(">I", 0))
                            + content_frames(got.channel, msg,
                                             128 * 1024))
                elif method == BASIC_ACK:
                    args = got.args()
                    tag = args.u64()
                    with self.lock:
                        self.unacked.pop(tag, None)
                elif method == BASIC_PUBLISH:
                    args = got.args()
                    args.u16()
                    pending = (args.shortstr(), args.shortstr())
                elif method == CONNECTION_CLOSE:
                    sock.sendall(method_frame(0, CONNECTION_CLOSE_OK))
                    break
                elif got.type == FRAME_HEADER and pending is not None:
                    _, _, body_size, _ = struct.unpack_from(
                        ">HHQH", got.payload)
                    body = b""
                    if body_size == 0:
                        delivery_tag += 1
                        self._route(*pending, b"")
                        sock.sendall(method_frame(
                            got.channel, BASIC_ACK,
                            struct.pack(">QB", delivery_tag, 0)))
                        pending = None
                elif got.type == FRAME_BODY and pending is not None:
                    body += got.payload
                    if len(body) >= body_size:
                        delivery_tag += 1
                        self._route(*pending, body)
                        sock.sendall(method_frame(
                            got.channel, BASIC_ACK,
                            struct.pack(">QB", delivery_tag, 0)))
                        pending = None
        finally:
            # AMQP connection-death semantics: exclusive queues die with
            # their connection, and this connection's unacked (checked
            # out) messages return to the FRONT of their queues.
            with self.lock:
                for name in [n for n, c in self.exclusive.items()
                             if c == conn_id]:
                    del self.exclusive[name]
                for tag in [t for t, (_, _, c) in self.unacked.items()
                            if c == conn_id]:
                    name, msg, _ = self.unacked.pop(tag)
                    self.queues.setdefault(name, []).insert(0, msg)
            sock.close()

    def close(self):
        self.listener.close()


class TestAmqpClient:
    def test_handshake_declare_publish_confirm(self):
        broker = MiniBroker()
        client = amqp.AmqpClient("127.0.0.1", broker.port,
                                 user="svc", password="secret")
        try:
            client.exchange_declare("tb.cdc", "topic")
            client.queue_declare("audit")
            client.queue_bind("audit", "tb.cdc", "cdc.#")
            client.confirm_select()
            client.publish("tb.cdc", "cdc.single_phase", b"hello")
            client.publish("tb.cdc", "cdc.two_phase_pending", b"x" * 300_000)
            client.wait_confirms()
        finally:
            client.close()
            broker.close()
        assert broker.auth == ("PLAIN", b"\x00svc\x00secret")
        assert ("tb.cdc", "topic") in broker.declared_exchanges
        assert "audit" in broker.declared_queues
        assert ("audit", "tb.cdc", "cdc.#") in broker.bindings
        assert broker.messages[0] == ("tb.cdc", "cdc.single_phase", b"hello")
        ex, rk, body = broker.messages[1]
        assert rk == "cdc.two_phase_pending" and body == b"x" * 300_000

    def test_wait_confirms_out_of_order_and_multiple(self):
        """Acks may arrive out of order and with `multiple` set; a nack is
        a delivery failure (AMQP 0.9.1 publisher-confirms semantics)."""
        client = amqp.AmqpClient.__new__(amqp.AmqpClient)
        client.confirm_mode = True
        client.outstanding = {1, 2, 3}
        acks = [
            amqp.Frame(amqp.FRAME_METHOD, 1,
                       struct.pack(">HHQB", 60, 80, 3, 0)),  # ack tag 3
            amqp.Frame(amqp.FRAME_METHOD, 1,
                       struct.pack(">HHQB", 60, 80, 2, 1)),  # ack <=2
        ]
        client._recv_frame = lambda: acks.pop(0)
        client.wait_confirms()
        assert client.outstanding == set()

        client.outstanding = {1}
        nack = amqp.Frame(amqp.FRAME_METHOD, 1,
                          struct.pack(">HHQB", 60, 120, 1, 0))
        client._recv_frame = lambda: nack
        try:
            client.wait_confirms()
            assert False, "nack must raise"
        except amqp.ProtocolError as e:
            assert "nacked" in str(e)

    def test_frame_roundtrip_and_parse_publishes(self):
        raw = (method_frame(1, BASIC_PUBLISH,
                            struct.pack(">H", 0) + shortstr("e")
                            + shortstr("k") + b"\x00")
               + amqp.content_frames(1, b"payload"))
        got = list(amqp.parse_publishes(raw))
        assert got == [("e", "k", b"payload")]


class TestAmqpCommand:
    def test_cdc_pump_from_live_replica(self, tmp_path):
        """format -> start -> commit transfers -> `amqp --once` pumps the
        change events into the broker (reference: `tigerbeetle amqp`)."""
        import os
        import signal
        import subprocess
        import sys
        import time

        from tigerbeetle_tpu.main import _parse_addresses, main
        from tigerbeetle_tpu.types import Account, Transfer
        from tigerbeetle_tpu.vsr.client import Client

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        address = f"127.0.0.1:{port}"
        path = tmp_path / "r0.tigerbeetle"
        subprocess.run(
            [sys.executable, "-m", "tigerbeetle_tpu", "format", "--cluster=4",
             "--replica=0", "--replica-count=1", "--small", str(path)],
            check=True, cwd=REPO, timeout=60,
            stdout=subprocess.DEVNULL)
        proc = subprocess.Popen(
            [sys.executable, "-m", "tigerbeetle_tpu", "start",
             f"--addresses={address}", "--replica=0", "--cluster=4",
             "--engine=oracle", "--small", str(path)],
            cwd=REPO, env=dict(os.environ),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        broker = MiniBroker()
        try:
            client = Client(cluster=4, client_id=5,
                            replica_addresses=_parse_addresses(address))
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                try:
                    client.create_accounts([Account(id=1, ledger=1, code=1),
                                            Account(id=2, ledger=1, code=1)])
                    break
                except TimeoutError:
                    continue
            client.create_transfers([
                Transfer(id=10, debit_account_id=1, credit_account_id=2,
                         amount=9, ledger=1, code=1)])
            client.close()
            rc = main(["amqp", f"--addresses={address}", "--cluster=4",
                       f"--amqp=127.0.0.1:{broker.port}", "--once"])
            assert rc == 0
        finally:
            broker.close()
            proc.send_signal(signal.SIGINT)
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
        cdc = [(ex, rk, b) for ex, rk, b in broker.messages
               if rk.startswith("cdc.")]
        assert len(cdc) == 1
        record = json.loads(cdc[0][2])
        assert record["transfer_id"] == 10 and record["transfer_amount"] == 9
        # The watermark went to the broker-resident progress queue.
        progress = [b for ex, rk, b in broker.messages
                    if rk == "tb.internal.progress.4"]
        assert len(progress) == 1
        assert json.loads(progress[0])["timestamp_processed"] > 0


class TestCdcAmqpSink:
    def test_runner_publishes_change_events_with_confirms(self):
        from tigerbeetle_tpu.cdc import AmqpSink, CDCRunner
        from tigerbeetle_tpu.state_machine import StateMachine
        from tigerbeetle_tpu.types import Account, Transfer, TransferFlags

        sm = StateMachine(engine="oracle")
        ts = 10**9
        sm.create_accounts(
            [Account(id=i, ledger=1, code=1) for i in (1, 2)], ts)
        ts += 1000
        sm.create_transfers(
            [Transfer(id=10, debit_account_id=1, credit_account_id=2,
                      amount=5, ledger=1, code=1),
             Transfer(id=11, debit_account_id=1, credit_account_id=2,
                      amount=3, ledger=1, code=1,
                      flags=int(TransferFlags.pending))], ts)

        broker = MiniBroker()
        sink = AmqpSink("127.0.0.1", broker.port)
        try:
            runner = CDCRunner(sm, sink)
            published = runner.run_until_idle()
        finally:
            sink.close()
            broker.close()
        assert published == 2
        keys = [rk for _, rk, _ in broker.messages]
        assert keys == ["cdc.single_phase", "cdc.two_phase_pending"]
        record = json.loads(broker.messages[0][2])
        assert record["transfer_amount"] == 5
        assert record["type"] == "single_phase"

    def _sm(self, n):
        from tigerbeetle_tpu.state_machine import StateMachine
        from tigerbeetle_tpu.types import Account, Transfer

        sm = StateMachine(engine="oracle")
        ts = 10**9
        sm.create_accounts(
            [Account(id=i, ledger=1, code=1) for i in (1, 2)], ts)
        for i in range(1, n + 1):
            sm.create_transfers(
                [Transfer(id=i, debit_account_id=1, credit_account_id=2,
                          amount=i, ledger=1, code=1)], ts + 1000 * i)
        return sm

    def test_broker_progress_queue_survives_runner_crash(self):
        """The watermark lives IN the broker (the reference's
        progress-tracker queue, cdc/runner.zig:34): runner A publishes
        two batches and dies; runner B recovers the watermark with
        basic.get and resumes exactly after the confirmed stream."""
        from tigerbeetle_tpu.cdc import AmqpProgress, AmqpSink, CDCRunner

        broker = MiniBroker()
        try:
            sm = self._sm(6)
            sink_a = AmqpSink("127.0.0.1", broker.port, cluster=7)
            prog_a = AmqpProgress("127.0.0.1", broker.port, cluster=7)
            runner_a = CDCRunner(sm, sink_a, batch_limit=2,
                                 progress=prog_a, pipeline=False)
            assert runner_a.recover() == 0
            runner_a.poll()
            runner_a.poll()  # events 1-4 confirmed, then "crash"
            sink_a.close()
            prog_a.close()

            sink_b = AmqpSink("127.0.0.1", broker.port, cluster=7)
            prog_b = AmqpProgress("127.0.0.1", broker.port, cluster=7)
            runner_b = CDCRunner(sm, sink_b, batch_limit=2,
                                 progress=prog_b, pipeline=False)
            watermark = runner_b.recover()
            assert watermark > 0
            assert runner_b.run_until_idle() == 2  # only 5, 6 remain
            sink_b.close()
            prog_b.close()
        finally:
            broker.close()
        cdc_bodies = [json.loads(b) for ex, rk, b in broker.messages
                      if rk.startswith("cdc.")]
        assert [r["transfer_id"] for r in cdc_bodies] == [1, 2, 3, 4, 5, 6]
        # Progress queue holds exactly one (newest) watermark message —
        # the runner's checkout returns to the queue as its connection
        # dies (broker-side requeue runs moments after close returns).
        import time as _t
        for _ in range(200):
            if len(broker.queues.get("tb.internal.progress.7", [])) == 1:
                break
            _t.sleep(0.01)
        assert len(broker.queues["tb.internal.progress.7"]) == 1

    def test_locker_queue_excludes_second_runner(self):
        """Two CDC runners for one cluster: the second's exclusive
        locker declare must fail (cdc/runner.zig:35 locker queue)."""
        import pytest

        from tigerbeetle_tpu.amqp import ProtocolError
        from tigerbeetle_tpu.cdc import AmqpSink

        broker = MiniBroker()
        try:
            first = AmqpSink("127.0.0.1", broker.port, cluster=9,
                             lock=True)
            with pytest.raises(ProtocolError, match="405"):
                AmqpSink("127.0.0.1", broker.port, cluster=9, lock=True)
            first.close()
            # Lock released with the connection: a successor acquires it.
            third = AmqpSink("127.0.0.1", broker.port, cluster=9,
                             lock=True)
            third.close()
        finally:
            broker.close()

    def test_pipelined_amqp_runner_overlaps_and_delivers_in_order(self):
        from tigerbeetle_tpu.cdc import AmqpProgress, AmqpSink, CDCRunner

        broker = MiniBroker()
        try:
            sm = self._sm(9)
            sink = AmqpSink("127.0.0.1", broker.port, cluster=3)
            prog = AmqpProgress("127.0.0.1", broker.port, cluster=3)
            runner = CDCRunner(sm, sink, batch_limit=2, progress=prog,
                               pipeline=True)
            runner.recover()
            assert runner.run_until_idle() == 9
            runner.close()
            sink.close()
            prog.close()
        finally:
            broker.close()
        cdc_bodies = [json.loads(b) for ex, rk, b in broker.messages
                      if rk.startswith("cdc.")]
        assert [r["transfer_id"] for r in cdc_bodies] == list(range(1, 10))
