"""Native C tb_client: echo mode, then a real cluster over TCP.

reference: src/clients/c/tb_client.zig (init_echo test harness) +
src/clients/python — the binding drives the same C ABI every language
client shares.
"""

import os
import signal
import socket
import subprocess
import sys
import time

import pytest

from tigerbeetle_tpu.clients import CClient, c_client_available
from tigerbeetle_tpu.types import Account, Operation, Transfer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.skipif(
    not c_client_available(), reason="native toolchain unavailable")


class TestEcho:
    def test_echo_roundtrip(self):
        client = CClient(cluster=1, replica_addresses=[], echo=True)
        try:
            for size in (0, 1, 128, 64 * 1024):
                body = os.urandom(size)
                assert client.request(Operation.create_transfers, body) == body
        finally:
            client.close()

    def test_echo_many_packets(self):
        client = CClient(cluster=1, replica_addresses=[], echo=True)
        try:
            bodies = [os.urandom(64) for _ in range(50)]
            for body in bodies:
                assert client.request(Operation.lookup_accounts, body) == body
        finally:
            client.close()

    def test_shutdown_clean(self):
        client = CClient(cluster=1, replica_addresses=[], echo=True)
        client.close()
        client.close()  # idempotent


def _free_ports(n):
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


@pytest.fixture
def single_replica(tmp_path):
    (port,) = _free_ports(1)
    address = f"127.0.0.1:{port}"
    path = tmp_path / "r0.tigerbeetle"
    env = dict(os.environ)
    subprocess.run(
        [sys.executable, "-m", "tigerbeetle_tpu", "format", "--cluster=9",
         "--replica=0", "--replica-count=1", "--small", str(path)],
        check=True, cwd=REPO, env=env, timeout=60,
        stdout=subprocess.DEVNULL)
    proc = subprocess.Popen(
        [sys.executable, "-m", "tigerbeetle_tpu", "start",
         f"--addresses={address}", "--replica=0", "--cluster=9",
         "--engine=oracle", "--small", str(path)],
        cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    try:
        yield address
    finally:
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


@pytest.mark.integration
def test_c_client_against_real_replica(single_replica):
    host, port = single_replica.split(":")
    client = CClient(cluster=9, replica_addresses=[(host, int(port))])
    try:
        deadline = time.monotonic() + 60
        results = None
        while time.monotonic() < deadline:
            try:
                results = client.create_accounts([
                    Account(id=1, ledger=700, code=10),
                    Account(id=2, ledger=700, code=10),
                ])
                break
            except TimeoutError:
                continue
        assert results is not None, "replica never became available"
        assert all(r.status.name in ("created", "exists") for r in results)

        results = client.create_transfers([
            Transfer(id=100, debit_account_id=1, credit_account_id=2,
                     amount=77, ledger=700, code=10)])
        assert [r.status.name for r in results] == ["created"]

        accounts = client.lookup_accounts([1, 2])
        assert accounts[0].debits_posted == 77
        assert accounts[1].credits_posted == 77
        transfers = client.lookup_transfers([100])
        assert transfers[0].amount == 77
    finally:
        client.close()
