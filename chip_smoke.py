#!/usr/bin/env python3
"""Chip smoke: the served ledger on one TPU chip, through the entry
points a user calls.

    python3 chip_smoke.py              # one chip (what the driver runs)
    python3 chip_smoke.py --four-chips # the partitioned route, 4 chips

One chip: `python -m tigerbeetle_tpu format` + `start --engine=device`
WITHOUT --small (production layout: 1 MiB messages, a_cap 2^17, t_cap
2^21), one replica, the only process that starts a JAX backend. This
process is the client (vsr/client.py over TCP) and the reference
(oracle/state_machine.py, pure Python): it never touches a device.
Traffic is made from --seed: >= 2^15 accounts, >= 16 create_transfers
requests at the widest batch the wire admits from two sessions, a limit
fixpoint batch, a linked chain with a rollback, pending -> post / void,
duplicate ids, then lookups and get_account_transfers. Every reply is
compared with the oracle's, result for result and balance for balance.

Fails (non-zero, no result line) on: any mismatch; a request that times
out at the client's default budget; host fallbacks or the mirror regime
on the server; a device that is not a TPU. The last stdout line of a
passing run is the contract's one JSON object.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import random
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
WORKDIR = os.path.join(HERE, "scratch", "chip_smoke")
BOOT_TIMEOUT_S = 1000  # cold warm-up compiles are minutes, not seconds
N_ACCOUNTS = 1 << 15
ROUNDS = 8  # concurrent wire-max requests per session


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ------------------------------------------------------------------ server


class Server:
    """`start --engine=device` as a child: the one process on the chip."""

    def __init__(self, port: int, path: str, log_path: str):
        env = dict(os.environ, PYTHONUNBUFFERED="1")
        self.log_path = log_path
        self.lines: list[str] = []
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "tigerbeetle_tpu", "start",
             f"--addresses=127.0.0.1:{port}", "--replica=0",
             "--engine=device", path],
            cwd=HERE, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        self._cond = threading.Condition()
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        with open(self.log_path, "w") as log:
            for line in self.proc.stdout:
                log.write(line)
                log.flush()
                with self._cond:
                    self.lines.append(line.rstrip("\n"))
                    self._cond.notify_all()
        with self._cond:
            self._cond.notify_all()

    def wait_line(self, pattern: str, timeout_s: float) -> re.Match:
        """First output line matching `pattern` (past lines included)."""
        rx = re.compile(pattern)
        deadline = time.monotonic() + timeout_s
        seen = 0
        with self._cond:
            while True:
                for line in self.lines[seen:]:
                    m = rx.search(line)
                    if m:
                        return m
                seen = len(self.lines)
                if self.proc.poll() is not None and not self._reader.is_alive():
                    raise SmokeFailure(
                        f"server exited ({self.proc.returncode}) before "
                        f"printing /{pattern}/; log tail:\n" + self.tail())
                left = deadline - time.monotonic()
                if left <= 0:
                    raise SmokeFailure(
                        f"server did not print /{pattern}/ within "
                        f"{timeout_s:.0f}s; log tail:\n" + self.tail())
                self._cond.wait(min(left, 1.0))

    def tail(self, n: int = 25) -> str:
        return "\n".join(self.lines[-n:])

    def stop(self) -> dict:
        """Orderly shutdown; returns the server's shutdown record."""
        self.proc.send_signal(signal.SIGINT)
        try:
            self.proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            raise SmokeFailure("server ignored SIGINT for 120s")
        self._reader.join(timeout=10)
        for line in reversed(self.lines):
            if line.startswith('{"shutdown"'):
                return json.loads(line)["shutdown"]
        raise SmokeFailure("server printed no shutdown record; log tail:\n"
                           + self.tail())

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ----------------------------------------------------------------- traffic


class Traffic:
    """Seeded requests + the oracle they are checked against."""

    def __init__(self, seed: int, n_accounts: int, n_max: int):
        from tigerbeetle_tpu.oracle.state_machine import StateMachineOracle

        self.rng = random.Random(seed)
        self.n_max = n_max
        self.oracle = StateMachineOracle()
        self.next_tid = (self.rng.getrandbits(40) << 64) | 1
        base = self.rng.getrandbits(48) | 1
        # 128-bit ids with both limbs in play.
        self.acct_ids = [((i % 5) << 64) | (base + 7 * i)
                         for i in range(n_accounts)]
        # A mix: every fourth account is debits_must_not_exceed_credits,
        # at most one wire-max request's worth (they are funded in one).
        limited = [a for i, a in enumerate(self.acct_ids) if i % 4 == 0]
        self.limited = limited[:n_max]
        lim = set(self.limited)
        self.plain = [a for a in self.acct_ids if a not in lim]
        # Kept out of the random traffic: the limit-fixpoint batch needs
        # accounts whose headroom it controls exactly.
        self.tight = self.limited[:8]
        tight = set(self.tight)
        self.pool = [a for a in self.acct_ids if a not in tight]
        self.stats = {"events": 0, "mismatches": 0, "statuses": {}}

    def tid(self) -> int:
        self.next_tid += 1
        return self.next_tid

    def accounts(self):
        from tigerbeetle_tpu.types import Account, AccountFlags

        lim = set(self.limited)
        return [Account(
            id=a, ledger=1, code=10, user_data_64=i,
            flags=(int(AccountFlags.debits_must_not_exceed_credits)
                   if a in lim else 0))
            for i, a in enumerate(self.acct_ids)]

    def funding(self):
        """Credits for every limited account in ONE wire-max request:
        deep headroom for the random traffic, 100 for the tight ones."""
        from tigerbeetle_tpu.types import Transfer

        tight = set(self.tight)
        return [Transfer(id=self.tid(), debit_account_id=self.plain[
            i % len(self.plain)], credit_account_id=a,
            amount=100 if a in tight else 10 ** 12, ledger=1, code=1)
            for i, a in enumerate(self.limited)]

    def random_transfers(self, n: int) -> list:
        """Mostly valid transfers over the whole population, ~1% that
        must fail (same account, unknown account, wrong ledger)."""
        from tigerbeetle_tpu.types import Transfer

        rng, pool = self.rng, self.pool
        out = []
        for _ in range(n):
            dr, cr = rng.sample(pool, 2)
            t = Transfer(id=self.tid(), debit_account_id=dr,
                         credit_account_id=cr,
                         amount=rng.randrange(1, 1000), ledger=1, code=1,
                         user_data_32=rng.getrandbits(16))
            roll = rng.random()
            if roll < 0.004:
                t.credit_account_id = dr
            elif roll < 0.007:
                t.debit_account_id = (9 << 64) | rng.getrandbits(40)
            elif roll < 0.010:
                t.ledger = 2
            out.append(t)
        return out

    def fill(self, head: list) -> list:
        """Every transfers request is exactly the wire maximum: the
        cases under test lead, random traffic fills the rest."""
        return head + self.random_transfers(self.n_max - len(head))


def prepare_timestamp(results, exists_status) -> int:
    """The prepare's timestamp, from the reply alone: event i of n
    carries ts - n + i + 1 unless it reports an existing object's."""
    n = len(results)
    found = {r.timestamp + (n - 1 - i) for i, r in enumerate(results)
             if r.status != exists_status}
    require(len(found) == 1,
            f"reply timestamps do not name one prepare: {sorted(found)[:4]}")
    return found.pop()


class Checker:
    """Feeds the oracle what the server committed, in commit order, and
    compares every result."""

    def __init__(self, traffic: Traffic):
        self.t = traffic
        self.last_ts = 0

    def create(self, kind: str, events: list, results: list) -> None:
        from tigerbeetle_tpu.types import (CreateAccountStatus,
                                           CreateTransferStatus)

        st = self.t.stats
        require(len(results) == len(events),
                f"{kind}: {len(results)} results for {len(events)} events")
        exists = (CreateAccountStatus.exists if kind == "accounts"
                  else CreateTransferStatus.exists)
        ts = prepare_timestamp(results, exists)
        require(ts > self.last_ts, f"{kind}: commit order regressed")
        self.last_ts = ts
        fn = (self.t.oracle.create_accounts if kind == "accounts"
              else self.t.oracle.create_transfers)
        want = fn(events, ts)
        bad = [(i, w, g) for i, (w, g) in enumerate(zip(want, results))
               if (w.status, w.timestamp) != (g.status, g.timestamp)]
        st["events"] += len(events)
        for g in results:
            st["statuses"][g.status.name] = \
                st["statuses"].get(g.status.name, 0) + 1
        if bad:
            st["mismatches"] += len(bad)
            i, w, g = bad[0]
            raise SmokeFailure(
                f"create_{kind}: {len(bad)} of {len(events)} results "
                f"differ from the oracle; first at event {i}: "
                f"want {w.status.name}@{w.timestamp}, "
                f"got {g.status.name}@{g.timestamp}")

    def same(self, what: str, got: list, want: list) -> None:
        if got != want:
            self.t.stats["mismatches"] += 1
            n = next((i for i, (g, w) in enumerate(zip(got, want))
                      if g != w), min(len(got), len(want)))
            raise SmokeFailure(
                f"{what}: reply differs from the oracle "
                f"({len(got)} vs {len(want)} rows; first at row {n}: "
                f"got {got[n] if n < len(got) else None}, "
                f"want {want[n] if n < len(want) else None})")


def decode_results(op, body: bytes) -> list:
    from tigerbeetle_tpu import multi_batch
    from tigerbeetle_tpu.types import (CreateAccountResult,
                                       CreateTransferResult, Operation)

    cls = (CreateAccountResult if op == Operation.create_accounts
           else CreateTransferResult)
    (payload,) = multi_batch.decode(body, 16)
    return [cls.unpack(payload[i:i + 16])
            for i in range(0, len(payload), 16)]


# --------------------------------------------------------------- one chip


def run_served(args) -> dict:
    from jax._src import xla_bridge

    from tigerbeetle_tpu.clients.common import encode_batch, events_max
    from tigerbeetle_tpu.constants import HEADER_SIZE
    from tigerbeetle_tpu.types import (AccountFilter, AccountFilterFlags,
                                       CreateTransferStatus, Operation,
                                       Transfer, TransferFlags)
    from tigerbeetle_tpu.vsr.client import Client
    from tigerbeetle_tpu.vsr.storage import StorageLayout

    O = Operation
    layout = StorageLayout()
    body_max = layout.message_size_max - HEADER_SIZE
    n_max = events_max(O.create_transfers, body_max)
    n_lookup = events_max(O.lookup_accounts, body_max)
    say(f"layout: message_size_max={layout.message_size_max} "
        f"data file {layout.size / 1e9:.2f} GB; widest create request "
        f"{n_max} events, widest lookup {n_lookup} ids")

    shutil.rmtree(WORKDIR, ignore_errors=True)
    os.makedirs(WORKDIR)
    path = os.path.join(WORKDIR, "0_0.tigerbeetle")
    subprocess.run(
        [sys.executable, "-m", "tigerbeetle_tpu", "format", "--cluster=0",
         "--replica=0", "--replica-count=1", path],
        cwd=HERE, check=True, timeout=300)

    port = free_port()
    t_boot = time.monotonic()
    server = Server(port, path, os.path.join(WORKDIR, "server.log"))
    clients = []
    try:
        m = server.wait_line(
            r"^device: platform=(\S+) kind='([^']*)' count=(\d+)", 600)
        device = {"platform": m.group(1), "kind": m.group(2),
                  "count": int(m.group(3))}
        say(f"server device: {device}")
        say(server.wait_line(r"^compile cache: ", 60).string)
        say(server.wait_line(r"^storage engine: ", 60).string)
        m = server.wait_line(r"^kernels warm in ([0-9.]+)s.*", BOOT_TIMEOUT_S)
        warm_s = float(m.group(1))
        say(m.string)
        server.wait_line(r"^replica 0 listening", 120)
        boot_s = time.monotonic() - t_boot
        say(f"boot to listening {boot_s:.1f}s (warm-up {warm_s:.1f}s)")

        traffic = Traffic(args.seed, N_ACCOUNTS, n_max)
        check = Checker(traffic)
        addr = [("127.0.0.1", port)]
        clients = [Client(cluster=0, client_id=0xC0FFEE + i,
                          replica_addresses=addr) for i in range(2)]
        c0 = clients[0]
        secs: dict[str, list] = {k: [] for k in (
            "create_accounts", "create_transfers", "lookup_accounts",
            "lookup_transfers", "get_account_transfers")}

        def timed(name, fn, *a):
            """One request at the client's DEFAULT timeout: a
            TimeoutError here is the finding (a server still compiling
            what it serves)."""
            t0 = time.monotonic()
            try:
                out = fn(*a)
            except TimeoutError as e:
                raise SmokeFailure(
                    f"{name}: no reply within the client's default "
                    f"timeout ({e}) — the server was not ready for what "
                    "it serves")
            secs[name].append(round(time.monotonic() - t0, 3))
            return out

        def create(client, op, events):
            body = encode_batch(op, [e.pack() for e in events], body_max)
            return decode_results(
                op, timed(op.name, client.request, op, body))

        # Phase 1: accounts, wire-max requests.
        accounts = traffic.accounts()
        for i in range(0, len(accounts), n_max):
            chunk = accounts[i:i + n_max]
            check.create("accounts", chunk,
                         create(c0, O.create_accounts, chunk))
        say(f"accounts: {len(accounts)} created in "
            f"{len(secs['create_accounts'])} requests")

        # Phase 2: fund the limited accounts (one wire-max request).
        funding = traffic.fill(traffic.funding())
        check.create("transfers", funding,
                     create(c0, O.create_transfers, funding))

        # Phase 3: wire-max requests from two sessions at once: prepares
        # queue behind the one executing, and the reply order is the
        # server's, not the senders'. (A solo primary commits prepare by
        # prepare as each quorum completes — Replica._check_quorum — so
        # no commit WINDOW forms here; windows form on backups and in
        # WAL replay. The shutdown record says how many did.) Applied to
        # the oracle in commit order.
        done: list = []
        errors: list = []
        barrier = threading.Barrier(2)

        def session(client, batches):
            try:
                for events in batches:
                    barrier.wait(timeout=120)
                    done.append((events,
                                 create(client, O.create_transfers, events)))
            except BaseException as e:  # surfaced below, never swallowed
                errors.append(e)
                barrier.abort()

        per_session = [[traffic.fill([]) for _ in range(ROUNDS)]
                       for _ in clients]
        threads = [threading.Thread(target=session, args=(c, b))
                   for c, b in zip(clients, per_session)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
            require(not th.is_alive(), "a client session hung")
        if errors:
            raise errors[0]
        done.sort(key=lambda er: prepare_timestamp(
            er[1], CreateTransferStatus.exists))
        for events, results in done:
            check.create("transfers", events, results)
        say(f"concurrent phase: {len(done)} wire-max requests "
            f"from {len(clients)} sessions")

        # Phase 4: the hard cases, each leading a wire-max request.
        pl, tight = traffic.plain, traffic.tight
        F = TransferFlags

        def xfer(dr, cr, amount, **kw):
            return Transfer(id=traffic.tid(), debit_account_id=dr,
                            credit_account_id=cr, amount=amount,
                            ledger=1, code=1, **kw)

        # 4a. Limit fixpoint: a tight account (credits 100) debited past
        # its headroom, refilled mid-batch — order-dependent verdicts.
        L = tight[0]
        limit_batch = traffic.fill([
            xfer(L, pl[0], 60), xfer(L, pl[1], 60), xfer(pl[2], L, 30),
            xfer(L, pl[3], 60), xfer(L, pl[4], 60), xfer(pl[5], L, 500),
            xfer(L, pl[6], 400), xfer(tight[1], pl[7], 101),
            xfer(tight[1], pl[7], 100)])
        res = create(c0, O.create_transfers, limit_batch)
        check.create("transfers", limit_batch, res)
        require(any(r.status == CreateTransferStatus.exceeds_credits
                    for r in res[:9]),
                "the limit batch breached no limit")

        # 4b. Linked chain with a rollback, a chain that commits,
        # duplicate ids (same and altered), and two pendings.
        dup, other = done[0][0][0], done[1][0][0]
        dup_changed = dataclasses.replace(other, amount=other.amount + 1)
        pend_a = xfer(pl[10], pl[11], 700, flags=int(F.pending),
                      timeout=3600)
        pend_b = xfer(pl[12], tight[2], 50, flags=int(F.pending))
        mixed = traffic.fill([
            xfer(pl[20], pl[21], 10, flags=int(F.linked)),
            xfer(pl[21], pl[22], 10, flags=int(F.linked)),
            xfer(pl[22], pl[22], 10),  # breaks the chain
            xfer(pl[23], pl[24], 11, flags=int(F.linked)),
            xfer(pl[24], pl[25], 11),  # commits
            dup, dup_changed, pend_a, pend_b])
        res = create(c0, O.create_transfers, mixed)
        check.create("transfers", mixed, res)
        S = CreateTransferStatus
        require([r.status for r in res[:9]] == [
            S.linked_event_failed, S.linked_event_failed,
            S.accounts_must_be_different, S.created, S.created,
            S.exists, S.exists_with_different_amount, S.created,
            S.created], f"hard cases: {[r.status.name for r in res[:9]]}")

        # 4c. pending -> post, pending -> void, and a post of nothing.
        settle = traffic.fill([
            xfer(0, 0, 700, pending_id=pend_a.id,
                 flags=int(F.post_pending_transfer)),
            xfer(0, 0, 0, pending_id=pend_b.id,
                 flags=int(F.void_pending_transfer)),
            xfer(0, 0, 1, pending_id=traffic.tid(),
                 flags=int(F.post_pending_transfer))])
        res = create(c0, O.create_transfers, settle)
        check.create("transfers", settle, res)
        require([r.status for r in res[:3]] == [
            S.created, S.created, S.pending_transfer_not_found],
            f"settle: {[r.status.name for r in res[:3]]}")

        # Phase 5: reads. Every account, balance for balance.
        oracle = traffic.oracle
        unknown = (7 << 64) | 12345
        for i in range(0, len(traffic.acct_ids), n_lookup - 1):
            ids = traffic.acct_ids[i:i + n_lookup - 1] + [unknown]
            got = timed("lookup_accounts", c0.lookup_accounts, ids)
            check.same("lookup_accounts", got, oracle.lookup_accounts(ids))
        all_ids = [t.id for b in (funding, limit_batch, mixed, settle)
                   for t in b[:16]]
        all_ids += [t.id for events, _ in done for t in events[:400]]
        ids = all_ids[:n_lookup]
        got = timed("lookup_transfers", c0.lookup_transfers, ids)
        check.same("lookup_transfers", got, oracle.lookup_transfers(ids))
        by_ts = sorted((t for t in oracle.transfers.values()),
                       key=lambda t: t.timestamp)
        for acct in (L, pl[10], pl[22], traffic.pool[-1]):
            f = AccountFilter(
                account_id=acct, limit=n_lookup,
                flags=int(AccountFilterFlags.debits
                          | AccountFilterFlags.credits))
            raw = timed("get_account_transfers", c0.query,
                        O.get_account_transfers, f)
            got = [Transfer.unpack(raw[i:i + 128])
                   for i in range(0, len(raw), 128)]
            want = [t for t in by_ts if acct in (t.debit_account_id,
                                                 t.credit_account_id)]
            require(len(want) > 0, "query account saw no transfers")
            check.same("get_account_transfers", got, want[:n_lookup])

        for c in clients:
            c.close()
        clients = []
        shutdown = server.stop()
    finally:
        for c in clients:
            c.close()
        server.kill()
        # 1.6 GB sparse data file; the compile cache stays.
        shutil.rmtree(WORKDIR, ignore_errors=True)

    # The parent stayed off the device: the chip had one owner.
    require(not xla_bridge.backends_are_initialized(),
            "chip_smoke's own process started a JAX backend")
    require(not any(m.startswith("tigerbeetle_tpu.ops")
                    for m in sys.modules),
            "chip_smoke's own process imported the device code")

    fb = shutdown["fallback_stats"]
    st = traffic.stats
    n_wire_max = len(secs["create_transfers"])
    for name, v in secs.items():
        say(f"{name}: {len(v)} requests, seconds "
            f"min {min(v)} median {sorted(v)[len(v) // 2]} max {max(v)}; "
            f"in order sent: {v}")
    say(f"transfers requests at the wire maximum ({n_max}): {n_wire_max}; "
        f"events {st['events']}; mismatches {st['mismatches']}")
    say(f"statuses: {json.dumps(st['statuses'], sort_keys=True)}")
    say(f"server shutdown record: {json.dumps(shutdown, sort_keys=True)}")
    require(n_wire_max >= 16, f"only {n_wire_max} wire-max requests")
    require(st["mismatches"] == 0, "oracle mismatches")
    require(fb["host_fallbacks"] == 0,
            f"host fallbacks: {fb['host_fallbacks']} ({fb['causes']})")
    require(not shutdown["mirror_regime"],
            "the ledger ended in the host-mirror regime")
    require(fb["fixpoint_batches"] >= 1,
            "the limit batch never reached the fixpoint tier")
    return device


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=20260926)
    p.add_argument("--four-chips", action="store_true",
                   help="run ONLY the partitioned 4-device phase")
    args = p.parse_args(argv)
    t0 = time.monotonic()
    try:
        if args.four_chips:
            # The partitioned route, one process on a 4-device mesh:
            # that phase and its comparison, and no other.
            from tigerbeetle_tpu.testing.partitioned_smoke import run

            device = run(seed=args.seed, say=say)
        else:
            device = run_served(args)
        want = 4 if args.four_chips else 1
        require(device["platform"] == "tpu",
                f"every comparison passed, but the device is "
                f"{device['platform']!r}, not a TPU")
        require(device["count"] == want,
                f"expected {want} device(s), JAX reports {device['count']}")
    except (SmokeFailure, AssertionError) as e:
        print(f"[chip_smoke] FAILED after {time.monotonic() - t0:.0f}s: {e}",
              file=sys.stderr, flush=True)
        return 1
    say(f"passed in {time.monotonic() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
