"""The comparison that decides `correct`.

Every answer the client received (set-up and window) is replayed
through the plain reference (chipbench/reference/ledger.py) in the
order the server committed, and the state the server holds after the
window is read back through lookup_accounts / lookup_transfers and
compared row for row. All comparisons are exact: each number's limit
is 0.

    result_mismatches     events whose (status, timestamp) differs
    read_mismatches       rows of a lookup sent among the writes (a mix's
                          `reads`, and set-up's un-timed one) that differ
                          from the reference's at the read's place in
                          the commit order
    account_mismatches    accounts read back that differ, or are missing
    transfer_mismatches   sampled transfers read back that differ
    order_violations      prepares whose order contradicts real time,
                          or that share a timestamp (strict
                          serializability as far as a client can see)
    unanswered            requests with no reply, or one that does not decode

The commit order is the server's own claim (each reply's timestamps
name its prepare); the claim is checked against the client's clock, and
the reference then has to reproduce every answer in that order. A
read's reply names no prepare: its place is the one its caller's clock
gives it (`place_reads`).
"""

from __future__ import annotations

import numpy as np

from .reference.ledger import StateMachineOracle
from .reference.ledger_types import Account, Transfer

# The guarantees a configuration file may state (its `guarantees`
# block, key by key), each with the compared numbers that hold the
# program to it. A configuration that states another has no cell until
# a comparison for it exists: the harness refuses to run it.
GUARANTEES = {
    "replicas": ("unanswered",),
    "acknowledged_means": ("unanswered", "account_mismatches",
                           "transfer_mismatches"),
    "consistency": ("order_violations", "read_mismatches"),
    "limits": ("result_mismatches", "account_mismatches"),
    "results": ("result_mismatches", "read_mismatches", "account_mismatches",
                "transfer_mismatches"),
}
LIMITS = {"result_mismatches": 0, "read_mismatches": 0,
          "account_mismatches": 0, "transfer_mismatches": 0,
          "order_violations": 0, "unanswered": 0}


def prepare_timestamp(results: np.ndarray) -> int:
    """Event i of n carries ts - n + i + 1: the median candidate names
    the prepare even if some event's timestamp is wrong (which the
    replay then counts)."""
    n = len(results)
    cand = results["timestamp"].astype(np.int64) + (n - 1 - np.arange(n))
    return int(np.sort(cand)[n // 2])  # exact: a float median rounds 1e18


def commit_order(answered: list) -> tuple[list, int]:
    """Requests sorted by their prepare's timestamp, and how many
    contradict real time: b replied before a was sent, yet a is ordered
    first; or two prepares with one timestamp."""
    order = sorted(answered, key=lambda s: s.ts)
    violations = 0
    latest_send = float("-inf")
    last_ts = None
    for s in order:
        if s.t_reply < latest_send or s.ts == last_ts:
            violations += 1
        latest_send = max(latest_send, s.t_send)
        last_ts = s.ts
    return order, violations


def place_reads(order: list, reads: list) -> list:
    """The writes in commit order with each read where its caller's
    clock puts it: after the last write of that order that had been
    answered when the read was sent, reads of one place in send order.
    That is the read's one possible place where a single caller sends
    one request at a time (which `run.servable` requires of a mix with
    reads): the next write the caller sent must then be ordered after
    it, which `commit_order` holds the writes' own timestamps to."""
    after: dict[int, list] = {}
    for r in sorted(reads, key=lambda r: r.t_send):
        at = max((i for i, w in enumerate(order) if w.t_reply <= r.t_send),
                 default=-1)
        after.setdefault(at, []).append(r)
    placed = list(after.get(-1, []))
    for i, w in enumerate(order):
        placed.append(w)
        placed += after.get(i, [])
    return placed


def ordered(sent: list) -> tuple[list, int]:
    """The answered requests in commit order, each write with its
    prepare's timestamp as `.ts`, each read at its place among them, and
    the count of order violations."""
    answered = [s for s in sent if s.error is None]
    writes = [s for s in answered if not s.request.is_read]
    for s in writes:
        s.ts = prepare_timestamp(s.results)
    order, violations = commit_order(writes)
    return place_reads(order, [s for s in answered if s.request.is_read]), \
        violations


def apply(reference: StateMachineOracle, s) -> list:
    """One request through the reference at its prepare's timestamp."""
    payload, size = s.request.payload, s.request.event_size
    accounts = s.request.operation == "create_accounts"
    cls = Account if accounts else Transfer
    events = [cls.unpack(payload[i:i + size])
              for i in range(0, len(payload), size)]
    return (reference.create_accounts(events, s.ts) if accounts
            else reference.create_transfers(events, s.ts))


def int_ids(pairs: np.ndarray) -> list[int]:
    """(n, 2) u64 (id_lo, id_hi) pairs as the 128-bit ids they spell."""
    return [(int(h) << 64) | int(l) for l, h in pairs]


def read_rows(reference: StateMachineOracle, s) -> list[bytes]:
    """The rows the reference answers a read with, asked now."""
    return [a.pack()
            for a in reference.lookup_accounts(int_ids(s.request.ids))]


def replay(reference: StateMachineOracle, order: list) -> int:
    """Feed the reference in commit order; the number of events whose
    (status, timestamp) differs from what the client received."""
    return replay_with_reads(reference, order)[0]


def replay_with_reads(reference: StateMachineOracle,
                      order: list) -> tuple[int, int]:
    """`replay`, and beside its count the number of rows of the reads
    in `order` that differ from what the reference holds when the
    replay reaches each."""
    mismatches = reads = 0
    for s in order:
        if s.request.is_read:
            reads += rows_differ(s.results.tobytes(), read_rows(reference, s))
            continue
        want = apply(reference, s)
        if len(s.results) != len(want):
            mismatches += max(len(want), len(s.results))
            continue
        want_ts = np.fromiter((w.timestamp for w in want), np.uint64,
                              len(want))
        want_st = np.fromiter((int(w.status) for w in want), np.uint32,
                              len(want))
        mismatches += int(((want_ts != s.results["timestamp"])
                           | (want_st != s.results["status"])).sum())
    return mismatches, reads


def rows_differ(got: bytes | None, want_rows: list[bytes]) -> int:
    """Rows of a lookup reply against the reference's, position by
    position; a missing or surplus row counts, and so does every row of
    a lookup that was never answered."""
    got = got or b""
    got_rows = [got[i:i + 128] for i in range(0, len(got), 128)]
    n = max(len(got_rows), len(want_rows))
    same = sum(1 for g, w in zip(got_rows, want_rows) if g == w)
    return n - same


def judge(sent: list, readback: dict) -> dict:
    """The compared numbers, by name, from every request of the run and
    the read-back replies ({"accounts": [(ids, reply)], "transfers":
    [(ids, reply)]})."""
    order, violations = ordered(sent)
    reference = StateMachineOracle()
    result_mismatches, read_mismatches = replay_with_reads(reference, order)
    return {
        "result_mismatches": result_mismatches,
        "read_mismatches": read_mismatches,
        "account_mismatches": sum(
            rows_differ(reply, [a.pack() for a in
                                reference.lookup_accounts(ids)])
            for ids, reply in readback["accounts"]),
        "transfer_mismatches": sum(
            rows_differ(reply, [t.pack() for t in
                                reference.lookup_transfers(ids)])
            for ids, reply in readback["transfers"]),
        "order_violations": violations,
        "unanswered": len(sent) - len(order)
        + sum(1 for k in readback for _, reply in readback[k]
              if reply is None),
    }


def verdict(numbers: dict) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)


def compared(numbers: dict) -> dict:
    """Each number beside its limit, for the result line and stderr."""
    return {k: {"value": numbers[k], "limit": LIMITS[k]} for k in LIMITS}
