#!/usr/bin/env python3
"""The benchmark's launcher with the timed path broken underneath, for
chipbench/tests/test_faults.py only. CHIPBENCH_FAULT names the fault;
the first two strike every 5th create_transfers dispatch of the device
ledger, the third the state machine's second lookup_accounts (the first
is set-up's un-timed one, the read-back's come later).

  answer_altered    one event's status is changed where it is produced
  state_unchanged   the dispatch answers "created" for every event and
                    leaves the ledger's state as it was
  row_altered       one row of one lookup's answer is changed where the
                    state machine produces it
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import dataclasses  # noqa: E402

import numpy as np  # noqa: E402

from chipbench import server_launcher  # noqa: E402


def install_row_altered() -> None:
    from tigerbeetle_tpu.state_machine import StateMachine

    original = StateMachine.lookup_accounts
    calls = [0]

    def broken(self, ids):
        rows = original(self, ids)
        calls[0] += 1
        if calls[0] == 2 and rows:
            at = len(rows) // 2
            rows = list(rows)
            rows[at] = dataclasses.replace(
                rows[at], credits_posted=rows[at].credits_posted + 1)
        return rows

    StateMachine.lookup_accounts = broken


def install(fault: str) -> None:
    if fault == "row_altered":
        return install_row_altered()
    from tigerbeetle_tpu.ops.ledger import DeviceLedger

    original = DeviceLedger.create_transfers_soa
    calls = [0]

    def broken(self, ev, timestamp, *a, **kw):
        calls[0] += 1
        strike = calls[0] % 5 == 0
        if strike and fault == "state_unchanged":
            n = len(ev["id_lo"])
            st = np.full(n, (1 << 32) - 1, dtype=np.uint32)
            ts = np.uint64(timestamp) - np.uint64(n) + np.arange(
                1, n + 1, dtype=np.uint64)
            return st, ts
        st, ts = original(self, ev, timestamp, *a, **kw)
        if strike and fault == "answer_altered":
            st = np.array(st, copy=True)
            st[len(st) // 2] = 54  # another status than the true one
        return st, ts

    DeviceLedger.create_transfers_soa = broken


if __name__ == "__main__":
    install(os.environ["CHIPBENCH_FAULT"])
    sys.exit(server_launcher.main())
