#!/usr/bin/env python3
"""The benchmark's launcher with the timed path broken underneath, for
chipbench/tests/test_faults.py only. CHIPBENCH_FAULT names the fault;
it strikes every 5th create_transfers dispatch of the device ledger.

  answer_altered    one event's status is changed where it is produced
  state_unchanged   the dispatch answers "created" for every event and
                    leaves the ledger's state as it was
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from chipbench import server_launcher  # noqa: E402


def install(fault: str) -> None:
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
