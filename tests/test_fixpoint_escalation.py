"""Deep limit cascades: the plain tier escalates to the converging
fixpoint tier, which runs as many rounds as the cascade needs in one
dispatch instead of falling back to the host.

A K-wave cascade is constructed from K linked chains: chain k's first
member debits limited account L_k (which only has headroom if chain
k-1's second member's credit to L_k landed), and its second member
credits L_{k+1}. Chain 0 is poisoned, so the sequential truth unwinds
one chain per wave — resolvable only by a fixpoint with >= K rounds
(reference semantics: balance limits, src/tigerbeetle.zig:34-42; chain
rollback, src/state_machine.zig:3116-3150).
"""

import pytest

# Tier: jit-heavy parity/differential suite (see pytest.ini) —
# excluded from the quick gate; run via scripts/gate.py --tier slow.
pytestmark = pytest.mark.slow

import numpy as np

from tigerbeetle_tpu.oracle import StateMachineOracle
from tigerbeetle_tpu.ops.ledger import DeviceLedger
from tigerbeetle_tpu.types import (
    Account,
    AccountFlags as AF,
    Transfer,
    TransferFlags as TF,
)

TS = 10_000_000_000_000


def _cascade_events(k_chains, first_id=10_000):
    """k_chains linked pairs forming a k-wave limit cascade. Account
    layout: FUND (id 1, unlimited) and limited accounts L_1..L_{k+1}
    (ids 2..k+2), each with debits_must_not_exceed_credits and a
    pre-batch credit of 10. Chain k (0-based): [debit L_{k+1} by 20,
    credit L_{k+2} by 10]. Chain 0's second member is poisoned (missing
    account). Truth: chain 0 rolls back; L_2 never gets its relief
    credit, so chain 1's debit of 20 > 10+10 breaches; chain 1 rolls
    back; and so on — one chain per wave."""
    events = []
    tid = first_id
    for k in range(k_chains):
        dr_acct = 2 + k  # L_{k+1}
        cr_acct = 3 + k  # L_{k+2}
        poison = 999_999 if k == 0 else cr_acct
        events.append(Transfer(id=tid, debit_account_id=dr_acct,
                               credit_account_id=1, ledger=1, code=1,
                               amount=20, flags=TF.linked))
        events.append(Transfer(id=tid + 1, debit_account_id=1,
                               credit_account_id=poison, ledger=1,
                               code=1, amount=10))
        tid += 2
    return events


def _setup(n_limited):
    led = DeviceLedger(a_cap=1 << 10, t_cap=1 << 12)
    sm = StateMachineOracle()
    accounts = [Account(id=1, ledger=1, code=1)]
    accounts += [Account(id=i, ledger=1, code=1,
                         flags=AF.debits_must_not_exceed_credits)
                 for i in range(2, n_limited + 2)]
    led.create_accounts(accounts, TS)
    sm.create_accounts(accounts, TS)
    # Fund every limited account with credit 10 (headroom for one debit
    # of 20 only WITH the in-batch relief credit of 10).
    funds = [Transfer(id=100 + i, debit_account_id=1,
                      credit_account_id=i, ledger=1, code=1, amount=10)
             for i in range(2, n_limited + 2)]
    ts = TS + 1000
    led.create_transfers(funds, ts)
    sm.create_transfers(funds, ts)
    return led, sm


def _diff(led, sm, events, ts):
    got = led.create_transfers(events, ts)
    want = sm.create_transfers(events, ts)
    assert [(r.timestamp, r.status.name) for r in got] == \
           [(r.timestamp, r.status.name) for r in want]


def test_shallow_cascade_stays_in_first_tier():
    led, sm = _setup(8)
    _diff(led, sm, _cascade_events(4), TS + 5000)
    assert led.fallbacks == 0
    assert led.deep_fixpoint_batches == 0
    assert led.fixpoint_batches >= 1


def test_deep_cascade_escalates_on_device():
    """12 waves > the 8 rounds an unrolled tier starts with: one
    converging dispatch resolves them, never a deeper tier or the
    host."""
    led, sm = _setup(16)
    _diff(led, sm, _cascade_events(12), TS + 5000)
    assert led.fallbacks == 0, "escalation must not touch the host path"
    assert led.deep_fixpoint_batches == 0
    assert led.fixpoint_rounds["dispatches"] == 1
    assert led.fixpoint_rounds["rounds"] >= 12


def test_warm_kernels_is_inert():
    led = DeviceLedger(a_cap=1 << 10, t_cap=1 << 12)
    led.create_accounts([Account(id=1, ledger=1, code=1),
                         Account(id=2, ledger=1, code=1)], TS)
    before = {k: np.asarray(v).copy()
              for k, v in led.state["transfers"].items() if k != "count"}
    count_before = int(led.state["transfers"]["count"])
    led.warm_kernels(256)
    assert int(led.state["transfers"]["count"]) == count_before
    for k, v in before.items():
        np.testing.assert_array_equal(
            np.asarray(led.state["transfers"][k]), v)
    # Ledger still fully functional afterward.
    res = led.create_transfers(
        [Transfer(id=50, debit_account_id=1, credit_account_id=2,
                  ledger=1, code=1, amount=5)], TS + 100)
    assert res[0].status.name == "created"
