"""The converging limit tier (`create_transfers_fixpoint`): one dispatch
runs the status rounds until they stop changing, however deep the limit
cascade.

The batches are `tb_bench_limits_1r`'s requests at the scaled form of
tests/test_limits_deployment.py (32 accounts, every second one unable to
overdraw, 480 events a request), straight into a `DeviceLedger` beside
the oracle. Once the customer accounts' funding runs low their cascades
outgrow the 32 rounds the unrolled tiers stop at. Every answer, the
state after them, and the rounds each dispatch ran are held against the
oracle and against a plain replay of the rounds the kernel iterates.
"""

import json
import os

import numpy as np
import pytest

from chipbench.traffic import Deployment
from tigerbeetle_tpu.ops import fast_kernels as fk
from tigerbeetle_tpu.ops.batch import transfers_soa_from_bytes
from tigerbeetle_tpu.ops.ledger import DeviceLedger
from tigerbeetle_tpu.oracle import StateMachineOracle
from tigerbeetle_tpu.types import (
    Account,
    AccountFlags,
    CreateTransferStatus,
    Transfer,
    TransferFlags as TF,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "chipbench", "configs", "tb_bench_limits_1r.json")
ACCOUNTS = 32
WIDTH = 480
REQUESTS = 24
TS0 = 10 ** 12
DR_LIMIT = int(AccountFlags.debits_must_not_exceed_credits)
CR_LIMIT = int(AccountFlags.credits_must_not_exceed_debits)
LIMIT_CODES = {int(CreateTransferStatus.exceeds_credits),
               int(CreateTransferStatus.exceeds_debits)}


def _records(cls, payload: bytes) -> list:
    return [cls.unpack(payload[i:i + 128])
            for i in range(0, len(payload), 128)]


def replay_rounds(transfers: list, accounts: dict) -> tuple[int, list]:
    """The kernel's round iteration, one event at a time: a round takes
    the previous round's limit decisions, applies every event they left
    standing to each account's balances in batch order, and decides each
    event's limit check against the balances before it. Returns the
    rounds run until a round decides as the one before it did (that
    round included) and the decisions. Single-phase events with no
    chains, as the deployment sends: an event that fails a check of its
    own accounts (missing, the same on both sides, another ledger) never
    applies."""
    def own_fault(t):
        dr, cr = accounts.get(t.debit_account_id), \
            accounts.get(t.credit_account_id)
        return (dr is None or cr is None or dr.id == cr.id
                or not t.ledger == dr.ledger == cr.ledger)

    live = [not own_fault(t) for t in transfers]
    over = [False] * len(transfers)
    rounds = 0
    while True:
        rounds += 1
        bal = {a.id: {"dp": a.debits_pending, "dpos": a.debits_posted,
                      "cp": a.credits_pending, "cpos": a.credits_posted}
               for a in accounts.values()}
        decided = []
        for t, ok, was_over in zip(transfers, live, over):
            if not ok:
                decided.append(False)
                continue
            dr, cr = bal[t.debit_account_id], bal[t.credit_account_id]
            decided.append(bool(
                (accounts[t.debit_account_id].flags & DR_LIMIT
                 and dr["dp"] + dr["dpos"] + t.amount > dr["cpos"])
                or (accounts[t.credit_account_id].flags & CR_LIMIT
                    and cr["cp"] + cr["cpos"] + t.amount > cr["dpos"])))
            if not was_over:
                dr["dpos"] += t.amount
                cr["cpos"] += t.amount
        if decided == over:
            return rounds, decided
        over = decided


class _Run:
    """The deployment's accounts, funding and REQUESTS transfer requests
    through a DeviceLedger and the oracle side by side: the answers of
    each, the rounds of each converging dispatch, and each request's
    replayed rounds from the oracle's balances before it."""

    def __init__(self, seed: int):
        with open(CONFIG) as f:
            dep = Deployment(json.load(f), seed, accounts_cut=ACCOUNTS)
        self.led = DeviceLedger(a_cap=1 << 9, t_cap=1 << 14)
        self.sm = StateMachineOracle()
        ts = TS0
        for request in dep.account_requests(ACCOUNTS):
            accounts = _records(Account, request.payload)
            ts += len(accounts)
            got = self.led.create_accounts(accounts, ts)
            assert got == self.sm.create_accounts(accounts, ts)
        self.answers, self.rounds, self.replayed = [], [], []
        for request in dep.funding_requests(WIDTH) + [
                dep.transfer_request(0, k, WIDTH) for k in range(REQUESTS)]:
            transfers = _records(Transfer, request.payload)
            replay = replay_rounds(transfers, dict(self.sm.accounts))
            ts += len(transfers)
            before = dict(self.led.fixpoint_rounds)
            st, got_ts = self.led.create_transfers_soa(
                transfers_soa_from_bytes(request.payload), ts)
            want = self.sm.create_transfers(transfers, ts)
            self.answers.append((
                list(zip(st.tolist(), got_ts.tolist())),
                [(int(r.status), r.timestamp) for r in want]))
            after = self.led.fixpoint_rounds
            if after["dispatches"] > before["dispatches"]:
                assert after["dispatches"] == before["dispatches"] + 1
                self.rounds.append(after["rounds"] - before["rounds"])
                self.replayed.append((replay, want))


@pytest.fixture(scope="module")
def run():
    return _Run(seed=4400000001)


def test_every_answer_and_the_state_equal_the_oracles(run):
    for got, want in run.answers:
        assert got == want
    host = run.led.to_host()
    assert host.accounts == run.sm.accounts
    assert host.transfers == run.sm.transfers


def test_cascades_past_the_unrolled_budgets_are_judged_on_the_device(run):
    assert max(run.rounds) > fk.LIMIT_FIXPOINT_ROUNDS_DEEP, run.rounds
    assert run.led.fallbacks == 0
    assert run.led.fallback_causes == {}
    assert set(run.led.judged) == {
        "create_accounts_fast", "create_transfers_fast",
        "create_transfers_fixpoint"}
    stats = run.led.fixpoint_rounds
    assert stats["dispatches"] == len(run.rounds)
    assert stats["rounds"] == sum(run.rounds)
    assert stats["rounds_max"] == max(run.rounds)


def test_the_rounds_are_those_of_a_plain_replay(run):
    assert len(run.rounds) >= REQUESTS - 1
    for rounds, ((replayed, over), want) in zip(run.rounds, run.replayed):
        assert rounds == replayed
        # The replay's decisions are the oracle's limit declines.
        assert over == [int(r.status) in LIMIT_CODES for r in want]


def test_the_round_loop_stops_at_a_two_cycle():
    """A closing pending in a chain whose next member debits the account
    it closes: applied, the pending closes the account and the member
    fails; rolled back, the account is open and the member passes. The
    decisions alternate, so the loop stops on the second round, which
    brings back the first's input, where a cap of N + 1 would run 1,025;
    the batch falls back to the host, which answers it."""
    led, sm = DeviceLedger(a_cap=1 << 10, t_cap=1 << 12), \
        StateMachineOracle()
    accounts = [Account(id=i, ledger=1, code=1) for i in (1, 2, 3)]
    for eng in (led, sm):
        eng.create_accounts(accounts, TS0)
    events = [
        Transfer(id=1, debit_account_id=2, credit_account_id=3, amount=1,
                 ledger=1, code=1, timeout=60,
                 flags=int(TF.linked | TF.pending | TF.closing_debit)),
        Transfer(id=2, debit_account_id=2, credit_account_id=1, amount=1,
                 ledger=1, code=1)]
    got = led.create_transfers(events, TS0 + 10)
    assert got == sm.create_transfers(events, TS0 + 10)
    assert led.fixpoint_rounds == {"dispatches": 1, "rounds": 2,
                                   "rounds_max": 2}
    assert led.fallbacks == 1


@pytest.mark.parametrize("op, scan", [("add", "_cumsum"), ("maximum", "_cummax")])
@pytest.mark.parametrize("shape, axis", [((1,), 0), ((1000,), 0),
                                         ((4, 4, 24), 2), ((3, 17), -1)])
def test_the_shift_scan_equals_the_associative_scan(op, scan, shape, axis):
    """The round loop's scans: the same prefix sums and maxima as the
    associative scans the unrolled tiers keep, bit for bit."""
    import jax.numpy as jnp

    rng = np.random.default_rng(45)
    for dtype in (np.uint64, np.int32):
        x = jnp.asarray(rng.integers(0 if dtype == np.uint64 else -9,
                                     1 << 30, size=shape).astype(dtype))
        want = getattr(fk, scan)(x, axis=axis)
        got = fk._shift_scan(getattr(jnp, op), x, axis=axis)
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------- limits on pending rows

CUSTOMER, OPERATOR = 1, 2


def _limited_pair(funding: int):
    """A DeviceLedger and the oracle, each holding a customer account
    that must not be overdrawn, funded with `funding` from an operator
    account that may."""
    led, sm = DeviceLedger(a_cap=1 << 9, t_cap=1 << 14), StateMachineOracle()
    accounts = [Account(id=CUSTOMER, ledger=1, code=1, flags=DR_LIMIT),
                Account(id=OPERATOR, ledger=1, code=1)]
    fund = [Transfer(id=1, debit_account_id=OPERATOR,
                     credit_account_id=CUSTOMER, amount=funding, ledger=1,
                     code=1)]
    for eng in (led, sm):
        eng.create_accounts(accounts, TS0)
        eng.create_transfers(fund, TS0 + 10)
    return led, sm


def _both(led, sm, events: list, ts: int) -> list:
    """One batch through both; the converging tier judged it on the
    device; the answers and the state afterwards equal the oracle's."""
    got = led.create_transfers(events, ts)
    want = sm.create_transfers(events, ts)
    assert got == want
    assert led._last_tier == "create_transfers_fixpoint"
    assert led.fallbacks == 0
    host = led.to_host()
    assert host.accounts == sm.accounts
    assert host.transfers == sm.transfers
    return [r.status for r in want]


def _pending(tid, dr, cr, amount):
    return Transfer(id=tid, debit_account_id=dr, credit_account_id=cr,
                    amount=amount, ledger=1, code=1, flags=int(TF.pending))


def _resolve(tid, pending_id, flag, amount=0):
    return Transfer(id=tid, pending_id=pending_id, amount=amount, ledger=1,
                    code=1, flags=int(flag))


def test_a_pending_credit_gives_a_limited_account_no_headroom():
    """A pending credit of 500 to the customer, then in the same batch a
    pending debit of 300 from it: the limit counts debits_pending and
    debits_posted against credits_posted alone, so the debit is declined
    on 100 of funding; a debit of 50 after it fits. The debits breach
    the headroom proof, and the converging tier judges the batch."""
    led, sm = _limited_pair(funding=100)
    got = _both(led, sm, [_pending(10, OPERATOR, CUSTOMER, 500),
                          _pending(11, CUSTOMER, OPERATOR, 300),
                          _pending(12, CUSTOMER, OPERATOR, 50)], TS0 + 100)
    assert got == [CreateTransferStatus.created,
                   CreateTransferStatus.exceeds_credits,
                   CreateTransferStatus.created]
    assert led.judged_kinds["pending"]["escalated"] == 1


def test_a_post_of_a_declined_pending_finds_no_pending():
    """The batch after: the post of the declined pending answers
    pending_transfer_not_found, the post of the one reserved is created
    and moves its 50 from debits_pending to debits_posted. The router's
    hysteresis sends the posts to the converging tier."""
    led, sm = _limited_pair(funding=100)
    _both(led, sm, [_pending(10, CUSTOMER, OPERATOR, 80),
                    _pending(11, CUSTOMER, OPERATOR, 50)], TS0 + 100)
    got = _both(led, sm, [_resolve(20, 11, TF.post_pending_transfer, 50),
                          _resolve(21, 10, TF.post_pending_transfer, 80)],
                TS0 + 200)
    assert got == [CreateTransferStatus.pending_transfer_not_found,
                   CreateTransferStatus.created]
    assert sm.accounts[CUSTOMER].debits_posted == 80
    assert sm.accounts[CUSTOMER].debits_pending == 0
    kinds = led.judged_kinds
    assert kinds["post_void"]["by_tier"] == {"create_transfers_fixpoint": 1}
    assert kinds["post_void"]["fixpoint_rounds"] == 1


def test_a_void_frees_the_headroom_for_a_later_request():
    """80 of the customer's 100 reserved (a second reservation of 50
    declined), then voided; a later request reserves 90, which fits only
    because the void released the 80, and a further 20 in the same
    request is declined. A CPU test only: the benchmark's cell sends no
    voids."""
    led, sm = _limited_pair(funding=100)
    _both(led, sm, [_pending(10, CUSTOMER, OPERATOR, 80),
                    _pending(11, CUSTOMER, OPERATOR, 50)], TS0 + 100)
    got = _both(led, sm, [_resolve(20, 10, TF.void_pending_transfer)],
                TS0 + 200)
    assert got == [CreateTransferStatus.created]
    assert sm.accounts[CUSTOMER].debits_pending == 0
    got = _both(led, sm, [_pending(30, CUSTOMER, OPERATOR, 90),
                          _pending(31, CUSTOMER, OPERATOR, 20)], TS0 + 300)
    assert got == [CreateTransferStatus.created,
                   CreateTransferStatus.exceeds_credits]
    assert sm.accounts[CUSTOMER].debits_pending == 90
    assert led.judged_kinds["pending"]["escalated"] == 2
