"""Full 33-tree forest schema (reference: src/state_machine.zig:45-90
tree_ids — accounts 9, transfers 14, transfers_pending 2, account_events 8)
and the queries/cleanup the new trees serve."""

from tigerbeetle_tpu.lsm.query import ForestQuery
from tigerbeetle_tpu.lsm.scan import TreeScan, composite_key
from tigerbeetle_tpu.state_machine import StateMachine
from tigerbeetle_tpu.types import (
    Account,
    AccountFilter,
    AccountFilterFlags,
    AccountFlags,
    ChangeEventsFilter,
    CreateTransferStatus,
    Transfer,
    TransferFlags,
)
from tigerbeetle_tpu.vsr.durable import SCHEMA, DurableState
from tigerbeetle_tpu.vsr.storage import TEST_LAYOUT, MemoryStorage

TS_MAX = (1 << 63) - 1


CREATED = CreateTransferStatus.created


def _mk():
    sm = StateMachine(engine="oracle")
    storage = MemoryStorage(TEST_LAYOUT)
    durable = DurableState(storage)
    return sm, durable, storage


def _count(tree, key_min: bytes, key_max: bytes) -> int:
    return sum(1 for _ in TreeScan(tree, key_min, key_max))


class TestFullForestSchema:
    def test_schema_has_33_trees(self):
        # reference: 4 grooves / 33 trees with fixed ids 1..33
        # (src/state_machine.zig:45-90).
        assert len(SCHEMA) == 33

    def test_closed_index_tracks_reopen(self):
        sm, durable, storage = _mk()
        ts = 1000
        sm.create_accounts(
            [Account(id=i, ledger=1, code=1) for i in (1, 2)], ts)
        ts += 100
        closing = [Transfer(id=10, debit_account_id=1, credit_account_id=2,
                            amount=0, ledger=1, code=1,
                            flags=int(TransferFlags.pending
                                      | TransferFlags.closing_debit))]
        res = sm.create_transfers(closing, ts)
        assert res[0].status == CREATED
        durable.flush(sm.state)
        trees = durable.forest.trees
        a1_ts = sm.state.accounts[1].timestamp
        key = composite_key(1, a1_ts, 1)
        assert trees["acct_by_closed"].get(key) == b"\x01"
        assert trees["xfer_by_closing"].get(
            composite_key(1, sm.state.transfers[10].timestamp, 1)) == b"\x01"

        ts += 100
        void = [Transfer(id=11, pending_id=10, ledger=1, code=1,
                         flags=int(TransferFlags.void_pending_transfer))]
        res = sm.create_transfers(void, ts)
        assert res[0].status == CREATED
        assert not (sm.state.accounts[1].flags & AccountFlags.closed)
        durable.flush(sm.state)
        assert trees["acct_by_closed"].get(key) is None  # reopened

    def test_amount_and_imported_indexes(self):
        sm, durable, storage = _mk()
        imported = int(AccountFlags.imported)
        sm.create_accounts(
            [Account(id=1, ledger=1, code=1, flags=imported, timestamp=100),
             Account(id=2, ledger=1, code=1, flags=imported, timestamp=101)],
            timestamp=1000)
        sm.create_transfers(
            [Transfer(id=10, debit_account_id=1, credit_account_id=2,
                      amount=777, ledger=1, code=1,
                      flags=int(TransferFlags.imported), timestamp=500)],
            timestamp=2000)
        assert 10 in sm.state.transfers
        durable.flush(sm.state)
        trees = durable.forest.trees
        assert _count(trees["acct_by_imported"],
                      composite_key(1, 1, 1), composite_key(1, TS_MAX, 1)) == 2
        assert trees["xfer_by_amount"].get(
            composite_key(777, 500, 16)) == b"\x01"
        assert trees["xfer_by_imported"].get(
            composite_key(1, 500, 1)) == b"\x01"

    def test_account_timestamp_event_index(self):
        sm, durable, storage = _mk()
        hist = int(AccountFlags.history)
        sm.create_accounts(
            [Account(id=1, ledger=1, code=1, flags=hist),
             Account(id=2, ledger=1, code=1)], 1000)
        ts = 2000
        for i in range(4):
            sm.create_transfers(
                [Transfer(id=100 + i, debit_account_id=1,
                          credit_account_id=2, amount=5 + i,
                          ledger=1, code=1)], ts)
            ts += 100
        durable.flush(sm.state)
        q = ForestQuery(durable.forest)
        a1_ts = sm.state.accounts[1].timestamp
        rows = q.account_history_events(a1_ts)
        # Only account 1 has history: one index row per event, debit side.
        assert len(rows) == 4
        assert [r.debits_posted for r in rows] == [5, 11, 18, 26]
        # Exactly the rows get_account_balances serves for the account.
        f = AccountFilter(
            account_id=1, limit=8190,
            flags=int(AccountFilterFlags.debits | AccountFilterFlags.credits))
        assert [(b.timestamp, b.debits_posted)
                for b in q.get_account_balances(f)] == \
               [(r.timestamp, r.debits_posted) for r in rows]
        # The no-history account contributed no index rows.
        a2_ts = sm.state.accounts[2].timestamp
        assert q.account_history_events(a2_ts) == []

    def test_expired_event_indexes(self):
        sm, durable, storage = _mk()
        sm.create_accounts(
            [Account(id=1, ledger=7, code=1), Account(id=2, ledger=7, code=1)],
            1000)
        sm.create_transfers(
            [Transfer(id=10, debit_account_id=1, credit_account_id=2,
                      amount=50, ledger=7, code=1,
                      flags=int(TransferFlags.pending), timeout=1)],
            2_000_000_000)
        expired = sm.state.expire_pending_transfers(10_000_000_000)
        assert expired == 1
        durable.flush(sm.state)
        q = ForestQuery(durable.forest)
        rec = q.expiry_event_of_pending(10)
        assert rec is not None and rec.transfer_pending.id == 10
        assert [r.transfer_pending.id
                for r in q.expired_events_by_account(1, "dr")] == [10]
        assert [r.transfer_pending.id
                for r in q.expired_events_by_account(2, "cr")] == [10]
        trees = durable.forest.trees
        assert _count(trees["ev_by_ledger_expired"],
                      composite_key(7, 1, 4),
                      composite_key(7, TS_MAX, 4)) == 1
        # Pending-status index has one row per event (2 creates + 1 pending
        # + 1 expiry here).
        assert _count(trees["ev_by_pstat"],
                      composite_key(0, 1, 1),
                      composite_key(4, TS_MAX, 1)) == len(
                          sm.state.account_events)

    def test_prunable_index_and_prune_job(self):
        sm, durable, storage = _mk()
        hist = int(AccountFlags.history)
        sm.create_accounts(
            [Account(id=1, ledger=1, code=1, flags=hist),
             Account(id=2, ledger=1, code=1),
             Account(id=3, ledger=1, code=1)], 1000)
        ts = 2000
        # 1<->2 events keep history (account 1); 2<->3 events are prunable.
        sm.create_transfers(
            [Transfer(id=10, debit_account_id=1, credit_account_id=2,
                      amount=5, ledger=1, code=1)], ts)
        sm.create_transfers(
            [Transfer(id=11, debit_account_id=2, credit_account_id=3,
                      amount=6, ledger=1, code=1)], ts + 100)
        durable.flush(sm.state)
        trees = durable.forest.trees
        n_events = len(sm.state.account_events)
        assert _count(trees["events"], bytes(8), b"\xff" * 8) == n_events
        prunable = _count(trees["ev_by_prunable"], bytes(8), b"\xff" * 8)
        assert prunable == 1  # only the 2->3 transfer event
        q = ForestQuery(durable.forest)
        before = q.get_change_events(ChangeEventsFilter(limit=100))
        pruned = durable.prune_events(TS_MAX)
        assert pruned == 1
        assert _count(trees["events"], bytes(8), b"\xff" * 8) == n_events - 1
        assert _count(trees["ev_by_prunable"], bytes(8), b"\xff" * 8) == 0
        after = q.get_change_events(ChangeEventsFilter(limit=100))
        assert len(after) == len(before) - 1
        # History rows survive: the account_timestamp index still serves.
        a1_ts = sm.state.accounts[1].timestamp
        assert len(q.account_history_events(a1_ts)) == 1

    def test_checkpoint_after_prune_still_opens(self):
        """A checkpoint taken after prune_events must restore (the meta
        events count is monotonic; the tree holds fewer rows) — and
        further flushes must persist exactly the new tail."""
        sm, durable, storage = _mk()
        sm.create_accounts(
            [Account(id=1, ledger=1, code=1),
             Account(id=2, ledger=1, code=1)], 1000)
        sm.create_transfers(
            [Transfer(id=10, debit_account_id=1, credit_account_id=2,
                      amount=5, ledger=1, code=1)], 2000)
        durable.flush(sm.state)
        assert durable.prune_events(TS_MAX) == len(sm.state.account_events)
        root = durable.checkpoint(sm.state)

        durable2 = DurableState(storage)
        restored = durable2.open(root)  # load_events=True must not raise
        assert restored.account_events == []
        assert restored.events_base == len(sm.state.account_events)
        # New events after restore land in the tree exactly once.
        restored.create_transfers(
            [Transfer(id=11, debit_account_id=1, credit_account_id=2,
                      amount=6, ledger=1, code=1)], 3000)
        durable2.flush(restored)
        trees = durable2.forest.trees
        assert _count(trees["events"], bytes(8), b"\xff" * 8) == 1

    def test_closed_index_writes_only_on_transitions(self):
        """Balance churn on never-closed accounts must not touch
        acct_by_closed (write-amp guard)."""
        sm, durable, storage = _mk()
        sm.create_accounts(
            [Account(id=1, ledger=1, code=1),
             Account(id=2, ledger=1, code=1)], 1000)
        ts = 2000
        for i in range(5):
            sm.create_transfers(
                [Transfer(id=100 + i, debit_account_id=1,
                          credit_account_id=2, amount=1,
                          ledger=1, code=1)], ts)
            ts += 100
            durable.flush(sm.state)
        assert durable.forest.trees["acct_by_closed"].memtable_rows() == {}

    def test_checkpoint_roundtrip_with_full_schema(self):
        sm, durable, storage = _mk()
        hist = int(AccountFlags.history)
        sm.create_accounts(
            [Account(id=1, ledger=1, code=1, flags=hist),
             Account(id=2, ledger=1, code=1)], 1000)
        sm.create_transfers(
            [Transfer(id=10, debit_account_id=1, credit_account_id=2,
                      amount=5, ledger=1, code=1,
                      flags=int(TransferFlags.pending), timeout=1)], 2000)
        sm.state.expire_pending_transfers(10**12)
        root = durable.checkpoint(sm.state)
        durable2 = DurableState(storage)
        durable2.open(root)
        q = ForestQuery(durable2.forest)
        assert q.expiry_event_of_pending(10) is not None
        a1_ts = sm.state.accounts[1].timestamp
        assert len(q.account_history_events(a1_ts)) == 2  # create + expiry
