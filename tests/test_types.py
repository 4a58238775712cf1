"""Data-model tests: sizes, codec round-trips, enum codes, precedence order.

Modeled on the reference's inline comptime asserts (src/tigerbeetle.zig:28-32,
111-115, 193-214, 401-423) and unit tests.
"""

import pytest

from tigerbeetle_tpu.constants import BATCH_MAX, U128_MAX
from tigerbeetle_tpu.types import (
    Account,
    AccountBalance,
    AccountFilter,
    AccountFlags,
    ChangeEventsFilter,
    CREATE_ACCOUNT_PRECEDENCE,
    CREATE_TRANSFER_PRECEDENCE,
    CreateAccountResult,
    CreateAccountStatus,
    CreateTransferResult,
    CreateTransferStatus,
    Operation,
    QueryFilter,
    Transfer,
    TransferFlags,
)


def test_sizes():
    assert len(Account().pack()) == 128
    assert len(Transfer().pack()) == 128
    assert len(AccountBalance().pack()) == 128
    assert len(AccountFilter().pack()) == 128
    assert len(QueryFilter().pack()) == 64
    assert len(ChangeEventsFilter().pack()) == 64
    assert len(CreateAccountResult().pack()) == 16
    assert len(CreateTransferResult().pack()) == 16
    assert BATCH_MAX == 8190


def test_account_roundtrip():
    a = Account(
        id=(1 << 127) + 5,
        debits_pending=1,
        debits_posted=(1 << 100),
        credits_pending=3,
        credits_posted=4,
        user_data_128=U128_MAX - 1,
        user_data_64=2**64 - 2,
        user_data_32=7,
        ledger=700,
        code=17,
        flags=int(AccountFlags.history | AccountFlags.closed),
        timestamp=999,
    )
    assert Account.unpack(a.pack()) == a


def test_transfer_roundtrip():
    t = Transfer(
        id=123456789012345678901234567890,
        debit_account_id=1,
        credit_account_id=2,
        amount=U128_MAX,
        pending_id=42,
        user_data_128=5,
        user_data_64=6,
        user_data_32=7,
        timeout=3600,
        ledger=1,
        code=1,
        flags=int(TransferFlags.pending | TransferFlags.linked),
        timestamp=1234,
    )
    assert Transfer.unpack(t.pack()) == t


def test_transfer_field_offsets():
    """Wire layout byte-for-byte (reference extern struct field order)."""
    t = Transfer(id=1, debit_account_id=2, credit_account_id=3, amount=4,
                 pending_id=5, user_data_128=6, user_data_64=7, user_data_32=8,
                 timeout=9, ledger=10, code=11, flags=12, timestamp=13)
    raw = t.pack()
    assert int.from_bytes(raw[0:16], "little") == 1
    assert int.from_bytes(raw[16:32], "little") == 2
    assert int.from_bytes(raw[32:48], "little") == 3
    assert int.from_bytes(raw[48:64], "little") == 4
    assert int.from_bytes(raw[64:80], "little") == 5
    assert int.from_bytes(raw[80:96], "little") == 6
    assert int.from_bytes(raw[96:104], "little") == 7
    assert int.from_bytes(raw[104:108], "little") == 8
    assert int.from_bytes(raw[108:112], "little") == 9
    assert int.from_bytes(raw[112:116], "little") == 10
    assert int.from_bytes(raw[116:118], "little") == 11
    assert int.from_bytes(raw[118:120], "little") == 12
    assert int.from_bytes(raw[120:128], "little") == 13


def test_status_wire_codes():
    """Spot-check wire codes against reference values (tigerbeetle.zig:153-319)."""
    assert CreateAccountStatus.linked_event_failed == 1
    assert CreateAccountStatus.exists == 21
    assert CreateAccountStatus.imported_event_timestamp_must_not_regress == 26
    assert CreateAccountStatus.created == (1 << 32) - 1

    assert CreateTransferStatus.linked_event_failed == 1
    assert CreateTransferStatus.exists == 46
    assert CreateTransferStatus.id_already_failed == 68
    assert CreateTransferStatus.exceeds_credits == 54
    assert CreateTransferStatus.exceeds_debits == 55
    assert CreateTransferStatus.exists_with_different_ledger == 67
    assert CreateTransferStatus.created == (1 << 32) - 1


def test_status_codes_dense():
    """Codes 1..max must be gap-free (reference comptime asserts :193-214)."""
    account_codes = {int(s) for s in CreateAccountStatus} - {0, (1 << 32) - 1}
    assert account_codes == set(range(1, 27))
    transfer_codes = {int(s) for s in CreateTransferStatus} - {0, (1 << 32) - 1}
    assert transfer_codes == set(range(1, 69))


def test_precedence_order():
    """Precedence = declaration order, not numeric order."""
    P = CREATE_TRANSFER_PRECEDENCE
    # imported_event_expected (code 56) outranks timestamp_must_be_zero (code 3).
    assert P[CreateTransferStatus.imported_event_expected] < P[CreateTransferStatus.timestamp_must_be_zero]
    # exists checks outrank flags_are_mutually_exclusive.
    assert P[CreateTransferStatus.exists] < P[CreateTransferStatus.flags_are_mutually_exclusive]
    # exceeds_credits is almost last.
    assert P[CreateTransferStatus.exceeds_credits] > P[CreateTransferStatus.overflows_timeout]
    assert P[CreateTransferStatus.linked_event_failed] == 0
    assert CREATE_ACCOUNT_PRECEDENCE[CreateAccountStatus.linked_event_failed] == 0
    # created ranks last in both.
    assert P[CreateTransferStatus.created] == max(P.values())


def test_transient_statuses():
    assert CreateTransferStatus.debit_account_not_found.transient()
    assert CreateTransferStatus.exceeds_credits.transient()
    assert CreateTransferStatus.debit_account_already_closed.transient()
    assert not CreateTransferStatus.exists.transient()
    assert not CreateTransferStatus.linked_event_failed.transient()
    assert not CreateTransferStatus.overflows_debits.transient()


def test_balance_limit_predicates():
    a = Account(
        flags=int(AccountFlags.debits_must_not_exceed_credits),
        debits_pending=10,
        debits_posted=20,
        credits_posted=100,
    )
    assert not a.debits_exceed_credits(70)
    assert a.debits_exceed_credits(71)
    assert not a.credits_exceed_debits(10**30)  # flag not set


def test_operation_codes():
    assert Operation.pulse == 128
    assert Operation.create_accounts == 146
    assert Operation.create_transfers == 147
    assert Operation.create_transfers.is_batchable()
    assert Operation.create_transfers.is_multi_batch()
    assert not Operation.get_change_events.is_multi_batch()
    assert not Operation.pulse.is_batchable()


@pytest.mark.parametrize("message_size_max", [1024 * 1024, 64 * 1024])
@pytest.mark.parametrize("op_name", ["create_accounts", "create_transfers",
                                     "lookup_accounts", "lookup_transfers"])
def test_client_refuses_what_the_replica_would_drop(op_name,
                                                    message_size_max):
    """clients/common.py admits exactly what the replica's on_request
    admits: the largest count passes input_valid and both size bounds,
    one more raises (it used to be sent, dropped in silence, and resent
    until the client's timeout)."""
    from tigerbeetle_tpu.clients.common import encode_batch, events_max
    from tigerbeetle_tpu.constants import HEADER_SIZE
    from tigerbeetle_tpu.state_machine import OPERATION_SPECS, StateMachine
    from tigerbeetle_tpu.types import Operation
    from tigerbeetle_tpu.vsr.replica import _reply_fits

    op = Operation[op_name]
    body_max = message_size_max - HEADER_SIZE
    n = events_max(op, body_max)
    event = b"\x01" * OPERATION_SPECS[op].event_size
    body = encode_batch(op, [event] * n, body_max)
    assert HEADER_SIZE + len(body) <= message_size_max
    assert _reply_fits(op, len(body), message_size_max)
    assert StateMachine(engine="oracle").input_valid(op, body)
    with pytest.raises(ValueError, match=f"carries {n}"):
        encode_batch(op, [event] * (n + 1), body_max)
    # One more would break a replica bound, so n is the maximum.
    over = encode_batch(op, [event] * (n + 1), 10 ** 9)
    assert (HEADER_SIZE + len(over) > message_size_max
            or not _reply_fits(op, len(over), message_size_max))


def test_served_maximum_is_one_below_batch_max():
    from tigerbeetle_tpu.clients.common import events_max
    from tigerbeetle_tpu.constants import BATCH_MAX
    from tigerbeetle_tpu.types import Operation

    assert BATCH_MAX == 8190
    assert events_max(Operation.create_transfers) == 8189
    assert events_max(Operation.create_accounts) == 8189
