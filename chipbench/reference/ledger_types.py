"""The benchmark's own copy of the ledger's data model and wire codec.

Copied from tigerbeetle_tpu/types.py and constants.py (PR 26) so that
the plain reference and the traffic generator import nothing of the
program: a later PR may change the program, not this file. Python ints
for u128 fields; exact little-endian 128-byte wire layouts.
"""

from __future__ import annotations

import dataclasses
import enum
import struct

U128_MAX = (1 << 128) - 1
U64_MAX = (1 << 64) - 1
U63_MAX = (1 << 63) - 1
U32_MAX = (1 << 32) - 1
TIMESTAMP_MIN = 1
TIMESTAMP_MAX = U63_MAX
NS_PER_S = 1_000_000_000
BATCH_MAX = 8190


def timestamp_valid(timestamp: int) -> bool:
    return TIMESTAMP_MIN <= timestamp <= TIMESTAMP_MAX

class AccountFlags:
    """Plain ints, not an IntFlag: the enum's `&` was half of the
    reference's run time, which every benchmark run pays.
    reference: src/tigerbeetle.zig:45-68 (packed struct(u16), bit order = field order)."""

    linked = 1 << 0
    debits_must_not_exceed_credits = 1 << 1
    credits_must_not_exceed_debits = 1 << 2
    history = 1 << 3
    imported = 1 << 4
    closed = 1 << 5

    @staticmethod
    def padding_mask() -> int:
        return ~0x3F & 0xFFFF


class TransferFlags:
    """Plain ints (see AccountFlags).
    reference: src/tigerbeetle.zig:132-148 (packed struct(u16))."""

    linked = 1 << 0
    pending = 1 << 1
    post_pending_transfer = 1 << 2
    void_pending_transfer = 1 << 3
    balancing_debit = 1 << 4
    balancing_credit = 1 << 5
    closing_debit = 1 << 6
    closing_credit = 1 << 7
    imported = 1 << 8

    @staticmethod
    def padding_mask() -> int:
        return ~0x1FF & 0xFFFF


class TransferPendingStatus(enum.IntEnum):
    """reference: src/tigerbeetle.zig:118-130"""

    none = 0
    pending = 1
    posted = 2
    voided = 3
    expired = 4


# Struct formats (little-endian, no padding — reference structs are extern with
# comptime no_padding asserts; u128 fields serialized as 16 LE bytes).
_U128 = "16s"


def _u128_to_bytes(x: int) -> bytes:
    return x.to_bytes(16, "little")


def _u128_from_bytes(b: bytes) -> int:
    return int.from_bytes(b, "little")


_ACCOUNT_FMT = struct.Struct("<16s16s16s16s16s16sQIIIHHQ")
assert _ACCOUNT_FMT.size == 128


@dataclasses.dataclass
class Account:
    """reference: src/tigerbeetle.zig:10-43 — 128 bytes, no padding."""

    id: int = 0
    debits_pending: int = 0
    debits_posted: int = 0
    credits_pending: int = 0
    credits_posted: int = 0
    user_data_128: int = 0
    user_data_64: int = 0
    user_data_32: int = 0
    reserved: int = 0
    ledger: int = 0
    code: int = 0
    flags: int = 0
    timestamp: int = 0

    def pack(self) -> bytes:
        return _ACCOUNT_FMT.pack(
            _u128_to_bytes(self.id),
            _u128_to_bytes(self.debits_pending),
            _u128_to_bytes(self.debits_posted),
            _u128_to_bytes(self.credits_pending),
            _u128_to_bytes(self.credits_posted),
            _u128_to_bytes(self.user_data_128),
            self.user_data_64,
            self.user_data_32,
            self.reserved,
            self.ledger,
            self.code,
            self.flags,
            self.timestamp,
        )

    @classmethod
    def unpack(cls, data: bytes) -> "Account":
        f = _ACCOUNT_FMT.unpack(data)
        return cls(
            id=_u128_from_bytes(f[0]),
            debits_pending=_u128_from_bytes(f[1]),
            debits_posted=_u128_from_bytes(f[2]),
            credits_pending=_u128_from_bytes(f[3]),
            credits_posted=_u128_from_bytes(f[4]),
            user_data_128=_u128_from_bytes(f[5]),
            user_data_64=f[6],
            user_data_32=f[7],
            reserved=f[8],
            ledger=f[9],
            code=f[10],
            flags=f[11],
            timestamp=f[12],
        )

    def debits_exceed_credits(self, amount: int) -> bool:
        """reference: src/tigerbeetle.zig:34-38"""
        return bool(
            self.flags & AccountFlags.debits_must_not_exceed_credits
            and self.debits_pending + self.debits_posted + amount > self.credits_posted
        )

    def credits_exceed_debits(self, amount: int) -> bool:
        """reference: src/tigerbeetle.zig:39-42"""
        return bool(
            self.flags & AccountFlags.credits_must_not_exceed_debits
            and self.credits_pending + self.credits_posted + amount > self.debits_posted
        )


_TRANSFER_FMT = struct.Struct("<16s16s16s16s16s16sQIIIHHQ")
assert _TRANSFER_FMT.size == 128


@dataclasses.dataclass
class Transfer:
    """reference: src/tigerbeetle.zig:85-116 — 128 bytes, no padding."""

    id: int = 0
    debit_account_id: int = 0
    credit_account_id: int = 0
    amount: int = 0
    pending_id: int = 0
    user_data_128: int = 0
    user_data_64: int = 0
    user_data_32: int = 0
    timeout: int = 0
    ledger: int = 0
    code: int = 0
    flags: int = 0
    timestamp: int = 0

    def timeout_ns(self) -> int:
        """reference: src/tigerbeetle.zig:106-109"""
        return self.timeout * NS_PER_S

    def pack(self) -> bytes:
        return _TRANSFER_FMT.pack(
            _u128_to_bytes(self.id),
            _u128_to_bytes(self.debit_account_id),
            _u128_to_bytes(self.credit_account_id),
            _u128_to_bytes(self.amount),
            _u128_to_bytes(self.pending_id),
            _u128_to_bytes(self.user_data_128),
            self.user_data_64,
            self.user_data_32,
            self.timeout,
            self.ledger,
            self.code,
            self.flags,
            self.timestamp,
        )

    @classmethod
    def unpack(cls, data: bytes) -> "Transfer":
        f = _TRANSFER_FMT.unpack(data)
        return cls(
            id=_u128_from_bytes(f[0]),
            debit_account_id=_u128_from_bytes(f[1]),
            credit_account_id=_u128_from_bytes(f[2]),
            amount=_u128_from_bytes(f[3]),
            pending_id=_u128_from_bytes(f[4]),
            user_data_128=_u128_from_bytes(f[5]),
            user_data_64=f[6],
            user_data_32=f[7],
            timeout=f[8],
            ledger=f[9],
            code=f[10],
            flags=f[11],
            timestamp=f[12],
        )


_ACCOUNT_BALANCE_FMT = struct.Struct("<16s16s16s16sQ56s")
assert _ACCOUNT_BALANCE_FMT.size == 128


@dataclasses.dataclass
class AccountBalance:
    """reference: src/tigerbeetle.zig:70-83 — 128 bytes."""

    debits_pending: int = 0
    debits_posted: int = 0
    credits_pending: int = 0
    credits_posted: int = 0
    timestamp: int = 0

    def pack(self) -> bytes:
        return _ACCOUNT_BALANCE_FMT.pack(
            _u128_to_bytes(self.debits_pending),
            _u128_to_bytes(self.debits_posted),
            _u128_to_bytes(self.credits_pending),
            _u128_to_bytes(self.credits_posted),
            self.timestamp,
            b"\x00" * 56,
        )

    @classmethod
    def unpack(cls, data: bytes) -> "AccountBalance":
        f = _ACCOUNT_BALANCE_FMT.unpack(data)
        return cls(
            debits_pending=_u128_from_bytes(f[0]),
            debits_posted=_u128_from_bytes(f[1]),
            credits_pending=_u128_from_bytes(f[2]),
            credits_posted=_u128_from_bytes(f[3]),
            timestamp=f[4],
        )


class CreateAccountStatus(enum.IntEnum):
    """Wire codes (reference: src/tigerbeetle.zig:153-215).

    Declaration order here matches the reference's declaration order, which is
    the *precedence* order (descending). Use CREATE_ACCOUNT_PRECEDENCE for
    rank comparisons; the numeric values are wire-compatible codes.
    """

    ok = 0  # deprecated_ok
    created = (1 << 32) - 1  # maxInt(u32)

    linked_event_failed = 1
    linked_event_chain_open = 2

    imported_event_expected = 22
    imported_event_not_expected = 23

    timestamp_must_be_zero = 3

    imported_event_timestamp_out_of_range = 24
    imported_event_timestamp_must_not_advance = 25

    reserved_field = 4
    reserved_flag = 5

    id_must_not_be_zero = 6
    id_must_not_be_int_max = 7

    exists_with_different_flags = 15
    exists_with_different_user_data_128 = 16
    exists_with_different_user_data_64 = 17
    exists_with_different_user_data_32 = 18
    exists_with_different_ledger = 19
    exists_with_different_code = 20
    exists = 21

    flags_are_mutually_exclusive = 8

    debits_pending_must_be_zero = 9
    debits_posted_must_be_zero = 10
    credits_pending_must_be_zero = 11
    credits_posted_must_be_zero = 12
    ledger_must_not_be_zero = 13
    code_must_not_be_zero = 14

    imported_event_timestamp_must_not_regress = 26


class CreateTransferStatus(enum.IntEnum):
    """Wire codes (reference: src/tigerbeetle.zig:220-319). Declaration order =
    precedence (descending), numeric values = wire codes."""

    ok = 0  # deprecated_ok
    created = (1 << 32) - 1  # maxInt(u32)

    linked_event_failed = 1
    linked_event_chain_open = 2

    imported_event_expected = 56
    imported_event_not_expected = 57

    timestamp_must_be_zero = 3

    imported_event_timestamp_out_of_range = 58
    imported_event_timestamp_must_not_advance = 59

    reserved_flag = 4

    id_must_not_be_zero = 5
    id_must_not_be_int_max = 6

    exists_with_different_flags = 36
    exists_with_different_pending_id = 40
    exists_with_different_timeout = 44
    exists_with_different_debit_account_id = 37
    exists_with_different_credit_account_id = 38
    exists_with_different_amount = 39
    exists_with_different_user_data_128 = 41
    exists_with_different_user_data_64 = 42
    exists_with_different_user_data_32 = 43
    exists_with_different_ledger = 67
    exists_with_different_code = 45
    exists = 46

    id_already_failed = 68

    flags_are_mutually_exclusive = 7

    debit_account_id_must_not_be_zero = 8
    debit_account_id_must_not_be_int_max = 9
    credit_account_id_must_not_be_zero = 10
    credit_account_id_must_not_be_int_max = 11
    accounts_must_be_different = 12

    pending_id_must_be_zero = 13
    pending_id_must_not_be_zero = 14
    pending_id_must_not_be_int_max = 15
    pending_id_must_be_different = 16
    timeout_reserved_for_pending_transfer = 17

    closing_transfer_must_be_pending = 64

    ledger_must_not_be_zero = 19
    code_must_not_be_zero = 20

    debit_account_not_found = 21
    credit_account_not_found = 22

    accounts_must_have_the_same_ledger = 23
    transfer_must_have_the_same_ledger_as_accounts = 24

    pending_transfer_not_found = 25
    pending_transfer_not_pending = 26

    pending_transfer_has_different_debit_account_id = 27
    pending_transfer_has_different_credit_account_id = 28
    pending_transfer_has_different_ledger = 29
    pending_transfer_has_different_code = 30

    exceeds_pending_transfer_amount = 31
    pending_transfer_has_different_amount = 32

    pending_transfer_already_posted = 33
    pending_transfer_already_voided = 34

    pending_transfer_expired = 35

    imported_event_timestamp_must_not_regress = 60
    imported_event_timestamp_must_postdate_debit_account = 61
    imported_event_timestamp_must_postdate_credit_account = 62
    imported_event_timeout_must_be_zero = 63

    debit_account_already_closed = 65
    credit_account_already_closed = 66

    overflows_debits_pending = 47
    overflows_credits_pending = 48
    overflows_debits_posted = 49
    overflows_credits_posted = 50
    overflows_debits = 51
    overflows_credits = 52
    overflows_timeout = 53

    exceeds_credits = 54
    exceeds_debits = 55

    deprecated_18 = 18  # amount_must_not_be_zero

    def transient(self) -> bool:
        """Transient errors poison the transfer id: retrying with the same id
        returns id_already_failed (reference: src/tigerbeetle.zig:320-399,
        src/state_machine.zig:3215-3252)."""
        return self in _TRANSIENT_TRANSFER_STATUSES


_TRANSIENT_TRANSFER_STATUSES = frozenset(
    {
        CreateTransferStatus.debit_account_not_found,
        CreateTransferStatus.credit_account_not_found,
        CreateTransferStatus.pending_transfer_not_found,
        CreateTransferStatus.exceeds_credits,
        CreateTransferStatus.exceeds_debits,
        CreateTransferStatus.debit_account_already_closed,
        CreateTransferStatus.credit_account_already_closed,
    }
)

# Precedence rank tables: rank by declaration order (lower rank = higher
# precedence = reported first when several checks fail). `created` ranks last
# (reference Ordered enum: src/tigerbeetle.zig:432-468).
def _precedence(enum_cls, created):
    errors = [s for s in enum_cls if s not in (enum_cls.ok, created)]
    table = {status: rank for rank, status in enumerate(errors)}
    table[created] = len(errors)
    return table


CREATE_ACCOUNT_PRECEDENCE = _precedence(CreateAccountStatus, CreateAccountStatus.created)
CREATE_TRANSFER_PRECEDENCE = _precedence(CreateTransferStatus, CreateTransferStatus.created)


_RESULT_FMT = struct.Struct("<QII")
assert _RESULT_FMT.size == 16


@dataclasses.dataclass
class CreateAccountResult:
    """reference: src/tigerbeetle.zig:471-481 — {timestamp: u64, status: u32, reserved: u32}."""

    timestamp: int = 0
    status: CreateAccountStatus = CreateAccountStatus.ok

    def pack(self) -> bytes:
        return _RESULT_FMT.pack(self.timestamp, int(self.status), 0)

    @classmethod
    def unpack(cls, data: bytes) -> "CreateAccountResult":
        t, s, _ = _RESULT_FMT.unpack(data)
        return cls(timestamp=t, status=CreateAccountStatus(s))


@dataclasses.dataclass
class CreateTransferResult:
    """reference: src/tigerbeetle.zig:483-493."""

    timestamp: int = 0
    status: CreateTransferStatus = CreateTransferStatus.ok

    def pack(self) -> bytes:
        return _RESULT_FMT.pack(self.timestamp, int(self.status), 0)

    @classmethod
    def unpack(cls, data: bytes) -> "CreateTransferResult":
        t, s, _ = _RESULT_FMT.unpack(data)
        return cls(timestamp=t, status=CreateTransferStatus(s))


