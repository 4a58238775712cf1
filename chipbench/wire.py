"""Wire layouts as numpy record types, and the multi-batch trailer.

The benchmark builds request bodies and reads replies with its own
codec (upstream: src/tigerbeetle.zig Account/Transfer, 128 bytes each,
no padding; src/vsr/multi_batch.zig trailer), so that what is sent and
what is compared do not depend on the program's packers. u128 fields
are (lo, hi) u64 pairs, little-endian.
"""

from __future__ import annotations

import struct

import numpy as np

TRANSFER = np.dtype([
    ("id_lo", "<u8"), ("id_hi", "<u8"),
    ("debit_lo", "<u8"), ("debit_hi", "<u8"),
    ("credit_lo", "<u8"), ("credit_hi", "<u8"),
    ("amount_lo", "<u8"), ("amount_hi", "<u8"),
    ("pending_lo", "<u8"), ("pending_hi", "<u8"),
    ("ud128_lo", "<u8"), ("ud128_hi", "<u8"),
    ("ud64", "<u8"), ("ud32", "<u4"), ("timeout", "<u4"),
    ("ledger", "<u4"), ("code", "<u2"), ("flags", "<u2"),
    ("timestamp", "<u8")])
ACCOUNT = np.dtype([
    ("id_lo", "<u8"), ("id_hi", "<u8"),
    ("debits_pending_lo", "<u8"), ("debits_pending_hi", "<u8"),
    ("debits_posted_lo", "<u8"), ("debits_posted_hi", "<u8"),
    ("credits_pending_lo", "<u8"), ("credits_pending_hi", "<u8"),
    ("credits_posted_lo", "<u8"), ("credits_posted_hi", "<u8"),
    ("ud128_lo", "<u8"), ("ud128_hi", "<u8"),
    ("ud64", "<u8"), ("ud32", "<u4"), ("reserved", "<u4"),
    ("ledger", "<u4"), ("code", "<u2"), ("flags", "<u2"),
    ("timestamp", "<u8")])
RESULT = np.dtype([("timestamp", "<u8"), ("status", "<u4"), ("pad", "<u4")])
assert TRANSFER.itemsize == 128 and ACCOUNT.itemsize == 128
assert RESULT.itemsize == 16

CREATED = (1 << 32) - 1  # CreateTransferStatus.created / CreateAccountStatus.created
ID_SIZE = 16
_PAD = 0xFFFF


def trailer_size(element_size: int) -> int:
    """Trailer bytes of a one-batch body: two u16s (count, batch_count)
    rounded up to the element size."""
    return -(-4 // element_size) * element_size


def encode_one(payload: bytes, element_size: int) -> bytes:
    """A one-batch multi-batch body: payload, 0xFFFF padding, the
    batch's element count, then the batch count (1) as the last u16."""
    assert len(payload) % element_size == 0
    n_items = trailer_size(element_size) // 2
    items = [_PAD] * n_items
    items[-1] = 1
    items[-2] = len(payload) // element_size
    return payload + struct.pack(f"<{n_items}H", *items)


def decode_one(body: bytes, element_size: int) -> bytes:
    """The payload of a one-batch reply body; ValueError if the trailer
    does not describe exactly one batch filling the body."""
    tsize = trailer_size(element_size)
    if len(body) < tsize:
        raise ValueError(f"reply body of {len(body)} bytes has no trailer")
    count, batches = struct.unpack_from("<HH", body, len(body) - 4)
    if batches != 1 or count * element_size + tsize != len(body):
        raise ValueError(
            f"reply trailer says {batches} batches, {count} elements of "
            f"{element_size} B; body is {len(body)} B")
    return body[:len(body) - tsize]


def ids_payload(ids: list[int]) -> bytes:
    return b"".join(i.to_bytes(ID_SIZE, "little") for i in ids)
