"""Typed request helpers shared by every client stack.

Both the pure-Python client (vsr/client.py) and the native C binding
(clients/c_client.py) expose `request(operation, body) -> bytes`; these
helpers encode/decode the operation payloads on top of it (reference: the
per-language typed wrappers over tb_client share batch encoding the same
way, src/clients/*).
"""

from __future__ import annotations

from .. import multi_batch
from ..constants import MESSAGE_BODY_SIZE_MAX
from ..state_machine import OPERATION_SPECS
from ..types import (
    Account,
    CreateAccountResult,
    CreateTransferResult,
    Operation,
    Transfer,
)


def events_max(operation: Operation,
               body_max: int = MESSAGE_BODY_SIZE_MAX) -> int:
    """The most events one single-batch request of `operation` may carry
    on a layout whose message body holds `body_max` bytes, by the
    replica's own admission rules (vsr/replica.py on_request): the
    events and the multi-batch trailer must fit the request, and where
    results are wider than events (lookups) the worst-case reply must
    fit too. On the production layout that is 8189 for create_accounts/
    create_transfers: a bare 128-byte-event body holds BATCH_MAX = 8190,
    the trailer takes the last slot."""
    spec = OPERATION_SPECS[operation]
    size = spec.event_size
    trailer = (multi_batch.trailer_size(1, size)
               if operation.is_multi_batch() else 0)
    n = (body_max - trailer) // size
    if spec.result_size > size:
        # _reply_fits counts every `size` bytes of the body, trailer
        # included, as one worst-case result next to the body itself.
        n = min(n, body_max // (spec.result_size + size) - trailer // size)
    return n


def encode_batch(operation: Operation, events: list,
                 body_max: int = MESSAGE_BODY_SIZE_MAX) -> bytes:
    """One single-batch request body, refused here — not dropped by the
    replica until the client's timeout — when it cannot fit a message."""
    limit = events_max(operation, body_max)
    if len(events) > limit:
        raise ValueError(
            f"{operation.name}: {len(events)} events exceed the "
            f"{body_max}-byte message body; the largest admissible "
            f"request carries {limit}")
    size = OPERATION_SPECS[operation].event_size
    return multi_batch.encode([b"".join(events)], size)


class ClientHelpers:
    """Mixin over a `request(operation: Operation, body: bytes) -> bytes`."""

    def create_accounts(self, accounts: list[Account]) -> list[CreateAccountResult]:
        body = encode_batch(Operation.create_accounts,
                            [a.pack() for a in accounts])
        out = self.request(Operation.create_accounts, body)
        (payload,) = multi_batch.decode(out, 16)
        return [CreateAccountResult.unpack(payload[i:i + 16])
                for i in range(0, len(payload), 16)]

    def create_transfers(self, transfers: list[Transfer]) -> list[CreateTransferResult]:
        body = encode_batch(Operation.create_transfers,
                            [t.pack() for t in transfers])
        out = self.request(Operation.create_transfers, body)
        (payload,) = multi_batch.decode(out, 16)
        return [CreateTransferResult.unpack(payload[i:i + 16])
                for i in range(0, len(payload), 16)]

    def lookup_accounts(self, ids: list[int]) -> list[Account]:
        body = encode_batch(Operation.lookup_accounts,
                            [i.to_bytes(16, "little") for i in ids])
        out = self.request(Operation.lookup_accounts, body)
        (payload,) = multi_batch.decode(out, 128)
        return [Account.unpack(payload[i:i + 128])
                for i in range(0, len(payload), 128)]

    def lookup_transfers(self, ids: list[int]) -> list[Transfer]:
        body = encode_batch(Operation.lookup_transfers,
                            [i.to_bytes(16, "little") for i in ids])
        out = self.request(Operation.lookup_transfers, body)
        (payload,) = multi_batch.decode(out, 128)
        return [Transfer.unpack(payload[i:i + 128])
                for i in range(0, len(payload), 128)]

    def query(self, operation: Operation, filter_obj) -> bytes:
        """Single-filter query ops; returns the raw result payload."""
        spec = OPERATION_SPECS[operation]
        body = filter_obj.pack()
        if operation.is_multi_batch():
            body = multi_batch.encode([body], spec.event_size)
        out = self.request(operation, body)
        if operation.is_multi_batch():
            (out,) = multi_batch.decode(out, spec.result_size)
        return out
