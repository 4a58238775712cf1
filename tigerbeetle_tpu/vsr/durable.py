"""DurableState: the LSM forest under the replica.

The incremental-checkpoint engine (replacing the round-1 whole-state
snapshots): state-machine objects are written through to LSM trees sharing
one copy-on-write grid in the data file's grid zone, compaction is paced
deterministically by op number, and a checkpoint serializes only manifests
plus the free set into one small root blob the superblock references.

reference mapping:
  grooves / object trees        src/lsm/groove.zig, forest.zig  -> Forest
  grid zone (CoW blocks)        src/vsr/grid.zig                -> lsm/grid.py
  checkpoint trailer (free set) src/vsr/checkpoint_trailer.zig  -> root blob
  write-through after commit    groove insert/update at commit

Determinism contract (load-bearing, like the reference's physical
determinism, docs/ARCHITECTURE.md:281-307): given an identical committed op
sequence, every replica produces byte-identical grid zones. Achieved by
(a) sorted dirty-set flush order, (b) op-derived compaction pacing, and
(c) deterministic grid allocation (cursor scan, reset at checkpoint).
"""

from __future__ import annotations

import struct
from typing import Optional

from ..lsm.forest import Forest
from ..lsm.grid import Grid
from ..lsm.scan import composite_key
from ..oracle.state_machine import AccountEventRecord, StateMachineOracle
from ..trace import Event, NullTracer
from ..types import (Account, AccountFlags, Transfer, TransferFlags,
                     TransferPendingStatus)
from .storage import Storage

# Fixed-size AccountEventRecord row (reference: 256-byte AccountEvent,
# src/state_machine.zig:104-220; ours carries both full account snapshots).
_EVENT_SIZE = 8 + 2 + 1 + 1 + 128 + 128 + 16 + 16 + 128

SCHEMA = {
    "accounts": (16, 128),
    "transfers": (16, 128),
    "pending": (8, 1),
    "expiry": (8, 8),
    "orphaned": (16, 1),
    "events": (8, _EVENT_SIZE),
    # Secondary indexes (reference: the groove index trees,
    # src/state_machine.zig:45-90 — accounts: 9 trees, transfers: 14):
    # composite key = field prefix || timestamp (composite_key.zig);
    # timestamp trees map ts -> id for the object lookup hop
    # (scan_lookup.zig).
    "acct_by_ts": (8, 16),
    "acct_by_ud128": (24, 1),
    "acct_by_ud64": (16, 1),
    "acct_by_ud32": (12, 1),
    "acct_by_ledger": (12, 1),
    "acct_by_code": (10, 1),
    "xfer_by_ts": (8, 16),
    "xfer_by_dr": (24, 1),
    "xfer_by_cr": (24, 1),
    "xfer_by_pid": (24, 1),
    "xfer_by_ud128": (24, 1),
    "xfer_by_ud64": (16, 1),
    "xfer_by_ud32": (12, 1),
    "xfer_by_ledger": (12, 1),
    "xfer_by_code": (10, 1),
    # Flag indexes (reference: tree_ids 23-26 — presence-keyed; `closed`
    # and `closing` are the only mutable indexed attributes, maintained
    # put/remove on every dirty flush, which is deterministic and
    # idempotent across replicas):
    "acct_by_imported": (9, 1),
    "acct_by_closed": (9, 1),
    "xfer_by_amount": (24, 1),
    "xfer_by_imported": (9, 1),
    "xfer_by_closing": (9, 1),
    # account_events secondary trees (reference: tree_ids 27-33,
    # src/state_machine.zig:525-605 — account_timestamp put per
    # history-flagged side in account_event() :4452-4466; *_expired only
    # for expiry rows; prunable when neither side keeps history):
    "ev_by_acct_ts": (16, 1),
    "ev_by_pstat": (9, 1),
    "ev_by_dr_expired": (24, 1),
    "ev_by_cr_expired": (24, 1),
    "ev_by_pid_expired": (24, 1),
    "ev_by_ledger_expired": (12, 1),
    "ev_by_prunable": (8, 1),
}

_META_SIZE = 40  # scalars appended to the checkpoint root blob

_NO_PENDING = b"\x00" * 128
_FLAGS_NONE = 0xFFFF  # transfer_flags=None sentinel (expiry events)


def _k8(x: int) -> bytes:
    return x.to_bytes(8, "big")  # big-endian: lexicographic == numeric


def _k16(x: int) -> bytes:
    return x.to_bytes(16, "big")


def _pack_event(rec: AccountEventRecord) -> bytes:
    flags = _FLAGS_NONE if rec.transfer_flags is None else rec.transfer_flags
    return (struct.pack(
        "<QHBB", rec.timestamp, flags, int(rec.transfer_pending_status),
        1 if rec.transfer_pending is not None else 0)
        + rec.dr_account.pack() + rec.cr_account.pack()
        + rec.amount_requested.to_bytes(16, "little")
        + rec.amount.to_bytes(16, "little")
        + (rec.transfer_pending.pack() if rec.transfer_pending is not None
           else _NO_PENDING))


def _unpack_event(raw: bytes) -> AccountEventRecord:
    ts, flags, pstat, has_p = struct.unpack_from("<QHBB", raw)
    pos = 12
    dr = Account.unpack(raw[pos:pos + 128]); pos += 128
    cr = Account.unpack(raw[pos:pos + 128]); pos += 128
    amount_requested = int.from_bytes(raw[pos:pos + 16], "little"); pos += 16
    amount = int.from_bytes(raw[pos:pos + 16], "little"); pos += 16
    pending = Transfer.unpack(raw[pos:pos + 128]) if has_p else None
    return AccountEventRecord(
        timestamp=ts, dr_account=dr, cr_account=cr,
        transfer_flags=None if flags == _FLAGS_NONE else flags,
        transfer_pending_status=TransferPendingStatus(pstat),
        transfer_pending=pending,
        amount_requested=amount_requested, amount=amount)


def mirror_quiescent(state, events_persisted: int) -> bool:
    """True when the host mirror holds nothing the durable flush would
    have to serialize object-side: no dirty stores and every mirror
    event already persisted. The ONE predicate behind (a) the column
    flush contract, (b) the replica's drain-before-flush decision, and
    (c) commit-window formation — they must agree or the window path's
    per-op flush cadence silently diverges."""
    return not (
        state.accounts.dirty or state.transfers.dirty
        or state.pending_status.dirty or state.expiry.dirty
        or state.orphaned.dirty
        or events_persisted < (state.events_base
                               + len(state.account_events)))


def checkpoint_manifest(root_with_meta: bytes):
    """(manifest BlockAddress, manifest size) of a checkpoint root."""
    from ..lsm.grid import ADDRESS_SIZE, BlockAddress

    address = BlockAddress.unpack(root_with_meta[:ADDRESS_SIZE])
    (size,) = struct.unpack_from("<I", root_with_meta, ADDRESS_SIZE)
    return address, size


def manifest_children(manifest_raw: bytes) -> list:
    """(tree name, key_size, TableInfo) per table referenced by a forest
    manifest blob — the first expansion step of a checkpoint's block
    reachability graph (used by delta state sync)."""
    from ..lsm.table import TableInfo

    out = []
    (count,) = struct.unpack_from("<I", manifest_raw)
    pos = 4
    for _ in range(count):
        name_len, size = struct.unpack_from("<HI", manifest_raw, pos)
        pos += 6
        name = manifest_raw[pos:pos + name_len].decode()
        pos += name_len
        raw = manifest_raw[pos:pos + size]
        pos += size
        key_size = SCHEMA[name][0]
        # Tree blob: u64 beat, u8 level count (lsm.tree manifest_pack);
        # per level: u64 next_seq, u32 entry count.
        (n_levels,) = struct.unpack_from("<B", raw, 8)
        tpos = 9
        for _ in range(n_levels):
            (n_tables,) = struct.unpack_from("<I", raw, tpos + 8)
            tpos += 12
            for _ in range(n_tables):
                # Each entry: snapshot range + seq (3x u64,
                # lsm.manifest_level) then the TableInfo. History entries
                # (removed, unpruned) are reachable too — their blocks
                # stay allocated until the retention bar elapses.
                tpos += 24
                info, tpos = TableInfo.unpack(raw, tpos)
                out.append((name, key_size, info))
    return out


def index_children(index_raw: bytes, key_size: int) -> list:
    """(BlockAddress, size) of every value block an index block references
    (mirrors lsm.table.Table.__init__'s parse)."""
    from ..lsm.grid import ADDRESS_SIZE, BlockAddress
    from ..lsm.schema import BlockKind, unwrap

    index_raw = unwrap(index_raw, BlockKind.index)
    (count,) = struct.unpack_from("<I", index_raw)
    out = []
    pos = 4
    for _ in range(count):
        addr = BlockAddress.unpack(index_raw[pos:pos + ADDRESS_SIZE])
        pos += ADDRESS_SIZE
        (size,) = struct.unpack_from("<I", index_raw, pos)
        pos += 4 + key_size
        out.append((addr, size))
    return out


def allocated_blocks(root_with_meta: bytes) -> list[int]:
    """Grid block indices a checkpoint root reaches (the complement of its
    free set) — the exact transfer set for state sync."""
    from .. import ewah
    from ..lsm.grid import ADDRESS_SIZE

    root = root_with_meta[:-_META_SIZE]
    (free_size,) = struct.unpack_from("<I", root, ADDRESS_SIZE + 4)
    free_blob = root[ADDRESS_SIZE + 8:ADDRESS_SIZE + 8 + free_size]
    bits = ewah.decode_bitset(free_blob)
    return [i for i, free in enumerate(bits) if not free]


class _DictDevice:
    """Read-only staging device over a {block index: raw bytes} dict — used
    to validate state-synced blocks BEFORE they touch the live grid zone."""

    def __init__(self, blocks: dict, block_size: int):
        self.blocks = blocks
        self.block_size = block_size

    def read(self, off: int, size: int) -> bytes:
        idx, within = divmod(off, self.block_size)
        raw = self.blocks.get(idx, b"").ljust(self.block_size, b"\x00")
        return raw[within:within + size]

    def write(self, off: int, data: bytes) -> None:
        raise RuntimeError("staging device is read-only")


def validate_staged_checkpoint(blocks: dict, layout,
                               root_forest: bytes) -> StateMachineOracle:
    """Open a checkpoint root entirely from staged blocks; every read
    validates its parent-held checksum, so success proves the transfer is
    complete and uncorrupted. Raises on any fault — the caller must not
    have written anything to the live grid yet."""
    staged = DurableState.__new__(DurableState)
    # The staged blocks are in memory already: a small cache, not the
    # served grid's, so validation does not hold a second copy of them.
    staged.grid = Grid(
        _DictDevice(blocks, layout.grid_block_size),
        block_size=layout.grid_block_size,
        block_count=layout.grid_block_count, cache_sets=64)
    staged.forest = Forest(staged.grid, SCHEMA)
    staged.events_persisted = 0
    staged._indexed_accounts = set()
    staged._closed_indexed = set()
    return staged.open(root_forest)


class _ZoneDevice:
    """Adapter: a storage zone as the grid's flat byte device."""

    def __init__(self, storage: Storage, zone: str):
        self.storage = storage
        self.zone = zone

    def read(self, off: int, size: int) -> bytes:
        return self.storage.read(self.zone, off, size)

    def read_batch(self, reqs: list) -> list:
        return self.storage.read_batch(self.zone, reqs)

    def read_submit(self, reqs: list):
        return self.storage.read_submit(self.zone, reqs)

    def read_fetch(self, token, size: int) -> bytes:
        return self.storage.read_fetch(token, size)

    def write(self, off: int, data: bytes) -> None:
        self.storage.write(self.zone, off, data)


class DurableState:
    """Write-behind LSM persistence for one replica's state machine."""

    def __init__(self, storage: Storage, tracer=None):
        self.tracer = tracer if tracer is not None else NullTracer()
        # Transfer rows put into the trees, by path (the `path` tag of
        # durable_rows_put), and the checkpoints taken: plain ints, kept
        # whether or not a tracer records. `object_at_checkpoint` rows
        # are those a checkpoint's flush puts through the object path.
        # `run` rows entered the memtables as part of a column run
        # (Tree.put_run; once a transfer, not once a tree); `folded`
        # counts the rows of runs, a tree at a time, that a read by key
        # or range made pay per key after all (lsm/memtable.py).
        self._rows_put = {"column": 0, "object": 0,
                          "object_at_checkpoint": 0, "checkpoints": 0,
                          "run": 0, "folded": 0}
        # Rows of the column path by the pending status they set, over
        # the life of this object: counted from the delta's `pstat`
        # column inside the two-phase loop, so an op with no such row
        # counts nothing.
        self.two_phase_rows = {"pending": 0, "posted": 0, "voided": 0}
        # The column flush's previous-row reads of the accounts tree,
        # over the life of this object: keys asked, of them the ones no
        # memtable answered, the tables probed for those and the value
        # blocks searched for them (the tree's own counters, read around
        # the reads).
        self.account_reads = {"flush_reads": 0, "flush_reads_from_tables": 0,
                              "table_probes": 0, "blocks_searched": 0}
        layout = storage.layout
        # The grid records its block reads, checksums and writes through
        # the same tracer (grid_read / grid_verify / grid_write).
        self.grid = Grid(
            _ZoneDevice(storage, "grid"),
            block_size=layout.grid_block_size,
            block_count=layout.grid_block_count,
            tracer=self.tracer)
        self.forest = Forest(self.grid, SCHEMA)
        self.events_persisted = 0
        # Accounts whose (immutable) index entries are already in the
        # trees: balance updates re-dirty accounts every batch, but only
        # the object row changes — index keys are written once.
        self._indexed_accounts: set[int] = set()
        # Accounts whose key is currently present in acct_by_closed —
        # the one mutable account index writes only on transitions
        # (rebuilt from the tree at open()).
        self._closed_indexed: set[int] = set()

    # ------------------------------------------------------------- writes

    def flush(self, state: StateMachineOracle, flush_columns=None,
              op: int = 0, at_checkpoint: bool = False):
        """Write every object mutated since the last flush into the trees
        (sorted key order: byte-deterministic across replicas). Returns
        (flushed account ids, flushed transfer ids) so the serving layer
        can write its bounded object caches through (state_machine.py
        cache_upsert).

        `op` tags the spans (flush_columns where there are chunks, with
        flush_account_reads inside it, and flush_two_phase and
        memtable_fold where a chunk holds two-phase rows; flush_objects
        always); `at_checkpoint` says the
        caller is checkpoint(), for the row counters.

        flush_columns: drained device-delta transfer columns
        (DeviceLedger.take_flush_columns). Transfers covered by them are
        flushed through the VECTORIZED path — values and index keys built
        in numpy passes and handed to each tree as one run
        (Tree.put_run) — and skipped by the object loop. Same puts, same
        bytes; memtable freeze sorts, so put order cannot affect the
        on-grid result."""
        vector_tids: list = []
        vector_aids: list = []
        if flush_columns:
            with self.tracer.span(Event.flush_columns, op=op):
                self._flush_columns(state, flush_columns,
                                    vector_tids, vector_aids, op)
            self._count_rows("column", len(vector_tids))
            self._count_rows("run", len(vector_tids))
        with self.tracer.span(Event.flush_objects, op=op):
            flushed_accounts, flushed_transfers = self._flush_objects(
                state, vector_tids)
        self._count_rows(
            "object_at_checkpoint" if at_checkpoint else "object",
            len(flushed_transfers))
        self._count_folded()
        return (flushed_accounts + vector_aids,
                flushed_transfers + vector_tids)

    def _count_rows(self, path: str, n: int) -> None:
        if n:
            self._rows_put[path] += n
            self.tracer.count(Event.durable_rows_put, n, path=path)

    def _count_folded(self) -> None:
        """Bring `folded` up to what the trees' memtables have folded."""
        folded = sum(tree.memtable.rows_folded
                     for tree in self.forest.trees.values())
        self._count_rows("folded", folded - self._rows_put["folded"])

    @property
    def rows_put(self) -> dict:
        """The row counters. Reads fold between flushes too (lookups, the
        scrubber), so `folded` is read off the trees here as well as at
        the end of every flush."""
        self._count_folded()
        return self._rows_put

    def _flush_columns(self, state, flush_columns, vector_tids: list,
                       vector_aids: list, op: int) -> None:
        """The vectorized path over an op's chunks; appends the flushed
        transfer and account ids to the two lists."""
        trees = self.forest.trees
        # Contract: the column path is only valid against a QUIESCENT
        # mirror — interleaved mirror writes (hard-regime handoffs,
        # account creations, expiries) carry ordering the two paths
        # cannot merge; the caller must drain and flush the object
        # path instead (vsr/replica.py does exactly that).
        assert mirror_quiescent(state, self.events_persisted), \
            "column flush with a dirty/unpersisted mirror: drain first"
        for (t_cols, e_cols, der_cols, n_new, abs_start,
             orphan_ids) in flush_columns:
            # Orphan puts are idempotent: flushed even for zero-create
            # chunks (transient failures poison ids without creating).
            for oid in orphan_ids:
                trees["orphaned"].put(_k16(oid), b"\x01")
            if n_new == 0:
                continue
            if abs_start + n_new <= self.events_persisted:
                # Stale chunk: an object-path flush (after a mirror
                # drain) already covered it — every put would be a
                # re-put of identical bytes.
                continue
            assert abs_start >= self.events_persisted, \
                "flush chunks must arrive whole and in order"
            vector_tids.extend(self._flush_transfer_columns(
                trees, t_cols, n_new))
            vector_aids.extend(self._flush_side_columns(
                trees, t_cols, e_cols, der_cols, n_new, op))
            self.events_persisted = abs_start + n_new

    def _flush_objects(self, state, vector_tids: list):
        """The object loops: every dirty object of the mirror the column
        path did not cover. Returns (flushed account ids, flushed
        transfer ids)."""
        trees = self.forest.trees
        # A dirty key absent from its dict was created then rolled back by a
        # linked-chain scope within one commit — it was never flushed, so
        # skip it (accounts/transfers/pending are never legitimately
        # removed; only expiry needs real tombstones).
        acc = state.accounts
        flushed_accounts = sorted(a for a in acc.dirty if a in acc)
        for aid in flushed_accounts:
            a = acc[aid]
            trees["accounts"].put(_k16(aid), a.pack())
            # `closed` is the one mutable indexed account attribute
            # (closing transfers set it; voiding them clears it) —
            # written only on transitions.
            closed = bool(a.flags & AccountFlags.closed)
            if closed != (aid in self._closed_indexed):
                closed_key = composite_key(1, a.timestamp, 1)
                if closed:
                    trees["acct_by_closed"].put(closed_key, b"\x01")
                    self._closed_indexed.add(aid)
                else:
                    trees["acct_by_closed"].remove(closed_key)
                    self._closed_indexed.discard(aid)
            if aid in self._indexed_accounts:
                continue  # balances changed; indexed fields immutable
            self._indexed_accounts.add(aid)
            ts = a.timestamp
            if a.flags & AccountFlags.imported:
                trees["acct_by_imported"].put(
                    composite_key(1, ts, 1), b"\x01")
            trees["acct_by_ts"].put(_k8(ts), _k16(aid))
            trees["acct_by_ud128"].put(
                composite_key(a.user_data_128, ts, 16), b"\x01")
            trees["acct_by_ud64"].put(
                composite_key(a.user_data_64, ts, 8), b"\x01")
            trees["acct_by_ud32"].put(
                composite_key(a.user_data_32, ts, 4), b"\x01")
            trees["acct_by_ledger"].put(
                composite_key(a.ledger, ts, 4), b"\x01")
            trees["acct_by_code"].put(
                composite_key(a.code, ts, 2), b"\x01")
        acc.dirty.clear()
        xfr = state.transfers
        xfr.dirty.difference_update(vector_tids)
        flushed_transfers = sorted(t for t in xfr.dirty if t in xfr)
        for tid in flushed_transfers:
            t = xfr[tid]
            ts = t.timestamp
            trees["transfers"].put(_k16(tid), t.pack())
            trees["xfer_by_ts"].put(_k8(ts), _k16(tid))
            trees["xfer_by_dr"].put(
                composite_key(t.debit_account_id, ts, 16), b"\x01")
            trees["xfer_by_cr"].put(
                composite_key(t.credit_account_id, ts, 16), b"\x01")
            if t.pending_id:
                # Zero means 'not a post/void' — never indexed
                # (reference: the pending_id tree likewise only holds
                # resolutions; ForestQuery.transfers_by_pending_id
                # reads it).
                trees["xfer_by_pid"].put(
                    composite_key(t.pending_id, ts, 16), b"\x01")
            trees["xfer_by_ud128"].put(
                composite_key(t.user_data_128, ts, 16), b"\x01")
            trees["xfer_by_ud64"].put(
                composite_key(t.user_data_64, ts, 8), b"\x01")
            trees["xfer_by_ud32"].put(
                composite_key(t.user_data_32, ts, 4), b"\x01")
            trees["xfer_by_ledger"].put(
                composite_key(t.ledger, ts, 4), b"\x01")
            trees["xfer_by_code"].put(
                composite_key(t.code, ts, 2), b"\x01")
            trees["xfer_by_amount"].put(
                composite_key(t.amount, ts, 16), b"\x01")
            if t.flags & TransferFlags.imported:
                trees["xfer_by_imported"].put(
                    composite_key(1, ts, 1), b"\x01")
            if t.flags & (TransferFlags.closing_debit
                          | TransferFlags.closing_credit):
                trees["xfer_by_closing"].put(
                    composite_key(1, ts, 1), b"\x01")
        xfr.dirty.clear()
        pend = state.pending_status
        for ts in sorted(pend.dirty):
            if ts in pend:
                trees["pending"].put(_k8(ts), bytes([int(pend[ts])]))
        pend.dirty.clear()
        exp = state.expiry
        for ts in sorted(exp.dirty):
            if ts in exp:
                trees["expiry"].put(_k8(ts), struct.pack("<Q", exp[ts]))
            else:
                trees["expiry"].remove(_k8(ts))
        exp.dirty.clear()
        orph = state.orphaned
        for oid in sorted(orph.dirty):
            trees["orphaned"].put(_k16(oid), b"\x01")
        orph.dirty.clear()
        for rec in state.account_events[self.events_persisted
                                        - state.events_base:]:
            ets = rec.timestamp
            trees["events"].put(_k8(ets), _pack_event(rec))
            if rec.dr_account.flags & AccountFlags.history:
                trees["ev_by_acct_ts"].put(
                    composite_key(rec.dr_account.timestamp, ets, 8), b"\x01")
            if rec.cr_account.flags & AccountFlags.history:
                trees["ev_by_acct_ts"].put(
                    composite_key(rec.cr_account.timestamp, ets, 8), b"\x01")
            trees["ev_by_pstat"].put(
                composite_key(int(rec.transfer_pending_status), ets, 1),
                b"\x01")
            if rec.transfer_pending_status == TransferPendingStatus.expired:
                trees["ev_by_dr_expired"].put(
                    composite_key(rec.dr_account.id, ets, 16), b"\x01")
                trees["ev_by_cr_expired"].put(
                    composite_key(rec.cr_account.id, ets, 16), b"\x01")
                trees["ev_by_pid_expired"].put(
                    composite_key(rec.transfer_pending.id, ets, 16), b"\x01")
                trees["ev_by_ledger_expired"].put(
                    composite_key(rec.dr_account.ledger, ets, 4), b"\x01")
            if not ((rec.dr_account.flags | rec.cr_account.flags)
                    & AccountFlags.history):
                trees["ev_by_prunable"].put(_k8(ets), b"\x01")
        # max(): with the drain deferred, the mirror's event list lags the
        # column watermark — never rewind it.
        self.events_persisted = max(
            self.events_persisted,
            state.events_base + len(state.account_events))
        return flushed_accounts, flushed_transfers

    def _flush_transfer_columns(self, trees, t, n: int) -> list:
        """Vectorized transfer flush from drained device columns: value
        bytes and every index key are built in whole-column numpy passes
        and each tree takes its rows as ONE run (Tree.put_run) — no
        Python work per row. Returns the flushed transfer ids.
        Bit-identical to the object path (the wire codec IS the object
        pack format)."""
        import numpy as np

        from ..ops.batch import TRANSFER_WIRE
        from ..types import TransferFlags as TF

        flags = t["flags"][:n]
        rec = np.zeros(n, dtype=TRANSFER_WIRE)
        for f in ("id_lo", "id_hi", "dr_lo", "dr_hi", "cr_lo", "cr_hi",
                  "amt_lo", "amt_hi", "pid_lo", "pid_hi",
                  "ud128_lo", "ud128_hi", "ud64", "ud32", "timeout", "ts"):
            rec[f] = t[f][:n]
        rec["ledger"] = t["ledger"][:n]
        rec["code"] = t["code"][:n].astype(np.uint16)
        rec["flags"] = flags.astype(np.uint16)

        # Key matrices stay uint8 from here on: concatenating big-endian
        # integer arrays would hand back native byte order.
        def be(*cols, width=8):
            return np.stack([c[:n] for c in cols], axis=1).astype(
                f">u{width}").view(np.uint8)

        def with_ts(prefix):
            return np.concatenate([prefix, ts8], axis=1)

        ts = t["ts"]
        ts8 = be(ts)                                          # 8B rows
        idb = be(t["id_hi"], t["id_lo"])                      # 16B rows
        ONE = b"\x01"
        trees["transfers"].put_run(
            idb, rec.view(np.uint8).reshape(n, TRANSFER_WIRE.itemsize))
        trees["xfer_by_ts"].put_run(ts8, idb)
        trees["xfer_by_dr"].put_run(be(t["dr_hi"], t["dr_lo"], ts), ONE)
        trees["xfer_by_cr"].put_run(be(t["cr_hi"], t["cr_lo"], ts), ONE)
        # Zero means 'not a post/void' — never indexed.
        pid_live = (t["pid_hi"][:n] != 0) | (t["pid_lo"][:n] != 0)
        trees["xfer_by_pid"].put_run(
            be(t["pid_hi"], t["pid_lo"], ts)[pid_live], ONE)
        trees["xfer_by_ud128"].put_run(
            be(t["ud128_hi"], t["ud128_lo"], ts), ONE)
        trees["xfer_by_ud64"].put_run(be(t["ud64"], ts), ONE)
        trees["xfer_by_ud32"].put_run(with_ts(be(t["ud32"], width=4)), ONE)
        trees["xfer_by_ledger"].put_run(
            with_ts(be(t["ledger"], width=4)), ONE)
        trees["xfer_by_code"].put_run(with_ts(be(t["code"], width=2)), ONE)
        trees["xfer_by_amount"].put_run(
            be(t["amt_hi"], t["amt_lo"], ts), ONE)
        # Closing and imported transfers come through the fast path
        # (closing-native fixpoint tiers / the imported tiers), so the
        # column flush maintains their flag indexes exactly like the
        # object path does (composite_key(1, ts, 1) == b"\x01" + ts_be).
        flag_keys = with_ts(np.ones((n, 1), dtype=np.uint8))
        closing = (flags & np.uint32(int(TF.closing_debit
                                         | TF.closing_credit))) != 0
        trees["xfer_by_closing"].put_run(flag_keys[closing], ONE)
        imported = (flags & np.uint32(int(TF.imported))) != 0
        trees["xfer_by_imported"].put_run(flag_keys[imported], ONE)
        return ((t["id_hi"][:n].astype(object) << 64)
                | t["id_lo"][:n].astype(object)).tolist()

    def _flush_side_columns(self, trees, t, e, der, n: int,
                            op: int) -> list:
        """Vectorized flush of one chunk's NON-transfer effects: the
        account_events rows (+ their index trees), the touched accounts'
        object rows, and the pending/expiry trees — all from device delta
        columns, so the flush does not require materializing the mirror.
        The event rows are built as one uint8[n, 428] matrix and handed
        to their trees as runs; Python loops only over the chunk's
        DISTINCT accounts and over the rows that reference a pending
        transfer or set a pending status (_flush_two_phase_rows: they
        read trees).

        Immutable account metadata (user_data/ledger/code/timestamp) is
        spliced from the account's PREVIOUS tree value (the fast path
        never mutates it); the FLAGS word comes from the event columns,
        which carry the closing-native tiers' evolved closed bit — the
        closed-flag index transitions are maintained here exactly like
        the object path. Per-event balances come from the event columns.
        Byte-identical to the object path (oracle-exact snapshots either
        way). Returns the touched account ids."""
        import numpy as np

        from ..types import AccountFlags as AF

        def le(*cols, width=8):
            return np.stack([c[:n] for c in cols], axis=1).astype(
                f"<u{width}").view(np.uint8)

        pstat = e["pstat"][:n]
        assert ((pstat >= 0) & (pstat <= 3)).all(), \
            "expiry events never come from chunks"
        has_p = e["p_row"][:n] >= 0
        tflags = e["tflags"][:n]
        ets8 = t["ts"][:n].astype(">u8").view(np.uint8).reshape(n, 8)

        # The DISTINCT accounts of the chunk. Sides interleave (dr 0, cr
        # 0, dr 1, ...) so that a higher position is a later image; the
        # sort is stable, so each account's positions stay ascending.
        id_hi = np.stack([der["dr_id_hi"][:n], der["cr_id_hi"][:n]],
                         axis=1).reshape(2 * n)
        id_lo = np.stack([der["dr_id_lo"][:n], der["cr_id_lo"][:n]],
                         axis=1).reshape(2 * n)
        order = np.lexsort((id_lo, id_hi))
        id_hi, id_lo = id_hi[order], id_lo[order]
        first = np.ones(2 * n, dtype=bool)
        first[1:] = (id_hi[1:] != id_hi[:-1]) | (id_lo[1:] != id_lo[:-1])
        inverse = np.empty(2 * n, dtype=np.intp)  # position -> account
        inverse[order] = np.cumsum(first) - 1
        # Each account's last position: the one before the next's first.
        last = order[np.append(np.flatnonzero(first)[1:] - 1, 2 * n - 1)]
        # The immutable bytes of each distinct account, read once from
        # its previous tree value.
        acct_tree = trees["accounts"]
        raw = np.stack([id_hi[first], id_lo[first]], axis=1).astype(
            ">u8").tobytes()
        keys16 = [raw[p:p + 16] for p in range(0, len(raw), 16)]
        with self.tracer.span(Event.flush_account_reads, op=op):
            from_tables = acct_tree.keys_from_tables
            probes = acct_tree.table_probes
            blocks = acct_tree.blocks_searched
            got = acct_tree.get_many(keys16)
            reads = self.account_reads
            reads["flush_reads"] += len(keys16)
            reads["flush_reads_from_tables"] += (
                acct_tree.keys_from_tables - from_tables)
            reads["table_probes"] += acct_tree.table_probes - probes
            reads["blocks_searched"] += acct_tree.blocks_searched - blocks
        assert len(got) == len(keys16), "account flushed before transfers"
        olds = np.frombuffer(b"".join([got[k16] for k16 in keys16]),
                             dtype=np.uint8).reshape(-1, 128)
        meta = olds[:, 80:118][inverse].reshape(n, 2, 38)
        acct_ts = olds[:, 120:128][inverse].reshape(n, 2, 8)

        # events rows: <QHBB | dr account | cr account | amount_requested
        # | amount | pending transfer (zeros without one).
        ev = np.zeros((n, _EVENT_SIZE), dtype=np.uint8)
        ev[:, 0:8] = le(t["ts"])
        ev[:, 8:10] = le(np.where(tflags == 0xFFFFFFFF, _FLAGS_NONE, tflags),
                         width=2)
        ev[:, 10] = pstat
        ev[:, 11] = has_p
        for s, side in enumerate(("dr", "cr")):
            off = 12 + 128 * s
            # id + four balances (wire LE), then the immutable bytes
            # around the flags word.
            ev[:, off:off + 80] = le(
                der[f"{side}_id_lo"], der[f"{side}_id_hi"],
                e[f"{side}_dp_lo"], e[f"{side}_dp_hi"],
                e[f"{side}_dpos_lo"], e[f"{side}_dpos_hi"],
                e[f"{side}_cp_lo"], e[f"{side}_cp_hi"],
                e[f"{side}_cpos_lo"], e[f"{side}_cpos_hi"])
            ev[:, off + 80:off + 118] = meta[:, s]
            ev[:, off + 118:off + 120] = le(e[f"{side}_flags"], width=2)
            ev[:, off + 120:off + 128] = acct_ts[:, s]
        ev[:, 268:284] = le(e["areq_lo"], e["areq_hi"])
        ev[:, 284:300] = le(e["amt_lo"], e["amt_hi"])

        two_phase = np.flatnonzero(has_p | (pstat != 0))
        if two_phase.size:
            with self.tracer.span(Event.flush_two_phase, op=op):
                self._flush_two_phase_rows(
                    trees, t, der, ev, ets8, pstat, has_p, two_phase, op)

        def with_ets(prefix, mask=slice(None)):
            return np.concatenate([prefix[mask], ets8[mask]], axis=1)

        ONE = b"\x01"
        trees["events"].put_run(ets8, ev)
        trees["ev_by_pstat"].put_run(
            with_ets(pstat.astype(np.uint8).reshape(n, 1)), ONE)
        hist = (np.stack([e["dr_flags"][:n], e["cr_flags"][:n]], axis=1)
                & np.uint32(int(AF.history))) != 0
        # The accounts' timestamps big-endian: the image holds them LE.
        acct_ts_be = acct_ts[:, :, ::-1]
        trees["ev_by_acct_ts"].put_run(np.concatenate([
            with_ets(acct_ts_be[:, s], hist[:, s]) for s in (0, 1)]), ONE)
        trees["ev_by_prunable"].put_run(ets8[~hist.any(axis=1)], ONE)

        # The LAST image of each distinct account is its object row.
        images = ev[:, 12:268].reshape(2 * n, 128)[last].tobytes()
        put_acct = acct_tree.put
        closed_bit = int(AF.closed)
        by_closed = trees["acct_by_closed"]
        aids = []
        for u, k16 in enumerate(keys16):
            val = images[128 * u:128 * u + 128]
            put_acct(k16, val)
            # `closed` transitions (closing-native tiers evolve it on
            # the fast path): same put/remove-on-transition contract as
            # the object flush, keyed by the account's timestamp.
            aid = int.from_bytes(k16, "big")
            aids.append(aid)
            closed = bool(val[118] & closed_bit)  # flags u16 LE low byte
            if closed != (aid in self._closed_indexed):
                a_ts = int.from_bytes(val[120:128], "little")
                ckey = composite_key(1, a_ts, 1)
                if closed:
                    by_closed.put(ckey, b"\x01")
                    self._closed_indexed.add(aid)
                else:
                    by_closed.remove(ckey)
                    self._closed_indexed.discard(aid)
        # The touched account ids: the caller invalidates their cache
        # entries (reads must never serve pre-chunk balances).
        return aids

    def _flush_two_phase_rows(self, trees, t, der, ev, ets8, pstat, has_p,
                              rows, op: int) -> None:
        """The chunk's rows that reference a pending transfer or set a
        pending status (`rows`: their indices) read trees and touch
        pending / expiry (oracle semantics): a loop over those rows
        only. A post or void copies its pending transfer's row into
        `ev` (the chunk's event rows, written in place)."""
        import numpy as np

        xfer_tree = trees["transfers"]
        by_ts = trees["xfer_by_ts"]
        counts = np.bincount(pstat[rows], minlength=4)
        for status, name in ((1, "pending"), (2, "posted"), (3, "voided")):
            self.two_phase_rows[name] += int(counts[status])
        if has_p.any() and (by_ts.memtable.pending_runs
                            or xfer_tree.memtable.pending_runs):
            # The reads below go by key, and the first of them would fold
            # what _flush_transfer_columns appended as runs since the
            # last such read, a row at a time: done here, under a span of
            # its own.
            with self.tracer.span(Event.memtable_fold, op=op):
                by_ts.memtable.fold()
                xfer_tree.memtable.fold()
        put_pending = trees["pending"].put
        put_expiry = trees["expiry"].put
        rm_expiry = trees["expiry"].remove
        ONE = b"\x01"
        p_cache: dict = {}  # p_ts -> pending transfer value bytes
        for i in rows.tolist():
            p_val = None
            if has_p[i]:
                pts = int(der["p_ts"][i])
                p_val = p_cache.get(pts)
                if p_val is None:
                    ptid = by_ts.get(_k8(pts))
                    assert ptid is not None, "pending flushed before resolve"
                    p_val = p_cache[pts] = xfer_tree.get(ptid)
                ev[i, 300:428] = np.frombuffer(p_val, dtype=np.uint8)
            if pstat[i] == 1:
                ets = ets8[i].tobytes()
                put_pending(ets, ONE)
                if t["timeout"][i]:
                    put_expiry(ets, struct.pack("<Q", int(t["expires"][i])))
            elif pstat[i] in (2, 3):
                pk8 = _k8(int(der["p_ts"][i]))
                put_pending(pk8, bytes([int(pstat[i])]))
                if int.from_bytes(p_val[108:112], "little"):  # its timeout
                    rm_expiry(pk8)

    def prune_events(self, before_ts: int) -> int:
        """Delete prunable (no-history) event rows older than `before_ts`
        (the CDC consumer watermark) — the cleanup job the reference's
        `prunable` index exists for (src/state_machine.zig:590-601).
        Returns the number of rows pruned. Deterministic: driven purely by
        tree contents and the argument, so replicas pruning at the same
        op produce byte-identical grids."""
        from ..lsm.scan import TreeScan

        trees = self.forest.trees
        doomed = [key for key, _ in TreeScan(
            trees["ev_by_prunable"], _k8(0), _k8(max(0, before_ts - 1)))]
        for key in doomed:
            raw = trees["events"].get(key)
            if raw is not None:  # groove delete: object + every index row
                rec = _unpack_event(raw)
                ets = rec.timestamp
                trees["ev_by_pstat"].remove(
                    composite_key(int(rec.transfer_pending_status), ets, 1))
                if (rec.transfer_pending_status
                        == TransferPendingStatus.expired):
                    trees["ev_by_dr_expired"].remove(
                        composite_key(rec.dr_account.id, ets, 16))
                    trees["ev_by_cr_expired"].remove(
                        composite_key(rec.cr_account.id, ets, 16))
                    trees["ev_by_pid_expired"].remove(
                        composite_key(rec.transfer_pending.id, ets, 16))
                    trees["ev_by_ledger_expired"].remove(
                        composite_key(rec.dr_account.ledger, ets, 4))
            trees["events"].remove(key)
            trees["ev_by_prunable"].remove(key)
        return len(doomed)

    def compact_beat(self, op: int) -> None:
        with self.tracer.span(Event.compact_beat, op=op):
            self.forest.compact_beat(op)

    def checkpoint(self, state: StateMachineOracle,
                   flush_columns=None, op: int = 0) -> bytes:
        """Flush + forest checkpoint; returns the root blob to persist.
        The 40 scalar bytes (key maxes, pulse, commit timestamp, event
        count) ride in the root blob itself — they are only ever read at
        restore, so they don't belong in a tree (reference analog: the
        superblock's VSRState vs the checkpoint trailer)."""
        self._rows_put["checkpoints"] += 1
        with self.tracer.span(Event.checkpoint_flush, op=op):
            self.flush(state, flush_columns=flush_columns, op=op,
                       at_checkpoint=True)
        meta = struct.pack(
            "<QQQQQ",
            state.accounts_key_max or 0, state.transfers_key_max or 0,
            state.pulse_next_timestamp, state.commit_timestamp,
            self.events_persisted)
        with self.tracer.span(Event.checkpoint_forest, op=op):
            return self.forest.checkpoint() + meta

    # ------------------------------------------------------------- recover

    def open(self, root: Optional[bytes],
             load_events: bool = True) -> StateMachineOracle:
        """Restore the forest from a checkpoint root and rebuild the
        in-memory state (object dicts + derived timestamp indexes).

        load_events=False (the replica serving path) leaves the event
        history in the forest's events tree and starts the host list at
        events_base = the persisted count — bounded memory regardless of
        history size (history queries are forest-served)."""
        state = StateMachineOracle()
        if root is not None:
            meta = root[-_META_SIZE:]
            self.forest.open(root[:-_META_SIZE])
            trees = self.forest.trees
            lo16, hi16 = b"\x00" * 16, b"\xff" * 16
            lo8, hi8 = b"\x00" * 8, b"\xff" * 8
            for _, v in trees["accounts"].scan(lo16, hi16):
                a = Account.unpack(v)
                state.accounts[a.id] = a
                state.account_by_timestamp[a.timestamp] = a.id
                self._indexed_accounts.add(a.id)
            for _, v in trees["transfers"].scan(lo16, hi16):
                t = Transfer.unpack(v)
                state.transfers[t.id] = t
                state.transfer_by_timestamp[t.timestamp] = t.id
            for k, v in trees["pending"].scan(lo8, hi8):
                state.pending_status[int.from_bytes(k, "big")] = \
                    TransferPendingStatus(v[0])
            for k, v in trees["expiry"].scan(lo8, hi8):
                state.expiry[int.from_bytes(k, "big")] = \
                    struct.unpack("<Q", v)[0]
            for k, _ in trees["orphaned"].scan(lo16, hi16):
                state.orphaned.add(int.from_bytes(k, "big"))
            for k, _ in trees["acct_by_closed"].scan(
                    b"\x00" * 9, b"\xff" * 9):
                ats = int.from_bytes(k[-8:], "big")
                self._closed_indexed.add(state.account_by_timestamp[ats])
            if load_events:
                for _, v in trees["events"].scan(lo8, hi8):
                    state.account_events.append(_unpack_event(v))
            akm, tkm, pulse, commit_ts, events_len = struct.unpack("<QQQQQ", meta)
            state.accounts_key_max = akm or None
            state.transfers_key_max = tkm or None
            state.pulse_next_timestamp = pulse
            state.commit_timestamp = commit_ts
            if load_events:
                # prune_events removes rows from the events tree, but
                # events_len is the monotonic persisted COUNT — start the
                # host list past the pruned prefix so flush's
                # un-persisted-tail slice stays exact.
                assert events_len >= len(state.account_events)
                state.events_base = events_len - len(state.account_events)
            else:
                state.events_base = events_len
        # Everything just loaded is already durable.
        for container in (state.accounts, state.transfers,
                          state.pending_status, state.expiry, state.orphaned):
            container.dirty.clear()
        self.events_persisted = state.events_base + len(state.account_events)
        return state
