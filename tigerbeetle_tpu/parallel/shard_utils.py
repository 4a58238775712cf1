"""Shared SPMD plumbing for the sharded modules.

This lives here because BOTH parallel/full_sharded.py (replicated
state, sharded per-event stage) and parallel/partitioned.py (sharded
state, exchange-assembled per-event stage) need it and must agree:

  - `shard_of_id()`: the ownership function — which mesh shard owns a
    128-bit object id. The device kernels, the host packers
    (partitioned_from_oracle), and the oracle-side digest pack
    (state_epoch.pack_oracle_state_partitioned) all route through this
    ONE definition, so device and host can never disagree about
    ownership (the partitioned digest comparison depends on it).

Elastic shards (ISSUE 19) extend the base map with an *overlay*: a
tiny, generation-tagged table of hash ranges mid-migration. An overlay
entry `(lo, hi, src, dst, mode)` says: ids whose 64-bit ownership hash
falls in [lo, hi] (inclusive, so the full range is representable) AND
whose base owner is `src` are being moved to `dst`. `mode` is
OVERLAY_DOUBLE_WRITE (reads still served by src; writes applied by
BOTH src and dst — the copy-catchup stage) or OVERLAY_MIGRATED (reads
and writes owned by dst; src's copy awaits retirement). The overlay is
consulted bit-identically on host (`owner_read_int`) and device
(`owner_read` / `writes_here`): both derive the same `mix_id` hash and
walk the same static entry tuple, so a flip can never tear between the
packers and the kernels. An EMPTY overlay lowers to exactly the code
that existed before elastic shards — the serving op budgets and
jaxhound signatures see byte-identical HLO.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# The same splitmix64-style constants the two-choice hash table uses
# (ops/hash_table.py) — a different finalization order, so shard
# assignment and bucket choice stay decorrelated.
_C1 = 0x9E3779B97F4A7C15
_C2 = 0xBF58476D1CE4E5B9
_C3 = 0x94D049BB133111EB
_M64 = (1 << 64) - 1


def mix_id(k_hi, k_lo):
    """The full 64-bit ownership hash of a 128-bit id (array form;
    jnp or numpy u64 arrays). `shard_of_id` is its low bits; overlay
    ranges and the range digest fold are defined over the whole hash."""
    u64 = np.uint64
    h = (k_lo ^ (k_hi * u64(_C1))) * u64(_C2)
    h = (h ^ (h >> u64(31))) * u64(_C3)
    h = h ^ (h >> u64(29))
    return h


def mix_int(id128: int) -> int:
    """Host-side `mix_id` over a python 128-bit int. Bit-identical."""
    k_hi = (id128 >> 64) & _M64
    k_lo = id128 & _M64
    h = ((k_lo ^ (k_hi * _C1 & _M64)) * _C2) & _M64
    h = ((h ^ (h >> 31)) * _C3) & _M64
    return h ^ (h >> 29)


def shard_of_id(k_hi, k_lo, n_shards: int):
    """Owning shard of a 128-bit id (account, transfer, or orphan key).

    Pure function of the id: a splitmix-style 64-bit mix of the two
    limbs, masked to `n_shards` (power of two — mesh sizes are). Works
    on jnp arrays (traced, wrapping uint64), numpy arrays, and — via
    `shard_of_int` — python ints, producing identical assignments.
    """
    assert n_shards & (n_shards - 1) == 0, n_shards
    u64 = np.uint64
    return (mix_id(k_hi, k_lo) & u64(n_shards - 1)).astype(np.int32)


def shard_of_int(id128: int, n_shards: int) -> int:
    """Host-side shard_of_id over a python 128-bit int (oracle
    partitioning / digest packs). Bit-identical to the array form."""
    assert n_shards & (n_shards - 1) == 0, n_shards
    return mix_int(id128) & (n_shards - 1)


# ------------------------------------------------------------- overlay
# Migration modes an overlay entry can be in. Membership of an id in an
# entry is always tested against the BASE map (`base_owner == src`), so
# an entry's meaning never depends on other entries:
#
#   DOUBLE_WRITE  forward copy-catchup: src answers reads, BOTH src and
#                 dst apply writes (dst's copy stays current while the
#                 bulk copy streams).
#   MIGRATED      post-flip steady state: dst owns reads and writes;
#                 src's copy is stale (zeroed at retire). The entry
#                 persists as the collapsed base override — the base
#                 map is a pure hash, so "collapse" means the entry
#                 simply stops being part of any in-flight migration.
#   RETURNING     backward copy-catchup (merge home): dst still answers
#                 reads, both apply writes; the flip that completes it
#                 DROPS the entry, returning the range to the base map.
OVERLAY_DOUBLE_WRITE = 1
OVERLAY_MIGRATED = 2
OVERLAY_RETURNING = 3


def _validate_overlay(entries: tuple, n_shards: int) -> None:
    spans: list = []
    for e in entries:
        lo, hi, src, dst, mode = e
        assert 0 <= lo <= hi <= _M64, e
        assert 0 <= src < n_shards and 0 <= dst < n_shards, e
        assert src != dst, e
        assert mode in (OVERLAY_DOUBLE_WRITE, OVERLAY_MIGRATED,
                        OVERLAY_RETURNING), e
        for (plo, phi, psrc) in spans:
            if psrc == src and not (hi < plo or lo > phi):
                raise AssertionError(
                    f"overlapping overlay ranges for shard {src}")
        spans.append((lo, hi, src))


def owner_read(k_hi, k_lo, n_shards: int, overlay: tuple = ()):
    """READ owner of an id under an (optionally empty) overlay: the
    shard whose copy of the object is authoritative right now. With an
    empty overlay this IS `shard_of_id` — same lowering, same budget."""
    base = shard_of_id(k_hi, k_lo, n_shards)
    if not overlay:
        return base
    import jax.numpy as jnp
    u64 = np.uint64
    h = mix_id(k_hi, k_lo)
    owner = base
    for (lo, hi, src, dst, mode) in overlay:
        if mode == OVERLAY_DOUBLE_WRITE:
            continue  # copy-catchup ranges still read from src == base
        inr = (h >= u64(lo)) & (h <= u64(hi)) & (base == np.int32(src))
        owner = jnp.where(inr, np.int32(dst), owner)
    return owner


def writes_here(k_hi, k_lo, n_shards: int, me, overlay: tuple = ()):
    """Boolean per id: does shard `me` apply writes for it. Equals
    `owner_read(...) == me` except during copy-catchup, where the
    non-reading owner writes too (DOUBLE_WRITE: dst; RETURNING: src)."""
    w = owner_read(k_hi, k_lo, n_shards, overlay) == me
    if not overlay:
        return w
    u64 = np.uint64
    h = mix_id(k_hi, k_lo)
    base = shard_of_id(k_hi, k_lo, n_shards)
    for (lo, hi, src, dst, mode) in overlay:
        if mode == OVERLAY_MIGRATED:
            continue
        other = dst if mode == OVERLAY_DOUBLE_WRITE else src
        inr = (h >= u64(lo)) & (h <= u64(hi)) & (base == np.int32(src))
        w = w | (inr & (me == np.int32(other)))
    return w


def owner_read_int(id128: int, n_shards: int, overlay: tuple = ()) -> int:
    """Host-side `owner_read` over a python int — the packers' and the
    oracle digest's view of the same overlay. Bit-identical."""
    h = mix_int(id128)
    base = h & (n_shards - 1)
    for (lo, hi, src, dst, mode) in overlay:
        if (mode != OVERLAY_DOUBLE_WRITE and lo <= h <= hi
                and base == src):
            return dst
    return base


def write_owners_int(id128: int, n_shards: int,
                     overlay: tuple = ()) -> tuple:
    """Host-side write-owner set of an id (1 shard normally, 2 while
    its range is in copy-catchup)."""
    h = mix_int(id128)
    base = h & (n_shards - 1)
    owners = [owner_read_int(id128, n_shards, overlay)]
    for (lo, hi, src, dst, mode) in overlay:
        if mode == OVERLAY_MIGRATED or not (lo <= h <= hi
                                            and base == src):
            continue
        other = dst if mode == OVERLAY_DOUBLE_WRITE else src
        if other not in owners:
            owners.append(other)
    return tuple(sorted(owners))


@dataclass(frozen=True)
class OwnershipTable:
    """The host-side ownership authority: base map (splitmix over
    `n_shards`) plus the generation-tagged overlay. The controller
    mutates ownership ONLY by swapping in a new table with a bumped
    generation; traced step functions bake `entries` in as static
    closure constants, so a generation bump is what forces the router
    to select (or trace) the matching step."""
    n_shards: int
    generation: int = 0
    entries: tuple = ()

    def __post_init__(self):
        assert self.n_shards & (self.n_shards - 1) == 0, self.n_shards
        _validate_overlay(self.entries, self.n_shards)

    @property
    def active(self) -> bool:
        return bool(self.entries)

    def owner_read_int(self, id128: int) -> int:
        return owner_read_int(id128, self.n_shards, self.entries)

    def write_owners_int(self, id128: int) -> tuple:
        return write_owners_int(id128, self.n_shards, self.entries)

    def with_entry(self, lo: int, hi: int, src: int, dst: int,
                   mode: int) -> "OwnershipTable":
        return OwnershipTable(
            self.n_shards, self.generation + 1,
            self.entries + ((lo, hi, src, dst, mode),))

    def transition(self, entry: tuple, mode: int) -> "OwnershipTable":
        """The same range, next stage (e.g. DOUBLE_WRITE -> MIGRATED
    at a forward flip, MIGRATED -> RETURNING when a merge-home copy
    begins)."""
        lo, hi, src, dst, _m = entry
        out = tuple((lo, hi, src, dst, mode) if e[:4] == (lo, hi, src, dst)
                    else e for e in self.entries)
        table = OwnershipTable(self.n_shards, self.generation + 1, out)
        assert any(e[:4] == (lo, hi, src, dst) for e in out), entry
        return table

    def without_entry(self, entry: tuple) -> "OwnershipTable":
        """Drop a range from the overlay: the abort revert of an
        un-flipped migration, or the completing flip of a RETURNING
        merge (either way, ids in the range route by the base map
        again)."""
        out = tuple(e for e in self.entries if e[:4] != entry[:4])
        assert out != self.entries, (entry, self.entries)
        return OwnershipTable(self.n_shards, self.generation + 1, out)
