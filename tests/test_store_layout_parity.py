"""The device stores' HOST VIEW is pinned byte for byte (PR 32).

The transfers store, the event ring and both hash tables cross the jit
boundary as u32 (ops/ev_layout.py, ops/hash_table.py: what a v5e does
with a u64 store). Everything on the host — the mirror, the delta
fetch, the state epochs, the durable format — is written against the
packed u64 matrices, which are now a `.view(np.uint64)` of the u32
arrays. These tests run seeded requests through each tier's jit entry
and hold the u64 view of every store and table, and `ht_live_items`,
to digests taken from the u64 layout's own code (commit d50dfd6, the
parent of PR 32): the same statuses, the same rows in the same order,
the same table slots.

The requests: created and failed transfers, a transient failure (its
id is orphaned in the table), pending then post / void, a post of a
pending defined in the same batch (fixpoint tier: the in-window flip
rewrites the row the same dispatch inserts), ten ids whose first-choice
bucket is the same (two overflow into their second choice).
"""

import hashlib

import numpy as np
import pytest

A_CAP, T_CAP, ORPHAN_CAP, N_PAD = 64, 256, 64, 64

PEND, POST, VOID, IMP = 2, 4, 8, 256  # TransferFlags


def _same_bucket_ids(n_buckets, want, start):
    """`want` ids >= start whose FIRST bucket choice in a table of
    n_buckets buckets is the same: inserted into an empty table in one
    batch, the ninth and tenth overflow into their second choice."""
    from tigerbeetle_tpu.ops.hash_table import _buckets

    ids = np.arange(start, start + 4096, dtype=np.uint64)
    b1, b2 = _buckets(np.zeros_like(ids), ids, n_buckets)
    b1, b2 = np.asarray(b1), np.asarray(b2)
    ids, b1 = ids[b1 != b2], b1[b1 != b2]  # a second choice to go to
    full = np.flatnonzero(np.bincount(b1, minlength=n_buckets) >= want)[0]
    return [int(i) for i in ids[b1 == full][:want]]


def _run(entry, state, events, ts, pad):
    import jax

    n = len(events["id_lo"])
    state, out = entry(state, pad(events, N_PAD), np.uint64(ts),
                       np.int32(n))
    out = jax.device_get(out)
    assert not bool(out["fallback"]), "the tier must judge the batch"
    return state, [int(s) for s in out["r_status"][:n]]


def _accounts(state, table_buckets=None):
    from tigerbeetle_tpu.ops import fast_kernels as fk
    from tigerbeetle_tpu.ops.batch import accounts_to_arrays
    from tigerbeetle_tpu.ops.ledger import pad_account_events
    from tigerbeetle_tpu.types import Account, AccountFlags

    ids = list(range(1, 9))
    if table_buckets is not None:
        ids += _same_bucket_ids(table_buckets, 10, 1000)
    accs = [Account(id=i, ledger=1, code=1) for i in ids]
    accs.append(Account(
        id=9, ledger=1, code=1,
        flags=int(AccountFlags.debits_must_not_exceed_credits)))
    accs.append(Account(id=0, ledger=1, code=1))  # id_must_not_be_zero
    state, st1 = _run(fk.create_accounts_fast_jit, state,
                      accounts_to_arrays(accs), 1_000, pad_account_events)
    again = [Account(id=3, ledger=2, code=1),   # exists, differs
             Account(id=4, ledger=1, code=1),   # exists
             Account(id=20, ledger=1, code=7, user_data_32=0xDEADBEEF,
                     user_data_64=1 << 63, user_data_128=(5 << 64) | 6)]
    state, st2 = _run(fk.create_accounts_fast_jit, state,
                      accounts_to_arrays(again), 2_000, pad_account_events)
    return state, st1 + st2


def _transfers(state, tier):
    from tigerbeetle_tpu.ops import fast_kernels as fk
    from tigerbeetle_tpu.ops.batch import transfers_to_arrays
    from tigerbeetle_tpu.ops.hash_table import ht_buckets
    from tigerbeetle_tpu.ops.ledger import pad_transfer_events
    from tigerbeetle_tpu.types import Transfer

    entry = {"plain": fk.create_transfers_fast_jit,
             "fixpoint": fk.create_transfers_fixpoint_jit,
             "imported": fk.create_transfers_imported_jit}[tier]
    base = IMP if tier == "imported" else 0
    uts = iter(range(5_000, 9_000, 7))

    def t(id_, dr=1, cr=2, amt=5, flags=0, pid=0, timeout=0):
        return Transfer(
            id=id_, debit_account_id=dr, credit_account_id=cr, amount=amt,
            ledger=1, code=1, flags=flags | base, pending_id=pid,
            timeout=timeout, user_data_32=id_ & 0xFFFF,
            user_data_64=(1 << 40) + id_, user_data_128=(7 << 64) | id_,
            timestamp=next(uts) if base else 0)

    crowd = _same_bucket_ids(ht_buckets(state["xfer_ht"]), 10, 5000)
    first = [t(i, 1 + k % 4, 5 + k % 4, 10 + k)
             for k, i in enumerate(crowd)]
    first += [
        t(101, 1, 2, 70, PEND, timeout=0 if base else 30),
        t(102, 3, 4, 80, PEND),
        t(103, 5, 6, 90, PEND),
        t(104, 2, 2),             # accounts_must_be_different
        t(105, 1, 4242),          # credit account not found: orphaned
        t(107, 7, 8, (1 << 70) + 3),
    ]
    if tier == "fixpoint":
        # A limit breach and an in-window post: the plain tiers hand
        # both to this one.
        first += [t(106, 9, 1, 1),  # exceeds_credits: orphaned
                  t(108, 5, 7, 40, PEND),
                  t(109, 0, 0, 25, POST, pid=108),
                  t(110, 6, 8, 9)]
    state, st1 = _run(entry, state, transfers_to_arrays(first), 10 ** 9,
                      pad_transfer_events)
    second = [
        t(201, 0, 0, 60, POST, pid=101),
        t(202, 0, 0, 0, VOID, pid=102),
        t(203, 0, 0, 0, POST, pid=777),  # pending_transfer_not_found
        t(crowd[0], 1, 5, 10),        # exists
        t(105, 1, 2),                 # id_already_failed
        t(204, 3, 1, 11),
    ]
    state, st2 = _run(entry, state, transfers_to_arrays(second),
                      2 * 10 ** 9, pad_transfer_events)
    return state, st1 + st2


def _host_views(state) -> dict:
    """name -> array: the u64 view of every store and table, the live
    items of both tables, and the scalars."""
    from tigerbeetle_tpu.ops.ev_layout import widen
    from tigerbeetle_tpu.ops.hash_table import ht_live_items, ht_matrix

    out = {
        "accounts_u64": widen(np.asarray(state["accounts"]["u32"])),
        "accounts_bal": widen(np.asarray(state["accounts"]["bal"])),
        "transfers": widen(np.asarray(state["transfers"]["u32"])),
        "events": widen(np.asarray(state["events"]["u32"])),
        "acct_ht": ht_matrix(state["acct_ht"]),
        "xfer_ht": ht_matrix(state["xfer_ht"]),
    }
    for name in ("acct_ht", "xfer_ht"):
        for part, arr in zip(("hi", "lo", "val"),
                             ht_live_items(state[name])):
            out[f"{name}_live_{part}"] = arr
    out["scalars"] = np.array(
        [int(state["accounts"]["count"]), int(state["transfers"]["count"]),
         int(state["events"]["count"]), int(state["acct_key_max"]),
         int(state["xfer_key_max"]), int(state["commit_ts"]),
         int(state["pulse_next"])], dtype=np.uint64)
    return out


def digests(views: dict) -> dict:
    return {k: hashlib.sha256(
        str((v.dtype, v.shape)).encode()
        + np.ascontiguousarray(v).tobytes()).hexdigest()[:16]
        for k, v in views.items()}


def scenario(case):
    """(state, statuses) after the seeded requests of `case`."""
    from tigerbeetle_tpu.ops.ledger import init_state

    state = init_state(A_CAP, T_CAP, orphan_cap=ORPHAN_CAP)
    if case == "create_accounts_fast":
        return _accounts(state, table_buckets=2 * A_CAP // 8)
    state, _ = _accounts(state)
    return _transfers(state, case)


# Taken by running `scenario` on the u64 layout's code (commit d50dfd6)
# and hashing its stores as they were held there.
OK = 0xFFFFFFFF  # created
PARENT = {
    "create_accounts_fast": dict(
        statuses=[
            OK, OK, OK, OK, OK, OK, OK, OK, OK, OK, OK, OK, OK, OK, OK,
            OK, OK, OK, OK, 6, 19, 21, OK
        ],
        digests={
            "accounts_u64": "c9d2487066fbaafc",
            "accounts_bal": "a765af7e9b483fb5",
            "transfers": "0b7ce76f67b71ce2",
            "events": "2134bb1e674af4b3",
            "acct_ht": "823720e1afc5e80a",
            "xfer_ht": "8ba897ef35e83103",
            "acct_ht_live_hi": "4bf04f9d85cf4aeb",
            "acct_ht_live_lo": "8f95d01370ee3f9b",
            "acct_ht_live_val": "3443657f9107cbce",
            "xfer_ht_live_hi": "3fc97d86e410be56",
            "xfer_ht_live_lo": "3fc97d86e410be56",
            "xfer_ht_live_val": "2d259898b8311c78",
            "scalars": "94f831577043da0e",
        }),
    "fixpoint": dict(
        statuses=[
            OK, OK, OK, OK, OK, OK, OK, OK, OK, OK, OK, OK, OK, 12, 22,
            OK, 54, OK, OK, OK, OK, OK, 25, 46, 68, OK
        ],
        digests={
            "accounts_u64": "fac812433c817ff5",
            "accounts_bal": "a18bf1081df36f68",
            "transfers": "df10594e7281486f",
            "events": "83a0e2a7ccacd82b",
            "acct_ht": "8ec6f9499f284904",
            "xfer_ht": "35decfe94cda5caf",
            "acct_ht_live_hi": "b3de4ae16f49a240",
            "acct_ht_live_lo": "2238445d1ed151ac",
            "acct_ht_live_val": "3dc5f2b153f2f948",
            "xfer_ht_live_hi": "038ba2d7ff3d5c1c",
            "xfer_ht_live_lo": "31bd75f6e6bad1d5",
            "xfer_ht_live_val": "bee3377c88b19ca4",
            "scalars": "672a245caba72252",
        }),
    "imported": dict(
        statuses=[
            OK, OK, OK, OK, OK, OK, OK, OK, OK, OK, OK, OK, OK, 12, 22,
            OK, OK, OK, 25, 46, 68, OK
        ],
        digests={
            "accounts_u64": "fac812433c817ff5",
            "accounts_bal": "45d419908928e76c",
            "transfers": "5bcc52ef182d6f8d",
            "events": "d9283fbe1ebbcec1",
            "acct_ht": "8ec6f9499f284904",
            "xfer_ht": "62e519b604e70f1b",
            "acct_ht_live_hi": "b3de4ae16f49a240",
            "acct_ht_live_lo": "2238445d1ed151ac",
            "acct_ht_live_val": "3dc5f2b153f2f948",
            "xfer_ht_live_hi": "28064e5d099dbbd9",
            "xfer_ht_live_lo": "243fd8eef8ede8c1",
            "xfer_ht_live_val": "d699a767dd03e362",
            "scalars": "cc2d20ed91afdbb0",
        }),
    "plain": dict(
        statuses=[
            OK, OK, OK, OK, OK, OK, OK, OK, OK, OK, OK, OK, OK, 12, 22,
            OK, OK, OK, 25, 46, 68, OK
        ],
        digests={
            "accounts_u64": "fac812433c817ff5",
            "accounts_bal": "45d419908928e76c",
            "transfers": "a9e1cb3cfa3c49f3",
            "events": "ed92c5deacba6482",
            "acct_ht": "8ec6f9499f284904",
            "xfer_ht": "62e519b604e70f1b",
            "acct_ht_live_hi": "b3de4ae16f49a240",
            "acct_ht_live_lo": "2238445d1ed151ac",
            "acct_ht_live_val": "3dc5f2b153f2f948",
            "xfer_ht_live_hi": "28064e5d099dbbd9",
            "xfer_ht_live_lo": "243fd8eef8ede8c1",
            "xfer_ht_live_val": "d699a767dd03e362",
            "scalars": "b5d47d83cc16895a",
        }),
}


@pytest.mark.parametrize("case", sorted(PARENT))
def test_host_view_equals_the_u64_layouts_bytes(case):
    state, statuses = scenario(case)
    want = PARENT[case]
    assert statuses == want["statuses"]
    got = digests(_host_views(state))
    assert got == want["digests"], sorted(
        k for k in got if got[k] != want["digests"].get(k))


def test_the_scenarios_exercise_what_they_claim():
    """Guards the pins above against a scenario that silently stopped
    reaching a path: orphans in the table, both bucket choices used,
    flips applied, the in-window flip on the row its batch inserted."""
    from tigerbeetle_tpu.ops.ev_layout import xf_named
    from tigerbeetle_tpu.ops.hash_table import (
        ORPHAN_VAL, _buckets, ht_live_items)

    state, _ = scenario("fixpoint")
    hi, lo, val = ht_live_items(state["xfer_ht"])
    assert (val == ORPHAN_VAL).sum() >= 2
    rows = xf_named({"u32": np.asarray(state["transfers"]["u32"])})
    pstat = {int(i): int(p) for i, p in zip(rows["id_lo"], rows["pstat"])}
    assert (pstat[101], pstat[102], pstat[103], pstat[108]) == (2, 3, 1, 2)
    state, _ = scenario("create_accounts_fast")
    hi, lo, _ = ht_live_items(state["acct_ht"])
    b1, b2 = _buckets(hi, lo, 2 * A_CAP // 8)
    assert len(hi) == 20
    # Which bucket each live key sits in: slot order of ht_live_items.
    from tigerbeetle_tpu.ops.hash_table import SLOTS, ht_matrix

    m = ht_matrix(state["acct_ht"])[:-1]
    at = np.repeat(np.arange(m.shape[0]), SLOTS)[
        ((m[:, :SLOTS] != 0) | (m[:, SLOTS:2 * SLOTS] != 0)).reshape(-1)]
    assert (at == np.asarray(b2)).sum() >= 2 and \
        ((at == np.asarray(b1)) | (at == np.asarray(b2))).all()


def test_ht_lookup_finds_what_ht_insert_put():
    """`ht_insert` then `ht_lookup`, called by name: every inserted key
    is found with its value, ten that share a first-choice bucket (two
    of them sit in their second choice) among them; a key never
    inserted and the zero sentinel are absent."""
    import jax.numpy as jnp

    from tigerbeetle_tpu.ops import hash_table as ht

    table = ht.ht_init(1 << 9)
    crowd = np.array(_same_bucket_ids(ht.ht_buckets(table), 10, 1),
                     dtype=np.uint64)
    b1, _ = ht._buckets(np.zeros_like(crowd), crowd, ht.ht_buckets(table))
    assert len(set(np.asarray(b1).tolist())) == 1 and len(crowd) > ht.SLOTS
    rng = np.random.default_rng(5)
    k_lo = np.concatenate([crowd, np.setdiff1d(
        rng.integers(1 << 20, 1 << 40, 90, dtype=np.uint64), crowd)])
    k_hi = np.concatenate([np.zeros(len(crowd), dtype=np.uint64),
                           rng.integers(0, 1 << 63, len(k_lo) - len(crowd),
                                        dtype=np.uint64)])
    vals = np.arange(len(k_lo), dtype=np.int32)
    table, ok = ht.ht_insert(table, jnp.asarray(k_hi), jnp.asarray(k_lo),
                             jnp.asarray(vals),
                             jnp.ones(len(k_lo), dtype=bool))
    assert bool(ok)
    absent = np.array([1 << 50, 0], dtype=np.uint64)  # a miss, the sentinel
    found, val = ht.ht_lookup(
        table, jnp.asarray(np.concatenate([k_hi, np.zeros(2, np.uint64)])),
        jnp.asarray(np.concatenate([k_lo, absent])))
    found, val = np.asarray(found), np.asarray(val)
    assert found[:-2].all() and (val[:-2] == vals).all()
    assert not found[-2:].any() and (val[-2:] == -1).all()


@pytest.mark.parametrize("n", [64, 1 << 17])
def test_worst_case_loads_are_exact_in_u32_pieces(n):
    """The headroom proof's per-account sums accumulate in u32 pieces
    of a limb (16 bits at the served batch widths, 14 at 2^17 lanes):
    every sum equals the plain u64 sum, at the largest limbs and with
    every lane on one account."""
    import jax.numpy as jnp

    from tigerbeetle_tpu.ops.fast_kernels import _worst_case_loads

    rng = np.random.default_rng(n)
    a_rows = 9
    ral = rng.integers(0, 1 << 32, (n, 4), dtype=np.uint64)
    ral[: n // 2] = (1 << 32) - 1
    dr = rng.integers(0, a_rows, n).astype(np.int32)
    dr[: n // 2] = 3
    cr = rng.integers(0, a_rows, n).astype(np.int32)
    got = _worst_case_loads(jnp.asarray(ral), jnp.asarray(dr),
                            jnp.asarray(cr), a_rows)
    for side, rows in zip(got, (dr, cr)):
        for j in range(4):
            want = np.zeros(a_rows, dtype=np.uint64)
            np.add.at(want, rows, ral[:, j])
            assert (np.asarray(side[j]) == want).all(), (n, j)
