"""Superbatch (commit-window) kernel: bit-exact vs sequential dispatch.

K prepares stacked into one create_transfers_super_jit dispatch must
produce exactly the statuses, timestamps, and final device state of K
sequential create_transfers_fast_jit dispatches (the semantics the
replica relies on when aggregating a committed window). Reference
analog: the 8-deep prepare pipeline, src/config.zig:155 — batching is a
scheduling choice and must never be observable in results.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# Tier: jit-heavy parity/differential suite (see pytest.ini) —
# excluded from the quick gate; run via scripts/gate.py --tier slow.
pytestmark = pytest.mark.slow

from tigerbeetle_tpu.ops.batch import transfers_to_arrays
from tigerbeetle_tpu.ops.fast_kernels import (
    create_transfers_fast_jit,
    create_transfers_super_jit,
)
from tigerbeetle_tpu.ops.ledger import (
    DeviceLedger,
    pad_transfer_events,
    stack_superbatch,
)
from tigerbeetle_tpu.types import Account, Transfer, TransferFlags as TF

TS = 10_000_000_000_000
PAD = 256


def _fresh_state(n_accounts=8):
    led = DeviceLedger(a_cap=1 << 10, t_cap=1 << 12)
    led.create_accounts(
        [Account(id=i, ledger=1, code=1) for i in range(1, n_accounts + 1)],
        timestamp=TS,
    )
    assert led.fallbacks == 0
    return led.state


def _copy(state):
    return jax.tree.map(jnp.copy, state)


def _run_sequential(state, batches, tss):
    outs = []
    for tr, ts in zip(batches, tss):
        ev = {k: jax.device_put(v) for k, v in pad_transfer_events(
            transfers_to_arrays(tr), PAD).items()}
        state, out = create_transfers_fast_jit(
            state, ev, np.uint64(ts), np.int32(len(tr)))
        assert not bool(out["fallback"]), "sequential arm fell back"
        outs.append(out)
    return state, outs


def _run_super(state, batches, tss):
    ev_s, seg = stack_superbatch(
        [transfers_to_arrays(tr) for tr in batches], tss, PAD)
    ev_s = {k: jax.device_put(v) for k, v in ev_s.items()}
    seg = {k: jax.device_put(v) for k, v in seg.items()}
    return create_transfers_super_jit(state, ev_s, seg)


def _ht_content(table):
    """Logical content of a hash table: sorted (key_hi, key_lo, val)
    triples. Slot LAYOUT legitimately differs between sequential and
    superbatch arms (two-choice placement reads bucket occupancy at
    plan time, and the superbatch plans the whole window against the
    pre-window table) — but the mapping, hence every lookup and every
    derived result, must be identical."""
    from tigerbeetle_tpu.ops.hash_table import SLOTS, ht_matrix

    p = ht_matrix(table)[:-1]
    kh = p[:, :SLOTS].reshape(-1)
    kl = p[:, SLOTS:2 * SLOTS].reshape(-1)
    v = p[:, 2 * SLOTS:].reshape(-1)
    live = (kh != 0) | (kl != 0)
    trips = sorted(zip(kh[live].tolist(), kl[live].tolist(),
                       v[live].tolist()))
    return trips


def _assert_equal(seq_state, seq_outs, sup_state, sup_out, k):
    assert not bool(sup_out["fallback"]), "superbatch fell back"
    st = np.asarray(sup_out["r_status"]).reshape(k, PAD)
    ts = np.asarray(sup_out["r_ts"]).reshape(k, PAD)
    for b, out in enumerate(seq_outs):
        np.testing.assert_array_equal(st[b], np.asarray(out["r_status"]))
        np.testing.assert_array_equal(ts[b], np.asarray(out["r_ts"]))
    for key in seq_state:
        if key.endswith("_ht"):
            assert _ht_content(seq_state[key]) == _ht_content(
                sup_state[key]), key
            continue
        flat_seq = jax.tree.leaves(seq_state[key])
        flat_sup = jax.tree.leaves(sup_state[key])
        for a, b in zip(flat_seq, flat_sup):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=key)


def _diff_case(batches, tss):
    state = _fresh_state()
    seq_state, seq_outs = _run_sequential(_copy(state), batches, tss)
    sup_state, sup_out = _run_super(_copy(state), batches, tss)
    _assert_equal(seq_state, seq_outs, sup_state, sup_out, len(batches))


def test_regular_window():
    rng = np.random.default_rng(11)
    batches = []
    next_id = 1000
    for _ in range(3):
        trs = []
        for _ in range(40):
            dr = int(rng.integers(1, 9))
            cr = dr % 8 + 1
            trs.append(Transfer(id=next_id, debit_account_id=dr,
                                credit_account_id=cr, ledger=1, code=1,
                                amount=int(rng.integers(1, 100))))
            next_id += 1
        batches.append(trs)
    tss = [TS + 1000 + b * (PAD + 10) for b in range(3)]
    _diff_case(batches, tss)


def test_mixed_statuses_and_pendings():
    """Pendings with timeouts (pulse evolution spans the window), failures
    (not-found accounts), and posts of pendings committed BEFORE the
    window."""
    state = _fresh_state()
    # Commit a pending first (separate prepare, before the window).
    pend = [Transfer(id=500, debit_account_id=1, credit_account_id=2,
                     ledger=1, code=1, amount=50, timeout=3600,
                     flags=TF.pending)]
    ts0 = TS + 500
    ev = {k: jax.device_put(v) for k, v in pad_transfer_events(
        transfers_to_arrays(pend), PAD).items()}
    state, out = create_transfers_fast_jit(
        state, ev, np.uint64(ts0), np.int32(1))
    assert not bool(out["fallback"])

    batches = [
        # window batch 1: regular + a failing transfer + a new pending
        [Transfer(id=600, debit_account_id=1, credit_account_id=2,
                  ledger=1, code=1, amount=10),
         Transfer(id=601, debit_account_id=99, credit_account_id=2,
                  ledger=1, code=1, amount=10),
         Transfer(id=602, debit_account_id=3, credit_account_id=4,
                  ledger=1, code=1, amount=7, timeout=60,
                  flags=TF.pending)],
        # window batch 2: post the pre-window pending (full amount)
        [Transfer(id=700, pending_id=500, ledger=0, code=0,
                  amount=(1 << 128) - 1,
                  flags=TF.post_pending_transfer)],
    ]
    tss = [ts0 + 1000, ts0 + 2000]
    seq_state, seq_outs = _run_sequential(_copy(state), batches, tss)
    sup_state, sup_out = _run_super(_copy(state), batches, tss)
    _assert_equal(seq_state, seq_outs, sup_state, sup_out, 2)


def test_chain_at_boundary_does_not_merge():
    """A linked chain open at a sub-batch's end errors with
    linked_event_chain_open and must NOT absorb the next sub-batch's
    head (chains never span prepares)."""
    batches = [
        # ends with an OPEN chain: last event has linked set
        [Transfer(id=800, debit_account_id=1, credit_account_id=2,
                  ledger=1, code=1, amount=1),
         Transfer(id=801, debit_account_id=1, credit_account_id=2,
                  ledger=1, code=1, amount=1, flags=TF.linked)],
        # next sub-batch starts with a clean chain pair
        [Transfer(id=810, debit_account_id=3, credit_account_id=4,
                  ledger=1, code=1, amount=1, flags=TF.linked),
         Transfer(id=811, debit_account_id=3, credit_account_id=4,
                  ledger=1, code=1, amount=1)],
    ]
    tss = [TS + 1000, TS + 2000]
    _diff_case(batches, tss)
    # And the failing-chain case: poison inside a chain in batch 2.
    batches2 = [
        [Transfer(id=820, debit_account_id=1, credit_account_id=2,
                  ledger=1, code=1, amount=1)],
        [Transfer(id=830, debit_account_id=3, credit_account_id=4,
                  ledger=1, code=1, amount=1, flags=TF.linked),
         Transfer(id=831, debit_account_id=77, credit_account_id=4,
                  ledger=1, code=1, amount=1)],
    ]
    _diff_case(batches2, [TS + 3000, TS + 4000])


def test_cross_batch_duplicate_falls_back():
    """A duplicate id across the window's sub-batches is a cross-prepare
    dependency: the superbatch must fall back (the caller then executes
    the window sequentially), never silently diverge."""
    state = _fresh_state()
    batches = [
        [Transfer(id=900, debit_account_id=1, credit_account_id=2,
                  ledger=1, code=1, amount=1)],
        [Transfer(id=900, debit_account_id=1, credit_account_id=2,
                  ledger=1, code=1, amount=1)],
    ]
    tss = [TS + 1000, TS + 2000]
    _, sup_out = _run_super(_copy(state), batches, tss)
    assert bool(sup_out["fallback"])


def test_state_machine_commit_window_parity():
    """StateMachine.commit_window replies byte-identically to per-body
    commit, including multi-inner-batch bodies and served lookups
    afterward."""
    from tigerbeetle_tpu import multi_batch
    from tigerbeetle_tpu.state_machine import (
        OPERATION_SPECS,
        StateMachine,
    )
    from tigerbeetle_tpu.types import Operation

    def fresh():
        sm = StateMachine(engine="device", a_cap=1 << 10, t_cap=1 << 12)
        sm.create_accounts(
            [Account(id=i, ledger=1, code=1) for i in range(1, 9)], TS)
        return sm

    spec = OPERATION_SPECS[Operation.create_transfers]

    def payload(ids):
        return b"".join(
            Transfer(id=i, debit_account_id=(i % 8) + 1,
                     credit_account_id=(i % 8) % 8 + 2
                     if (i % 8) + 1 != (i % 8) % 8 + 2 else 1,
                     ledger=1, code=1, amount=1 + i % 97).pack()
            for i in ids)

    bodies = [
        multi_batch.encode([payload(range(1000, 1020))], spec.event_size),
        # two inner batches in one prepare
        multi_batch.encode([payload(range(2000, 2010)),
                            payload(range(2100, 2130))], spec.event_size),
        multi_batch.encode([payload(range(3000, 3040))], spec.event_size),
        multi_batch.encode([payload(range(4000, 4004))], spec.event_size),
    ]
    tss = [TS + 10_000 + i * 1000 for i in range(4)]

    sm_a = fresh()
    seq = [sm_a.commit(Operation.create_transfers, b, ts)
           for b, ts in zip(bodies, tss)]
    sm_b = fresh()
    win = sm_b.commit_window(Operation.create_transfers, bodies, tss)
    assert seq == win
    assert sm_b.led.window_fallbacks == 0
    # Served state agrees.
    a = sm_a.lookup_accounts(list(range(1, 9)))
    b = sm_b.lookup_accounts(list(range(1, 9)))
    assert [(x.id, x.debits_posted, x.credits_posted) for x in a] == \
           [(x.id, x.debits_posted, x.credits_posted) for x in b]


def test_commit_window_cross_prepare_dup_seq_fallback():
    """A window with a duplicate id across prepares produces the same
    replies as sequential commits. Since the chain route became the
    default dispatch mode (round 7) this resolves NATIVELY: prepare 2
    executes against the state prepare 1 evolved inside the one scan
    dispatch, so the duplicate reads 'exists' with ZERO fallbacks —
    the flat superbatch used to throw the whole window away (E2)."""
    from tigerbeetle_tpu import multi_batch
    from tigerbeetle_tpu.state_machine import (
        OPERATION_SPECS,
        StateMachine,
    )
    from tigerbeetle_tpu.types import Operation

    def fresh():
        sm = StateMachine(engine="device", a_cap=1 << 10, t_cap=1 << 12)
        sm.create_accounts(
            [Account(id=i, ledger=1, code=1) for i in range(1, 9)], TS)
        return sm

    spec = OPERATION_SPECS[Operation.create_transfers]
    tr = Transfer(id=5000, debit_account_id=1, credit_account_id=2,
                  ledger=1, code=1, amount=9).pack()
    bodies = [multi_batch.encode([tr], spec.event_size),
              multi_batch.encode([tr], spec.event_size)]
    tss = [TS + 50_000, TS + 51_000]
    sm_a = fresh()
    seq = [sm_a.commit(Operation.create_transfers, b, ts)
           for b, ts in zip(bodies, tss)]
    sm_b = fresh()
    win = sm_b.commit_window(Operation.create_transfers, bodies, tss)
    assert seq == win
    assert sm_b.led.window_fallbacks == 0
    assert sm_b.led.fallback_stats()["routes"]["windows"] == {"chain": 1}


def test_replica_catchup_windows_preserve_determinism():
    """A lagging device-engine replica catches up through WINDOWED
    commits (commit_journal forms windows over the replayed suffix)
    while its peers committed the same ops one at a time — physical
    checkpoints must still be byte-identical across replicas (the
    storage checker is the arbiter; per-op flush cadence with exact
    chunk attribution is what makes this hold)."""
    from tigerbeetle_tpu import multi_batch
    from tigerbeetle_tpu.state_machine import StateMachine
    from tigerbeetle_tpu.testing.cluster import Cluster
    from tigerbeetle_tpu.types import Operation

    cluster = Cluster(
        seed=31, replica_count=3,
        state_machine_factory=lambda: StateMachine(
            engine="device", a_cap=1 << 10, t_cap=1 << 12))
    client = cluster.client(77)

    def drive(op, body, ticks=4000):
        client.request(op, body)
        ok = cluster.run(ticks, until=lambda: client.idle)
        assert ok, cluster.debug_status()

    drive(Operation.create_accounts, multi_batch.encode(
        [b"".join(Account(id=i, ledger=1, code=1).pack()
                  for i in (1, 2, 3))], 128))
    victim = (cluster.replicas[0].primary_index() + 1) % 3
    cluster.crash(victim)
    # Lag by a multi-op suffix SMALL enough to stay below the state-sync
    # threshold (WAL replay, where windows form), then cross the
    # checkpoint boundary after the restart so every replica checkpoints
    # the same op for the byte-identity check.
    interval = cluster.replicas[0].options.checkpoint_interval
    lagged = max(4, interval - 8)
    k = 0
    for _ in range(lagged):
        drive(Operation.create_transfers, multi_batch.encode(
            [Transfer(id=5000 + k, debit_account_id=1,
                      credit_account_id=2, amount=1 + (k % 7),
                      ledger=1, code=1).pack()], 128))
        k += 1
    cluster.restart(victim)
    cluster.settle()
    r = cluster.replicas[victim]
    assert getattr(r, "_windows_committed", 0) >= 1, \
        "catch-up replay never formed a commit window"
    for _ in range(12):
        drive(Operation.create_transfers, multi_batch.encode(
            [Transfer(id=5000 + k, debit_account_id=1,
                      credit_account_id=2, amount=1 + (k % 7),
                      ledger=1, code=1).pack()], 128))
        k += 1
    cluster.settle()
    assert all(rep.superblock.op_checkpoint > 0
               for rep in cluster.replicas)
    total = sum(1 + (j % 7) for j in range(k))
    assert r.state_machine.state.accounts[2].credits_posted == total
    cluster.check_convergence()
    cluster.check_storage()


def test_varying_batch_sizes():
    rng = np.random.default_rng(13)
    batches = []
    next_id = 2000
    for n in (1, 37, 200):
        trs = []
        for _ in range(n):
            dr = int(rng.integers(1, 9))
            cr = dr % 8 + 1
            trs.append(Transfer(id=next_id, debit_account_id=dr,
                                credit_account_id=cr, ledger=1, code=1,
                                amount=int(rng.integers(1, 100))))
            next_id += 1
        batches.append(trs)
    tss = [TS + 1000 + b * (PAD + 10) for b in range(3)]
    _diff_case(batches, tss)


def test_balancing_window():
    """Balancing clamps whose cascades span prepare boundaries run
    natively on the balancing super tier, bit-exact vs sequential
    dispatches of the per-batch balancing kernel (amounts re-derived
    from exact prefix balances across the WHOLE window)."""
    from tigerbeetle_tpu.ops.fast_kernels import (
        create_transfers_balancing_jit,
        create_transfers_super_balancing_jit,
    )

    AMOUNT_MAX = (1 << 128) - 1
    BAL_DR = int(TF.balancing_debit)
    BAL_CR = int(TF.balancing_credit)
    PEND = int(TF.pending)

    state = _fresh_state()
    # Fund: account 1 gets 300 credits, account 3 gets 120 debits.
    fund = [Transfer(id=900, debit_account_id=2, credit_account_id=1,
                     amount=300, ledger=1, code=1),
            Transfer(id=901, debit_account_id=3, credit_account_id=4,
                     amount=120, ledger=1, code=1)]
    ev = {k: jax.device_put(v) for k, v in pad_transfer_events(
        transfers_to_arrays(fund), PAD).items()}
    state, out = create_transfers_fast_jit(
        state, ev, np.uint64(TS + 500), np.int32(2))
    assert not bool(out["fallback"])

    batches = [
        # prepare 1: sweep most of account 1's headroom, hold some.
        [Transfer(id=1000, debit_account_id=1, credit_account_id=5,
                  amount=200, ledger=1, code=1, flags=BAL_DR),
         Transfer(id=1001, debit_account_id=1, credit_account_id=5,
                  amount=AMOUNT_MAX, ledger=1, code=1,
                  flags=BAL_DR | PEND, timeout=3600)],
        # prepare 2: the clamp here must see prepare 1's effects: zero
        # headroom left on 1; balancing_credit into 3 clamps at 120.
        [Transfer(id=1010, debit_account_id=1, credit_account_id=5,
                  amount=AMOUNT_MAX, ledger=1, code=1, flags=BAL_DR),
         Transfer(id=1011, debit_account_id=6, credit_account_id=3,
                  amount=AMOUNT_MAX, ledger=1, code=1, flags=BAL_CR)],
        # prepare 3: both flags; headroom restored by new funding.
        [Transfer(id=1020, debit_account_id=5, credit_account_id=1,
                  amount=50, ledger=1, code=1),
         Transfer(id=1021, debit_account_id=1, credit_account_id=3,
                  amount=AMOUNT_MAX, ledger=1, code=1,
                  flags=BAL_DR | BAL_CR)],
    ]
    tss = [TS + 1000 + b * (PAD + 10) for b in range(3)]

    # Sequential arm on the per-batch balancing tier.
    seq_state = _copy(state)
    seq_outs = []
    for tr, ts in zip(batches, tss):
        evb = {k: jax.device_put(v) for k, v in pad_transfer_events(
            transfers_to_arrays(tr), PAD).items()}
        seq_state, o = create_transfers_balancing_jit(
            seq_state, evb, np.uint64(ts), np.int32(len(tr)))
        assert not bool(o["fallback"]), "sequential balancing arm fell back"
        seq_outs.append(o)

    ev_s, seg = stack_superbatch(
        [transfers_to_arrays(tr) for tr in batches], tss, PAD)
    ev_s = {k: jax.device_put(v) for k, v in ev_s.items()}
    seg = {k: jax.device_put(v) for k, v in seg.items()}
    sup_state, sup_out = create_transfers_super_balancing_jit(
        _copy(state), ev_s, seg)
    _assert_equal(seq_state, seq_outs, sup_state, sup_out, len(batches))


def test_balancing_window_through_ledger_vs_oracle():
    """create_transfers_window with balancing prepares: native (no
    window fallback), results and balances identical to the oracle fed
    the same prepares sequentially."""
    from tigerbeetle_tpu.oracle import StateMachineOracle

    AMOUNT_MAX = (1 << 128) - 1
    BAL_DR = int(TF.balancing_debit)

    led = DeviceLedger(a_cap=1 << 10, t_cap=1 << 12)
    sm = StateMachineOracle()
    accts = [Account(id=i, ledger=1, code=1) for i in range(1, 9)]
    for eng in (led, sm):
        r = eng.create_accounts(accts, TS)
        assert all(x.status.name == "created" for x in r)
    fund = [Transfer(id=900, debit_account_id=2, credit_account_id=1,
                     amount=100, ledger=1, code=1)]
    got = led.create_transfers(fund, TS + 500)
    want = sm.create_transfers(fund, TS + 500)
    assert [(r.timestamp, r.status) for r in got] == \
           [(r.timestamp, r.status) for r in want]

    batches = [
        [Transfer(id=1000, debit_account_id=1, credit_account_id=5,
                  amount=60, ledger=1, code=1, flags=BAL_DR)],
        [Transfer(id=1010, debit_account_id=1, credit_account_id=5,
                  amount=AMOUNT_MAX, ledger=1, code=1, flags=BAL_DR),
         Transfer(id=1011, debit_account_id=1, credit_account_id=5,
                  amount=AMOUNT_MAX, ledger=1, code=1, flags=BAL_DR)],
    ]
    tss = [TS + 1000, TS + 1000 + PAD + 10]
    evs = [transfers_to_arrays(tr) for tr in batches]
    res = led.create_transfers_window(evs, tss)
    assert res is not None and led.window_fallbacks == 0
    flat = []
    for (st, ts_arr), tr in zip(res, batches):
        flat += [(int(t), int(s)) for s, t in zip(st, ts_arr)]
    want = []
    for tr, ts in zip(batches, tss):
        want += [(r.timestamp, int(r.status))
                 for r in sm.create_transfers(tr, ts)]
    assert flat == want
    # Clamp cascade across the window: 60, then 40, then 0.
    assert [t.amount for t in led.lookup_transfers([1000, 1010, 1011])] \
        == [60, 40, 0]
    a_led = {a.id: a for a in led.lookup_accounts([1, 5])}
    a_sm = {a.id: a for a in sm.lookup_accounts([1, 5])}
    assert a_led == a_sm
