"""Differential tests: partitioned ledger state vs the oracle.

The partitioned route (parallel/partitioned.py) shards EVERY store by
account/transfer id hash and resolves each batch through the on-device
exchange + mini-state judge. These tests pin the acceptance contract:
bit-exact statuses, result timestamps, flushed canonical columns, and
epoch digests vs the sequential oracle — at mesh sizes 1, 2, and 8,
with zero host fallbacks — on exactly the windows the exchange has to
get right: two-phase pairs straddling shards, closing×balancing across
shards, and a Zipfian hot-account window where one shard owns the hot
key.
"""

import jax
import numpy as np
import pytest

# Tier: jit-heavy parity/differential suite (see pytest.ini) —
# excluded from the quick gate; run via scripts/gate.py --tier slow.
pytestmark = pytest.mark.slow

from jax.experimental import mesh_utils
from jax.sharding import Mesh

from tigerbeetle_tpu.oracle import StateMachineOracle
from tigerbeetle_tpu.ops.batch import transfers_to_arrays
from tigerbeetle_tpu.ops.ev_layout import (
    EV_P32_POS, XF_NCOLS, XF_P32_POS, widen)
from tigerbeetle_tpu.ops.ledger import (
    DeviceLedger, _delta_gather_body, _pad_bucket, pad_transfer_events)
from tigerbeetle_tpu.ops.state_epoch import (
    partitioned_oracle_digest, partitioned_state_digest)
from tigerbeetle_tpu.parallel.partitioned import (
    PartitionedRouter, partitioned_state_bytes, replicated_state_bytes)
from tigerbeetle_tpu.parallel.shard_utils import shard_of_int
from tigerbeetle_tpu.types import Account, AccountFlags, Transfer, \
    TransferFlags as TF

PEND = int(TF.pending)
POST = int(TF.post_pending_transfer)
VOID = int(TF.void_pending_transfer)
BAL_DR = int(TF.balancing_debit)
BAL_CR = int(TF.balancing_credit)
CLOSE_DR = int(TF.closing_debit)
DR_LIMIT = int(AccountFlags.debits_must_not_exceed_credits)
AMOUNT_MAX = (1 << 128) - 1

A_CAP, T_CAP = 1 << 9, 1 << 11
MESH_SIZES = (1, 2, 8)

# Row-pointer words are shard-/mini-scope under the partitioned layout
# (module docstring) — everything else in the flush must be bit-exact.
_XF_PTR_COL = XF_P32_POS["dr_row"][0]
_EV_PTR_COL = EV_P32_POS["dr_row"][0]
_EV_PROW_COL = EV_P32_POS["p_row"][0]  # (pstat, p_row): pstat canonical


# Compile-once caches shared across tests: the partitioned step is a
# large program, and each (mesh size, tier) pair would otherwise
# recompile per test instance.
_MESHES: dict = {}
_STEPS: dict = {}


def _mesh(n_dev):
    if len(jax.devices()) < n_dev:
        pytest.skip(f"needs {n_dev} devices")
    if n_dev not in _MESHES:
        _MESHES[n_dev] = Mesh(mesh_utils.create_device_mesh(
            (n_dev,), devices=jax.devices()[:n_dev]), ("batch",))
    return _MESHES[n_dev]


class Harness:
    """Oracle + partitioned router + single-chip ledger in lockstep;
    every batch asserts statuses/timestamps vs the oracle and the
    flushed canonical columns vs the single-chip delta gather."""

    def __init__(self, n_dev, accounts, ts0=10 ** 9):
        self.mesh = _mesh(n_dev)
        self.n_dev = n_dev
        self.oracle = StateMachineOracle()
        # The single-chip reference needs t/e caps >= N_PAD so the
        # flush-parity delta gather can slice a full padded batch.
        self.led = DeviceLedger(a_cap=A_CAP, t_cap=1 << 14)
        self.oracle.create_accounts(accounts, 50)
        self.led.create_accounts(accounts, 50)
        self.router = PartitionedRouter(self.mesh, a_cap=A_CAP,
                                        t_cap=T_CAP)
        self.router._steps = _STEPS.setdefault(n_dev, {})
        self.state = self.router.from_oracle(self.oracle)
        self.ts = ts0

    def step(self, evs, expect_statuses=None):
        self.ts += 300
        n = len(evs)
        ev = pad_transfer_events(transfers_to_arrays(evs))
        N = ev["id_lo"].shape[0]
        t0 = int(np.asarray(self.led.state["transfers"]["count"]))
        e0 = int(np.asarray(self.led.state["events"]["count"]))
        self.state, out, fb = self.router.step(self.state, ev, self.ts, n)
        assert not fb, jax.device_get(out["fb_causes"])
        want = self.oracle.create_transfers(evs, self.ts)
        self.led.create_transfers(evs, self.ts)
        st = np.asarray(out["r_status"][:n])
        rts = np.asarray(out["r_ts"][:n])
        got = [(int(rts[i]), int(st[i])) for i in range(n)]
        exp = [(r.timestamp, int(r.status)) for r in want]
        assert got == exp, list(zip(got, exp))
        if expect_statuses is not None:
            assert [r.status.name for r in want] == expect_statuses
        self._check_flush(out, t0, e0, N)
        return want

    def _check_flush(self, out, t0, e0, N):
        c = int(np.asarray(out["created_count"]))
        flush = jax.device_get(out["flush"])
        ref = jax.device_get(_delta_gather_body(
            self.led.state, t0, e0, N, N))
        for k in ("dr_id_hi", "dr_id_lo", "cr_id_hi", "cr_id_lo"):
            assert (flush[k][:c] == ref[k][:c]).all(), k
        # p_ts is only defined on ring rows referencing a pending
        # (p_row >= 0); elsewhere the gather reads row 0 of whichever
        # scope — not a canonical value.
        flush = dict(flush, t={"u64": widen(flush["t"]["u32"])},
                     e={"u64": widen(flush["e"]["u32"])})
        ref = dict(ref, t={"u64": widen(ref["t"]["u32"])},
                   e={"u64": widen(ref["e"]["u32"])})
        prow_hi = (ref["e"]["u64"][:c, _EV_PROW_COL]
                   >> np.uint64(32)).astype(np.uint32)
        has_p = prow_hi != np.uint32(0xFFFFFFFF)
        assert (flush["p_ts"][:c] == ref["p_ts"][:c])[has_p].all(), "p_ts"
        for col in range(XF_NCOLS):
            if col == _XF_PTR_COL:
                continue
            assert (flush["t"]["u64"][:c, col]
                    == ref["t"]["u64"][:c, col]).all(), ("t", col)
        ncols_e = flush["e"]["u64"].shape[1]
        for col in range(ncols_e):
            if col == _EV_PTR_COL:
                continue
            a = flush["e"]["u64"][:c, col]
            b = ref["e"]["u64"][:c, col]
            if col == _EV_PROW_COL:
                a = a & np.uint64(0xFFFFFFFF)
                b = b & np.uint64(0xFFFFFFFF)
            assert (a == b).all(), ("e", col)

    def finish(self):
        assert self.router.host_fallbacks == 0
        dd = partitioned_state_digest(self.state)
        od = partitioned_oracle_digest(self.oracle, A_CAP, self.n_dev)
        assert dd == od, (dd, od)


def _cross_shard_pairs(n_dev, count, rng):
    """(dr, cr) account-id pairs on DIFFERENT shards (any pair when
    n_dev == 1), drawn from ids 1..40."""
    pairs = []
    ids = list(range(1, 41))
    while len(pairs) < count:
        dr, cr = rng.choice(ids, 2, replace=False)
        if n_dev == 1 or shard_of_int(int(dr), n_dev) != shard_of_int(
                int(cr), n_dev):
            pairs.append((int(dr), int(cr)))
    return pairs


@pytest.mark.parametrize("n_dev", MESH_SIZES)
class TestPartitioned:
    def test_two_phase_cross_shard(self, n_dev):
        """Pending/post/void pairs whose debit and credit accounts —
        and whose pending vs post/void transfer ids — straddle shards:
        the exchange's two-phase join (pending row fetched in phase 1,
        its accounts in phase 2) is on the critical path of every
        event."""
        rng = np.random.default_rng(11)
        accts = [Account(id=i, ledger=1, code=1) for i in range(1, 41)]
        h = Harness(n_dev, accts)
        nid = 10 ** 6
        pendings = []
        for _ in range(3):
            evs = []
            for dr, cr in _cross_shard_pairs(n_dev, 60, rng):
                roll = rng.random()
                if roll < 0.5 or not pendings:
                    evs.append(Transfer(
                        id=nid, debit_account_id=dr,
                        credit_account_id=cr,
                        amount=int(rng.integers(1, 60)), ledger=1,
                        code=1, flags=PEND))
                    pendings.append(nid)
                else:
                    pid = pendings.pop(0)
                    f = POST if rng.random() < 0.5 else VOID
                    evs.append(Transfer(
                        id=nid, pending_id=pid,
                        amount=AMOUNT_MAX if f == POST else 0, flags=f))
                nid += 1
            h.step(evs)
        h.finish()
        if n_dev > 1:
            assert h.router.cross_shard_transfers > 0

    def test_closing_balancing_cross_shard(self, n_dev):
        """Closing×balancing across shards: limit accounts funded from
        remote shards, balancing debits clamped against them, a closing
        pending shuts a remote account mid-window, and its void
        reopens it — the fixpoint/balancing tiers run on the
        exchange-assembled mini-state."""
        accts = [Account(id=i, ledger=1, code=1,
                         flags=DR_LIMIT if i <= 8 else 0)
                 for i in range(1, 41)]
        h = Harness(n_dev, accts)
        rng = np.random.default_rng(13)
        pairs = _cross_shard_pairs(n_dev, 16, rng)
        # Fund the limit accounts (plain tier, cross-shard rows).
        evs = [Transfer(id=1000 + i, debit_account_id=20 + i % 16,
                        credit_account_id=1 + i % 8, amount=100 + i,
                        ledger=1, code=1) for i in range(16)]
        h.step(evs)
        # Balancing debits off the limit accounts to remote credits.
        evs = [Transfer(id=2000 + i, debit_account_id=1 + i % 8,
                        credit_account_id=dr if dr > 8 else cr,
                        amount=AMOUNT_MAX, ledger=1, code=1,
                        flags=BAL_DR)
               for i, (dr, cr) in enumerate(pairs[:8])]
        h.step(evs)
        # Closing pending on a remote pair + interleaved balancing,
        # then the void reopens the closed account next batch.
        dr, cr = pairs[8]
        evs = [
            Transfer(id=3000, debit_account_id=dr,
                     credit_account_id=cr, amount=1, ledger=1, code=1,
                     flags=PEND | CLOSE_DR),
            Transfer(id=3001, debit_account_id=dr,
                     credit_account_id=cr, amount=5, ledger=1, code=1),
            Transfer(id=3002, debit_account_id=1, credit_account_id=cr,
                     amount=AMOUNT_MAX, ledger=1, code=1,
                     flags=BAL_DR),
        ]
        h.step(evs)
        h.step([Transfer(id=3003, pending_id=3000, amount=0,
                         flags=VOID),
                Transfer(id=3004, debit_account_id=dr,
                         credit_account_id=cr, amount=2, ledger=1,
                         code=1)])
        h.finish()

    def test_zipfian_hot_account(self, n_dev):
        """Zipfian account draw: one shard owns the hot key, so its
        exchange lanes and write-backs concentrate there while the
        mini-state judge stays replicated — the skew-tolerance shape of
        the partitioned route."""
        rng = np.random.default_rng(17)
        accts = [Account(id=i, ledger=1, code=1) for i in range(1, 41)]
        h = Harness(n_dev, accts)
        nid = 10 ** 6
        for _ in range(3):
            draws = np.minimum(rng.zipf(1.3, size=(150, 2)), 40)
            evs = []
            for dr, cr in draws:
                dr, cr = int(dr), int(cr)
                if dr == cr:
                    cr = dr % 40 + 1
                evs.append(Transfer(
                    id=nid, debit_account_id=dr, credit_account_id=cr,
                    amount=int(rng.integers(1, 40)), ledger=1, code=1))
                nid += 1
            h.step(evs)
        h.finish()
        owned = h.router.stats()["events_owned"]
        assert sum(owned) == h.router.batches * 150

    def test_state_bytes_scale(self, n_dev):
        """Per-device resident bytes ~1/n_shards vs the replicated
        route at the same caps (the HBM-clamp removal the layout
        exists for)."""
        accts = [Account(id=i, ledger=1, code=1) for i in range(1, 9)]
        h = Harness(n_dev, accts)
        pb = partitioned_state_bytes(h.state)
        rb = replicated_state_bytes(A_CAP, T_CAP)
        assert pb <= rb // n_dev + rb // 50, (pb, rb, n_dev)


_CHAIN_STEPS: dict = {}


@pytest.mark.parametrize("n_dev", MESH_SIZES)
class TestPartitionedChain:
    """The fused default window route: ONE shard_map+lax.scan dispatch
    per eligible commit window, differential vs the oracle AND vs the
    per-batch partitioned ladder — including a poisoned window whose
    clean prefix must stay committed inside the dispatch while the
    fallen-back prepare replays per-batch, with host_fallbacks==0."""

    def _fresh(self, n_dev, accounts):
        mesh = _mesh(n_dev)
        oracle = StateMachineOracle()
        oracle.create_accounts(accounts, 50)
        router = PartitionedRouter(mesh, a_cap=A_CAP, t_cap=T_CAP)
        router._steps = _STEPS.setdefault(n_dev, {})
        router._chain_steps = _CHAIN_STEPS.setdefault(n_dev, {})
        return oracle, router, router.from_oracle(oracle)

    def _window(self, oracle, router, state, evs_list, tss):
        """step_window + per-prepare oracle parity on every result."""
        state, results = router.step_window(
            state, [transfers_to_arrays(e) for e in evs_list], tss)
        assert len(results) == len(evs_list)
        for evs, t, (st, rts) in zip(evs_list, tss, results):
            want = oracle.create_transfers(evs, t)
            exp = [(r.timestamp, int(r.status)) for r in want]
            got = [(int(rts[i]), int(st[i])) for i in range(len(evs))]
            assert got == exp, (got[:5], exp[:5])
        return state

    def test_two_phase_straddling_prepares_one_dispatch(self, n_dev):
        """Cross-shard two-phase pairs whose pending lands in an
        EARLIER prepare than its post/void, all inside one scanned
        window: the in-dispatch carry must expose prepare b's writes to
        prepare b+1 on every shard, exactly like W separate
        dispatches."""
        rng = np.random.default_rng(23)
        accts = [Account(id=i, ledger=1, code=1) for i in range(1, 41)]
        oracle, router, state = self._fresh(n_dev, accts)
        nid, ts = 10 ** 6, 10 ** 9
        pendings = []
        w, tss = [], []
        for b in range(4):
            evs = []
            for dr, cr in _cross_shard_pairs(n_dev, 12, rng):
                if b < 2 or not pendings:
                    evs.append(Transfer(
                        id=nid, debit_account_id=dr,
                        credit_account_id=cr,
                        amount=int(rng.integers(1, 30)), ledger=1,
                        code=1, flags=PEND))
                    pendings.append(nid)
                else:
                    pid = pendings.pop(0)
                    f = POST if rng.random() < 0.5 else VOID
                    evs.append(Transfer(
                        id=nid, pending_id=pid,
                        amount=AMOUNT_MAX if f == POST else 0, flags=f))
                nid += 1
            ts += 300
            w.append(evs)
            tss.append(ts)
        state = self._window(oracle, router, state, w, tss)
        # The whole clean window took ONE fused dispatch.
        assert router.window_routes == {"partitioned_chain": 1}
        assert router.chain_batch_fallbacks == {}
        assert router.host_fallbacks == 0
        if n_dev > 1:
            assert router.cross_shard_transfers > 0
        dd = partitioned_state_digest(state)
        assert dd == partitioned_oracle_digest(oracle, A_CAP, n_dev)

    def test_poisoned_window_parity_vs_per_batch(self, n_dev):
        """A limit-cascade prepare (e3 headroom proof) poisons the
        chain mid-window: the prefix stays committed, prepare k replays
        per-batch (plain -> fixpoint escalation ON DEVICE), the suffix
        re-windows — and the final state is bit-identical to running
        the whole workload through the per-batch ladder, and to the
        oracle, with zero host fallbacks on both routes."""
        rng = np.random.default_rng(29)
        accts = [Account(id=i, ledger=1, code=1,
                         flags=DR_LIMIT if i <= 4 else 0)
                 for i in range(1, 41)]
        oracle, router, state = self._fresh(n_dev, accts)
        oracle_b, router_b, state_b = self._fresh(n_dev, accts)
        nid, ts = 10 ** 6, 10 ** 9
        windows = []
        for wi in range(2):
            w, tss = [], []
            for b in range(3):
                evs = [Transfer(id=nid + i, debit_account_id=dr,
                                credit_account_id=cr,
                                amount=int(rng.integers(1, 30)),
                                ledger=1, code=1)
                       for i, (dr, cr) in enumerate(
                           _cross_shard_pairs(n_dev, 8, rng))]
                nid += 8
                if wi == 0 and b == 1:
                    # Debit off a DR_LIMIT account beyond its funded
                    # credits: the plain tier's headroom proof falls
                    # back limit_only, poisoning the chain at k=1.
                    evs.append(Transfer(
                        id=nid, debit_account_id=1,
                        credit_account_id=9, amount=10 ** 6,
                        ledger=1, code=1))
                    nid += 1
                ts += 300
                w.append(evs)
                tss.append(ts)
            windows.append((w, tss))
        for w, tss in windows:
            state = self._window(oracle, router, state, w, tss)
            arrays = [transfers_to_arrays(e) for e in w]
            n_pad = _pad_bucket(max(len(e) for e in w))
            state_b, res_b = router_b._window_per_batch(
                state_b, arrays, tss, n_pad)
            for evs, t, (st, rts) in zip(w, tss, res_b):
                want = oracle_b.create_transfers(evs, t)
                got = [(int(rts[i]), int(st[i]))
                       for i in range(len(evs))]
                assert got == [(r.timestamp, int(r.status))
                               for r in want]
        assert router.host_fallbacks == 0
        assert router_b.host_fallbacks == 0
        # The poison was absorbed per-prepare, not per-window: the
        # chain route still carried the clean windows and the replayed
        # suffix, and the e3 cause landed in the chain counters.
        assert router.window_routes.get("partitioned_chain", 0) >= 2
        assert router.chain_batch_fallbacks.get("e3_limit", 0) >= 1
        assert router.escalations >= 1
        dd = partitioned_state_digest(state)
        assert dd == partitioned_state_digest(state_b)
        assert dd == partitioned_oracle_digest(oracle, A_CAP, n_dev)

    def test_flagged_window_preroutes_per_batch(self, n_dev):
        """Windows carrying flags the plain chain body cannot serve
        (balancing) pre-route to the per-batch ladder — route counters
        must say so, and parity still holds."""
        accts = [Account(id=i, ledger=1, code=1,
                         flags=DR_LIMIT if i <= 2 else 0)
                 for i in range(1, 41)]
        oracle, router, state = self._fresh(n_dev, accts)
        ts = 10 ** 9
        # Fund account 1, then a balancing debit window.
        w = [[Transfer(id=100, debit_account_id=10,
                       credit_account_id=1, amount=50, ledger=1,
                       code=1)],
             [Transfer(id=101, debit_account_id=1,
                       credit_account_id=11, amount=AMOUNT_MAX,
                       ledger=1, code=1, flags=BAL_DR),
              Transfer(id=102, debit_account_id=12,
                       credit_account_id=13, amount=3, ledger=1,
                       code=1)]]
        tss = [ts + 300, ts + 600]
        state = self._window(oracle, router, state, w[:1], tss[:1])
        state = self._window(oracle, router, state, w[1:], tss[1:])
        assert router.window_routes.get("partitioned_per_batch") == 2
        assert "partitioned_chain" not in router.window_routes
        assert router.host_fallbacks == 0
        dd = partitioned_state_digest(state)
        assert dd == partitioned_oracle_digest(oracle, A_CAP, n_dev)


class TestShardLoss:
    def test_resync_required_and_recovers(self):
        """Partitioned shard loss cannot reroute to a single chip (the
        lost range exists nowhere else): the router refuses to serve,
        and resync(oracle) rebuilds via the shard_resync recovery
        cause."""
        accts = [Account(id=i, ledger=1, code=1) for i in range(1, 9)]
        h = Harness(2, accts)
        h.step([Transfer(id=500, debit_account_id=1,
                         credit_account_id=2, amount=5, ledger=1,
                         code=1)])
        h.router.drop_device(h.mesh.devices.flat[0])
        ev = pad_transfer_events(transfers_to_arrays(
            [Transfer(id=501, debit_account_id=2, credit_account_id=3,
                      amount=1, ledger=1, code=1)]))
        with pytest.raises(RuntimeError, match="resync"):
            h.router.step(h.state, ev, h.ts + 300, 1)
        h.state = h.router.resync(h.oracle)
        assert h.router.shard_resyncs == 1
        assert not h.router.lost_devices
        h.step([Transfer(id=502, debit_account_id=2,
                         credit_account_id=3, amount=1, ledger=1,
                         code=1)])
        h.finish()
