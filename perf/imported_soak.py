"""Extended imported-differential soak: many seeds, bigger batches,
sync windows mixing imported/non-imported prepares — kernel vs oracle
bit-exact or die."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

import tigerbeetle_tpu  # noqa: F401
from tigerbeetle_tpu.oracle.state_machine import StateMachineOracle
from tigerbeetle_tpu.ops.batch import transfers_to_arrays
from tigerbeetle_tpu.ops.ledger import DeviceLedger
from tigerbeetle_tpu.types import Account, AccountFlags, Transfer, TransferFlags

IMP = int(TransferFlags.imported)
PEND = int(TransferFlags.pending)
POST = int(TransferFlags.post_pending_transfer)
VOID = int(TransferFlags.void_pending_transfer)
AIMP = int(AccountFlags.imported)


def run_seed(seed: int) -> int:
    rng = np.random.default_rng(seed)
    led = DeviceLedger(a_cap=1 << 10, t_cap=1 << 15)
    ora = StateMachineOracle()
    # Mix imported and regular accounts.
    accs = []
    uts_a = 500
    for i in range(1, 33):
        if rng.random() < 0.4:
            uts_a += int(rng.integers(1, 9))
            accs.append(Account(id=i, ledger=1, code=1, flags=AIMP,
                                timestamp=uts_a))
        else:
            accs.append(Account(id=i, ledger=1, code=1))
    # Homogeneity: oracle requires per-batch; split by kind.
    imp_accs = [a for a in accs if a.flags & AIMP]
    reg_accs = [a for a in accs if not a.flags & AIMP]
    ts = 10 ** 9
    for group in (imp_accs, reg_accs):
        if group:
            g = led.create_accounts(group, ts)
            w = ora.create_accounts(group, ts)
            assert [(x.status, x.timestamp) for x in g] == \
                [(x.status, x.timestamp) for x in w], f"seed {seed} accounts"
            ts += 10 ** 6
    checked = 0
    nid = 10 ** 5
    base_uts = 100_000
    pend_ids: list = []
    for step in range(10):
        use_window = rng.random() < 0.4
        n_batches = int(rng.integers(2, 5)) if use_window else 1
        evs, tss, wants = [], [], []
        for _ in range(n_batches):
            n = int(rng.integers(8, 96))
            batch_imp = bool(rng.integers(0, 2))
            xs = []
            for _ in range(n):
                imp = batch_imp if rng.random() > 0.08 else not batch_imp
                dr = int(rng.integers(1, 33))
                cr = int(rng.integers(1, 33))
                if dr == cr:
                    cr = dr % 32 + 1
                flags = IMP if imp else 0
                kind = rng.random()
                pid = 0
                amt = int(rng.integers(1, 500))
                if kind < 0.12 and pend_ids:
                    flags |= POST if rng.random() < 0.5 else VOID
                    pid = int(rng.choice(pend_ids))
                    if rng.random() < 0.5:
                        amt = (1 << 128) - 1 if flags & POST else 0
                elif kind < 0.3:
                    flags |= PEND
                uts = base_uts + int(rng.integers(-25, 25))
                base_uts += int(rng.integers(0, 10))
                t = Transfer(id=nid, debit_account_id=dr,
                             credit_account_id=cr, amount=amt, ledger=1,
                             code=1, flags=flags, pending_id=pid,
                             timestamp=uts if imp else 0,
                             timeout=int(rng.integers(0, 3))
                             if (flags & PEND and not imp) else 0)
                if flags & (POST | VOID):
                    t.debit_account_id = 0
                    t.credit_account_id = 0
                    t.ledger = 0
                    t.code = 0
                xs.append(t)
                nid += 1
            evs.append(xs)
            tss.append(ts)
            ts += 10 ** 6
        if use_window and n_batches > 1:
            arrays = [transfers_to_arrays(b) for b in evs]
            results = led.create_transfers_window(arrays, tss)
            wants = [ora.create_transfers(b, t)
                     for b, t in zip(evs, tss)]
            assert results is not None  # sync window always returns
            for (st, rts), w in zip(results, wants):
                got = list(zip(st.tolist(), rts.tolist()))
                want = [(int(x.status), x.timestamp) for x in w]
                assert got == want, f"seed {seed} step {step} window"
                checked += len(w)
        else:
            for b, t in zip(evs, tss):
                g = led.create_transfers(b, t)
                w = ora.create_transfers(b, t)
                assert [(x.status, x.timestamp) for x in g] == \
                    [(x.status, x.timestamp) for x in w], \
                    f"seed {seed} step {step}"
                checked += len(w)
                wants.append(w)
        for b, w in zip(evs, wants):
            for t, r in zip(b, w):
                if r.status.name == "created" and t.flags & PEND:
                    pend_ids.append(t.id)
        pend_ids = pend_ids[-64:]
    return checked


if __name__ == "__main__":
    total = 0
    n_seeds = int(sys.argv[1]) if len(sys.argv) > 1 else 30
    seeds = range(100, 100 + n_seeds)
    for seed in seeds:
        total += run_seed(seed)
        print(f"seed {seed} ok (cum {total})", flush=True)
    print(f"SOAK CLEAN: {len(list(seeds))} seeds, {total} events diffed")
