"""How many fixpoint rounds does the config4 workload actually need?

Runs the config4 two-phase-under-limits workload shape through the deep
superbatch kernel at several static round budgets and reports, per
window, whether the fixpoint converged (out["fix_unconverged"]) — the
data that decides between adaptive tiering (cheap rounds + escalation)
and a round-body op cut.
"""
import functools
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import numpy as np

import tigerbeetle_tpu  # noqa: F401  (enables x64)
from tigerbeetle_tpu.benchmark import _soa
from tigerbeetle_tpu.ops import fast_kernels as fk
from tigerbeetle_tpu.ops.ledger import DeviceLedger, stack_superbatch
from tigerbeetle_tpu.types import Account, AccountFlags, TransferFlags

U128_MAX = (1 << 128) - 1
N = 1024
ACCOUNTS = 64
W_PAIRS = 4
WINDOWS = 6
T_CAP = 1 << 18


def mk_workload():
    rng = np.random.default_rng(4)
    limit = int(AccountFlags.debits_must_not_exceed_credits)
    accounts = [Account(id=i, ledger=1, code=1,
                        flags=limit if i % 2 == 0 else 0)
                for i in range(1, ACCOUNTS + 1)]
    pend = int(TransferFlags.pending)
    post = int(TransferFlags.post_pending_transfer)
    void = int(TransferFlags.void_pending_transfer)
    next_id = 10 ** 7
    ts = 10 ** 12
    windows = []
    for _ in range(WINDOWS):
        evs, tss = [], []
        for _ in range(W_PAIRS):
            pend_base = next_id
            next_id += N
            dr = rng.integers(1, ACCOUNTS + 1, N, dtype=np.uint64)
            cr = rng.integers(1, ACCOUNTS + 1, N, dtype=np.uint64)
            clash = dr == cr
            cr[clash] = dr[clash] % ACCOUNTS + 1
            ev = _soa(np.arange(pend_base, pend_base + N), dr, cr,
                      rng.integers(1, 100, N),
                      flags=np.full(N, pend, dtype=np.uint32))
            evs.append(ev); tss.append(ts + N + 10)
            even = np.arange(N) % 2 == 0
            rev = _soa(np.arange(next_id, next_id + N),
                       np.zeros(N, dtype=np.uint64),
                       np.zeros(N, dtype=np.uint64),
                       np.where(even, np.uint64(U128_MAX & ((1 << 64) - 1)),
                                np.uint64(0)),
                       flags=np.where(even, post, void).astype(np.uint32),
                       pid=np.arange(pend_base, pend_base + N))
            rev["amt_hi"] = np.where(even, np.uint64(U128_MAX >> 64),
                                     np.uint64(0))
            rev["ledger"] = np.zeros(N, dtype=np.uint32)
            rev["code"] = np.zeros(N, dtype=np.uint32)
            next_id += N
            evs.append(rev); tss.append(ts + 2 * (N + 10))
            ts += 2 * (N + 10)
        windows.append((evs, tss))
    return accounts, windows


def run(rounds: int):
    accounts, windows = mk_workload()
    led = DeviceLedger(a_cap=1 << 12, t_cap=T_CAP)
    led.create_accounts(accounts, timestamp=ACCOUNTS)
    kern = jax.jit(functools.partial(
        fk.create_transfers_fast, limit_rounds=rounds),
        static_argnames=(), donate_argnums=0)

    unconv = []
    fellback = []
    for evs, tss in windows:
        ev_s, seg = stack_superbatch(evs, tss)
        ev_s = {k: jax.device_put(v) for k, v in ev_s.items()}
        seg = {k: jax.device_put(v) for k, v in seg.items()}
        import jax.numpy as jnp
        new_state, out = kern(led.state, ev_s,
                              jnp.uint64(0), jnp.int32(0), seg=seg)
        led.state = new_state
        unconv.append(bool(jax.device_get(out["fix_unconverged"])))
        fellback.append(bool(jax.device_get(out["fallback"])))
    return unconv, fellback


if __name__ == "__main__":
    for rounds in (14, 16, 20, 24):
        unconv, fb = run(rounds)
        print(f"rounds={rounds:2d} unconverged_windows={sum(unconv)}/"
              f"{len(unconv)} fallback={sum(fb)} per-window={unconv}",
              flush=True)
