"""Serving warm-up: what `start --engine=device` compiles before it
prints `listening`, so no client request pays a compile out of its
timeout budget (vsr/client.py: 10 s by default).

At production caps (a_cap 2^17, t_cap 2^21) one create_transfers tier
takes the TPU compiler one to two minutes of ONE core (the plain tier
52-73 s, the limit fixpoint 119 s: sandbox compile, PR 32), and the dispatch surface is tiers x four batch buckets x
window depths 2..8 — warming all of it cold would take the better part
of an hour. So the warm set is chosen, not exhaustive:

- the smallest and the widest batch bucket (1024: the everyday request;
  8192: the wire maximum), each on the plain tier and the limit
  fixpoint tier the plain tier escalates to;
- create_accounts (always padded to the widest bucket);
- the write-through delta gathers those dispatch.

Still compiled on demand, inside some request's budget: the 2048 and
4096 buckets, the deep fixpoint tiers, and the balancing / imported
tiers. Commit windows (depths 2..8 x bucket, one program each) are not
warmed either: a primary commits prepare by prepare, so only a backup
catching up or a WAL replay at `open` dispatches them — before
`listening`, or off the client's clock. The read path is not warmed
because it needs no warming: a served replica's first `lookup_accounts`
and `lookup_transfers` read the forest, and what they and the first
requests still compile after `listening` is five small programs, 0.24 s
in all, in a two-phase deployment as in a single-phase one (first
lookups of 7,278 ids 77 and 205 ms: my chip run, PR 35).

Cold, the expensive entries compile AHEAD OF TIME and IN PARALLEL into
the persistent compile cache (compile_cache.py; one thread each — the
compiler is single-threaded per program); the warm-up proper then
drives a throwaway serving-mode ledger through the real entry points,
which finds them there. With the cache already populated (every boot
after the first) the first phase is a handful of cache reads.
"""

from __future__ import annotations

import functools
import os
import time as _time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .hash_table import SLOTS
from .ledger import (
    N_PAD,
    DeviceLedger,
    init_state,
    pad_account_events,
    pad_transfer_events,
    stack_superbatch,
)

WARM_BUCKETS = (1024, N_PAD)
PRECOMPILE_WORKERS = 6  # one core and ~3-5 GB of compiler memory each
# What warmup_kernels itself puts into the throwaway ledger: a batch
# that lands in each bucket, once on the plain tier and once on the
# fixpoint tier, between three accounts.
WARM_SIZES = tuple(b // 2 + 1 for b in WARM_BUCKETS)
WARM_TRANSFERS = 2 * sum(WARM_SIZES)
WARM_ACCOUNTS = 3


def capacity_error(a_cap: int, t_cap: int) -> str | None:
    """Why `start` cannot serve at these store capacities, in words, or
    None. `init_state` sizes its hash tables by shifts (`ht_init`
    asserts a power of two of at least two buckets), and the warm-up
    drives a ledger of the same capacities through WARM_TRANSFERS
    transfers before `listening`."""
    for flag, cap, least in (
            ("--account-capacity", a_cap, max(WARM_ACCOUNTS, SLOTS)),
            ("--transfer-capacity", t_cap, WARM_TRANSFERS)):
        floor = 1 << (least - 1).bit_length()
        if cap < floor or cap & (cap - 1):
            return (f"{flag}={cap}: the device stores take a power of two "
                    f"of at least {floor} rows (the hash tables are sized "
                    f"by shifts, and the warm-up alone creates "
                    f"{WARM_ACCOUNTS} accounts and {WARM_TRANSFERS} "
                    "transfers in stores of this size)")
    return None


def abstract(tree, sharding=None):
    """The shapes of `tree` (optionally placed on `sharding`: the chip
    compile tests hand in a described, unattached device)."""
    import jax

    def one(x):
        x = x if hasattr(x, "shape") else np.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    return jax.tree.map(one, tree)


@functools.lru_cache(maxsize=4)
def abstract_state(a_cap: int, t_cap: int, sharding=None):
    """Shapes of the ledger state (cached: tracing init_state fills a
    host array the size of the event ring)."""
    import jax

    return abstract(jax.eval_shape(lambda: init_state(a_cap, t_cap)),
                    sharding)


def batch_args(a_cap, t_cap, n_pad=N_PAD, sharding=None, accounts=False):
    """(state, events, timestamp, n) of the per-batch tiers:
    create_transfers at bucket `n_pad`, or create_accounts (always
    padded to the widest bucket)."""
    from .batch import accounts_to_arrays, transfers_to_arrays

    ev = (pad_account_events(accounts_to_arrays([])) if accounts
          else pad_transfer_events(transfers_to_arrays([]), n_pad))
    return (abstract_state(a_cap, t_cap, sharding),
            *abstract((ev, np.uint64(1), np.int32(0)), sharding))


def window_args(a_cap, t_cap, depth, n_pad=N_PAD, sharding=None,
                stack=stack_superbatch):
    """(state, events, seg) of a `depth`-prepare window: flattened into
    one superbatch (the replica's all-or-nothing commit window), or with
    stack=stack_chain_window the scan-form chain's stacked inputs."""
    from .batch import transfers_to_arrays

    packed = stack([transfers_to_arrays([])] * depth,
                   [10 ** 12] * depth, n_pad)
    return (abstract_state(a_cap, t_cap, sharding),
            *abstract(packed, sharding))


def warm_set(a_cap: int, t_cap: int, sharding=None) -> dict:
    """name -> (jit entry, abstract args): the expensive programs of the
    warm set (module docstring)."""
    from . import fast_kernels as fk

    out = {"create_accounts_fast@8192": (
        fk.create_accounts_fast_jit,
        batch_args(a_cap, t_cap, sharding=sharding, accounts=True))}
    for n_pad in WARM_BUCKETS:
        args = batch_args(a_cap, t_cap, n_pad, sharding)
        out[f"create_transfers_fast@{n_pad}"] = (
            fk.create_transfers_fast_jit, args)
        out[f"create_transfers_fixpoint@{n_pad}"] = (
            fk.create_transfers_fixpoint_jit, args)
    return out


def precompile(entries: dict) -> dict:
    """Compile `entries` concurrently. Nothing is kept in memory: the
    point is the persistent cache entry each compile leaves behind.
    Returns name -> seconds."""

    def one(item):
        name, (jitfn, args) = item
        t0 = _time.monotonic()
        jitfn.lower(*args).compile()
        return name, round(_time.monotonic() - t0, 1)

    workers = max(1, min(PRECOMPILE_WORKERS, len(entries),
                         os.cpu_count() or 1))
    with ThreadPoolExecutor(workers) as pool:
        return dict(pool.map(one, entries.items()))


def _transfers(n, debit, credit, first_id):
    from ..types import Transfer
    from .batch import transfers_to_arrays

    return transfers_to_arrays([
        Transfer(id=first_id + i, debit_account_id=debit,
                 credit_account_id=credit, amount=1, ledger=1, code=1)
        for i in range(n)])


def warmup_kernels(a_cap: int = 1 << 17, t_cap: int = 1 << 21) -> float:
    """Compile the warm set (module docstring); returns elapsed seconds.
    Reference analog: none — the reference serves cold
    (src/tigerbeetle/main.zig:251), it has no compile step."""
    import jax

    from ..oracle.state_machine import StateMachineOracle
    from ..types import Account, AccountFlags

    t0 = _time.monotonic()
    if jax.config.jax_compilation_cache_dir:
        precompile(warm_set(a_cap, t_cap))
    # The throwaway ledger serves as the replica's does: write-through
    # to a host mirror, event ring recycled, flush columns retained
    # (StateMachine.attach_durable) — those select the delta gathers.
    led = DeviceLedger(a_cap=a_cap, t_cap=t_cap,
                       write_through=StateMachineOracle())
    led.recycle_events = True
    led.retain_flush_columns = True
    led.create_accounts(
        [Account(id=1, ledger=1, code=1), Account(id=2, ledger=1, code=1),
         Account(id=3, ledger=1, code=1,
                 flags=int(AccountFlags.debits_must_not_exceed_credits))],
        1_000)
    ts = 10_000
    nid = 1
    # Plain tier first: a breach leaves the ledger dispatching
    # fixpoint-first until a breach-free batch cools it down.
    sizes = WARM_SIZES  # n lands in bucket b
    for n in sizes:
        ts += n
        led.create_transfers_soa(_transfers(n, 1, 2, nid), ts)
        nid += n
    for n in sizes:
        # Account 3 has no credits: the plain tier proves nothing and
        # hands the batch to the limit fixpoint.
        ts += n
        led.create_transfers_soa(_transfers(n, 3, 2, nid), ts)
        nid += n
    assert led.fixpoint_batches == len(sizes), \
        "breach batches must warm the fixpoint tier"
    assert led.fallbacks == 0, "warm-up must stay on the device"
    # What a commit does next: the durable flusher takes the captured
    # delta columns and the mirror drains (device -> host fetches).
    led.drain_mirror()
    led.take_flush_columns()
    return _time.monotonic() - t0
