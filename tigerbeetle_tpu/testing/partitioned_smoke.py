"""The partitioned route on a 4-device mesh — `chip_smoke.py
--four-chips`, the one phase that needs four chips.

The multi-chip route the serving code reaches is
DeviceLedger.attach_partitioned over a PartitionedRouter: account-range
sharded state, one fused shard_map+lax.scan dispatch per commit window
(parallel/partitioned.py step_window). One process, a 4-device mesh
from jax.devices(), and against the pure-Python oracle:

- GLOBAL caps a_cap 2^17 / t_cap 2^21 (the one-chip production caps),
  so 2^15 account rows and 2^19 transfer rows PER SHARD;
- >= 2^15 accounts, a mix of plain and debits_must_not_exceed_credits,
  funded so the limited ones have headroom;
- a few commit windows of wire-max prepares over uniformly random
  account pairs (3 of 4 pairs cross shards on 4 devices);
- every result against the oracle, the final sharded state's digest
  against the oracle's, zero host fallbacks, the fused route taken,
- and every state leaf sharded over all four devices — code that never
  saw a second chip may have put everything on the first.

Rehearse on the CPU backend with
XLA_FLAGS=--xla_force_host_platform_device_count=4.
"""

from __future__ import annotations

import time

import numpy as np

N_DEVICES = 4
A_CAP = 1 << 17
T_CAP = 1 << 21
N_PAD = 8192
N_ACCOUNTS = 1 << 15
WINDOW_DEPTH = 4
N_WINDOWS = 3


def build_router(devices):
    """(mesh, router) over `devices` (real, virtual or described)."""
    from jax.sharding import Mesh

    from ..parallel.partitioned import PartitionedRouter

    mesh = Mesh(np.array(list(devices)[:N_DEVICES]), ("batch",))
    return mesh, PartitionedRouter(mesh, a_cap=A_CAP, t_cap=T_CAP)


def abstract_chain_args(mesh):
    """Abstract (state, ev_stack, ts_stack, n_stack, None) of the fused
    window step at this smoke's shapes, placed on `mesh` — what the
    chip compile test hands to a mesh of described devices."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..ops.batch import transfers_to_arrays
    from ..ops.ledger import init_state
    from ..ops.warmup import abstract
    from ..parallel.partitioned import stack_partitioned_window

    n = mesh.shape["batch"]
    t_cap_s = T_CAP // n
    # Per-shard sub-states stacked on a leading shard axis, exactly as
    # partitioned_from_oracle lays them out.
    stacked = jax.eval_shape(lambda: jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n, *jnp.shape(x))),
        init_state(A_CAP // n, t_cap_s,
                   orphan_cap=max((1 << 16) // n, t_cap_s),
                   e_cap=t_cap_s)))
    packed = stack_partitioned_window(
        [transfers_to_arrays([])] * WINDOW_DEPTH,
        [10 ** 12] * WINDOW_DEPTH, N_PAD)
    return (abstract(stacked, NamedSharding(mesh, P("batch"))),
            *abstract(packed, NamedSharding(mesh, P())), None)


def check_sharded(state, mesh) -> int:
    """Every leaf of the sharded state has a distinct 1/n slice on each
    device of the mesh. Returns the leaf count."""
    import jax

    want = set(mesh.devices.flat)
    n = len(want)
    leaves = jax.tree.leaves(state)
    for leaf in leaves:
        shards = leaf.addressable_shards
        assert {s.device for s in shards} == want, \
            f"leaf {leaf.shape} lives on {sorted(map(str, {s.device for s in shards}))}"
        assert not leaf.sharding.is_fully_replicated, \
            f"leaf {leaf.shape} is replicated, not sharded"
        assert all(s.data.shape[0] * n == leaf.shape[0] for s in shards), \
            f"leaf {leaf.shape} is not split {n} ways on axis 0"
    return len(leaves)


def run(seed: int, say=print) -> dict:
    import jax

    from ..clients.common import events_max
    from ..constants import HEADER_SIZE
    from ..ops.batch import transfers_to_arrays
    from ..ops.ledger import DeviceLedger
    from ..ops.state_epoch import (partitioned_oracle_digest,
                                   partitioned_state_digest)
    from ..oracle import StateMachineOracle
    from ..types import (Account, AccountFlags, CreateTransferStatus,
                         Operation, Transfer)
    from ..vsr.storage import StorageLayout

    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    say(f"devices: {device}")
    assert len(dev) >= N_DEVICES, f"need {N_DEVICES} devices, have {dev}"
    n_max = events_max(Operation.create_transfers,
                       StorageLayout().message_size_max - HEADER_SIZE)
    rng = np.random.default_rng(seed)
    n_accounts = N_ACCOUNTS
    t0 = time.monotonic()
    mesh, router = build_router(dev)
    say(f"mesh {dict(mesh.shape)}; global caps a_cap={A_CAP} t_cap={T_CAP} "
        f"(per shard {A_CAP // N_DEVICES} / {T_CAP // N_DEVICES}); "
        f"prepares of {n_max} events, windows of {WINDOW_DEPTH}")

    oracle = StateMachineOracle()
    limited = int(AccountFlags.debits_must_not_exceed_credits)
    ids = [int(x) for x in (1 << 40) + np.arange(n_accounts) * 3]
    ts = 10 ** 9
    for lo in range(0, n_accounts, n_max):
        chunk = ids[lo:lo + n_max]
        ts += len(chunk)
        oracle.create_accounts(
            [Account(id=a, ledger=1, code=1,
                     flags=limited if (lo + i) % 4 == 0 else 0)
             for i, a in enumerate(chunk)], ts)
    plain = [a for i, a in enumerate(ids) if i % 4]
    lim = [a for i, a in enumerate(ids) if i % 4 == 0]
    tid = 1 << 50
    for lo in range(0, len(lim), n_max):  # fund: deep headroom
        chunk = lim[lo:lo + n_max]
        ts += len(chunk)
        res = oracle.create_transfers(
            [Transfer(id=tid + i, debit_account_id=plain[i % len(plain)],
                      credit_account_id=a, amount=10 ** 12, ledger=1,
                      code=1) for i, a in enumerate(chunk)], ts)
        assert all(r.status == CreateTransferStatus.created for r in res)
        tid += len(chunk)
    state = router.from_oracle(oracle)
    n_leaves = check_sharded(state, mesh)
    say(f"state built from {n_accounts} accounts in "
        f"{time.monotonic() - t0:.1f}s: {n_leaves} leaves, each split "
        f"over {N_DEVICES} devices")

    # The serving ledger's attach mode: its own single-chip tables stay
    # at their (small) attach-time snapshot; commits land on the mesh.
    led = DeviceLedger(a_cap=1 << 8, t_cap=1 << 10)
    led.attach_partitioned(router, state)
    created = mismatches = 0
    seconds = []
    ids_arr = np.asarray(ids, dtype=object)
    for w in range(N_WINDOWS):
        prepares, tss = [], []
        for _ in range(WINDOW_DEPTH):
            dr = rng.integers(0, n_accounts, n_max)
            cr = (dr + rng.integers(1, n_accounts, n_max)) % n_accounts
            prepares.append([
                Transfer(id=tid + i, debit_account_id=ids_arr[dr[i]],
                         credit_account_id=ids_arr[cr[i]],
                         amount=int(rng.integers(1, 1000)), ledger=1,
                         code=1) for i in range(n_max)])
            tid += n_max
            ts += n_max + 10
            tss.append(ts)
        arrays = [transfers_to_arrays(p) for p in prepares]
        t1 = time.monotonic()
        results = led.create_transfers_window(arrays, tss)
        seconds.append(round(time.monotonic() - t1, 3))
        for p, t, (st, rts) in zip(prepares, tss, results):
            want = oracle.create_transfers(p, t)
            got = list(zip(rts.tolist(), st.tolist()))
            exp = [(r.timestamp, int(r.status)) for r in want]
            mismatches += sum(g != e for g, e in zip(got, exp))
            created += sum(r.status == CreateTransferStatus.created
                           for r in want)
    stats = router.stats()
    say(f"{N_WINDOWS} windows x {WINDOW_DEPTH} prepares x {n_max} events: "
        f"{created} created, {mismatches} mismatches; window seconds "
        f"{seconds} (the first includes the compile)")
    say(f"router: routes={stats['routes']['windows']} "
        f"host_fallbacks={stats['host_fallbacks']} "
        f"escalations={stats['escalations']} "
        f"cross_shard_fraction={stats['cross_shard_fraction']:.3f} "
        f"exchange_overflows={stats['exchange_overflows']} "
        f"events_owned={stats['events_owned']}")
    assert mismatches == 0, f"{mismatches} results differ from the oracle"
    assert stats["host_fallbacks"] == 0 and led.fallbacks == 0, stats
    assert stats["routes"]["windows"].get("partitioned_chain", 0) \
        == N_WINDOWS, stats["routes"]
    assert stats["cross_shard_fraction"] > 0.5, stats
    final = led.partitioned_state
    check_sharded(final, mesh)
    got = partitioned_state_digest(final)
    want = partitioned_oracle_digest(oracle, A_CAP, N_DEVICES)
    assert got == want, f"state digest {got} != oracle's {want}"
    say(f"final sharded state digest equals the oracle's "
        f"({len(got)} components); state still split over "
        f"{N_DEVICES} devices")
    return device
