"""Serving-entry registry: every compiled entry the static passes audit.

One place enumerates the dispatch surface — the flat per-batch/
superbatch tiers, the scan-form chain route, the replicated sharded
steps, the partitioned (account-range-sharded) steps, and the fused
partitioned chain — so a pass added once runs over ALL of them, and a
new route added to the ledger without a registry entry is a visible
gap, not a silent one. Fixtures mirror perf/opbudget.py's (the
committed censuses are traced from identical shapes); the registry is
self-contained so the analysis plane never imports the perf scripts.

Each Entry carries thunks, not artifacts: nothing traces, lowers, or
compiles until a pass asks. `make_args(depth)` builds the REAL
dispatch-layer inputs (stack_chain_window / stack_partitioned_window /
pad_transfer_events) at a given window depth W — the retrace auditor
drives it across DEPTH_MATRIX; depth-independent entries ignore the
argument.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

# The retrace auditor's window-depth matrix; the representative depth
# is what the jaxpr-level passes trace at (matches opbudget's chain
# fixture depth).
DEPTH_MATRIX = (1, 2, 8, 32)
REP_DEPTH = 4

_N_SUPER = 1024
_STACK = 4


@dataclasses.dataclass
class Entry:
    """One audited serving entry.

    route: flat | chain | sharded | partitioned | partitioned_chain.
    jit_fn: the jit-wrapped dispatch callable (lowerable).
    raw_fn: the traceable function (jax.make_jaxpr target).
    make_args: depth -> concrete args (real stacking/padding drivers).
    depths: the retrace matrix this entry is driven across.
    mesh: the Mesh tracing/lowering must run under (None = none).
    n_state_leaves: donated-state leaf count (sharding verifier).
    """

    name: str
    route: str
    jit_fn: Callable
    raw_fn: Callable
    make_args: Callable[[int], tuple]
    depths: tuple = (1,)
    mesh: object = None
    n_state_leaves: int = 0

    def _ctx(self):
        import contextlib

        return self.mesh if self.mesh is not None \
            else contextlib.nullcontext()

    def trace(self, depth: int = REP_DEPTH):
        """ClosedJaxpr of the entry at `depth` (representative)."""
        import jax

        with self._ctx():
            return jax.make_jaxpr(self.raw_fn)(*self.make_args(depth))

    def lower(self, depth: int = REP_DEPTH):
        """Lowered artifact of the jit entry at `depth`."""
        with self._ctx():
            return self.jit_fn.lower(*self.make_args(depth))


def _mk_prepares(n_prepares, n=_N_SUPER, nid0=10 ** 6, seed=0):
    import numpy as np

    from tigerbeetle_tpu.ops.batch import transfers_soa

    rng = np.random.default_rng(seed)
    evs, tss = [], []
    nid = nid0
    for b in range(n_prepares):
        dr = rng.integers(1, 64, n, dtype=np.uint64)
        cr = (dr % 63) + 1
        evs.append(transfers_soa(np.arange(nid, nid + n), dr, cr,
                                 rng.integers(1, 100, n)))
        nid += n
        tss.append(10 ** 12 + b * (n + 10))
    return evs, tss


def _flat_fixtures():
    from tigerbeetle_tpu.ops.batch import transfers_to_arrays
    from tigerbeetle_tpu.ops.ledger import (
        init_state, pad_transfer_events, stack_superbatch)
    from tigerbeetle_tpu.types import Transfer

    state = init_state(1 << 10, 1 << 12)
    ev = pad_transfer_events(transfers_to_arrays(
        [Transfer(id=1, debit_account_id=1, credit_account_id=2,
                  amount=1, ledger=1, code=1)]))
    evs, tss = _mk_prepares(_STACK)
    ev_s, seg = stack_superbatch(evs, tss)
    return state, ev, ev_s, seg


def _chain_args_at(depth):
    from tigerbeetle_tpu.ops.ledger import stack_chain_window

    evs, tss = _mk_prepares(depth)
    return stack_chain_window(evs, tss, _N_SUPER)


def _partitioned_state(mesh, axis="batch"):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tigerbeetle_tpu.ops.ledger import init_state

    n = mesh.shape[axis]
    sub = jax.tree.map(np.asarray, init_state(
        (1 << 10) // n, (1 << 12) // n, orphan_cap=(1 << 16) // n))
    stacked = jax.tree.map(lambda x: np.stack([x] * n), sub)
    return jax.device_put(stacked, NamedSharding(mesh, P(axis)))


def entries(include_partitioned: bool | None = None) -> dict[str, Entry]:
    """name -> Entry for the full audited dispatch surface. The mesh
    tiers (sharded/partitioned/partitioned_chain) need >= 8 devices;
    include_partitioned=None auto-detects."""
    import jax
    import numpy as np

    from tigerbeetle_tpu.ops import fast_kernels as fk

    state, ev, ev_s, seg = _flat_fixtures()
    n_leaves = len(jax.tree_util.tree_leaves(state))
    ts = np.uint64(1000)
    n = np.int32(1)
    out: dict[str, Entry] = {}

    def add_flat(name, jitfn, args):
        out[name] = Entry(
            name=name, route="flat", jit_fn=jitfn,
            raw_fn=jitfn, make_args=lambda _d, a=args: a,
            n_state_leaves=n_leaves)

    add_flat("create_transfers_fast_jit",
             fk.create_transfers_fast_jit, (state, ev, ts, n))
    add_flat("create_transfers_fixpoint_jit",
             fk.create_transfers_fixpoint_jit, (state, ev, ts, n))
    add_flat("create_transfers_fixpoint_deep_jit",
             fk.create_transfers_fixpoint_deep_jit, (state, ev, ts, n))
    add_flat("create_transfers_balancing_jit",
             fk.create_transfers_balancing_jit, (state, ev, ts, n))
    add_flat("create_transfers_imported_jit",
             fk.create_transfers_imported_jit, (state, ev, ts, n))
    add_flat("create_transfers_imported_fixpoint_jit",
             fk.create_transfers_imported_fixpoint_jit,
             (state, ev, ts, n))
    add_flat("create_transfers_super_jit",
             fk.create_transfers_super_jit, (state, ev_s, seg))
    add_flat("create_transfers_super_deep_jit",
             fk.create_transfers_super_deep_jit, (state, ev_s, seg))
    add_flat("create_transfers_super_ring_jit",
             fk.create_transfers_super_ring_jit, (state, ev_s, seg))
    add_flat("create_transfers_super_deep_ring_jit",
             fk.create_transfers_super_deep_ring_jit, (state, ev_s, seg))
    add_flat("create_transfers_super_balancing_jit",
             fk.create_transfers_super_balancing_jit, (state, ev_s, seg))

    def chain_args(depth, st=state):
        ev_c, seg_c = _chain_args_at(depth)
        return (st, ev_c, seg_c)

    for name, jitfn in (
            ("create_transfers_chain_jit", fk.create_transfers_chain_jit),
            ("create_transfers_chain_ring_jit",
             fk.create_transfers_chain_ring_jit),
            ("create_transfers_chain_unrolled_jit",
             fk.create_transfers_chain_unrolled_jit)):
        out[name] = Entry(
            name=name, route="chain", jit_fn=jitfn, raw_fn=jitfn,
            make_args=chain_args, depths=DEPTH_MATRIX,
            n_state_leaves=n_leaves)

    if include_partitioned is None:
        include_partitioned = len(jax.devices()) >= 8
    if not include_partitioned:
        return out

    from jax.sharding import Mesh

    from tigerbeetle_tpu.parallel.full_sharded import (
        make_sharded_create_transfers)
    from tigerbeetle_tpu.parallel.partitioned import (
        make_partitioned_chain_create_transfers,
        make_partitioned_create_transfers,
        stack_partitioned_window,
    )

    mesh = Mesh(np.array(jax.devices()[:8]), ("batch",))
    for mode in ("plain", "fixpoint"):
        step = make_sharded_create_transfers(mesh, mode=mode)
        out[f"sharded_{mode}_step"] = Entry(
            name=f"sharded_{mode}_step", route="sharded",
            jit_fn=step, raw_fn=step.__wrapped__,
            make_args=lambda _d, a=(state, ev, np.uint64(1000),
                                    np.int32(1)): a,
            mesh=mesh, n_state_leaves=n_leaves)

    pstate = _partitioned_state(mesh)
    for mode in ("plain", "fixpoint"):
        pstep = make_partitioned_create_transfers(mesh, mode=mode)
        out[f"partitioned_{mode}_step"] = Entry(
            name=f"partitioned_{mode}_step", route="partitioned",
            jit_fn=pstep, raw_fn=pstep.__wrapped__,
            make_args=lambda _d, a=(pstate, ev, np.uint64(1000),
                                    np.int32(1)): a,
            mesh=mesh, n_state_leaves=n_leaves)

    cstep = make_partitioned_chain_create_transfers(mesh, mode="plain")

    def pchain_args(depth, st=pstate):
        evs, tss = _mk_prepares(depth)
        ev_p, ts_p, n_p = stack_partitioned_window(evs, tss, _N_SUPER)
        return (st, ev_p, ts_p, n_p, None)

    out["partitioned_chain_step"] = Entry(
        name="partitioned_chain_step", route="partitioned_chain",
        jit_fn=cstep, raw_fn=cstep.__wrapped__,
        make_args=pchain_args, depths=DEPTH_MATRIX,
        mesh=mesh, n_state_leaves=n_leaves)
    return out
