"""Full-semantics SPMD create_transfers over a device mesh — deep tiers.

The multi-chip form of the single-chip kernel stack
(ops/fast_kernels.py), with FULL semantics — eligibility E1-E7, chains,
idempotency, two-phase post/void, event-ring snapshots — across EVERY
kernel tier: plain, limit-fixpoint (closing-native, in-window pending
refs), balancing, and imported.

Decomposition (reference mapping: the batch axis of
docs/ARCHITECTURE.md:358-362 sharded over ICI):

  1. per-event stage (SHARDED): each device takes its slice of the
     batch and runs per_event_status() — the 5 hash probes and the ~50
     order-independent checks — against the REPLICATED ledger state.
     This is where the per-event FLOPs are; it scales linearly with
     devices. The imported tier's batch context (homogeneity flag,
     commit timestamp, account-ts collision) is computed replicated and
     fed in sliced.
  2. all_gather (ICI): the compact per-event bundle (status, resolved
     amount, touched rows — ~50 B/event) is gathered so every device
     holds the full batch's results.
  3. global tail (REPLICATED): eligibility reductions, the in-window
     join + substitution fixup (fixpoint tiers), the K-round
     limit/closing/balancing/imported fixpoint, the chain first-failure
     broadcast, row planning, and state application run identically on
     every device over the gathered bundle — a few O(N log N) sorts on
     compact arrays. Determinism makes the replicated ledger state
     bit-identical across the mesh, the SPMD restatement of the
     reference's determinism doctrine (docs/ARCHITECTURE.md:281-307).

Exactness: each sharded step returns bit-identical (new_state, out) to
its single-chip sibling, which is itself bit-exact vs the sequential
oracle under eligibility (tests/test_full_sharded.py runs the
differentials on an 8-device CPU mesh).

`ShardedRouter` is the host-side driver: per-batch flag routing to the
matching tier (the SPMD analog of DeviceLedger's pre-route), on-device
escalation (plain -> fixpoint), and per-cause fallback counters — a
mixed balancing+imported+closing window executes with ZERO per-shard
host fallbacks, and that is a measured number, not an assumption.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.fast_kernels import (
    LIMIT_FIXPOINT_ROUNDS_WINDOW_DEEP,
    create_transfers_fast,
    imported_batch_ctx,
    per_event_status,
)
from ..trace import Event, NullTracer

__all__ = ["make_sharded_create_transfers", "shard_batch", "ShardedRouter",
           "MODES"]

MODES = ("plain", "fixpoint", "balancing", "imported")

# Tail kwargs per tier — the SAME static flags the single-chip jit
# entries use, so the sharded step IS the single-chip kernel with the
# per-event stage plugged in.
_MODE_KWARGS = {
    "plain": {},
    "fixpoint": dict(limit_rounds=LIMIT_FIXPOINT_ROUNDS_WINDOW_DEEP),
    "balancing": dict(limit_rounds=LIMIT_FIXPOINT_ROUNDS_WINDOW_DEEP,
                      balancing_mode=True),
    "imported": dict(limit_rounds=LIMIT_FIXPOINT_ROUNDS_WINDOW_DEEP,
                     imported_mode=True),
}


def make_sharded_create_transfers(mesh: Mesh, axis: str = "batch",
                                  mode: str = "plain"):
    """Build the jitted full-semantics SPMD step over `mesh` for one
    kernel tier (`mode` in MODES).

    Returns step(state, ev, timestamp, n) -> (new_state, out), the same
    contract as the matching single-chip jit entry. `ev` arrays must be
    divisible by the mesh axis size (pad_transfer_events' N_PAD=8192
    divides any power-of-two mesh)."""
    from jax import shard_map

    assert mode in MODES, mode
    n_dev = mesh.shape[axis]
    # The imported tier's after_regress_codes is a STATIC tuple derived
    # inside per_event_status from its literal check lists; it cannot
    # ride the shard_map outputs (arrays only), so the traced body
    # captures it here and the tail re-attaches it.
    static_codes: list = []

    def step(state, ev, timestamp, n):
        N = ev["id_lo"].shape[0]
        assert N % n_dev == 0, (N, n_dev)
        shard = N // n_dev
        idxs = jnp.arange(N, dtype=jnp.int32)
        ts_full = (timestamp - n.astype(jnp.uint64)
                   + idxs.astype(jnp.uint64) + jnp.uint64(1))
        if mode == "imported":
            # Batch context replicated (global reductions + one sorted-
            # column membership probe), then sliced into the shards;
            # key_max stays a replicated scalar.
            ctx_full = imported_batch_ctx(state, ev, ts_full,
                                          ev["valid"], idxs)
            key_max = ctx_full.pop("key_max")
        else:
            ctx_full = key_max = None

        def per_event_shard(state, ev_shard, *ctx_args):
            # Global event positions for this shard: the event timestamp
            # ts_event = timestamp - n + i + 1 depends on the global index.
            dev = jax.lax.axis_index(axis)
            sh_idx = (dev * shard
                      + jnp.arange(shard, dtype=jnp.int32)).astype(
                          jnp.uint64)
            ts_event = (timestamp - n.astype(jnp.uint64) + sh_idx
                        + jnp.uint64(1))
            ictx = None
            if mode == "imported":
                (ctx_shard,) = ctx_args
                ictx = dict(ctx_shard, key_max=key_max)
            pe = per_event_status(state, ev_shard, ts_event,
                                  imported_ctx=ictx)
            codes = pe.pop("after_regress_codes", None)
            if codes is not None and not static_codes:
                static_codes.append(codes)
            # all_gather(tiled): every device ends with the full batch's
            # compact bundle, concatenated in device order == batch order.
            return {k: jax.lax.all_gather(v, axis, tiled=True)
                    for k, v in pe.items()}

        state_spec = jax.tree.map(lambda _: P(), state)
        ev_spec = {k: P(axis) for k in ev}
        # out_specs derived programmatically from the per-event pytree
        # (never a hardcoded key set): eval_shape the shard body's
        # bundle and map every leaf to the replicated spec.
        def _pe_struct(state, ev):
            ev_s = {k: v[:shard] for k, v in ev.items()}
            ictx = None
            if mode == "imported":
                ictx = dict({k: v[:shard] for k, v in ctx_full.items()},
                            key_max=key_max)
            pe = per_event_status(state, ev_s, ts_full[:shard],
                                  imported_ctx=ictx)
            pe.pop("after_regress_codes", None)
            return pe

        pe_struct = jax.eval_shape(_pe_struct, state, ev)
        out_specs = jax.tree.map(lambda _: P(), pe_struct)
        args = (state, ev)
        in_specs = (state_spec, ev_spec)
        if mode == "imported":
            args = args + ({k: v for k, v in ctx_full.items()},)
            in_specs = in_specs + ({k: P(axis) for k in ctx_full},)
        try:
            smapped = shard_map(
                per_event_shard, mesh=mesh,
                in_specs=in_specs, out_specs=out_specs, check_vma=False)
        except TypeError:  # pre-0.5 jax spells the kwarg check_rep
            smapped = shard_map(
                per_event_shard, mesh=mesh,
                in_specs=in_specs, out_specs=out_specs, check_rep=False)
        pe = smapped(*args)
        if mode == "imported":
            pe["after_regress_codes"] = static_codes[0]
        # Global tail on the gathered bundle: replicated, deterministic,
        # bit-exact vs the single-chip tier (it IS the single-chip
        # kernel with the per-event stage plugged in; the fixpoint
        # tiers additionally compute the in-window join here and
        # re-apply the substitution to the bundle).
        return create_transfers_fast(state, ev, timestamp, n,
                                     per_event=pe, **_MODE_KWARGS[mode])

    # Donate the replicated ledger buffers like every single-chip tier
    # (jaxhound's donation audit checks the lowered artifact): callers
    # consume the RETURNED state only — on fallback the masked writes
    # leave it bit-identical, so the escalation/replay contract is
    # unchanged. Platforms without donation support simply ignore it.
    return jax.jit(step, donate_argnums=0)


def shard_batch(mesh: Mesh, ev: dict, axis: str = "batch"):
    """Place a padded event dict with the batch axis sharded over `mesh`
    and return it (state stays replicated via P())."""
    sharding = NamedSharding(mesh, P(axis))
    return {k: jax.device_put(v, sharding) for k, v in ev.items()}


class ShardedRouter:
    """Host-side tier router over the sharded steps — the SPMD analog of
    DeviceLedger's flag pre-route. Inspects each batch's flags, runs the
    matching sharded step, redispatches device-resolvable escalations
    (plain -> fixpoint, exactly the single-chip limit_only contract:
    the failed kernel leaves donated state untouched), and accumulates
    per-cause host-fallback counters so "zero fallbacks on a mixed
    balancing+imported+closing window" is a measured invariant."""

    def __init__(self, mesh: Mesh, axis: str = "batch", tracer=None):
        self.mesh = mesh
        self.axis = axis
        self.tracer = tracer if tracer is not None else NullTracer()
        self._steps: dict = {}
        self._single_steps: dict = {}
        self.batches = 0
        self.escalations = 0
        self.host_fallbacks = 0
        self.fallback_causes: dict = {}
        # Chaos/degraded mode: mesh devices marked lost. While any
        # device is lost, every batch re-routes to the single-chip step
        # (the SAME create_transfers_fast math without the shard_map) —
        # results stay bit-exact, throughput degrades, and the reroute
        # is a counted event (testing/chaos.py injects the loss).
        self.lost_devices: set = set()
        self.shard_loss_reroutes = 0

    def drop_device(self, device) -> None:
        """Mark one mesh device lost (simulated ICI/host failure). The
        replicated ledger state means ANY surviving chip — or the
        single-chip path — can serve; we take the single-chip path
        until restore_devices() (re-meshing is a driver concern).

        This reroute is a REPLICATED-state privilege: the partitioned
        sibling (parallel/partitioned.PartitionedRouter.drop_device)
        cannot take it — a lost shard takes its account range with it —
        and resyncs from the oracle instead (`shard_resync` cause)."""
        self.lost_devices.add(device)

    def restore_devices(self) -> None:
        """The mesh healed: route back to the sharded steps."""
        self.lost_devices.clear()

    def _step(self, mode: str):
        fn = self._steps.get(mode)
        if fn is None:
            fn = self._steps[mode] = make_sharded_create_transfers(
                self.mesh, self.axis, mode=mode)
        return fn

    def _single_step(self, mode: str):
        """Single-chip sibling of the sharded step: the same
        create_transfers_fast tail with the same static tier kwargs, no
        mesh — the degraded-mode target when a shard is lost."""
        fn = self._single_steps.get(mode)
        if fn is None:
            import functools

            fn = self._single_steps[mode] = jax.jit(
                functools.partial(create_transfers_fast,
                                  **_MODE_KWARGS[mode]),
                donate_argnums=0)
        return fn

    @staticmethod
    def route(ev: dict) -> str:
        """Flag-derived tier for one (padded or raw) event dict. Same
        precedence as DeviceLedger: imported > balancing > closing;
        limit breaches and in-batch pending refs are invisible to flags
        and escalate from the plain step instead."""
        from ..types import TransferFlags as TF

        flags = np.asarray(ev["flags"])
        if (flags & np.uint32(int(TF.imported))).any():
            return "imported"
        if (flags & np.uint32(int(TF.balancing_debit
                                  | TF.balancing_credit))).any():
            return "balancing"
        if (flags & np.uint32(int(TF.closing_debit
                                  | TF.closing_credit))).any():
            return "fixpoint"
        return "plain"

    def step(self, state, ev: dict, timestamp: int, n: int):
        """Run one padded batch. Returns (new_state, out, fell_back).
        On fell_back=True the state is untouched (masked writes) and the
        caller owns the exact-path replay."""
        self.batches += 1
        mode = self.route(ev)
        degraded = bool(self.lost_devices)
        if degraded:
            self.shard_loss_reroutes += 1
            self.tracer.count(Event.router_reroute)
        pick = self._single_step if degraded else self._step
        # Route observability: the same catalog counter the serving
        # supervisor emits per window, so sharded and single-chip
        # dispatch routes read off one metric.
        self.tracer.count(
            Event.dispatch_route,
            route=("single_chip_" if degraded else "sharded_") + mode)
        with self.tracer.span(Event.router_step, mode=mode,
                              degraded=int(degraded)):
            new_state, out = pick(mode)(
                state, ev, np.uint64(timestamp), np.int32(n))
            fallback, limit_only = (bool(x) for x in jax.device_get(
                (out["fallback"], out["limit_only"])))
            if fallback and limit_only and mode == "plain":
                # Breach / collision / closing: resolvable on the
                # sharded fixpoint step (the plain kernel left state
                # untouched).
                self.escalations += 1
                new_state, out = pick("fixpoint")(
                    new_state, ev, np.uint64(timestamp), np.int32(n))
                fallback = bool(jax.device_get(out["fallback"]))
        if fallback:
            self.host_fallbacks += 1
            for k, v in jax.device_get(out["fb_causes"]).items():
                if bool(v):
                    self.fallback_causes[k] = (
                        self.fallback_causes.get(k, 0) + 1)
                    self.tracer.count(Event.router_fallback, cause=k)
        return new_state, out, fallback

    def stats(self) -> dict:
        return {
            "batches": self.batches,
            "escalations": self.escalations,
            "host_fallbacks": self.host_fallbacks,
            "causes": dict(self.fallback_causes),
            "lost_devices": len(self.lost_devices),
            "shard_loss_reroutes": self.shard_loss_reroutes,
        }
