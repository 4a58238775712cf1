"""Device-memory watermark plane: the static-allocation ledger.

The reference's core memory discipline (docs/ARCHITECTURE.md:189-230)
is that serving memory is statically allocated: every resident buffer
is sized by a cap chosen at startup, so the footprint is a FUNCTION OF
CAPS, not of history. This module makes that discipline machine-
checkable (ISSUE 20):

- ``component_bytes(led)`` walks a live DeviceLedger and attributes
  every resident allocation to a named component — the state pytree's
  top-level stores (accounts / transfers / events ring / both hash
  tables / scalars), the double-buffered staged operand pack, the
  harvested device-telemetry block, and the partitioned router's
  per-shard state — bytes computed from shapes and dtypes
  (deterministic on every backend, no allocator introspection needed).
- ``static_ledger(a_cap, t_cap, ...)`` predicts the same components
  from caps alone (it builds the init_state shapes host-side), so the
  prediction can be asserted against measured device bytes
  (tests/test_memory_bounds.py does, on 1/2/8-device meshes).
- ``check_budget(measured, budget)`` compares a measurement against
  the committed ``perf/membudget_r*.json``: any component growing past
  its pinned bytes (beyond the budget's tolerance), any NEW component
  the budget has never heard of, or total growth is a RED — the gate's
  profile leg enforces it with an injected-leak negative.
- ``MemWatch`` emits the watermark as catalog gauges
  (``memory_watermark_bytes`` / ``memory_budget_headroom_bytes``) so
  the footprint flows into StatsD/Prometheus like any metric,
  and samples per-device allocator stats (``device.memory_stats()``)
  where the backend provides them (TPU does; CPU typically returns
  nothing — the shape-derived ledger is the deterministic source of
  truth everywhere).
"""

from __future__ import annotations

import json
import math
import os
from typing import Optional

from .event import Event

# Worst-case staged-pack accounting: one pipelined window's stacked
# operands at depth W over the largest pad bucket. Kept in sync with
# ops/ledger.py's PAD_BUCKETS tail and the serving pipeline depth.
STAGED_PACK_DEPTH = 2


def leaf_bytes(leaf) -> int:
    """Resident bytes of one array-like leaf (shape x itemsize — works
    for numpy, jax.Array, and ShapeDtypeStruct alike; scalars count
    their dtype width)."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * dtype.itemsize


def pytree_bytes(tree) -> int:
    """Total resident bytes of a pytree (sum over leaves)."""
    import jax

    return sum(leaf_bytes(x) for x in jax.tree_util.tree_leaves(tree))


def state_component_bytes(state) -> dict:
    """Bytes per top-level store of a ledger state pytree. Nested
    sub-trees are summed under their top key; bare scalar leaves are
    grouped under ``scalars``."""
    out: dict = {}
    scalars = 0
    for key, sub in state.items():
        b = pytree_bytes(sub) if isinstance(sub, dict) else leaf_bytes(sub)
        if isinstance(sub, dict):
            out[f"state.{key}"] = b
        else:
            scalars += b
    out["state.scalars"] = scalars
    return out


def staged_pack_max_bytes(n_pad: int, depth: int = STAGED_PACK_DEPTH,
                          kind: str = "transfer") -> int:
    """Worst-case bytes of one staged window pack: `depth` prepares'
    padded event columns plus their timestamp/count lanes. Measured
    from a real padded-event dict (the exact columns the stager device-
    puts), not a hand-kept formula."""
    from ..ops.batch import transfers_to_arrays
    from ..ops.ledger import pad_transfer_events
    from ..types import Transfer

    ev = pad_transfer_events(transfers_to_arrays(
        [Transfer(id=1, debit_account_id=1, credit_account_id=2,
                  amount=1, ledger=1, code=1)]), n_pad)
    per_prepare = pytree_bytes(ev)
    # + one u64 timestamp and one i32 count lane per prepare.
    return depth * (per_prepare + 8 + 4)


def telemetry_block_bytes(n_shards: int, depth: int) -> int:
    """The harvested [n_shards, W, TEL_WORDS] u32 device-telemetry
    block of one fused partitioned-chain window."""
    from ..parallel.partitioned import TEL_WORDS

    return n_shards * depth * TEL_WORDS * 4


def static_ledger(a_cap: int, t_cap: int, *, n_shards: int = 1,
                  window_depth: int = 8, n_pad: Optional[int] = None,
                  orphan_cap: Optional[int] = None,
                  e_cap: Optional[int] = None) -> dict:
    """The deterministic static-allocation ledger: predicted resident
    bytes per component from caps alone. For a partitioned mesh the
    per-shard caps divide by n_shards (matching PartitionedRouter /
    jaxhound.registry fixtures) and components are GLOBAL (x n_shards);
    ``per_device_bytes`` is the ~1/n per-shard share."""
    from ..ops.ledger import N_PAD, init_state

    if n_pad is None:
        n_pad = N_PAD
    if n_shards > 1:
        sub = init_state(a_cap // n_shards, t_cap // n_shards,
                         orphan_cap=(orphan_cap or (1 << 16)) // n_shards,
                         e_cap=None if e_cap is None else e_cap // n_shards)
        comps = {k: v * n_shards
                 for k, v in state_component_bytes(sub).items()}
    else:
        comps = state_component_bytes(init_state(
            a_cap, t_cap, orphan_cap=orphan_cap, e_cap=e_cap))
    comps["staged_pack"] = staged_pack_max_bytes(n_pad)
    comps["telemetry_block"] = telemetry_block_bytes(
        n_shards, window_depth) if n_shards > 1 else 0
    total = sum(comps.values())
    return {
        "caps": {"a_cap": a_cap, "t_cap": t_cap, "n_shards": n_shards,
                 "window_depth": window_depth, "n_pad": n_pad},
        "components": comps,
        "total_bytes": total,
        "per_device_bytes": total // max(1, n_shards),
    }


def measure_ledger(led) -> dict:
    """The LIVE counterpart of static_ledger: component bytes measured
    from a DeviceLedger's actual resident pytrees (state, any staged
    pack in flight, the partitioned router's sharded state + telemetry
    block). Shape-derived, so it is exact and deterministic — the
    watermark can never wobble with allocator internals."""
    comps = state_component_bytes(led.state)
    staged = getattr(led, "_staged", None)
    staged_b = 0
    if staged is not None:
        fut = staged[-1]
        if fut.done() and not fut.cancelled():
            try:
                payload, _ = fut.result()
                staged_b = pytree_bytes(payload)
            except Exception:
                staged_b = 0
    comps["staged_pack"] = staged_b
    router = getattr(led, "_part_router", None)
    n_shards = 1
    if router is not None:
        n_shards = router.n_shards
        pstate = getattr(led, "_part_state", None)
        if pstate is not None:
            comps["partitioned_state"] = pytree_bytes(pstate)
        comps["telemetry_block"] = telemetry_block_bytes(
            n_shards, STAGED_PACK_DEPTH)
    total = sum(comps.values())
    return {"components": comps, "total_bytes": total,
            "per_device_bytes": total // max(1, n_shards),
            "n_shards": n_shards}


def device_memory_stats() -> list:
    """Per-device allocator stats where the backend provides them
    (``bytes_in_use`` / ``peak_bytes_in_use`` on TPU/GPU). Returns one
    dict per device; ``stats`` is None where unsupported (CPU) — the
    static ledger is the watermark source of truth there."""
    import jax

    out = []
    for d in jax.devices():
        stats = None
        try:
            s = d.memory_stats()
        except Exception:
            if d.platform == "tpu":
                raise  # a chip that cannot report its memory is a fault
            s = None
        if s:
            stats = {k: int(v) for k, v in s.items()
                     if isinstance(v, (int, float))
                     and k in ("bytes_in_use", "peak_bytes_in_use",
                               "bytes_limit", "largest_alloc_size")}
        out.append({"device": str(d), "platform": d.platform,
                    "stats": stats})
    return out


def check_budget(measured: dict, budget: dict) -> list:
    """Budget audit: measured components vs the committed membudget.
    REDs on (a) any component past its pinned bytes beyond tolerance,
    (b) any component the budget never pinned (a leak shows up as a
    new allocation before it shows up as growth), (c) total growth.
    Returns human-readable RED lines (empty = green)."""
    tol = float(budget.get("tolerance", 0.02))
    pinned = budget.get("components", {})
    reds = []
    for comp, got in sorted(measured["components"].items()):
        limit = pinned.get(comp)
        if limit is None:
            if got:
                reds.append(
                    f"memwatch RED: component {comp!r} ({got} bytes) is "
                    f"not in the committed budget (new allocation — "
                    f"re-pin perf/membudget with --write if intended)")
            continue
        if got > math.ceil(limit * (1.0 + tol)):
            reds.append(
                f"memwatch RED: component {comp!r} grew to {got} bytes "
                f"vs pinned {limit} (tolerance {tol:.0%})")
    total, limit = measured["total_bytes"], budget.get("total_bytes")
    if limit is not None and total > math.ceil(limit * (1.0 + tol)):
        reds.append(
            f"memwatch RED: total watermark {total} bytes vs pinned "
            f"{limit} (tolerance {tol:.0%})")
    return reds


def load_budget(path: Optional[str] = None) -> dict:
    """The committed membudget (newest perf/membudget_r*.json)."""
    if path is None:
        from ..jaxhound import newest_membudget_path

        path = newest_membudget_path()
    with open(path) as f:
        budget = json.load(f)
    for key in ("components", "total_bytes"):
        if key not in budget:
            raise ValueError(
                f"membudget {os.path.basename(str(path))} is missing "
                f"{key!r} — not a valid static-allocation budget")
    return budget


class MemWatch:
    """The watermark sampler the serving supervisor ticks: measures the
    static-allocation ledger, emits the catalog gauges, and keeps the
    last observation (+ budget verdict) for ``stats()``."""

    def __init__(self, tracer=None, budget_path: Optional[str] = None,
                 budget: Optional[dict] = None):
        from .tracer import NullTracer

        self.tracer = tracer if tracer is not None else NullTracer()
        self._budget_path = budget_path
        self._budget = budget
        self.observations = 0
        self.last: Optional[dict] = None
        self.reds: list = []

    @property
    def budget(self) -> Optional[dict]:
        if self._budget is None:
            try:
                self._budget = load_budget(self._budget_path)
            except (OSError, ValueError):
                self._budget = None
        return self._budget

    def observe(self, led, with_device_stats: bool = False) -> dict:
        """One watermark sample: measure, gauge, audit. Cheap (a pytree
        walk over shapes), so the supervisor ticks it at every epoch
        verification."""
        rec = measure_ledger(led)
        self.observations += 1
        self.tracer.gauge(Event.memory_watermark_bytes,
                          rec["total_bytes"])
        budget = self.budget
        if budget is not None:
            rec["budget_total_bytes"] = budget["total_bytes"]
            rec["headroom_bytes"] = (budget["total_bytes"]
                                     - rec["total_bytes"])
            self.tracer.gauge(Event.memory_budget_headroom_bytes,
                              rec["headroom_bytes"])
            self.reds = check_budget(rec, budget)
            rec["budget_ok"] = not self.reds
        if with_device_stats:
            rec["device_memory_stats"] = device_memory_stats()
        self.last = rec
        return rec

    def stats(self) -> dict:
        return {"observations": self.observations,
                "last": self.last, "reds": list(self.reds)}
