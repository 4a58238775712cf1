"""Per-kernel op-budget ledger: heavy-op counts + operand bytes, gated.

The kernels are gather/scatter/sort programs with no matmul content,
so the budget counts *executed heavy ops* and the operand bytes they
read: the lever it guards is fewer, fatter ops. (What one op costs on
a local v5e is not measured yet — PERF.md.) This module makes that lever
un-regressable:

  - census: jaxpr-level heavy-op counts by class (sort / gather /
    scatter / segment_sum / scan — tigerbeetle_tpu.jaxhound.heavy_census)
    plus the operand bytes those ops read, for every create_transfers
    kernel tier INCLUDING the SPMD lowerings (8-device CPU mesh).
  - budgets: perf/opbudget_r09.json commits a per-tier budget. A kernel
    change that raises any tier's heavy-op count or operand bytes past
    its budget fails `--check` (wired into scripts/gate.py) — raising a
    budget is an explicit, reviewed edit of the JSON (see
    ARCHITECTURE.md "Op-budget workflow"). Round 7 added the CHAIN
    entries: the scan-form whole-window route's whole-program census
    (chain_w{2,8,32} — ~constant in window depth, the route's whole
    point) and its per-iteration BODY census (chain_body_w8, via
    jaxhound.scan_body_census — pinned <= the per-batch plain tier).
    Round 8 adds the PARTITIONED tiers (sharded state, on-device
    exchange): cross-device collectives are a counted class
    ('collective'), so the budget pins the exchange's op count, and
    the lints additionally reject any collective moving a whole-state
    operand (jaxhound.state_gathers). Round 9 fuses the two: the
    PARTITIONED CHAIN tiers census the whole-window scan dispatch over
    sharded state (partitioned_chain_w{2,8,32} — whole-program, flat
    in W) and its per-iteration body (partitioned_chain_body, via
    scan_body_census — pinned == the per-batch partitioned_plain tier,
    collectives INSIDE the scan body included, with their ICI byte
    mass broken out as collective_operand_bytes).
  - lints: `--lint` runs the jaxhound static checks over the serving-
    path jit entries: no closure constant > 4 KiB (a baked-in
    table is re-materialized per program instead of passed as an
    operand), no while/fori loop in any serving lowering beyond an
    entry's declared allowance (the chain entries' ONE deliberate scan
    lowers to one stablehlo.while; everything else allows zero), and
    every state-carrying entry donates its ledger buffers
    (donated-input count == state leaf count in the lowered artifact —
    the chain entries are audited too, incl. the unrolled form).

CLI:
    python perf/opbudget.py             # print the census table
    python perf/opbudget.py --check    # fail (rc=1) on budget excess
    python perf/opbudget.py --lint     # fail (rc=1) on lint violations
    python perf/opbudget.py --write    # refresh the 'post' column of
                                       # the budget file IN PLACE
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The sharded tiers trace against an 8-device CPU mesh in-process.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from tigerbeetle_tpu import jaxhound  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# The budget trail is append-oriented (a new opbudget_r<N>.json per
# round that moves a pinned census); always check/write the head.
BUDGET_PATH = jaxhound.newest_budget_path(os.path.join(REPO, "perf"))

STACK = 4
N_SUPER = 1024
# Chain-route census depths: the whole-program census must be
# ~constant across these (the scan body lowers once).
CHAIN_DEPTHS = (2, 8, 32)


def _mk_prepares(n_prepares, n=N_SUPER, nid0=10 ** 6, seed=0):
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


def _fixtures():
    from tigerbeetle_tpu.ops.batch import transfers_to_arrays
    from tigerbeetle_tpu.ops.ledger import (
        init_state, pad_transfer_events, stack_superbatch)
    from tigerbeetle_tpu.types import Transfer

    state = init_state(1 << 10, 1 << 12)
    ev = pad_transfer_events(transfers_to_arrays(
        [Transfer(id=1, debit_account_id=1, credit_account_id=2,
                  amount=1, ledger=1, code=1)]))
    evs, tss = _mk_prepares(STACK)
    ev_s, seg = stack_superbatch(evs, tss)
    return state, ev, ev_s, seg


def _chain_fixture(depth):
    from tigerbeetle_tpu.ops.ledger import stack_chain_window

    evs, tss = _mk_prepares(depth)
    return stack_chain_window(evs, tss, N_SUPER)


def _partitioned_chain_fixture(depth):
    from tigerbeetle_tpu.parallel.partitioned import (
        stack_partitioned_window)

    evs, tss = _mk_prepares(depth)
    return stack_partitioned_window(evs, tss, N_SUPER)


def _partitioned_fixture(mesh, axis="batch"):
    """Stacked empty partitioned state over `mesh` (per-shard caps =
    the replicated fixture caps / n_shards; the census and lints only
    need shapes, not contents)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tigerbeetle_tpu.ops.ledger import init_state

    n = mesh.shape[axis]
    sub = jax.tree.map(np.asarray, init_state(
        (1 << 10) // n, (1 << 12) // n, orphan_cap=(1 << 16) // n))
    stacked = jax.tree.map(lambda x: np.stack([x] * n), sub)
    return jax.device_put(stacked, NamedSharding(mesh, P(axis)))


def census_tiers() -> dict:
    """tier name -> heavy_census dict for every kernel tier."""
    from tigerbeetle_tpu.ops import fast_kernels as fk

    state, ev, ev_s, seg = _fixtures()
    N = ev["id_lo"].shape[0]
    ts = np.uint64(1000)
    n = np.int32(1)
    ts_vec = jnp.full((N,), 1000, jnp.uint64)
    idxs = jnp.arange(N, dtype=jnp.int32)

    def pe_plain(state, ev, ts_vec):
        return fk.per_event_status(state, ev, ts_vec)

    def pe_imported(state, ev, ts_vec):
        ctx = fk.imported_batch_ctx(state, ev, ts_vec, ev["valid"], idxs)
        return fk.per_event_status(state, ev, ts_vec, imported_ctx=ctx)

    def super_(limit_rounds):
        def f(state, ev_s, seg):
            return fk.create_transfers_fast(
                state, ev_s, jnp.uint64(0), jnp.int32(0), seg=seg,
                limit_rounds=limit_rounds)
        return f

    tiers = {
        "per_event_plain": (pe_plain, (state, ev, ts_vec)),
        "per_event_imported": (pe_imported, (state, ev, ts_vec)),
        "plain": (fk.create_transfers_fast, (state, ev, ts, n)),
        "imported": (functools.partial(
            fk.create_transfers_fast, imported_mode=True),
            (state, ev, ts, n)),
        "fixpoint_8": (functools.partial(
            fk.create_transfers_fast, limit_rounds=8), (state, ev, ts, n)),
        "fixpoint_deep_32": (functools.partial(
            fk.create_transfers_fast, limit_rounds=32),
            (state, ev, ts, n)),
        "balancing_8": (functools.partial(
            fk.create_transfers_fast, limit_rounds=8,
            balancing_mode=True), (state, ev, ts, n)),
        "imported_fixpoint_8": (functools.partial(
            fk.create_transfers_fast, imported_mode=True, limit_rounds=8),
            (state, ev, ts, n)),
        "super_plain_s4": (super_(1), (state, ev_s, seg)),
        "super_deep24_s4": (super_(
            fk.LIMIT_FIXPOINT_ROUNDS_WINDOW_DEEP), (state, ev_s, seg)),
    }
    out = {}
    for name, (fn, args) in tiers.items():
        out[name] = jaxhound.heavy_census(jax.make_jaxpr(fn)(*args))
    # Chain route (the default whole-window scan dispatch): the
    # whole-program census at three depths — ~constant heavy totals
    # prove the scan body lowers once — plus the per-iteration BODY
    # census the gate pins against the per-batch plain tier.
    for w in CHAIN_DEPTHS:
        ev_c, seg_c = _chain_fixture(w)
        cj = jax.make_jaxpr(fk._create_transfers_chain)(
            state, ev_c, seg_c)
        out[f"chain_w{w}"] = jaxhound.heavy_census(cj)
        if w == 8:
            out["chain_body_w8"] = jaxhound.scan_body_census(cj)
    if len(jax.devices()) >= 8:
        from jax.sharding import Mesh
        from tigerbeetle_tpu.parallel.full_sharded import (
            make_sharded_create_transfers)

        mesh = Mesh(np.array(jax.devices()[:8]), ("batch",))
        for mode in ("plain", "fixpoint"):
            step = make_sharded_create_transfers(mesh, mode=mode)
            with mesh:
                cj = jax.make_jaxpr(
                    lambda st, e: step.__wrapped__(
                        st, e, jnp.uint64(1000), jnp.int32(1)))(state, ev)
            out[f"sharded_{mode}"] = jaxhound.heavy_census(cj)
        # Partitioned tiers (sharded STATE + on-device exchange): the
        # 'collective' class pins the exchange's ICI round trips.
        from tigerbeetle_tpu.parallel.partitioned import (
            make_partitioned_create_transfers)

        pstate = _partitioned_fixture(mesh)
        for mode in ("plain", "fixpoint"):
            pstep = make_partitioned_create_transfers(mesh, mode=mode)
            with mesh:
                cj = jax.make_jaxpr(
                    lambda st, e: pstep.__wrapped__(
                        st, e, jnp.uint64(1000), jnp.int32(1)))(pstate, ev)
            out[f"partitioned_{mode}"] = jaxhound.heavy_census(cj)
        # Partitioned CHAIN (the fused default window route): the
        # whole-program census must be flat across depths (the scan
        # body — exchange collectives included — lowers ONCE), and the
        # per-iteration BODY census is pinned == the per-batch
        # partitioned_plain tier: the window amortizes dispatch, it
        # must not add op mass per prepare.
        from tigerbeetle_tpu.parallel.partitioned import (
            make_partitioned_chain_create_transfers)

        cstep = make_partitioned_chain_create_transfers(mesh, mode="plain")
        for w in CHAIN_DEPTHS:
            ev_p, ts_p, n_p = _partitioned_chain_fixture(w)
            with mesh:
                cj = jax.make_jaxpr(
                    lambda st, e, t, nn: cstep.__wrapped__(
                        st, e, t, nn, None))(pstate, ev_p, ts_p, n_p)
            out[f"partitioned_chain_w{w}"] = jaxhound.heavy_census(cj)
            if w == 8:
                out["partitioned_chain_body"] = \
                    jaxhound.scan_body_census(cj)
    return out


def serving_entries() -> dict:
    """name -> (lowered artifact, expected donated-input count, allowed
    while count) for the state-carrying jit entries on the serving/scan
    paths. The chain entries allow exactly ONE stablehlo.while (their
    deliberate lax.scan); everything else allows zero."""
    from tigerbeetle_tpu.ops import fast_kernels as fk

    state, ev, ev_s, seg = _fixtures()
    n_leaves = len(jax.tree_util.tree_leaves(state))
    ts = np.uint64(1000)
    n = np.int32(1)
    entries = {}

    def add(name, jitfn, *args, max_while=0):
        entries[name] = (jitfn.lower(*args), n_leaves, max_while)

    add("create_transfers_fast_jit", fk.create_transfers_fast_jit,
        state, ev, ts, n)
    add("create_transfers_fixpoint_jit", fk.create_transfers_fixpoint_jit,
        state, ev, ts, n)
    add("create_transfers_fixpoint_deep_jit",
        fk.create_transfers_fixpoint_deep_jit, state, ev, ts, n)
    add("create_transfers_balancing_jit",
        fk.create_transfers_balancing_jit, state, ev, ts, n)
    add("create_transfers_imported_jit",
        fk.create_transfers_imported_jit, state, ev, ts, n)
    add("create_transfers_imported_fixpoint_jit",
        fk.create_transfers_imported_fixpoint_jit, state, ev, ts, n)
    add("create_transfers_super_jit", fk.create_transfers_super_jit,
        state, ev_s, seg)
    add("create_transfers_super_deep_jit",
        fk.create_transfers_super_deep_jit, state, ev_s, seg)
    add("create_transfers_super_ring_jit",
        fk.create_transfers_super_ring_jit, state, ev_s, seg)
    add("create_transfers_super_deep_ring_jit",
        fk.create_transfers_super_deep_ring_jit, state, ev_s, seg)
    add("create_transfers_super_balancing_jit",
        fk.create_transfers_super_balancing_jit, state, ev_s, seg)
    # Chain entries (the default whole-window route): the scan form's
    # one deliberate while is allowed; the unrolled fallback form must
    # stay straight-line — and BOTH must donate the state carry
    # (create_transfers_chain_unrolled_jit used to escape this audit
    # because only per-batch tiers were enumerated here).
    ev_c, seg_c = _chain_fixture(4)
    add("create_transfers_chain_jit", fk.create_transfers_chain_jit,
        state, ev_c, seg_c, max_while=1)
    add("create_transfers_chain_ring_jit",
        fk.create_transfers_chain_ring_jit, state, ev_c, seg_c,
        max_while=1)
    add("create_transfers_chain_unrolled_jit",
        fk.create_transfers_chain_unrolled_jit, state, ev_c, seg_c)
    # Sharded steps (8-device CPU mesh): same donation contract.
    if len(jax.devices()) >= 8:
        from jax.sharding import Mesh
        from tigerbeetle_tpu.parallel.full_sharded import (
            make_sharded_create_transfers)

        mesh = Mesh(np.array(jax.devices()[:8]), ("batch",))
        for mode in ("plain", "fixpoint"):
            step = make_sharded_create_transfers(mesh, mode=mode)
            with mesh:
                entries[f"sharded_{mode}_step"] = (
                    step.lower(state, ev, np.uint64(1000), np.int32(1)),
                    n_leaves, 0)
        # Partitioned steps: same donation contract over the stacked
        # (device-sharded) state pytree.
        from tigerbeetle_tpu.parallel.partitioned import (
            make_partitioned_create_transfers)

        pstate = _partitioned_fixture(mesh)
        for mode in ("plain", "fixpoint"):
            pstep = make_partitioned_create_transfers(mesh, mode=mode)
            with mesh:
                entries[f"partitioned_{mode}_step"] = (
                    pstep.lower(pstate, ev, np.uint64(1000), np.int32(1)),
                    n_leaves, 0)
        # Partitioned chain step: one deliberate scan (max_while=1),
        # donated sharded state carry.
        from tigerbeetle_tpu.parallel.partitioned import (
            make_partitioned_chain_create_transfers)

        cstep = make_partitioned_chain_create_transfers(mesh, mode="plain")
        ev_p, ts_p, n_p = _partitioned_chain_fixture(4)
        with mesh:
            entries["partitioned_chain_step"] = (
                cstep.lower(pstate, ev_p, ts_p, n_p, None),
                n_leaves, 1)
    return entries


def run_lints() -> list[str]:
    """Serving-path static lints (jaxhound): closure constants, while
    loops, donation. Returns human-readable failure strings."""
    fails = []
    for name, (lowered, n_donate, max_while) in serving_entries().items():
        # The serving path must stay straight-line: lax.scan/while both
        # lower to stablehlo.while. The chain entries declare their ONE
        # deliberate scan (max_while=1); anything beyond an entry's
        # allowance — e.g. a searchsorted left on the default scan
        # method — is a red.
        text = lowered.as_text()
        n_while = text.count("stablehlo.while")
        if n_while > max_while:
            fails.append(
                f"{name}: {n_while} while loop(s) in the lowering "
                f"(> allowed {max_while}; one executed while degrades "
                "every later dispatch to 5-8 ms — PERF.md)")
        donated = jaxhound.donated_inputs(lowered)
        if donated < n_donate:
            fails.append(
                f"{name}: {donated} donated inputs < {n_donate} state "
                "leaves (missing donate_argnums => every dispatch pays "
                "a full state copy)")
    # Closure constants are a trace-level property: re-trace the raw fns.
    from tigerbeetle_tpu.ops import fast_kernels as fk

    state, ev, ev_s, seg = _fixtures()
    ev_c, seg_c = _chain_fixture(4)
    for name, fn, args in (
            ("create_transfers_fast", fk.create_transfers_fast,
             (state, ev, np.uint64(1000), np.int32(1))),
            ("create_transfers_super",
             lambda st, e, s: fk.create_transfers_fast(
                 st, e, jnp.uint64(0), jnp.int32(0), seg=s),
             (state, ev_s, seg)),
            ("create_transfers_chain", fk._create_transfers_chain,
             (state, ev_c, seg_c)),
    ):
        big = jaxhound.closure_constants(jax.make_jaxpr(fn)(*args))
        for label, size in big:
            fails.append(
                f"{name}: closure constant {label} = {size} B > "
                f"{jaxhound.CLOSURE_CONST_LIMIT} B (pass tables as "
                "operands, never as baked-in constants)")
    # Partitioned entries: the exchange must never regress into moving
    # whole-state operands through a collective — that would rebuild
    # the replicated route inside the partitioned one.
    if len(jax.devices()) >= 8:
        from jax.sharding import Mesh

        from tigerbeetle_tpu.parallel.partitioned import (
            make_partitioned_create_transfers)

        mesh = Mesh(np.array(jax.devices()[:8]), ("batch",))
        pstate = _partitioned_fixture(mesh)
        for mode in ("plain", "fixpoint"):
            pstep = make_partitioned_create_transfers(mesh, mode=mode)
            with mesh:
                cj = jax.make_jaxpr(
                    lambda st, e: pstep.__wrapped__(
                        st, e, jnp.uint64(1000), jnp.int32(1)))(pstate, ev)
            for prim, nbytes in jaxhound.state_gathers(cj):
                fails.append(
                    f"partitioned_{mode}_step: {prim} moves {nbytes} B "
                    f"per device (> {jaxhound.STATE_GATHER_LIMIT} B — "
                    "the exchange regressed into a whole-state gather)")
            for label, size in jaxhound.closure_constants(cj):
                fails.append(
                    f"partitioned_{mode}_step: closure constant {label} "
                    f"= {size} B > {jaxhound.CLOSURE_CONST_LIMIT} B")
        # The fused chain runs the exchange INSIDE its scan body;
        # state_gathers recurses into scan bodies, so a whole-state
        # collective can't hide behind the scan either.
        from tigerbeetle_tpu.parallel.partitioned import (
            make_partitioned_chain_create_transfers)

        cstep = make_partitioned_chain_create_transfers(mesh, mode="plain")
        ev_p, ts_p, n_p = _partitioned_chain_fixture(4)
        with mesh:
            cj = jax.make_jaxpr(
                lambda st, e, t, nn: cstep.__wrapped__(
                    st, e, t, nn, None))(pstate, ev_p, ts_p, n_p)
        for prim, nbytes in jaxhound.state_gathers(cj):
            fails.append(
                f"partitioned_chain_step: {prim} moves {nbytes} B "
                f"per device (> {jaxhound.STATE_GATHER_LIMIT} B — "
                "the scanned exchange regressed into a whole-state "
                "gather)")
        for label, size in jaxhound.closure_constants(cj):
            fails.append(
                f"partitioned_chain_step: closure constant {label} "
                f"= {size} B > {jaxhound.CLOSURE_CONST_LIMIT} B")
    return fails


def telemetry_report() -> dict:
    """Census the device-telemetry plane of the fused partitioned
    chain (round 10): the pack's lane count (jaxhound.telemetry_census
    — the telemetry block cannot grow a word silently), and the
    telemetry-on vs telemetry-off DELTA of the scan body's heavy
    census (the pack is elementwise + a named stack, so the pinned
    allowance is zero heavy ops — observability must ride the existing
    op mass, not add to it). Returns {} on < 8 devices (the
    partitioned tiers need the mesh)."""
    if len(jax.devices()) < 8:
        return {}
    from jax.sharding import Mesh
    from tigerbeetle_tpu.parallel.partitioned import (
        make_partitioned_chain_create_transfers)

    mesh = Mesh(np.array(jax.devices()[:8]), ("batch",))
    pstate = _partitioned_fixture(mesh)
    ev_p, ts_p, n_p = _partitioned_chain_fixture(8)
    bodies = {}
    tel = None
    for on in (True, False):
        cstep = make_partitioned_chain_create_transfers(
            mesh, mode="plain", telemetry=on)
        with mesh:
            cj = jax.make_jaxpr(
                lambda st, e, t, nn: cstep.__wrapped__(
                    st, e, t, nn, None))(pstate, ev_p, ts_p, n_p)
        bodies[on] = jaxhound.scan_body_census(cj)["heavy_total"]
        if on:
            tel = jaxhound.telemetry_census(cj)
    return {
        "lanes": tel["lanes"],
        "pack_sites": tel["sites"],
        "pack_ops": tel["ops"],
        "chain_body_heavy_on": bodies[True],
        "chain_body_heavy_off": bodies[False],
        "chain_body_heavy_delta": bodies[True] - bodies[False],
    }


def check_telemetry(report: dict | None = None) -> list[str]:
    """Gate leg: the telemetry-lane census vs the committed budget's
    `telemetry` section. Reds when the pack grows lanes/ops past the
    committed words, when the pack disappeared from the fused route
    (dead telemetry plane), or when the scan body's heavy-op delta
    exceeds the pinned allowance."""
    with open(BUDGET_PATH) as f:
        committed = json.load(f)
    budget = committed.get("telemetry")
    if budget is None:
        return [f"{os.path.basename(BUDGET_PATH)} has no 'telemetry' "
                "section (run --write on >= 8 devices)"]
    if report is None:
        report = telemetry_report()
    if not report:
        return []  # no mesh: the partitioned tiers are not censusable
    fails = []
    if report["lanes"] != budget["lanes"]:
        fails.append(
            f"telemetry lanes {report['lanes']} != committed "
            f"{budget['lanes']} (TEL_LAYOUT changed without a budget "
            "bump — commit a new opbudget round)")
    if report["pack_sites"] < 1:
        fails.append("telemetry pack missing from the fused chain "
                     "route (dead telemetry plane)")
    if report["pack_ops"] > budget["pack_ops"]:
        fails.append(
            f"telemetry pack ops {report['pack_ops']} > committed "
            f"{budget['pack_ops']} (compute smuggled into the "
            "observability plane)")
    delta_max = budget.get("chain_body_heavy_delta_max", 0)
    if report["chain_body_heavy_delta"] > delta_max:
        fails.append(
            f"telemetry heavy-op delta "
            f"{report['chain_body_heavy_delta']} > allowed {delta_max} "
            "(the telemetry block added heavy ops to the scan body)")
    return fails


def check_budgets(current: dict | None = None) -> list[str]:
    """Compare the current census against the committed budgets.
    Returns failure strings (empty = within budget)."""
    with open(BUDGET_PATH) as f:
        committed = json.load(f)
    budgets = committed.get("budget", {})
    if current is None:
        current = census_tiers()
    fails = []
    for tier, budget in budgets.items():
        cur = current.get(tier)
        if cur is None:
            fails.append(f"{tier}: no current census (tier removed? "
                         "update the committed budget JSON)")
            continue
        if cur["heavy_total"] > budget["heavy_total"]:
            fails.append(
                f"{tier}: heavy_total {cur['heavy_total']} > budget "
                f"{budget['heavy_total']}")
        for cls, limit in budget.get("heavy", {}).items():
            if cur["heavy"].get(cls, 0) > limit:
                fails.append(
                    f"{tier}: {cls} count {cur['heavy'].get(cls, 0)} > "
                    f"budget {limit}")
        limit_b = budget.get("heavy_operand_bytes")
        if limit_b is not None and cur["heavy_operand_bytes"] > limit_b:
            fails.append(
                f"{tier}: heavy operand bytes "
                f"{cur['heavy_operand_bytes']} > budget {limit_b}")
    return fails


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="fail when any tier exceeds its budget")
    ap.add_argument("--lint", action="store_true",
                    help="run the jaxhound serving-path lints")
    ap.add_argument("--write", action="store_true",
                    help="refresh the budget file's 'post'+'budget' "
                         "columns from the current census")
    args = ap.parse_args()

    current = census_tiers()
    for tier, c in current.items():
        print(f"{tier:24s} heavy={c['heavy_total']:4d} "
              + " ".join(f"{k}={v}" for k, v in c["heavy"].items())
              + f" operand_MB={c['heavy_operand_bytes'] / 1e6:.2f}")

    rc = 0
    tel_report = telemetry_report()
    if tel_report:
        print(f"telemetry                lanes={tel_report['lanes']} "
              f"pack_ops={tel_report['pack_ops']} "
              f"body_delta={tel_report['chain_body_heavy_delta']}")
    if args.write:
        with open(BUDGET_PATH) as f:
            committed = json.load(f)
        committed["post"] = current
        committed["budget"] = {
            t: {"heavy_total": c["heavy_total"], "heavy": c["heavy"],
                "heavy_operand_bytes": c["heavy_operand_bytes"]}
            for t, c in current.items()}
        if tel_report:
            committed["telemetry"] = {
                "lanes": tel_report["lanes"],
                "pack_ops": tel_report["pack_ops"],
                "chain_body_heavy_delta_max": 0,
                # Measured wall-clock bound, enforced by the gate's
                # telemetry leg (testing/telemetry_smoke.py): fused
                # dispatch ms/window with telemetry on vs off.
                "overhead_ratio_max": committed.get(
                    "telemetry", {}).get("overhead_ratio_max", 1.10),
            }
        with open(BUDGET_PATH, "w") as f:
            json.dump(committed, f, indent=1)
        print(f"[opbudget] wrote {BUDGET_PATH}")
    if args.check:
        fails = check_budgets(current) + check_telemetry(tel_report)
        for f_ in fails:
            print(f"[opbudget] OVER BUDGET: {f_}")
        if fails:
            rc = 1
        else:
            print("[opbudget] within budget")
    if args.lint:
        fails = run_lints()
        for f_ in fails:
            print(f"[opbudget] LINT: {f_}")
        if fails:
            rc = 1
        else:
            print("[opbudget] lints clean")
    return rc


if __name__ == "__main__":
    sys.exit(main())
