"""Seeded device-fault injection for the TPU serving pipeline.

The VOPR proves the VSR/LSM layer under seeded cluster chaos; this
module is the same doctrine pointed at the SERVING path: a
deterministic `FaultPlan(seed)` injects device-state bit-flips,
dispatch failures/timeouts, poisoned delta fetches, forced fallback
storms, and (in the mesh scenario) shard loss — and the run is audited
end-to-end against the pure oracle. The acceptance bar is **zero
silent corruption**: for every injected fault the pipeline either
recovers to bit-exact oracle parity (authoritative history, full state,
mirror spot checks at 100% sampling) or fails loudly with the fault
attributed. Deterministic per seed; a failure reproduces with

    python -m tigerbeetle_tpu cfo --kind chaos --seed <seed>

Injection points (all at architectural boundaries, none inside a
kernel):

  state_bitflip     flip one bit of a digest-covered column of a live
                    device row between windows (HBM corruption model).
  dispatch_fail     raise TransientDispatchError at the dispatch
                    boundary, before the kernel runs (state untouched);
                    `count` <= retry budget exercises pure retry,
                    `count` > budget exercises recovery.
  dispatch_timeout  same, as DispatchTimeout (deadline model).
  poison_fetch      corrupt one value of a queued device->host delta
                    chunk (bad DMA model) — the mirror diverges from
                    both device and oracle and must be caught.
  fallback_storm    force the host-mirror regime for a stretch of
                    windows (every batch leaves the device): exactness
                    must hold and the storm must be a counted event.
  shard_loss        (mesh scenario) drop a mesh device; ShardedRouter
                    re-routes to the single-chip step bit-exactly.
  shard_resync      (mesh scenario) drop a mesh device under the
                    PARTITIONED router: the lost account range exists
                    nowhere else, so the router must refuse to serve
                    until a bounded oracle-replay resync rebuilds the
                    sharded state (`shard_resync` recovery cause). The
                    quarantine must also freeze the flight-recorder
                    ring into an on-disk artifact whose last record is
                    the failing window — asserted here.
"""

from __future__ import annotations

import json
import os
import random

import numpy as np

from ..oracle.state_machine import StateMachineOracle
from ..serving import (DispatchTimeout, RetryPolicy, ServingSupervisor,
                       TransientDispatchError)
from ..types import Account, Transfer, TransferFlags

FAULT_KINDS = ("state_bitflip", "dispatch_fail", "dispatch_timeout",
               "poison_fetch", "fallback_storm")

# Corruption-class faults MUST produce at least one recovery (silent
# survival would mean undetected corruption); dispatch faults below the
# retry budget legitimately resolve without one.
CORRUPTION_KINDS = frozenset({"state_bitflip", "poison_fetch"})


class ChaosDispatchFailure(TransientDispatchError):
    """Injected dispatch failure (seeded; state untouched)."""


class FaultPlan:
    """Deterministic per-seed fault schedule over a run's windows.

    `schedule[w]` is the fault descriptor injected around window `w`.
    The plan guarantees at least one fault per run (a chaos run that
    injects nothing proves nothing) and spreads kinds round-robin
    through a seed-shuffled deck so every kind appears across a small
    seed sweep."""

    def __init__(self, seed: int, n_windows: int, kinds=FAULT_KINDS,
                 fault_rate: float = 0.5):
        self.seed = seed
        self.rng = random.Random((seed * 0x9E3779B1 + 0xC8A05) & 0xFFFFFFFF)
        self.schedule: dict[int, dict] = {}
        self._deck: list[str] = []
        self._kinds = tuple(kinds)
        for w in range(n_windows):
            if self.rng.random() < fault_rate:
                self._add(w)
        if not self.schedule and n_windows:
            self._add(n_windows - 1)

    def _add(self, w: int) -> None:
        if not self._deck:
            self._deck = list(self._kinds)
            self.rng.shuffle(self._deck)
        kind = self._deck.pop()
        f = {"kind": kind, "window": w, "applied": False}
        if kind == "state_bitflip":
            f.update(target=self.rng.choice(
                ("accounts_u64", "accounts_bal", "transfers_u64")),
                row_pick=self.rng.randrange(1 << 30),
                col_pick=self.rng.randrange(1 << 30),
                bit=self.rng.randrange(64))
        elif kind in ("dispatch_fail", "dispatch_timeout"):
            # Sometimes within the retry budget (pure retry),
            # sometimes past it (forces replay recovery).
            f.update(count=self.rng.choice((1, 2, 4)), fired=0)
        elif kind == "poison_fetch":
            f.update(row_pick=self.rng.randrange(1 << 30),
                     bit=self.rng.randrange(32),
                     key=self.rng.choice(
                         ("amt_lo", "ud64", "code", "ledger")))
        elif kind == "fallback_storm":
            f.update(duration=self.rng.choice((1, 2, 3)))
        self.schedule[w] = f

    # ------------------------------------------------------ installation

    def dispatch_hook(self, win: int, what: str) -> None:
        """ServingSupervisor fault hook: wraps the jit dispatch — raises
        before the kernel call, so the device state is untouched."""
        if what != "window":
            return
        f = self.schedule.get(win)
        if not f or f["kind"] not in ("dispatch_fail", "dispatch_timeout"):
            return
        if f["fired"] >= f["count"]:
            return
        f["fired"] += 1
        f["applied"] = True
        if f["kind"] == "dispatch_timeout":
            raise DispatchTimeout(
                f"chaos seed {self.seed}: injected dispatch timeout "
                f"(window {win}, {f['fired']}/{f['count']})")
        raise ChaosDispatchFailure(
            f"chaos seed {self.seed}: injected dispatch failure "
            f"(window {win}, {f['fired']}/{f['count']})")

    def _reschedule(self, f: dict, win: int) -> None:
        """A fault found nothing to corrupt (no live rows / no queued
        delta yet): deterministically retry it one window later, unless
        that slot is taken or the run is over."""
        nxt = win + 1
        if nxt in self.schedule:
            return
        del self.schedule[win]
        f["window"] = nxt
        self.schedule[nxt] = f

    def apply_pre(self, sup: ServingSupervisor, win: int) -> None:
        """Between-window faults injected BEFORE window `win`."""
        f = self.schedule.get(win)
        if not f:
            return
        if f["kind"] == "state_bitflip":
            f["applied"] = inject_state_bitflip(sup.led, f)
            if not f["applied"]:
                self._reschedule(f, win)
        elif f["kind"] == "poison_fetch" and not f["applied"]:
            # The previous window's delta may still be queued (no epoch
            # check consumed it): poisoning pre-window works too.
            f["applied"] = poison_delta_fetch(sup.led, f)
        elif f["kind"] == "fallback_storm":
            led = sup.led
            if led._wt:
                # Force the host-mirror regime; the probe hysteresis
                # ends the storm after ~`duration` more mirror-routed
                # ops (the fast path then has to re-prove itself).
                led._hard_regime = True
                led._mirror_batches = max(
                    1, led.MIRROR_PROBE_INTERVAL - f["duration"])
                f["applied"] = True

    def apply_post(self, sup: ServingSupervisor, win: int) -> None:
        """Post-window faults (need the window's queued delta)."""
        f = self.schedule.get(win)
        if f and f["kind"] == "poison_fetch" and not f["applied"]:
            f["applied"] = poison_delta_fetch(sup.led, f)
            if not f["applied"]:
                self._reschedule(f, win)

    def summary(self) -> dict:
        out: dict = {}
        for f in self.schedule.values():
            key = f["kind"] + ("" if f["applied"] else "_skipped")
            out[key] = out.get(key, 0) + 1
        return out

    def applied(self, kinds=None) -> int:
        return sum(1 for f in self.schedule.values() if f["applied"]
                   and (kinds is None or f["kind"] in kinds))


# ------------------------------------------------------------- injectors

def inject_state_bitflip(led, f: dict) -> bool:
    """Flip one bit of a live, digest-covered cell of the device state
    pytree (the HBM-corruption model). Returns False when the chosen
    component has no live rows yet (nothing to corrupt)."""
    import jax.numpy as jnp

    from ..ops import state_epoch

    led.resolve_windows()
    st = led.state
    target = f["target"]
    comp = "accounts" if target.startswith("accounts") else "transfers"
    store = st[comp]
    count = int(store["count"])
    if count == 0:
        return False
    row = f["row_pick"] % count
    key = "bal" if target == "accounts_bal" else "u32"
    mat = store[key]
    # A store holds logical u64 column c as the u32 columns 2c (low
    # half) and 2c+1 (ev_layout).
    if target == "transfers_u64":
        cols = [j for j, m in enumerate(state_epoch.XF_COL_MASKS) if m]
    else:
        cols = list(range(mat.shape[1] // 2))
    col = cols[f["col_pick"] % len(cols)]
    bit64 = f["bit"] % 64
    c32 = 2 * col + bit64 // 32
    store[key] = mat.at[row, c32].set(
        mat[row, c32] ^ jnp.uint32(1 << (bit64 % 32)))
    f["where"] = f"{target}[{row},{col}] bit {f['bit'] % 64}"
    return True


def poison_delta_fetch(led, f: dict) -> bool:
    """Corrupt one value of the newest queued write-through delta chunk
    (the bad-DMA model): the mirror materializes the poisoned value and
    now disagrees with BOTH the device and the oracle — the spot audit
    or the epoch's mirror audit must catch it."""
    for t, e, der, t0, n_new, *_ in reversed(led._mirror_chunks):
        if not n_new or t is None:
            continue
        cols = t.load()
        key = f["key"]
        arr = np.array(cols[key], copy=True)
        row = f["row_pick"] % n_new
        arr[row] ^= arr.dtype.type(1 << (f["bit"] % (arr.dtype.itemsize * 8)))
        cols[key] = arr
        f["where"] = f"delta chunk rows {t0}..{t0 + n_new}, {key}[{row}]"
        return True
    return False


# ------------------------------------------------------------ chaos runs

def _chaos_workload(rng: random.Random, n_accounts: int, next_id: int,
                    n_events: int, open_pendings: list):
    """One batch of supervisor-servable transfers (plain + two-phase;
    balancing/imported tiers are covered by their own differential
    suites — chaos keeps the kernel-compile set small and pointed at
    the recovery machinery)."""
    F = TransferFlags
    events = []
    for _ in range(n_events):
        tid = next_id
        next_id += 1
        dr = rng.randrange(1, n_accounts + 1)
        cr = rng.randrange(1, n_accounts + 1)
        if cr == dr:
            cr = dr % n_accounts + 1
        roll = rng.random()
        if roll < 0.15:
            events.append(Transfer(
                id=tid, debit_account_id=dr, credit_account_id=cr,
                amount=rng.randrange(1, 1000), ledger=1, code=1,
                flags=int(F.pending), timeout=3600))
            open_pendings.append(tid)
        elif roll < 0.3 and open_pendings:
            pid = open_pendings.pop(0)
            post = rng.random() < 0.6
            events.append(Transfer(
                id=tid, pending_id=pid,
                amount=(1 << 128) - 1 if post else 0, ledger=1, code=1,
                flags=int(F.post_pending_transfer if post
                          else F.void_pending_transfer)))
        else:
            events.append(Transfer(
                id=tid, debit_account_id=dr, credit_account_id=cr,
                amount=rng.randrange(1, 1000), ledger=1, code=1))
    return events, next_id


# ------------------------------------------------- adversarial traffic

TRAFFIC_SHAPES = ("hot_skew", "pending_storm", "open_close_burst")


class TrafficShape:
    """Named adversarial traffic generator (ISSUE 18): a seeded,
    reproducible workload SHAPE that replaces the uniform chaos
    workload while the FaultPlan keeps injecting its fault classes
    around it — shapes and faults interleave, they do not exclude each
    other. Built on utils/zipfian.py (the reference's
    stdx.ZipfianGenerator):

    - ``hot_skew``: every debit/credit account drawn from a Zipfian
      with s=1.2 — a handful of accounts absorb almost all contention
      (the AT2 hot-account adversary).
    - ``pending_storm``: two-phase storm — the first half of the run
      floods two-phase PENDING transfers (growing the pending set),
      the second half bursts post/void resolutions of that backlog.
    - ``open_close_burst``: bursty open/close cycles — even windows
      open pendings in bulk, odd windows immediately post/void what
      the previous window opened.
    """

    def __init__(self, name: str, seed: int, n_accounts: int,
                 n_windows: int):
        from ..utils.zipfian import ZipfianGenerator

        assert name in TRAFFIC_SHAPES, name
        self.name = name
        self.n_accounts = n_accounts
        self.n_windows = max(1, n_windows)
        theta = 1.2 if name == "hot_skew" else 0.99
        self.zipf = ZipfianGenerator(n_accounts, theta=theta,
                                     seed=(seed * 0x9E3779B1) ^ 0x7A1F)

    def _pair(self):
        dr, cr = (int(v) + 1 for v in self.zipf.draw(2))
        if cr == dr:
            cr = dr % self.n_accounts + 1
        return dr, cr

    def batch(self, w: int, rng: random.Random, next_id: int,
              n_events: int, open_pendings: list):
        """One prepare's events under this shape (same contract as
        _chaos_workload). `w` is the window index — the storm/burst
        shapes phase on it."""
        F = TransferFlags
        events = []
        for _ in range(n_events):
            tid = next_id
            next_id += 1
            dr, cr = self._pair()
            if self.name == "hot_skew":
                kind = "pend" if rng.random() < 0.10 else "plain"
            elif self.name == "pending_storm":
                flood = w < self.n_windows // 2
                if flood:
                    kind = "pend" if rng.random() < 0.85 else "plain"
                else:
                    kind = "resolve" if (open_pendings
                                         and rng.random() < 0.85) \
                        else "plain"
            else:  # open_close_burst
                if w % 2 == 0:
                    kind = "pend"
                else:
                    kind = "resolve" if open_pendings else "plain"
            if kind == "pend":
                events.append(Transfer(
                    id=tid, debit_account_id=dr, credit_account_id=cr,
                    amount=rng.randrange(1, 1000), ledger=1, code=1,
                    flags=int(F.pending), timeout=3600))
                open_pendings.append(tid)
            elif kind == "resolve":
                pid = open_pendings.pop(0)
                post = rng.random() < 0.6
                events.append(Transfer(
                    id=tid, pending_id=pid,
                    amount=(1 << 128) - 1 if post else 0, ledger=1,
                    code=1,
                    flags=int(F.post_pending_transfer if post
                              else F.void_pending_transfer)))
            else:
                events.append(Transfer(
                    id=tid, debit_account_id=dr, credit_account_id=cr,
                    amount=rng.randrange(1, 1000), ledger=1, code=1))
        return events, next_id


def run_chaos_seed(seed: int, *, windows: int = 8,
                   batches_per_window: int = 2, events_per_batch: int = 48,
                   kinds=FAULT_KINDS, epoch_interval: int | None = None,
                   mesh_scenario: bool | None = None,
                   traffic: str | None = None,
                   tracer=None) -> dict:
    """One seed-deterministic audited chaos run against the serving
    supervisor. Raises on ANY silent corruption (the run must either
    recover to bit-exact oracle parity or have failed loudly already);
    returns a summary dict on success."""
    from .. import constants

    rng = random.Random(seed)
    if epoch_interval is None:
        epoch_interval = rng.choice((2, 3, 4))
    if mesh_scenario is None:
        # A steady minority of seeds also run the sharded-router loss
        # scenario (its kernel compile is the expensive part).
        mesh_scenario = rng.random() < 0.25
    was_verify = constants.VERIFY
    was_rate = os.environ.get("TB_VERIFY_SPOT_RATE")
    constants.set_verify(True)
    os.environ["TB_VERIFY_SPOT_RATE"] = "1.0"  # audit every drained row
    try:
        summary = _run_supervisor_chaos(
            seed, rng, windows, batches_per_window, events_per_batch,
            kinds, epoch_interval, tracer, traffic=traffic)
        if mesh_scenario:
            summary["shard_loss"] = shard_loss_scenario(seed)
            summary["shard_resync"] = shard_resync_scenario(seed)
            summary["reshard"] = reshard_chaos_scenario(seed)
    finally:
        constants.set_verify(was_verify)
        if was_rate is None:
            os.environ.pop("TB_VERIFY_SPOT_RATE", None)
        else:
            os.environ["TB_VERIFY_SPOT_RATE"] = was_rate
    return summary


def _run_supervisor_chaos(seed, rng, windows, batches_per_window,
                          events_per_batch, kinds, epoch_interval,
                          tracer=None, traffic: str | None = None) -> dict:
    n_accounts = 16
    shape = (TrafficShape(traffic, seed, n_accounts, windows)
             if traffic else None)
    sup = ServingSupervisor(
        a_cap=1 << 9, t_cap=1 << 12, epoch_interval=epoch_interval,
        retry=RetryPolicy(max_retries=2, base_delay_s=1e-3,
                          max_delay_s=4e-3, deadline_s=30.0),
        seed=seed, mirror_audit="full", sleep=lambda s: None,
        tracer=tracer)
    plan = FaultPlan(seed, windows, kinds=kinds)
    sup.fault_hook = plan.dispatch_hook

    script: list = []  # the full run, for the independent end audit
    accounts = [Account(id=i, ledger=1, code=1)
                for i in range(1, n_accounts + 1)]
    ts = 1_000
    sup.create_accounts(accounts, ts)
    script.append(("accounts", accounts, ts))

    next_id = 1_000
    open_pendings: list[int] = []
    ts = 10 ** 9
    for w in range(windows):
        plan.apply_pre(sup, w)
        batches, tss = [], []
        for _ in range(batches_per_window):
            if shape is not None:
                events, next_id = shape.batch(
                    w, rng, next_id, events_per_batch, open_pendings)
            else:
                events, next_id = _chaos_workload(
                    rng, n_accounts, next_id, events_per_batch,
                    open_pendings)
            ts += len(events) + 10
            batches.append(events)
            tss.append(ts)
        sup.create_transfers_window(batches, tss)
        script.append(("window", batches, tss))
        plan.apply_post(sup, w)
    sup.verify_epoch()  # final epoch: everything verified or recovered

    # ---- the independent audit: a clean oracle replay of the whole run
    audit = StateMachineOracle()
    expected: list = []
    for kind, payload, when in script:
        if kind == "accounts":
            expected.append([(r.timestamp, int(r.status))
                             for r in audit.create_accounts(payload, when)])
        else:
            expected.append([
                [(r.timestamp, int(r.status))
                 for r in audit.create_transfers(b, bts)]
                for b, bts in zip(payload, when)])
    assert sup.history == expected, \
        f"chaos seed {seed}: authoritative history diverged from oracle"
    host = sup.led.to_host()
    for field in ("accounts", "transfers", "pending_status", "orphaned",
                  "expiry", "account_events"):
        assert getattr(host, field) == getattr(audit, field), \
            f"chaos seed {seed}: device state diverged on {field}"
    # Zero silent corruption: every applied corruption-class fault must
    # have produced at least one detected recovery.
    n_corruptions = plan.applied(CORRUPTION_KINDS)
    recoveries = sum(sup.counters["recoveries"].values())
    assert n_corruptions == 0 or recoveries >= 1, \
        (f"chaos seed {seed}: {n_corruptions} corruption fault(s) "
         f"injected but zero recoveries — silent corruption")
    return dict(seed=seed, windows=windows,
                epoch_interval=epoch_interval,
                traffic=traffic,
                faults=plan.summary(),
                recoveries=dict(sup.counters["recoveries"]),
                retries=sup.counters["retries"],
                backoff_s=sup.counters["backoff_s"],
                replayed_windows=sup.counters["replayed_windows"],
                epochs_verified=sup.counters["epochs_verified"],
                checksum_mismatches=sup.counters["checksum_mismatches"],
                audited_ops=len(expected))


# ------------------------------------------------- shard-loss scenario

_SHARD_ROUTER = None


def shard_loss_scenario(seed: int, mesh=None) -> dict:
    """Drop a mesh device mid-run: ShardedRouter must re-route to the
    single-chip step with bit-exact results, count the reroutes, and
    route back after restore. Runs on whatever devices exist (a 1-chip
    CPU mesh degenerates gracefully); the router (and its compiled
    steps) is cached across seeds."""
    global _SHARD_ROUTER
    import jax
    from jax.sharding import Mesh

    from ..ops.batch import transfers_to_arrays
    from ..ops.ledger import DeviceLedger, pad_transfer_events
    from ..parallel.full_sharded import ShardedRouter, shard_batch

    rng = random.Random(seed ^ 0x5AFE)
    if mesh is not None:
        router = ShardedRouter(mesh)  # caller-owned mesh: no caching
    else:
        if _SHARD_ROUTER is None:
            _SHARD_ROUTER = ShardedRouter(
                Mesh(np.array(jax.devices()), ("batch",)))
        router = _SHARD_ROUTER
    mesh = router.mesh
    router.restore_devices()
    reroutes0 = router.shard_loss_reroutes

    n_accounts = 12
    accounts = [Account(id=i, ledger=1, code=1)
                for i in range(1, n_accounts + 1)]
    led = DeviceLedger(a_cap=1 << 8, t_cap=1 << 11)
    led.create_accounts(accounts, 1_000)
    oracle = StateMachineOracle()
    oracle.create_accounts(accounts, 1_000)
    state = led.state
    led.state = None  # the router owns (and donates) the state now

    ts = 10 ** 9
    next_id = 10_000
    dropped = None
    for step_i in range(4):
        if step_i == 1:
            dropped = mesh.devices.flat[rng.randrange(mesh.size)]
            router.drop_device(dropped)
        if step_i == 3:
            router.restore_devices()
        events = []
        for _ in range(24):
            dr = rng.randrange(1, n_accounts + 1)
            cr = dr % n_accounts + 1
            events.append(Transfer(
                id=next_id, debit_account_id=dr, credit_account_id=cr,
                amount=rng.randrange(1, 100), ledger=1, code=1))
            next_id += 1
        n = len(events)
        ts += n + 10
        evp = pad_transfer_events(transfers_to_arrays(events), 1024)
        evp = shard_batch(mesh, evp)
        state, out, fell = router.step(state, evp, ts, n)
        assert not fell, f"chaos seed {seed}: unexpected shard fallback"
        got = [(int(t), int(s)) for s, t in zip(
            np.asarray(out["r_status"][:n]).tolist(),
            np.asarray(out["r_ts"][:n]).tolist())]
        want = [(r.timestamp, int(r.status))
                for r in oracle.create_transfers(events, ts)]
        assert got == want, \
            (f"chaos seed {seed}: shard-loss step {step_i} diverged "
             f"(lost={sorted(map(str, router.lost_devices))})")
    reroutes = router.shard_loss_reroutes - reroutes0
    assert reroutes == 2, reroutes  # exactly the degraded steps
    return dict(devices=int(mesh.size), dropped=str(dropped),
                reroutes=reroutes)


# --------------------------------------------- partitioned resync scenario

_PART_ROUTER = None


def shard_resync_scenario(seed: int, mesh=None) -> dict:
    """Drop a mesh device under the PARTITIONED router (sharded state):
    the single-chip reroute is structurally unavailable — the lost
    shard's account range exists nowhere else — so the router must (a)
    refuse to serve while a shard is lost, and (b) recover to bit-exact
    oracle parity through resync(oracle), counted under the
    `shard_resync` recovery cause. The router and its compiled steps
    are cached across seeds."""
    global _PART_ROUTER
    import jax
    from jax.sharding import Mesh

    from ..ops.batch import transfers_to_arrays
    from ..ops.ledger import pad_transfer_events
    from ..parallel.partitioned import PartitionedRouter

    rng = random.Random(seed ^ 0xCAFE)
    if mesh is not None:
        router = PartitionedRouter(mesh, a_cap=1 << 9, t_cap=1 << 11)
    else:
        if _PART_ROUTER is None:
            _PART_ROUTER = PartitionedRouter(
                Mesh(np.array(jax.devices()), ("batch",)),
                a_cap=1 << 9, t_cap=1 << 11)
        router = _PART_ROUTER
    mesh = router.mesh
    router.restore_devices()
    resyncs0 = router.shard_resyncs
    fallbacks0 = router.host_fallbacks

    n_accounts = 12
    accounts = [Account(id=i, ledger=1, code=1)
                for i in range(1, n_accounts + 1)]
    oracle = StateMachineOracle()
    oracle.create_accounts(accounts, 1_000)
    state = router.from_oracle(oracle)

    ts = 10 ** 9
    next_id = 10_000
    dropped = None
    for step_i in range(4):
        events = []
        for _ in range(24):
            dr = rng.randrange(1, n_accounts + 1)
            cr = dr % n_accounts + 1
            events.append(Transfer(
                id=next_id, debit_account_id=dr, credit_account_id=cr,
                amount=rng.randrange(1, 100), ledger=1, code=1))
            next_id += 1
        n = len(events)
        ts += n + 10
        evp = pad_transfer_events(transfers_to_arrays(events), 1024)
        if step_i == 1:
            dropped = mesh.devices.flat[rng.randrange(mesh.size)]
            window_at_loss = router._window_seq
            dumps0 = router.flight.dumps
            router.drop_device(dropped)
            # Quarantine is a flight-recorder dump point: the artifact
            # must exist on disk and its LAST record must be the
            # quarantine marker for the failing window — the post-mortem
            # contract the recorder exists for.
            assert router.flight.dumps == dumps0 + 1
            flight_path = router.flight.last_dump_path
            assert flight_path and os.path.exists(flight_path), \
                (f"chaos seed {seed}: quarantine produced no flight "
                 f"artifact (path={flight_path!r})")
            with open(flight_path) as f:
                flight_doc = json.load(f)
            assert flight_doc["reason"] == "shard_loss_quarantine"
            last = flight_doc["records"][-1]
            assert last["route"] == "quarantined", last
            assert last["window"] == window_at_loss, \
                (last["window"], window_at_loss)
            # A lost range is NOT servable: the quarantine must be loud.
            try:
                router.step(state, evp, ts, n)
            except RuntimeError:
                pass
            else:
                raise AssertionError(
                    f"chaos seed {seed}: partitioned router served "
                    "with a lost shard")
            state = router.resync(oracle)
            assert router.flight.dumps == dumps0 + 2  # resync dumps too
        state, out, fell = router.step(state, evp, ts, n)
        assert not fell, \
            f"chaos seed {seed}: unexpected partitioned fallback"
        got = [(int(t), int(s)) for s, t in zip(
            np.asarray(out["r_status"][:n]).tolist(),
            np.asarray(out["r_ts"][:n]).tolist())]
        want = [(r.timestamp, int(r.status))
                for r in oracle.create_transfers(events, ts)]
        assert got == want, \
            (f"chaos seed {seed}: partitioned step {step_i} diverged "
             f"after resync (dropped={dropped})")
    resyncs = router.shard_resyncs - resyncs0
    assert resyncs == 1, resyncs
    assert router.host_fallbacks == fallbacks0, "resync run fell back"
    return dict(devices=int(mesh.size), dropped=str(dropped),
                resyncs=resyncs,
                flight_dump=os.path.basename(flight_path))


# --------------------------------------------- elastic-reshard scenario

_RESHARD_ROUTER = None


def reshard_chaos_scenario(seed: int, mesh=None) -> dict:
    """Fault the five-stage elastic-shard handoff at every stage it can
    die in (ISSUE 19): crash (SIGKILL analog — the supervisor's
    recovery path: revert overlay, rebuild from the verified oracle)
    right after the snapshot, mid-copy, and under double-write; shard
    LOSS of the source and of the target mid-copy (quarantine must be
    loud, then the same recovery); a bit-corrupted chunk that must
    abort PRE-FLIP on the digest witness; and a crash after a completed
    flip (the MIGRATED override must survive the rebuild). Every abort
    leaves serving bit-exact vs the never-resharded oracle and freezes
    a FLIGHT_*_reshard_* artifact. The router and its compiled steps
    are cached across seeds."""
    global _RESHARD_ROUTER
    import glob as _glob
    import tempfile

    import jax
    from jax.sharding import Mesh

    from ..ops.batch import transfers_to_arrays
    from ..ops.state_epoch import (partitioned_oracle_digest,
                                   partitioned_state_digest)
    from ..parallel.partitioned import PartitionedRouter
    from ..parallel.resharding import (MigrationAborted,
                                       ReshardController, ReshardPlan)
    from ..parallel.shard_utils import OVERLAY_MIGRATED

    if mesh is None and len(jax.devices()) < 2:
        return {"skipped": "needs >= 2 devices"}
    rng = random.Random(seed ^ 0xE5A)
    a_cap = 1 << 9
    if mesh is not None:
        router = PartitionedRouter(mesh, a_cap=a_cap, t_cap=1 << 11)
    else:
        if _RESHARD_ROUTER is None:
            _RESHARD_ROUTER = PartitionedRouter(
                Mesh(np.array(jax.devices()[:2]), ("batch",)),
                a_cap=a_cap, t_cap=1 << 11)
        router = _RESHARD_ROUTER
    router.restore_devices()
    if router.ownership.entries:
        # The cached router may carry a MIGRATED override from the
        # previous seed's completed migration: base ownership again.
        from ..parallel.shard_utils import OwnershipTable
        router.set_ownership(OwnershipTable(
            router.n_shards, router.ownership.generation + 1, ()))
    mesh = router.mesh
    fallbacks0 = router.host_fallbacks

    n_accounts = 16
    oracle = StateMachineOracle()
    oracle.create_accounts([Account(id=i, ledger=1, code=1)
                            for i in range(1, n_accounts + 1)], 1_000)
    state = router.from_oracle(oracle)
    ctl = ReshardController(router, chunk_rows=4,
                            min_double_write_windows=2)
    plan = ReshardPlan(lo=0, hi=(1 << 63) - 1, src=0, dst=1,
                       kind="split")

    flight_dir = tempfile.mkdtemp(prefix=f"tb_reshard_chaos_{seed}_")
    was_dir = os.environ.get("TB_TPU_FLIGHT_DIR")
    os.environ["TB_TPU_FLIGHT_DIR"] = flight_dir

    nid, ts = [50_000], [10 ** 9]

    def drive(k):
        """k windows of live traffic; every batch bit-exact vs the
        never-resharded oracle; a digest-mismatch abort is adopted."""
        nonlocal state
        aborted = None
        for _ in range(k):
            obj_batches, batches, tss = [], [], []
            for _b in range(2):
                evs = []
                for _i in range(8):
                    dr = rng.randrange(1, n_accounts + 1)
                    cr = dr % n_accounts + 1
                    evs.append(Transfer(
                        id=nid[0], debit_account_id=dr,
                        credit_account_id=cr,
                        amount=rng.randrange(1, 50), ledger=1, code=1))
                    nid[0] += 1
                ts[0] += 300
                obj_batches.append(evs)
                batches.append(transfers_to_arrays(evs))
                tss.append(ts[0])
            try:
                state = ctl.on_window(state, batches)
            except MigrationAborted as e:
                state = e.state
                aborted = e
            state, results = router.step_window(state, batches, tss)
            for evs, t, (st_a, ts_a) in zip(obj_batches, tss, results):
                want = [(r.timestamp, int(r.status))
                        for r in oracle.create_transfers(evs, t)]
                got = [(int(ts_a[i]), int(st_a[i]))
                       for i in range(len(evs))]
                assert got == want, \
                    (f"reshard chaos seed {seed}: history diverged "
                     f"post-fault", got[:4], want[:4])
        return aborted

    def artifacts():
        return _glob.glob(os.path.join(flight_dir,
                                       "FLIGHT_*_reshard_*"))

    def crash():
        """The supervisor's recovery path for a crash mid-migration."""
        nonlocal state
        ctl.on_recovery()
        router.restore_devices()
        state = router.resync(oracle)

    faults = []
    try:
        drive(1)  # warm traffic

        # 1. crash right after the SNAPSHOT (stage: copy, cursor 0).
        state = ctl.begin(state, plan)
        assert ctl.stage == "copy", ctl.stage
        crash()
        faults.append("crash_snapshot")
        drive(1)

        # 2. crash MID-COPY (cursor advanced, nothing flipped).
        state = ctl.begin(state, plan)
        state = ctl.on_window(state)  # one quiesced chunk, no traffic
        assert ctl.stage == "copy", ctl.stage
        crash()
        faults.append("crash_mid_copy")
        drive(1)

        # 3+4. shard LOSS of the source and of the target mid-copy:
        # quarantine refuses to serve, then the crash recovery runs.
        for lost_shard, tag in ((plan.src, "loss_source"),
                                (plan.dst, "loss_target")):
            state = ctl.begin(state, plan)
            state = ctl.on_window(state)
            router.drop_device(mesh.devices.flat[lost_shard])
            try:
                router.step_window(state, *_one_window(rng, n_accounts,
                                                       nid, ts))
            except RuntimeError:
                pass
            else:
                raise AssertionError(
                    f"reshard chaos seed {seed}: served with lost "
                    f"shard {lost_shard} mid-copy")
            crash()
            faults.append(tag)
            drive(1)

        # 5. crash under DOUBLE-WRITE (overlay entry live, pre-flip).
        state = ctl.begin(state, plan)
        guard = 0
        while ctl.stage == "copy":
            state = ctl.on_window(state)
            guard += 1
            assert guard < 64, ctl.stage
        assert ctl.stage == "double_write", ctl.stage
        crash()
        assert router.ownership.entries == (), router.ownership.entries
        faults.append("crash_double_write")
        drive(1)

        # 6. bit-corrupted chunk: the digest witness must abort the
        # flip, revert the overlay, and keep serving bit-exact.
        state = ctl.begin(state, plan)
        ctl.corrupt_next_chunk = True
        aborted, guard = None, 0
        while aborted is None:
            aborted = drive(1)
            guard += 1
            assert guard < 64, "corrupted copy never aborted"
            assert ctl.stage != "done", \
                f"seed {seed}: flip went through on a corrupted copy"
        assert aborted.reason == "digest_mismatch", aborted.reason
        faults.append("digest_mismatch")
        drive(1)

        # 7. clean migration, then crash AFTER the flip: the MIGRATED
        # override is the collapsed base override and must survive the
        # oracle rebuild.
        state = ctl.begin(state, plan)
        guard = 0
        while ctl.stage != "done":
            drive(1)
            guard += 1
            assert guard < 64, ctl.stage
        entries = router.ownership.entries
        assert len(entries) == 1 and entries[0][4] == OVERLAY_MIGRATED
        crash()  # no-op on the idle controller; rebuild honors overlay
        assert router.ownership.entries == entries
        faults.append("crash_post_flip")
        drive(2)

        n_arts = len(artifacts())
        # Every abort froze an artifact: five crashes/losses + the
        # digest mismatch (the post-flip crash aborts nothing).
        assert len(ctl.aborts) == 6, ctl.aborts
        assert n_arts >= 6, (n_arts, os.listdir(flight_dir))
        assert len(ctl.migrations) == 1, ctl.migrations
        dd = partitioned_state_digest(state)
        want = partitioned_oracle_digest(
            oracle, a_cap, router.n_shards,
            overlay=router.ownership.entries)
        assert dd == want, f"seed {seed}: final digest diverged"
        assert router.host_fallbacks == fallbacks0
    finally:
        if was_dir is None:
            os.environ.pop("TB_TPU_FLIGHT_DIR", None)
        else:
            os.environ["TB_TPU_FLIGHT_DIR"] = was_dir
        # The cached router must come back clean for the next seed.
        if router.ownership.entries:
            from ..parallel.shard_utils import OwnershipTable
            router.set_ownership(OwnershipTable(
                router.n_shards, router.ownership.generation + 1, ()))
    return dict(devices=int(mesh.size), faults=faults,
                aborts=len(ctl.aborts), artifacts=n_arts,
                migrations=len(ctl.migrations))


def _one_window(rng, n_accounts, nid, ts):
    """One throwaway window (batches, tss) for the quarantine probe."""
    from ..ops.batch import transfers_to_arrays

    evs = []
    for _i in range(8):
        dr = rng.randrange(1, n_accounts + 1)
        evs.append(Transfer(id=nid[0], debit_account_id=dr,
                            credit_account_id=dr % n_accounts + 1,
                            amount=1, ledger=1, code=1))
        nid[0] += 1
    ts[0] += 300
    return [transfers_to_arrays(evs)], [ts[0]]


# ------------------------------------------------------------- CI gate

GATE_SEEDS = (1, 2, 3, 7)


def gate_main(seeds=GATE_SEEDS) -> int:
    """scripts/gate.py entry: the fixed chaos seed set that keeps the
    recovery path from rotting. One process, shared jit caches."""
    failures = 0
    for seed in seeds:
        try:
            s = run_chaos_seed(int(seed))
            print(f"[chaos] seed {seed} ok: faults={s['faults']} "
                  f"recoveries={s['recoveries']} "
                  f"epochs={s['epochs_verified']}", flush=True)
        except Exception as e:  # noqa: BLE001 — the gate wants ALL reds
            failures += 1
            print(f"[chaos] seed {seed} FAILED: {e!r}\n  reproduce: "
                  f"python -m tigerbeetle_tpu cfo --kind chaos "
                  f"--seed {seed}", flush=True)
    return 1 if failures else 0
