"""Serving supervisor: chaos-hardened orchestration around DeviceLedger.

The VSR/LSM layer already treats faults as repairable events (checksums
detect, peers heal, the VOPR proves it under a seed). This module gives
the TPU serving path the same property, in three parts:

1. **Bounded retry with backoff** — every device dispatch runs under a
   retry policy (exponential backoff with seeded jitter, a bounded
   attempt count, and a per-window deadline checked between attempts).
   Transient dispatch faults (`TransientDispatchError`, the class the
   chaos harness injects at the dispatch boundary) retry; exhaustion
   escalates to recovery instead of crashing or silently dropping the
   window.

2. **Verified state epochs** — every `epoch_interval` windows the
   supervisor quiesces the pipeline (resolve + drain), replays the
   epoch's logged inputs through the ORACLE engine (the pure-Python
   exact semantics — unreachable by device corruption), and checks
   three invariants: (a) the device-returned results match the oracle
   replay bit-for-bit, (b) the on-device state digest
   (ops/state_epoch.py — one tiny jitted fold, never part of a serving
   lowering) matches the digest of the replayed oracle state, and
   (c) the write-through mirror matches the replayed oracle object for
   object. A clean epoch advances the verified base (the replayed
   oracle IS the next epoch's replay source, so verification costs no
   extra snapshotting); any divergence quarantines the device state.

3. **Bounded replay recovery** — on quarantine (digest mismatch, result
   divergence, mirror divergence, retry exhaustion), the supervisor
   replays AT MOST the windows since the last verified epoch (asserted)
   through the oracle, revises the authoritative result history with
   the oracle's answers, rebuilds a fresh mirror + device state from
   the recovered oracle (`from_host`, the same path a restart takes),
   and resumes kernel serving. Per-cause recovery counters surface
   through `DeviceLedger.fallback_stats()["recovery"]`.

Fault model, detection latency, and the reproduction workflow are
documented in ARCHITECTURE.md ("Fault model & recovery"); the seeded
injection harness lives in testing/chaos.py and runs as
``python -m tigerbeetle_tpu cfo --kind chaos --seed <seed>``.
"""

from __future__ import annotations

import copy
import dataclasses
import random
import time
from dataclasses import dataclass

from .ops.ledger import DeviceLedger, MirrorDivergence, default_recovery_stats
from .oracle.state_machine import StateMachineOracle
from .trace import Event, FlightRecorder, NullTracer, fmt_trace_id


class TransientDispatchError(RuntimeError):
    """A device dispatch failed in a way worth retrying (the chaos
    harness's injected dispatch failures subclass this; a real backend
    wrapper would translate transient PJRT errors into it)."""


class DispatchTimeout(TransientDispatchError):
    """A dispatch exceeded its deadline (injected or wrapped)."""


class RecoveryNeeded(RuntimeError):
    """Internal escalation: the serving pipeline must quarantine device
    state and replay from the last verified epoch."""

    def __init__(self, cause: str, detail: str = ""):
        super().__init__(cause + (f": {detail}" if detail else ""))
        self.cause = cause
        self.detail = detail


@dataclass
class RetryPolicy:
    """Bounded-retry parameters for one device dispatch. Backoff is
    exponential from base_delay_s, capped at max_delay_s, with
    multiplicative seeded jitter in [1, 1+jitter); deadline_s bounds the
    whole attempt sequence (checked between attempts — a dispatch
    blocked inside the runtime cannot be preempted, only not retried)."""

    max_retries: int = 3
    base_delay_s: float = 0.01
    max_delay_s: float = 1.0
    deadline_s: float = 30.0
    jitter: float = 0.25

    def delay_s(self, attempt: int, rng: random.Random) -> float:
        base = min(self.max_delay_s,
                   self.base_delay_s * (2.0 ** max(0, attempt - 1)))
        return base * (1.0 + self.jitter * rng.random())

    def clamped(self, deadline_s: float | None) -> "RetryPolicy":
        """This policy with deadline_s tightened to a caller's remaining
        admission budget — never loosened. The admission plane threads a
        request's remaining deadline through the window dispatch so the
        whole retry sequence (attempts + backoff sleeps) is bounded by
        the budget the request was admitted under, instead of the
        policy's static per-window deadline."""
        if deadline_s is None or deadline_s >= self.deadline_s:
            return self
        return dataclasses.replace(self, deadline_s=max(0.0, deadline_s))


# Structural faults while consuming device-produced bytes (the drain
# materializes fetched delta chunks into the mirror): an unknown
# account/transfer id, an invalid enum code, or a bad index there is
# DETECTED corruption — corrupted device rows fed the chunk — so it
# routes to quarantine+replay, never to a retry or a raw crash.
_STRUCTURAL_FAULTS = (KeyError, IndexError, ValueError)


def call_with_retries(fn, policy: RetryPolicy, rng: random.Random,
                      counters: dict, *, sleep=time.sleep,
                      clock=time.monotonic, tracer=None):
    """Run `fn()` under `policy`. Transient faults retry with backoff;
    exhaustion (attempts or deadline) raises RecoveryNeeded, as do a
    MirrorDivergence and the structural drain faults (retrying cannot
    fix divergent state). Counters accumulate into the shared
    recovery-stats dict."""
    if tracer is None:
        tracer = NullTracer()
    t0 = clock()
    attempt = 0
    while True:
        try:
            return fn()
        except MirrorDivergence as e:
            raise RecoveryNeeded("mirror_divergence", str(e)) from e
        except _STRUCTURAL_FAULTS as e:
            raise RecoveryNeeded("drain_fault", repr(e)) from e
        except TransientDispatchError as e:
            attempt += 1
            counters["retries"] += 1
            tracer.count(Event.serving_retries)
            if attempt > policy.max_retries:
                raise RecoveryNeeded(
                    "dispatch_exhausted",
                    f"{attempt} attempts: {e!r}") from e
            remaining = policy.deadline_s - (clock() - t0)
            if remaining <= 0:
                raise RecoveryNeeded(
                    "dispatch_deadline",
                    f"deadline {policy.deadline_s}s: {e!r}") from e
            # The backoff sleep itself is capped by the remaining
            # deadline budget: under saturation, exponential backoff
            # must not stack the attempt sequence past the deadline the
            # caller (per-window or admission) is holding the line on.
            delay = min(policy.delay_s(attempt, rng), remaining)
            counters["backoff_s"] = round(
                counters["backoff_s"] + delay, 6)
            sleep(delay)


class ServingSupervisor:
    """Owns a write-through DeviceLedger and supervises its serving
    loop: retries, verified epochs, and bounded replay recovery.

    The caller submits Transfer/Account OBJECT batches (the supervisor
    keeps them as the epoch's replayable log); device dispatch uses the
    ledger's array paths underneath. `history` is the authoritative
    normalized result record — one entry per submitted op, revised with
    the oracle's answers whenever a recovery replays a suffix."""

    def __init__(self, a_cap: int = 1 << 17, t_cap: int = 1 << 21, *,
                 epoch_interval: int = 8, retry: RetryPolicy | None = None,
                 seed: int = 0, mirror_audit: str = "full",
                 fault_hook=None, sleep=time.sleep, tracer=None,
                 flight_recorder=None, pipeline_depth: int = 2,
                 profiler=None, memwatch=None, alert_engine=None):
        assert mirror_audit in ("full", "spot", "off")
        self.tracer = tracer if tracer is not None else NullTracer()
        # Flight recorder: every window's route decision and every
        # verified epoch digest ring here; any recovery — including
        # retry exhaustion (dispatch_exhausted / dispatch_deadline) —
        # freezes the ring into a post-mortem artifact.
        self.flight = flight_recorder if flight_recorder is not None \
            else FlightRecorder(tracer=self.tracer)
        # Performance observatory (ISSUE 20): all three hooks are
        # optional and None by default — the unobserved serving path
        # pays nothing. The profiler samples window dispatches, the
        # memwatch ticks at every verified epoch (the natural quiesce
        # point), and the alert engine ticks once per committed window
        # in the same tracer + flight-recorder universe as everything
        # else (a page-severity firing dumps OUR flight ring).
        self.profiler = profiler
        self.memwatch = memwatch
        self.alert_engine = alert_engine
        if alert_engine is not None:
            alert_engine.bind(self.tracer, self.flight)
        self.a_cap = a_cap
        self.t_cap = t_cap
        self.epoch_interval = epoch_interval
        self.retry = retry or RetryPolicy()
        self.rng = random.Random(seed)
        self.mirror_audit = mirror_audit
        # Chaos-injection point: called as hook(window_index, what) at
        # every dispatch attempt; raising TransientDispatchError /
        # DispatchTimeout injects a dispatch fault (testing/chaos.py).
        self.fault_hook = fault_hook
        self._sleep = sleep
        self.counters = default_recovery_stats()
        # The last VERIFIED epoch's state: a pure oracle advanced only
        # by replaying logged inputs — device corruption cannot reach
        # it. After each clean epoch it equals the live state.
        self.epoch_base = StateMachineOracle()
        self.log: list = []       # ops since the last verified epoch
        self.history: list = []   # normalized results, one per op ever
        self.last_recovery: dict | None = None
        self._windows_since_epoch = 0
        self.windows_total = 0
        # Overlapped serving (submit_transfers_window): in-flight
        # pipelined window records, oldest first. pipeline_depth bounds
        # how many stay unresolved — at depth the oldest resolves
        # before the next submit, and window k+1's host staging (the
        # ledger's background stager) overlaps exactly that blocking
        # resolve plus the in-flight dispatch. The synchronous
        # create_transfers_window path never populates this.
        self.pipeline_depth = max(1, int(pipeline_depth))
        self._pending: list = []
        # Trace ids of requests whose windows landed since the last
        # verified epoch: a recovery affects exactly these requests, so
        # tail retention force-keeps them (ISSUE 15) and the flight
        # artifact names them for cross-reference.
        self._epoch_trace_ids: list[str] = []
        # Elastic shards (ISSUE 19): set by attach_partitioned — the
        # sharded-state backend's router and the live-resharding
        # controller whose migrations interleave with commit windows.
        self.part_router = None
        self.resharder = None
        self._attach(DeviceLedger(a_cap, t_cap,
                                  write_through=StateMachineOracle()))

    def _attach(self, led: DeviceLedger) -> None:
        self.led = led
        # The ledger surfaces OUR counters through fallback_stats(),
        # next to the fallback causes.
        led.recovery_stats = self.counters
        # And OUR tracer flows down so window_stage spans + the
        # host-stall gauge land in the same catalog as everything else.
        led.tracer = self.tracer

    def attach_partitioned(self, router):
        """Switch serving to the partitioned (sharded-state) backend:
        a fresh un-mirrored DeviceLedger in attach mode over `router`,
        seeded from the current verified epoch base, plus a
        ReshardController for live migrations (driven by `reshard()`
        and the per-window tick). The write-through mirror does not
        exist in attach mode, so the epoch check's mirror audit is
        disabled; result parity and the sharded state digest remain.
        Create accounts BEFORE attaching (the epoch base seeds the
        sharded state). Returns the controller."""
        from .parallel.resharding import ReshardController

        # Fold the open log into the verified base first — the sharded
        # state is seeded from it, so anything still un-verified would
        # silently vanish from the new backend.
        self.verify_epoch()
        self.led.shutdown_staging()
        self.mirror_audit = "off"
        router.tracer = self.tracer
        router.flight = self.flight
        self.part_router = router
        led = DeviceLedger(self.a_cap, self.t_cap)
        led.attach_partitioned(router,
                               router.from_oracle(self.epoch_base))
        self._attach(led)
        self.resharder = ReshardController(router, tracer=self.tracer)
        return self.resharder

    def reshard(self, plan) -> None:
        """Begin a live migration (parallel/resharding.ReshardPlan).
        The snapshot is taken at a VERIFIED epoch — verify_epoch()
        quiesces, replays the log, and proves the digests first, so the
        frozen range is witness-backed and the epoch base can vouch for
        the copy (oracle digest leg + the range's ring rows). The
        migration then advances one copy chunk per submitted window
        (conflicting windows drain it), double-writes, and flips at a
        later window boundary; MigrationAborted propagates to the
        caller with ownership already reverted."""
        from .parallel.resharding import MigrationAborted

        assert self.part_router is not None, \
            "attach_partitioned() first"
        assert not self.resharder.active, "migration already in flight"
        self.verify_epoch()
        led = self.led
        try:
            led._part_state = self.resharder.begin(
                led.partitioned_state, plan, oracle=self.epoch_base)
        except MigrationAborted:
            # begin aborts before staging anything on device: the
            # artifact is frozen, ownership untouched, serving intact.
            raise

    def _reshard_tick(self, batches) -> None:
        """The per-window migration tick (both window paths call this
        BEFORE dispatching): quiesce the pipeline while a migration is
        active and advance it one step at this window boundary. An
        abort here is survivable by construction — ownership reverted,
        staged copy evicted — so serving continues on the pre-migration
        owner and the abort surfaces through the controller's records
        and the flight artifact rather than failing the window."""
        from .parallel.resharding import MigrationAborted

        ctl = self.resharder
        if ctl is None or not ctl.active:
            return
        self.drain_pipeline()
        self.led.resolve_windows()
        led = self.led
        try:
            led._part_state = ctl.on_window(led.partitioned_state,
                                            batches)
        except MigrationAborted as e:
            led._part_state = e.state

    # ------------------------------------------------------------ serving

    def create_accounts(self, accounts: list, timestamp: int):
        accounts = list(accounts)
        res = self._dispatch(
            lambda: self.led.create_accounts(accounts, timestamp),
            what="create_accounts")
        norm = [(r.timestamp, int(r.status)) for r in res]
        self.log.append(("accounts", accounts, timestamp))
        self.history.append(norm)
        return res

    def create_transfers_window(self, batches: list, timestamps: list,
                                trace_ctxs: list | None = None,
                                deadline_s: float | None = None):
        """Submit one commit window: `batches` is a list of Transfer
        object lists, `timestamps` the per-prepare commit timestamps.
        Returns the ledger's per-prepare (status u32[n], ts u64[n])
        pairs. Runs the epoch check when the interval elapses.

        `trace_ctxs` is the optional per-prepare TraceContext list
        (entries may be None): the window span joins the first traced
        request's causal tree and LINKS every constituent trace id —
        the fan-in edge assemble_traces() reads. A window that lands on
        the fallback route force-keeps its constituent traces (tail
        retention), as does any recovery that replays it."""
        from .ops.batch import transfers_to_arrays

        batches = [list(b) for b in batches]
        timestamps = list(timestamps)
        win = self.windows_total
        ctxs = [c for c in (trace_ctxs or ()) if c is not None]
        trace_ids = [fmt_trace_id(c.trace_id) for c in ctxs]
        self._epoch_trace_ids.extend(trace_ids)
        self._reshard_tick(batches)

        def thunk():
            evs = [transfers_to_arrays(b) for b in batches]
            return self.led.create_transfers_window(evs, timestamps)

        thunk = self._profiled(thunk)
        # window_commit wraps submit→resolve and is tagged late (the
        # ledger only knows which route it took after dispatch), so
        # each window lands in its route/tier latency class — the
        # per-class distributions the SLO objectives read.
        with self.tracer.span(Event.window_commit,
                              ctx=ctxs[0] if ctxs else None) as sp:
            for tid in trace_ids:
                sp.link(tid)
            out = self._dispatch(thunk, what="window", win=win,
                                 deadline_s=deadline_s)
            # The route the ledger actually took (chain is the default
            # whole-window scan dispatch) — counted into the trace
            # catalog so route regressions are visible next to
            # retry/recovery counters; retry/epoch-verify semantics are
            # route-independent.
            route = self.led.last_window_route
            if route:
                sp.tags["route"] = route
                tier = self.led.last_window_tier
                if tier:
                    sp.tags["tier"] = tier
                self.tracer.count(Event.dispatch_route, route=route)
        if route and "fallback" in route:
            for tid in trace_ids:
                self.tracer.keep_trace(tid, reason="fallback")
        self.flight.record(window=win, route=route or "unknown",
                           prepares=len(batches),
                           **({"trace_ids": trace_ids} if trace_ids
                              else {}))
        norm = [[(int(t), int(s)) for s, t in zip(st.tolist(), ts.tolist())]
                for st, ts in out]
        self.log.append(("window", batches, timestamps))
        self.history.append(norm)
        self.windows_total += 1
        self._windows_since_epoch += 1
        self._observatory_tick()
        if self._windows_since_epoch >= self.epoch_interval:
            self.verify_epoch()
        return out

    # ------------------------------------------------- overlapped serving

    def submit_transfers_window(self, batches: list, timestamps: list,
                                trace_ctxs: list | None = None,
                                deadline_s: float | None = None,
                                evs: list | None = None) -> int:
        """The overlapped serving hot loop's submit half: stage window
        k's stacked operands on the ledger's background stager FIRST,
        resolve the oldest in-flight window when the pipeline is at
        depth (the stage's pack+transfer overlaps that blocking resolve
        and the in-flight dispatch), then dispatch window k with zero
        host synchronization (DeviceLedger.submit_window — poison
        chaining unchanged). Returns the window's history index;
        results materialize at resolve_transfers_windows() /
        drain_pipeline(), or out of a recovery's oracle replay exactly
        like the synchronous path (the window is logged at dispatch, so
        bounded replay covers in-flight windows; a staged-but-
        undispatched pack dies with the quarantined ledger and is never
        committed). Windows the pipeline cannot take (flagged/imported/
        oversized) fall through to the synchronous window path inline.
        Runs the epoch check when the interval elapses — epoch verify
        drains the pipeline, as does recovery."""
        from .ops.batch import transfers_to_arrays

        batches = [list(b) for b in batches]
        timestamps = list(timestamps)
        win = self.windows_total
        ctxs = [c for c in (trace_ctxs or ()) if c is not None]
        trace_ids = [fmt_trace_id(c.trace_id) for c in ctxs]
        self._epoch_trace_ids.extend(trace_ids)
        self._reshard_tick(batches)
        # `evs` lets the admission plane pass the SAME array dicts it
        # already staged ahead (DeviceLedger.stage_window matches on
        # prepare-dict identity) — re-staging here would replace the
        # in-flight pack and forfeit the overlap.
        if evs is None:
            evs = [transfers_to_arrays(b) for b in batches]
        if not self.led.staged_matches(evs, timestamps):
            self.led.stage_window(evs, timestamps)
        if len(self._pending) >= self.pipeline_depth:
            self.resolve_transfers_windows(count=1)
        t0 = self.tracer.now_ns()
        ticket = self._dispatch(
            lambda: self.led.submit_window(evs, timestamps),
            what="window_submit", win=win, deadline_s=deadline_s)
        rec = {"hist_idx": len(self.history), "win": win,
               "ticket": ticket, "t0_ns": t0, "trace_ids": trace_ids,
               "route": self.led.last_window_route,
               "tier": self.led.last_window_tier, "results": None,
               "deadline_s": deadline_s}
        if ticket is None:
            # Ineligible for the pipeline: the synchronous window path
            # (which itself resolves everything in flight first, so
            # submit order is preserved).
            out = self._dispatch(
                lambda: self.led.create_transfers_window(evs,
                                                         timestamps),
                what="window", win=win, deadline_s=deadline_s)
            rec["route"] = self.led.last_window_route
            rec["tier"] = self.led.last_window_tier
            rec["results"] = [
                [(int(t), int(s))
                 for s, t in zip(st.tolist(), ts.tolist())]
                for st, ts in out]
        route = rec["route"]
        if route:
            self.tracer.count(Event.dispatch_route, route=route)
        if route and "fallback" in route:
            for tid in trace_ids:
                self.tracer.keep_trace(tid, reason="fallback")
        self.flight.record(window=win, route=route or "unknown",
                           prepares=len(batches),
                           **({"trace_ids": trace_ids} if trace_ids
                              else {}))
        self.log.append(("window", batches, timestamps))
        self.history.append(rec["results"])
        hist_idx = rec["hist_idx"]
        if rec["results"] is None:
            self._pending.append(rec)
        else:
            self._close_window_span(rec)
        self.windows_total += 1
        self._windows_since_epoch += 1
        self._observatory_tick()
        if self._windows_since_epoch >= self.epoch_interval:
            self.verify_epoch()
        return hist_idx

    def resolve_transfers_windows(self, count: int | None = None) -> list:
        """Resolve the oldest `count` pending pipelined windows (all of
        them when None), filling their history entries, and return
        their normalized per-prepare results ([(ts, status), ...] per
        prepare, the history/oracle shape). A mid-pipeline fallback or
        a recovery may resolve more than asked on the ledger side; the
        extra records simply materialize without blocking when their
        turn comes."""
        n = len(self._pending) if count is None \
            else min(count, len(self._pending))
        out = []
        for _ in range(n):
            rec = self._pending[0]
            tk = rec["ticket"]
            if rec["results"] is None and tk is not None \
                    and tk.results is None:
                self._dispatch(
                    lambda: self.led.resolve_windows(count=1),
                    what="window_resolve", win=rec["win"],
                    deadline_s=rec.get("deadline_s"))
                tk = rec["ticket"]  # a recovery replaces it with None
            self._pending.pop(0)
            if rec["results"] is None:
                _kind, pairs = tk.results
                rec["results"] = [
                    [(int(t), int(s))
                     for s, t in zip(st.tolist(), ts.tolist())]
                    for st, ts in pairs]
                self.history[rec["hist_idx"]] = rec["results"]
            self._close_window_span(rec)
            out.append(rec["results"])
        return out

    def drain_pipeline(self) -> list:
        """Resolve every pending pipelined window (epoch verify and
        recovery drain through here): history is fully materialized
        after this returns."""
        return self.resolve_transfers_windows()

    def _close_window_span(self, rec) -> None:
        """Emit the submit->resolve window_commit span for one
        pipelined window (explicit timing — its open/close sites are
        separate calls), tagged with the route/tier latency class the
        SLO engine partitions on."""
        t0 = rec["t0_ns"]
        tags = {}
        if rec["route"]:
            tags["route"] = rec["route"]
            if rec["tier"]:
                tags["tier"] = rec["tier"]
        self.tracer.record_span(Event.window_commit, t0,
                                self.tracer.now_ns() - t0, **tags)

    def expire_pending_transfers(self, timestamp: int) -> int:
        n = self._dispatch(
            lambda: self.led.expire_pending_transfers(timestamp),
            what="expire")
        self.log.append(("expire", None, timestamp))
        self.history.append(n)
        return n

    def _profiled(self, thunk):
        """Wrap one WINDOW dispatch thunk in the sampled profiler (when
        attached). Route/tier are resolved late — the ledger records
        them only after dispatching — via the profiler's callable-tag
        hook. Non-window dispatches stay unwrapped: the window routes
        (chain / partitioned_chain / per-batch) are the dispatch
        surface the roofline model attributes."""
        prof = self.profiler
        if prof is None:
            return thunk
        return lambda: prof.time(
            thunk,
            route=lambda: self.led.last_window_route or "unknown",
            tier=lambda: self.led.last_window_tier or "-")

    def _observatory_tick(self) -> None:
        """Advance the alert engine one committed window (it decimates
        internally); runs at every window close on both serving
        paths."""
        if self.alert_engine is not None:
            self.alert_engine.tick()

    def _dispatch(self, thunk, *, what: str = "", win: int | None = None,
                  deadline_s: float | None = None):
        hook = self.fault_hook
        idx = self.windows_total if win is None else win
        policy = self.retry.clamped(deadline_s)

        def run():
            if hook is not None:
                hook(idx, what)
            return thunk()

        try:
            with self.tracer.span(Event.serving_dispatch, what=what):
                return call_with_retries(run, policy, self.rng,
                                         self.counters, sleep=self._sleep,
                                         tracer=self.tracer)
        except RecoveryNeeded as e:
            self._recover(e.cause, detail=e.detail)
            # Fresh, verified state: one post-recovery re-dispatch of
            # the op itself (no fault hook — the injected fault was a
            # property of the quarantined attempt sequence).
            return thunk()

    # ------------------------------------------------------------- epochs

    def verify_epoch(self) -> bool:
        """Quiesce, replay the epoch's log through the oracle, and check
        results / state digest / mirror. Clean -> advance the verified
        base and return True; any divergence -> recover and return
        False. Calling with an empty log is a cheap no-op epoch."""
        with self.tracer.span(Event.serving_epoch_verify):
            return self._verify_epoch()

    def _verify_epoch(self) -> bool:
        from .ops import state_epoch

        # Quiesce the overlapped pipeline first: every pending window
        # resolves (filling its history entry) before the oracle replay
        # below compares against history. A recovery triggered inside
        # this drain clears the log and swaps the ledger — the checks
        # below then run against the freshly rebuilt state, trivially.
        self.drain_pipeline()
        led = self.led
        try:
            led.resolve_windows()
            led.drain_mirror()
        except MirrorDivergence as e:
            self._recover("mirror_divergence", detail=str(e))
            return False
        except _STRUCTURAL_FAULTS as e:
            self._recover("drain_fault", detail=repr(e))
            return False
        # An in-flight migration makes the whole-state digest
        # incomparable (staged copy rows bump the target's counts):
        # complete it — or let it abort cleanly — before judging the
        # epoch. Either way ownership is settled when the folds run.
        if self.resharder is not None and self.resharder.active:
            from .parallel.resharding import MigrationAborted
            try:
                led._part_state = self.resharder.drain(
                    led.partitioned_state)
            except MigrationAborted as e:
                led._part_state = e.state
        n_entries = len(self.log)
        replayed = self._replay_log_into_base()
        cause = None
        detail = ""
        # (a) result parity: device answers vs the oracle replay.
        start = len(self.history) - n_entries
        for i, want in enumerate(replayed):
            if self.history[start + i] != want:
                cause = "result_divergence"
                detail = f"op {start + i}"
                break
        # (b) state digest: device fold vs the replayed-oracle fold.
        # Partitioned backend: the sharded digest vs the oracle pack
        # placed by the CURRENT ownership table (overlay entries are
        # part of the epoch's identity — a flip moves rows between
        # shards and the pack must agree on where they landed).
        if cause is None:
            if self.part_router is not None:
                r = self.part_router
                got = state_epoch.partitioned_state_digest(
                    led.partitioned_state)
                want_d = state_epoch.partitioned_oracle_digest(
                    self.epoch_base, self.a_cap, r.n_shards,
                    overlay=r.ownership.entries)
            else:
                got = state_epoch.device_state_digest(led.state)
                want_d = state_epoch.oracle_state_digest(
                    self.epoch_base, self.a_cap)
            if got != want_d:
                self.counters["checksum_mismatches"] += 1
                cause = "state_digest"
                detail = ",".join(
                    state_epoch.diverging_components(got, want_d))
        # (c) mirror audit: write-through mirror vs the replayed oracle.
        if cause is None and self.mirror_audit != "off":
            bad = self._mirror_audit_fields(
                full=self.mirror_audit == "full")
            if bad:
                cause = "mirror_divergence"
                detail = ",".join(bad)
        if cause is None:
            self.counters["epochs_verified"] += 1
            self.flight.record(window=self.windows_total,
                               route="epoch_verified",
                               epoch_digest=got)
            self.log.clear()
            self._windows_since_epoch = 0
            self._epoch_trace_ids.clear()
            # Memory watermark at the quiesce point: the pipeline is
            # drained, so the measured components are the steady-state
            # residents (plus whatever pack the stager holds).
            if self.memwatch is not None:
                self.memwatch.observe(self.led)
            return True
        self._recover(cause, detail=detail, replayed=replayed)
        return False

    def _replay_log_into_base(self) -> list:
        """Apply the epoch log to the verified base oracle, returning
        normalized results per entry (the authoritative answers)."""
        base = self.epoch_base
        out = []
        for kind, payload, ts in self.log:
            if kind == "accounts":
                res = base.create_accounts(payload, ts)
                out.append([(r.timestamp, int(r.status)) for r in res])
            elif kind == "window":
                out.append([
                    [(r.timestamp, int(r.status))
                     for r in base.create_transfers(b, bts)]
                    for b, bts in zip(payload, ts)])
            else:
                assert kind == "expire", kind
                out.append(base.expire_pending_transfers(ts))
        return out

    def _mirror_audit_fields(self, full: bool) -> list[str]:
        """Object-level audit of the write-through mirror against the
        replayed oracle. full=True compares every container; spot mode
        compares sizes/scalars plus a seeded object sample."""
        sm = self.led.mirror
        base = self.epoch_base
        bad: list[str] = []
        if full:
            for field in ("accounts", "transfers", "pending_status",
                          "orphaned", "expiry"):
                if getattr(sm, field) != getattr(base, field):
                    bad.append(field)
            off = sm.events_base - base.events_base
            if not (0 <= off <= len(base.account_events)) or \
                    sm.account_events != base.account_events[off:]:
                bad.append("account_events")
            return bad
        if (len(sm.accounts) != len(base.accounts)
                or len(sm.transfers) != len(base.transfers)
                or sm.commit_timestamp != base.commit_timestamp):
            return ["sizes"]
        ids = list(base.transfers)
        for tid in (self.rng.sample(ids, min(4, len(ids))) if ids else ()):
            if sm.transfers.get(tid) != base.transfers.get(tid):
                bad.append(f"transfer:{tid}")
        return bad

    # ----------------------------------------------------------- recovery

    def _recover(self, cause: str, detail: str = "",
                 replayed: list | None = None) -> None:
        """Quarantine the device state and recover from the last
        verified epoch: oracle-replay the logged suffix (bounded),
        revise the authoritative history, rebuild mirror + device from
        the recovered oracle, resume serving.

        Recovery is THE flight-recorder dump point: freeze the
        last-N window records (+ epoch digests) as a JSON artifact
        tagged with the recovery cause before anything is rebuilt —
        covering retry exhaustion, deadline, divergence, and
        drain-fault causes alike."""
        # Tail retention: every request whose window sits in the
        # replayed suffix is force-kept regardless of head sampling,
        # and the flight artifact names the same trace ids so the
        # post-mortem can be cross-referenced with the causal traces.
        affected = list(dict.fromkeys(self._epoch_trace_ids))
        for tid in affected:
            self.tracer.keep_trace(tid, reason=cause)
        self.flight.record(window=self.windows_total, route="recovery",
                           cause=cause, detail=detail[:200],
                           **({"trace_ids": affected} if affected
                              else {}))
        self.flight.dump(cause)
        self.tracer.count(Event.serving_recoveries, cause=cause)
        with self.tracer.span(Event.serving_recovery_replay, cause=cause):
            self._recover_replay(cause, detail, replayed)

    def _recover_replay(self, cause: str, detail: str,
                        replayed: list | None) -> None:
        n_entries = len(self.log)
        n_windows = sum(1 for e in self.log if e[0] == "window")
        # Bounded-replay invariant: recovery never replays more windows
        # than fit between two epoch checks.
        assert n_windows <= self.epoch_interval, \
            (n_windows, self.epoch_interval)
        # The bounded-replay SLO (perf/slo.json) reads this
        # distribution: windows replayed per recovery, unit windows.
        self.tracer.observe(Event.serving_replay_windows, n_windows)
        if replayed is None:
            replayed = self._replay_log_into_base()
        start = len(self.history) - n_entries
        self.history[start:] = replayed
        # Pipelined windows still in flight at quarantine: every one of
        # them was LOGGED at dispatch, so the oracle replay above just
        # produced their authoritative results — adopt those and detach
        # the dead tickets. A staged-but-undispatched pack was never
        # logged: it dies with the quarantined ledger's stager
        # (shutdown_staging below) and is re-staged fresh if its window
        # is ever submitted again — drained cleanly, committed never.
        for rec in self._pending:
            rec["results"] = self.history[rec["hist_idx"]]
            rec["ticket"] = None
        self.counters["replayed_windows"] += n_windows
        recs = self.counters["recoveries"]
        recs[cause] = recs.get(cause, 0) + 1
        self.last_recovery = {"cause": cause, "detail": detail,
                              "replayed_entries": n_entries,
                              "replayed_windows": n_windows}
        # Fresh mirror from the recovered oracle (a deep copy: the
        # mirror evolves by write-through deltas, the base only by
        # replay) and a device rebuild through from_host — the same
        # path a restart/state-sync takes. The quarantined ledger's
        # stager drains first: its staged-but-undispatched window (if
        # any) is dropped, its worker joined.
        self.led.shutdown_staging()
        if self.part_router is not None:
            # Partitioned backend: an un-flipped migration reverts to
            # its pre-flip owner FIRST (the controller drops the
            # overlay entry and records the reshard_abort), then the
            # whole sharded state rebuilds from the verified base via
            # the router's resync — the pack places every range by the
            # reverted table, so staged copy rows simply never
            # reappear. A flipped migration keeps its MIGRATED entry
            # and the rebuild honors it.
            if self.resharder is not None:
                self.resharder.on_recovery()
            r = self.part_router
            state = r.resync(self.epoch_base)
            led = DeviceLedger(self.a_cap, self.t_cap)
            led.attach_partitioned(r, state)
            self._attach(led)
        else:
            new_mirror = copy.deepcopy(self.epoch_base)
            self._attach(DeviceLedger(self.a_cap, self.t_cap,
                                      write_through=new_mirror))
        self.log.clear()
        self._windows_since_epoch = 0
        self._epoch_trace_ids.clear()

    # -------------------------------------------------------------- stats

    def stats(self) -> dict:
        out = {k: (dict(v) if isinstance(v, dict) else v)
               for k, v in self.counters.items()}
        out["windows_total"] = self.windows_total
        out["windows_since_epoch"] = self._windows_since_epoch
        out["pipeline"] = {"depth": self.pipeline_depth,
                           "pending": len(self._pending)}
        out["last_recovery"] = self.last_recovery
        if self.resharder is not None:
            out["resharding"] = {
                "stage": self.resharder.stage,
                "migrations": list(self.resharder.migrations),
                "aborts": list(self.resharder.aborts)}
        out["flight"] = {"windows_recorded": self.flight.seq,
                         "dumps": self.flight.dumps,
                         "last_dump": self.flight.last_dump_path}
        observatory = {}
        if self.profiler is not None:
            observatory["profiler"] = self.profiler.stats()
        if self.memwatch is not None:
            observatory["memwatch"] = self.memwatch.stats()
        if self.alert_engine is not None:
            observatory["alerts"] = self.alert_engine.stats()
        if observatory:
            out["observatory"] = observatory
        out["ledger"] = self.led.fallback_stats()
        return out
