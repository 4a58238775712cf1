"""Chaos-hardened serving: verified state epochs, seeded device-fault
injection, and bounded replay recovery (tigerbeetle_tpu/serving.py,
ops/state_epoch.py, testing/chaos.py).

Quick tier: the pure-host pieces (digest fold, fault-plan determinism,
retry policy) plus supervisor recovery on single-batch windows (only
the fast kernel compiles, which the quick tier already pays for).
Slow tier: the 20-seed chaos sweep over superbatch windows and the
sharded-router shard-loss differential.
"""

import random

import numpy as np
import pytest

from tigerbeetle_tpu import constants
from tigerbeetle_tpu.ops import state_epoch
from tigerbeetle_tpu.ops.ev_layout import XF_P32_POS, XF_U64_IDX
from tigerbeetle_tpu.oracle.state_machine import StateMachineOracle
from tigerbeetle_tpu.serving import (DispatchTimeout, RecoveryNeeded,
                                     RetryPolicy, ServingSupervisor,
                                     TransientDispatchError,
                                     call_with_retries)
from tigerbeetle_tpu.testing.chaos import (CORRUPTION_KINDS, FAULT_KINDS,
                                           FaultPlan, inject_state_bitflip,
                                           run_chaos_seed,
                                           shard_loss_scenario)
from tigerbeetle_tpu.types import Account, Transfer

A_CAP = 1 << 8


def _small_oracle(n_transfers=12):
    sm = StateMachineOracle()
    sm.create_accounts([Account(id=i, ledger=1, code=1)
                        for i in range(1, 9)], 1_000)
    evs = [Transfer(id=100 + i, debit_account_id=1 + i % 7,
                    credit_account_id=2 + i % 6, amount=5 + i,
                    ledger=1, code=1) for i in range(n_transfers)]
    for e in evs:
        if e.debit_account_id == e.credit_account_id:
            e.credit_account_id = e.debit_account_id % 8 + 1
    sm.create_transfers(evs, 10_000)
    return sm


# ------------------------------------------------------- digest (host)

class TestStateDigest:
    def test_identical_states_digest_equal(self):
        a = state_epoch.oracle_state_digest(_small_oracle(), A_CAP)
        b = state_epoch.oracle_state_digest(_small_oracle(), A_CAP)
        assert a == b
        assert state_epoch.combine(a) == state_epoch.combine(b)

    def test_any_semantic_change_changes_digest(self):
        base = state_epoch.oracle_state_digest(_small_oracle(), A_CAP)
        changed = _small_oracle()
        t = changed.transfers[100]
        import dataclasses

        changed.transfers[100] = dataclasses.replace(t, amount=t.amount + 1)
        got = state_epoch.oracle_state_digest(changed, A_CAP)
        assert got != base
        assert state_epoch.diverging_components(got, base) \
            == ["transfers_u64"]

    def test_single_bit_in_pack_is_detected(self):
        sm = _small_oracle()
        pack = state_epoch.pack_oracle_state(sm, A_CAP)
        base = {k: int(v) for k, v in
                state_epoch._digest_components(pack, np).items()}
        rng = random.Random(7)
        for _ in range(20):
            comp = rng.choice(("accounts", "transfers"))
            # The pack is held as the device holds it: u32 halves,
            # whose u64 view is the digested matrix.
            mat = pack[comp]["u32"].view(np.uint64)
            covered = [j for j in range(mat.shape[1])
                       if comp == "accounts"
                       or state_epoch.XF_COL_MASKS[j]]
            r = rng.randrange(mat.shape[0])
            c = rng.choice(covered)
            bit = np.uint64(1 << rng.randrange(64))
            mat[r, c] ^= bit
            got = {k: int(v) for k, v in
                   state_epoch._digest_components(pack, np).items()}
            assert got != base, (comp, r, c)
            mat[r, c] ^= bit  # restore

    def test_excluded_columns_do_not_digest(self):
        # expires and the dr_row/cr_row cache column are deliberately
        # outside the digest (non-canonical across write paths).
        sm = _small_oracle()
        pack = state_epoch.pack_oracle_state(sm, A_CAP)
        base = state_epoch._digest_components(pack, np)
        mat = pack["transfers"]["u32"].view(np.uint64)
        mat[0, XF_U64_IDX["expires"]] ^= np.uint64(1 << 17)
        mat[1, XF_P32_POS["dr_row"][0]] ^= np.uint64(1 << 3)
        got = state_epoch._digest_components(pack, np)
        assert {k: int(v) for k, v in got.items()} \
            == {k: int(v) for k, v in base.items()}

    def test_device_digest_matches_oracle_digest(self):
        from tigerbeetle_tpu.ops.ledger import DeviceLedger

        sm = StateMachineOracle()
        led = DeviceLedger(a_cap=A_CAP, t_cap=1 << 10)
        accounts = [Account(id=i, ledger=1, code=1) for i in range(1, 9)]
        led.create_accounts(accounts, 1_000)
        sm.create_accounts(accounts, 1_000)
        evs = [Transfer(id=500 + i, debit_account_id=1 + i % 7,
                        credit_account_id=2 + i % 6, amount=3,
                        ledger=1, code=1) for i in range(16)]
        for e in evs:
            if e.debit_account_id == e.credit_account_id:
                e.credit_account_id = e.debit_account_id % 8 + 1
        led.create_transfers(evs, 10_000)
        sm.create_transfers(evs, 10_000)
        assert state_epoch.device_state_digest(led.state) \
            == state_epoch.oracle_state_digest(sm, A_CAP)


# -------------------------------------------------------- fault plans

class TestFaultPlan:
    def test_deterministic_per_seed(self):
        for seed in range(20):
            a = FaultPlan(seed, 10)
            b = FaultPlan(seed, 10)
            assert a.schedule == b.schedule

    def test_seeds_differ_and_always_inject(self):
        schedules = [tuple(sorted(
            (w, f["kind"]) for w, f in FaultPlan(s, 10).schedule.items()))
            for s in range(30)]
        assert len(set(schedules)) > 1
        for s in schedules:
            assert s  # at least one fault per run

    def test_every_kind_appears_across_seeds(self):
        seen = set()
        for s in range(40):
            seen.update(f["kind"]
                        for f in FaultPlan(s, 10).schedule.values())
        assert seen == set(FAULT_KINDS)


# ------------------------------------------------------- retry policy

class TestRetryPolicy:
    def _counters(self):
        from tigerbeetle_tpu.ops.ledger import default_recovery_stats

        return default_recovery_stats()

    def test_transient_faults_retry_then_succeed(self):
        calls = {"n": 0}
        sleeps = []

        def fn():
            calls["n"] += 1
            if calls["n"] <= 2:
                raise TransientDispatchError("flaky")
            return "ok"

        counters = self._counters()
        out = call_with_retries(fn, RetryPolicy(max_retries=3),
                                random.Random(0), counters,
                                sleep=sleeps.append)
        assert out == "ok"
        assert counters["retries"] == 2
        assert len(sleeps) == 2
        assert sleeps[1] > sleeps[0]  # exponential
        # the counter rounds to microseconds as it accumulates
        assert counters["backoff_s"] == pytest.approx(sum(sleeps), abs=1e-5)

    def test_backoff_jitter_is_seeded(self):
        def delays(seed):
            out = []
            calls = {"n": 0}

            def fn():
                calls["n"] += 1
                if calls["n"] <= 3:
                    raise TransientDispatchError("flaky")
                return None

            call_with_retries(fn, RetryPolicy(max_retries=3),
                              random.Random(seed), self._counters(),
                              sleep=out.append)
            return out

        assert delays(1) == delays(1)
        assert delays(1) != delays(2)

    def test_exhaustion_escalates_to_recovery(self):
        def fn():
            raise DispatchTimeout("wedged")

        with pytest.raises(RecoveryNeeded) as ei:
            call_with_retries(fn, RetryPolicy(max_retries=2),
                              random.Random(0), self._counters(),
                              sleep=lambda s: None)
        assert ei.value.cause == "dispatch_exhausted"

    def test_deadline_escalates_to_recovery(self):
        clock = {"t": 0.0}

        def fn():
            clock["t"] += 100.0
            raise TransientDispatchError("slow")

        with pytest.raises(RecoveryNeeded) as ei:
            call_with_retries(fn, RetryPolicy(max_retries=99,
                                              deadline_s=50.0),
                              random.Random(0), self._counters(),
                              sleep=lambda s: None,
                              clock=lambda: clock["t"])
        assert ei.value.cause == "dispatch_deadline"

    def test_clamped_deadline_bounds_total_retry_budget(self):
        # ISSUE 18 satellite: under saturation the whole retry sequence
        # — attempts AND backoff sleeps — is bounded by the request's
        # remaining admission deadline (RetryPolicy.clamped), so a
        # saturated pipeline degrades into a fast typed escalation
        # instead of every admitted request burning the policy's full
        # static 30s deadline.
        t = {"t": 0.0}

        def fn():
            t["t"] += 0.010  # each attempt costs 10ms of wall
            raise TransientDispatchError("saturated")

        policy = RetryPolicy(max_retries=99, base_delay_s=0.050,
                             max_delay_s=10.0, deadline_s=30.0,
                             jitter=0.0).clamped(0.080)
        assert policy.deadline_s == pytest.approx(0.080)
        with pytest.raises(RecoveryNeeded) as ei:
            call_with_retries(fn, policy, random.Random(0),
                              self._counters(),
                              sleep=lambda s: t.__setitem__(
                                  "t", t["t"] + s),
                              clock=lambda: t["t"])
        assert ei.value.cause == "dispatch_deadline"
        # Total elapsed <= clamped budget + one attempt's own cost (an
        # in-flight attempt cannot be preempted, only not retried) —
        # nowhere near the policy's static 30s.
        assert t["t"] <= 0.080 + 0.010 + 1e-9

    def test_clamped_tightens_never_loosens(self):
        p = RetryPolicy(deadline_s=0.5)
        assert p.clamped(30.0) is p
        assert p.clamped(None) is p
        assert p.clamped(0.1).deadline_s == pytest.approx(0.1)
        assert p.clamped(-1.0).deadline_s == 0.0

    def test_mirror_divergence_goes_straight_to_recovery(self):
        from tigerbeetle_tpu.ops.ledger import MirrorDivergence

        def fn():
            raise MirrorDivergence("verify: device/mirror divergence")

        counters = self._counters()
        with pytest.raises(RecoveryNeeded) as ei:
            call_with_retries(fn, RetryPolicy(), random.Random(0),
                              counters, sleep=lambda s: None)
        assert ei.value.cause == "mirror_divergence"
        assert counters["retries"] == 0


# ------------------------------------------- supervisor (fast kernel)

def _mk_supervisor(seed=0, epoch_interval=2, fault_hook=None):
    sup = ServingSupervisor(
        a_cap=A_CAP, t_cap=1 << 11, epoch_interval=epoch_interval,
        retry=RetryPolicy(max_retries=2, base_delay_s=1e-4,
                          max_delay_s=1e-3),
        seed=seed, fault_hook=fault_hook, sleep=lambda s: None)
    sup.create_accounts([Account(id=i, ledger=1, code=1)
                         for i in range(1, 9)], 1_000)
    return sup


def _simple_window(next_id, ts, n=24):
    rng = random.Random(next_id)
    evs = []
    for i in range(n):
        dr = rng.randrange(1, 9)
        evs.append(Transfer(id=next_id + i, debit_account_id=dr,
                            credit_account_id=dr % 8 + 1,
                            amount=rng.randrange(1, 50), ledger=1, code=1))
    return [evs], [ts]


def _audit(sup, script):
    audit = StateMachineOracle()
    expected = []
    for kind, payload, when in script:
        if kind == "accounts":
            expected.append([(r.timestamp, int(r.status))
                             for r in audit.create_accounts(payload, when)])
        else:
            expected.append([
                [(r.timestamp, int(r.status))
                 for r in audit.create_transfers(b, bts)]
                for b, bts in zip(payload, when)])
    assert sup.history == expected
    host = sup.led.to_host()
    for field in ("accounts", "transfers", "pending_status", "orphaned",
                  "expiry", "account_events"):
        assert getattr(host, field) == getattr(audit, field), field


class TestSupervisorRecovery:
    def _run(self, sup, windows, corrupt_at=None):
        script = [("accounts",
                   [Account(id=i, ledger=1, code=1) for i in range(1, 9)],
                   1_000)]
        ts = 10 ** 9
        next_id = 1_000
        for w in range(windows):
            if corrupt_at is not None and w == corrupt_at:
                f = {"target": "accounts_bal", "row_pick": 3,
                     "col_pick": 5, "bit": 11}
                assert inject_state_bitflip(sup.led, f), f
            ts += 40
            batches, tss = _simple_window(next_id, ts)
            next_id += 24
            sup.create_transfers_window(batches, tss)
            script.append(("window", batches, tss))
        sup.verify_epoch()
        return script

    def test_clean_run_verifies_epochs_and_never_recovers(self):
        sup = _mk_supervisor()
        script = self._run(sup, windows=4)
        _audit(sup, script)
        assert sup.counters["epochs_verified"] >= 2
        assert sup.counters["recoveries"] == {}
        assert sup.counters["replayed_windows"] == 0

    def test_bitflip_detected_and_recovered_to_parity(self):
        sup = _mk_supervisor(epoch_interval=2)
        script = self._run(sup, windows=4, corrupt_at=1)
        _audit(sup, script)
        recs = sup.counters["recoveries"]
        assert sum(recs.values()) >= 1, recs
        # Detected as a checksum/state divergence (digest or mirror),
        # never silently absorbed.
        assert set(recs) <= {"state_digest", "mirror_divergence",
                             "result_divergence", "drain_fault"}

    def test_replay_is_bounded_by_epoch_interval(self):
        sup = _mk_supervisor(epoch_interval=3)
        self._run(sup, windows=6, corrupt_at=1)
        assert sup.last_recovery is not None
        assert sup.last_recovery["replayed_windows"] <= 3
        assert sup.counters["replayed_windows"] <= 3

    def test_dispatch_faults_within_budget_just_retry(self):
        fails = {"left": 2}

        def hook(win, what):
            if what == "window" and fails["left"]:
                fails["left"] -= 1
                raise TransientDispatchError("injected")

        sup = _mk_supervisor(fault_hook=hook)
        script = self._run(sup, windows=2)
        _audit(sup, script)
        assert sup.counters["retries"] == 2
        assert sup.counters["recoveries"] == {}

    def test_dispatch_exhaustion_recovers_and_reserves(self):
        fails = {"left": 5}

        def hook(win, what):
            if what == "window" and fails["left"]:
                fails["left"] -= 1
                raise DispatchTimeout("injected")

        sup = _mk_supervisor(fault_hook=hook)
        script = self._run(sup, windows=3)
        _audit(sup, script)
        assert sup.counters["recoveries"].get("dispatch_exhausted", 0) >= 1

    def test_recovery_counters_surface_through_fallback_stats(self):
        sup = _mk_supervisor(epoch_interval=2)
        self._run(sup, windows=4, corrupt_at=1)
        rec = sup.led.fallback_stats()["recovery"]
        assert rec["replayed_windows"] == \
            sup.counters["replayed_windows"] > 0
        assert rec["recoveries"] == sup.counters["recoveries"]


# ------------------------------------------- chaos x causal tracing

class TestChaosTracing:
    """ISSUE 15 satellite: chaos and tracing compose. A seeded fault
    that lands mid-request must leave the affected requests' traces
    tail-kept (reason = the recovery cause) and cross-referenced from
    the flight-recorder artifact by trace id."""

    def _traced_supervisor(self, tmp_path):
        from tigerbeetle_tpu.trace import FlightRecorder, Tracer

        tracer = Tracer(pid=0)
        flight = FlightRecorder(tracer=tracer, out_dir=str(tmp_path))
        sup = ServingSupervisor(
            a_cap=A_CAP, t_cap=1 << 11, epoch_interval=2,
            retry=RetryPolicy(max_retries=2, base_delay_s=1e-4,
                              max_delay_s=1e-3),
            seed=0, sleep=lambda s: None, tracer=tracer,
            flight_recorder=flight)
        sup.create_accounts([Account(id=i, ledger=1, code=1)
                             for i in range(1, 9)], 1_000)
        return sup, tracer, flight

    def _run_traced(self, sup, windows, corrupt_at):
        from tigerbeetle_tpu.trace.context import (fmt_trace_id,
                                                   mint_context)

        trace_ids = []
        ts = 10 ** 9
        next_id = 1_000
        for w in range(windows):
            if w == corrupt_at:
                f = {"target": "accounts_bal", "row_pick": 3,
                     "col_pick": 5, "bit": 11}
                assert inject_state_bitflip(sup.led, f), f
            ts += 40
            batches, tss = _simple_window(next_id, ts)
            next_id += 24
            ctx = mint_context(3, w + 1, head_rate=1.0)
            trace_ids.append(fmt_trace_id(ctx.trace_id))
            sup.create_transfers_window(batches, tss, trace_ctxs=[ctx])
        sup.verify_epoch()
        return trace_ids

    def test_recovery_tail_keeps_affected_traces(self, tmp_path):
        sup, tracer, _ = self._traced_supervisor(tmp_path)
        trace_ids = self._run_traced(sup, windows=4, corrupt_at=1)
        recs = sup.counters["recoveries"]
        assert sum(recs.values()) >= 1, recs
        # Every tail-kept trace names the recovery cause as its reason
        # and is one of the requests in flight since the last epoch.
        assert tracer.kept_traces, "recovery kept no traces"
        assert set(tracer.kept_traces.values()) <= set(recs)
        assert set(tracer.kept_traces) <= set(trace_ids)
        assert tracer.counters["trace_tail_keep"] \
            == len(tracer.kept_traces)
        # The verified-epoch boundary clears the at-risk set: a later
        # clean run keeps nothing new.
        before = dict(tracer.kept_traces)
        self._run_traced(sup, windows=2, corrupt_at=None)
        assert tracer.kept_traces == before

    def test_flight_artifact_names_affected_trace_ids(self, tmp_path):
        import json

        sup, tracer, flight = self._traced_supervisor(tmp_path)
        trace_ids = self._run_traced(sup, windows=4, corrupt_at=1)
        assert flight.dumps >= 1 and flight.last_dump_path
        with open(flight.last_dump_path) as f:
            doc = json.load(f)
        named = set()
        for rec in doc["records"]:
            named.update((rec.get("detail") or {}).get("trace_ids", ()))
        # The artifact cross-references BOTH planes: the per-window
        # records carry each window's constituent trace ids (up to the
        # dump — the ring freezes AT recovery, later windows are not in
        # it), and the recovery record names the tail-kept set.
        assert named and named <= set(trace_ids)
        assert set(tracer.kept_traces) <= named
        recovery = [rec for rec in doc["records"]
                    if rec.get("route") == "recovery"]
        assert recovery, "recovery never reached the flight ring"
        assert set((recovery[-1].get("detail") or {})["trace_ids"]) \
            == set(tracer.kept_traces)

    def test_window_spans_link_constituent_traces(self, tmp_path):
        sup, tracer, _ = self._traced_supervisor(tmp_path)
        trace_ids = self._run_traced(sup, windows=2, corrupt_at=None)
        spans = [e for e in tracer.events
                 if e.get("name") == "window_commit"
                 and (e.get("args") or {}).get("links")]
        assert spans, "no window span carried fan-in links"
        linked = set()
        for s in spans:
            linked.update(s["args"]["links"])
        assert linked == set(trace_ids)


class TestSpotCheckDiagnostics:
    def test_divergence_names_op_and_fields(self, monkeypatch):
        import dataclasses

        from tigerbeetle_tpu.ops.ledger import MirrorDivergence
        from tigerbeetle_tpu.state_machine import StateMachine

        monkeypatch.setenv("TB_VERIFY_SPOT_RATE", "1.0")
        was = constants.VERIFY
        constants.set_verify(True)
        try:
            sm = StateMachine(engine="device", a_cap=1 << 10, t_cap=1 << 12)
            sm.create_accounts([Account(id=i, ledger=1, code=1)
                                for i in range(1, 9)], 100)
            evs = [Transfer(id=100 + i, debit_account_id=1 + i % 7,
                            credit_account_id=2 + i % 6, amount=1,
                            ledger=1, code=1) for i in range(8)]
            for e in evs:
                if e.debit_account_id == e.credit_account_id:
                    e.credit_account_id = e.debit_account_id % 8 + 1
            sm.create_transfers(evs, 10_000)
            _ = sm.state.transfers  # clean drain
            tid = next(iter(sm.state.transfers))
            sm.state.transfers[tid] = dataclasses.replace(
                sm.state.transfers[tid], amount=424242)
            sm.create_transfers(
                [Transfer(id=900, debit_account_id=1, credit_account_id=2,
                          amount=1, ledger=1, code=1)], 20_000)
            with pytest.raises(MirrorDivergence) as ei:
                _ = sm.state.transfers
            msg = str(ei.value)
            assert "device/mirror divergence" in msg
            assert "op " in msg           # which prepare produced it
            assert "amount" in msg        # the differing field, named
            assert "424242" in msg        # ... with both values
        finally:
            constants.set_verify(was)


# ------------------------------------------------------- chaos sweeps

@pytest.mark.slow
class TestChaosSweep:
    def test_twenty_seeds_zero_silent_corruption(self):
        """The acceptance sweep: >= 20 deterministic seeds across every
        fault class; each run either recovers to bit-exact oracle
        parity or fails loudly (run_chaos_seed asserts both, plus that
        every applied corruption produced a counted recovery)."""
        kinds_seen = set()
        recovered = 0
        for seed in range(1, 21):
            s = run_chaos_seed(seed, windows=6, batches_per_window=2,
                               events_per_batch=32, mesh_scenario=False)
            kinds_seen.update(k for k in s["faults"]
                              if not k.endswith("_skipped"))
            recovered += sum(s["recoveries"].values())
            assert s["replayed_windows"] <= \
                s["epoch_interval"] * (sum(s["recoveries"].values()) or 1)
        assert kinds_seen == set(FAULT_KINDS)
        assert recovered >= 5  # the sweep genuinely exercises recovery

    def test_chaos_seed_is_reproducible(self):
        a = run_chaos_seed(11, windows=4, batches_per_window=2,
                           events_per_batch=24, mesh_scenario=False)
        b = run_chaos_seed(11, windows=4, batches_per_window=2,
                           events_per_batch=24, mesh_scenario=False)
        assert a == b


# ------------------------------------- adversarial traffic shapes (18)

class TestTrafficShapes:
    @pytest.mark.slow
    def test_every_shape_runs_clean_and_reproducibly(self):
        from tigerbeetle_tpu.testing.chaos import TRAFFIC_SHAPES

        for shape in TRAFFIC_SHAPES:
            a = run_chaos_seed(9, windows=4, batches_per_window=2,
                               events_per_batch=24, mesh_scenario=False,
                               kinds=("dispatch_fail",), traffic=shape)
            b = run_chaos_seed(9, windows=4, batches_per_window=2,
                               events_per_batch=24, mesh_scenario=False,
                               kinds=("dispatch_fail",), traffic=shape)
            assert a == b, shape
            assert a["traffic"] == shape

    def test_shapes_generate_distinct_workloads(self):
        from tigerbeetle_tpu.testing.chaos import TrafficShape

        batches = {}
        for shape in ("hot_skew", "pending_storm", "open_close_burst"):
            s = TrafficShape(shape, seed=5, n_accounts=32, n_windows=4)
            evs, _nid = s.batch(0, random.Random(0), 1_000, 24, [])
            batches[shape] = [(e.debit_account_id, e.credit_account_id,
                               int(e.flags)) for e in evs]
        assert len({tuple(v) for v in batches.values()}) == 3


# ------------------------------- admission x saturation (ISSUE 18 #2)

class TestAdmissionSaturation:
    @pytest.mark.slow
    def test_saturated_pipeline_sheds_instead_of_timing_out(self):
        """Offered load ~6x the pump's service capacity: the plane must
        degrade into TYPED sheds (shed_line/deadline/no_credit) with
        every ADMITTED request's queue wait inside its class deadline —
        and the supervisor below must see zero dispatch_deadline
        recoveries, because shedding (not per-request retry timeouts)
        is how saturation is absorbed."""
        from tigerbeetle_tpu.admission import (AdmissionClass,
                                               AdmissionPlane,
                                               ShedResult, VirtualClock)

        clock = VirtualClock()
        sup = ServingSupervisor(
            a_cap=A_CAP, t_cap=1 << 11, epoch_interval=4,
            retry=RetryPolicy(max_retries=2, base_delay_s=1e-4,
                              max_delay_s=1e-3),
            seed=11, sleep=lambda s: None)
        classes = (
            AdmissionClass("critical", 0, slo_ms=60.0, deadline_ms=240.0),
            AdmissionClass("batch", 1, slo_ms=120.0, deadline_ms=240.0),
        )
        plane = AdmissionPlane(
            sup, classes=classes, prepare_max=8, window_prepares=1,
            max_windows_per_pump=1, session_credits=3, max_queue=64,
            burn_window_ticks=4, burn_budget=0.25, cool_ticks=2,
            clock=clock, seed=11)
        plane.open_accounts([Account(id=i, ledger=1, code=1)
                             for i in range(1, 9)], 1_000)
        nid = 10 ** 5
        reqs = []
        for tick in range(15):
            for sid in range(1, 13):  # 48 events offered vs 8 served
                cls = "critical" if sid == 1 else "batch"
                evs = [Transfer(id=nid + i, debit_account_id=1 + i % 7,
                                credit_account_id=2 + i % 6, amount=1,
                                ledger=1, code=1) for i in range(4)]
                nid += 4
                reqs.append(plane.submit(sid, evs, cls=cls))
            plane.pump()
            clock.advance(0.05)
        plane.drain()
        cons = plane.conservation()
        assert cons["ok"] and cons["queued"] == 0
        assert cons["shed"] > 0, "saturation produced no sheds"
        for r in reqs:
            assert r.state in ("admitted", "shed")
            if r.state == "shed":
                assert isinstance(r.shed, ShedResult), r.shed
            else:
                assert r.admit_wait_ms <= r.cls.deadline_ms + 1e-6
        # The pipeline below never escalated a retry-deadline recovery:
        # saturation was absorbed at the admission line, not burned in
        # per-request retry budgets.
        assert sup.last_recovery is None
        assert sup.counters["recoveries"] == {}
        assert sup.verify_epoch()
        hist, _ = plane.oracle_history()
        assert hist == sup.history
        sup.led.shutdown_staging()


@pytest.mark.slow
class TestShardLoss:
    def test_drop_and_restore_bit_exact(self):
        s = shard_loss_scenario(0)
        assert s["reroutes"] == 2
        assert s["devices"] >= 1

    def test_partitioned_loss_requires_resync(self):
        from tigerbeetle_tpu.testing.chaos import shard_resync_scenario

        s = shard_resync_scenario(0)
        assert s["resyncs"] == 1
        assert s["devices"] >= 1

    def test_corruption_kinds_is_subset(self):
        assert CORRUPTION_KINDS < set(FAULT_KINDS)
