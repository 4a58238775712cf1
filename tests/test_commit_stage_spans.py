"""The spans beneath commit_execute, commit_compact and commit_checkpoint,
the two that a two-phase row opens inside flush_columns, the serving
thread's busy turns, the collector's pauses, the durable row counters,
and the benchmark readers that turn them into per-layer metrics."""

import gc
import importlib.util
import json
import pathlib

import numpy as np
import pytest

from chipbench import check, wire
from chipbench.reference.ledger import StateMachineOracle
from chipbench.traffic import F_PENDING, F_POST, F_VOID, Deployment
from chipbench.window import Sent
from tigerbeetle_tpu import multi_batch
from tigerbeetle_tpu.state_machine import StateMachine
from tigerbeetle_tpu.testing.cluster import Cluster
from tigerbeetle_tpu.trace import (Event, NullTracer, Tracer,
                                   install_gc_spans)
from tigerbeetle_tpu.trace.span_tree import (STAGE_CHILDREN,
                                             children_share,
                                             keep_operation,
                                             stage_occurrences)
from tigerbeetle_tpu.types import Account, AccountFlags, Operation, Transfer

REPO = pathlib.Path(__file__).resolve().parent.parent
PER_OP = 200  # transfers a request: the children dwarf the glue code
ACCOUNTS = 8


def _device_machine():
    return StateMachine(engine="device", a_cap=1 << 9, t_cap=1 << 13)


def _drive_past_checkpoint(cluster, ops=None):
    """Accounts, then create_transfers requests of PER_OP events until
    one op past the first checkpoint. Returns the transfers created."""
    client = cluster.client(9)

    def drive(op, body):
        client.request(op, body)
        assert cluster.run(4000, until=lambda: client.idle), \
            cluster.debug_status()

    drive(Operation.create_accounts, multi_batch.encode(
        [b"".join(Account(id=i, ledger=1, code=1).pack()
                  for i in range(1, ACCOUNTS + 1))], 128))
    interval = cluster.replicas[0].options.checkpoint_interval
    tid, n_ops = 1000, ops if ops is not None else interval + 1
    for _ in range(n_ops):
        body = b"".join(
            Transfer(id=tid + j, debit_account_id=1 + j % ACCOUNTS,
                     credit_account_id=1 + (j + 1) % ACCOUNTS, amount=1,
                     ledger=1, code=1).pack() for j in range(PER_OP))
        tid += PER_OP
        drive(Operation.create_transfers, multi_batch.encode([body], 128))
    return n_ops * PER_OP


def _tiny_grid_cache(replica) -> None:
    from tigerbeetle_tpu.lsm.cache_map import ObjectCache

    replica.durable.grid.cache = ObjectCache(sets=1, ways=1)


@pytest.fixture(scope="module")
def traced_run():
    """One device-engine replica under a recording tracer, driven past
    its first checkpoint."""
    tracers = {}

    def make(i):
        tracers[i] = Tracer(pid=i)
        return tracers[i]

    cluster = Cluster(seed=3, replica_count=1, tracer_factory=make,
                      state_machine_factory=_device_machine)
    # Count the rows really put into the object tree, whatever the path.
    replica = cluster.replicas[0]
    # A grid cache of one block: the account reads after the checkpoint
    # and the lookup's go to the device (grid_read, grid_verify). The
    # blocks each batched read sent to the device, in order.
    _tiny_grid_cache(replica)
    grid, batches = replica.durable.grid, []
    read_blocks = grid.read_blocks

    def counting_read_blocks(reqs):
        before = grid.device_reads
        out = read_blocks(reqs)
        batches.append(grid.device_reads - before)
        return out

    grid.read_blocks = counting_read_blocks
    tree = replica.durable.forest.trees["transfers"]
    puts = [0]
    put, put_run = tree.put, tree.put_run

    def counting_put(key, value):
        puts[0] += 1
        put(key, value)

    def counting_put_run(keys, values):
        puts[0] += len(keys)
        put_run(keys, values)

    tree.put, tree.put_run = counting_put, counting_put_run
    created = _drive_past_checkpoint(cluster)
    # One served lookup, an id among them that no account has: the four
    # spans under a read's commit_execute (the cache holds none of the
    # accounts yet, so the tree's part opens).
    client = cluster.client(10)
    client.request(Operation.lookup_accounts, multi_batch.encode(
        [b"".join(i.to_bytes(16, "little")
                  for i in (*range(1, ACCOUNTS + 1), 999))], 16))
    assert cluster.run(4000, until=lambda: client.idle), \
        cluster.debug_status()
    assert len(multi_batch.decode(client.replies[-1].body, 128)[0]) \
        == 128 * ACCOUNTS
    events = tracers[0].chrome_dict()["traceEvents"]
    return {"replica": replica, "tracer": tracers[0], "events": events,
            "created": created, "transfer_puts": puts[0],
            "grid_batches": batches}


HOST_CHILDREN = ("execute_mirror_drain", "execute_host_judge",
                 "execute_mirror_push")


@pytest.fixture(scope="module")
def host_run():
    """The same replica under a recording tracer, with three requests of
    transfers: one the device judges, one whose balances would overflow
    (the device falls back to the host mirror, which holds the regime),
    and one the hard regime routes to the mirror with no dispatch."""
    tracer = Tracer(pid=0)
    cluster = Cluster(seed=7, replica_count=1,
                      tracer_factory=lambda i: tracer,
                      state_machine_factory=_device_machine)
    client = cluster.client(9)

    def drive(op, transfers):
        client.request(op, multi_batch.encode(
            [b"".join(t.pack() for t in transfers)], 128))
        assert cluster.run(4000, until=lambda: client.idle), \
            cluster.debug_status()

    drive(Operation.create_accounts,
          [Account(id=i, ledger=1, code=1) for i in (1, 2)])
    big = (1 << 128) - 1
    for transfers in (
            [Transfer(id=10, debit_account_id=1, credit_account_id=2,
                      amount=5, ledger=1, code=1)],
            [Transfer(id=11 + k, debit_account_id=1, credit_account_id=2,
                      amount=big, ledger=1, code=1) for k in (0, 1)],
            [Transfer(id=13, debit_account_id=2, credit_account_id=1,
                      amount=1, ledger=1, code=1)]):
        drive(Operation.create_transfers, transfers)
    led = cluster.replicas[0].state_machine.led
    return {"events": tracer.chrome_dict()["traceEvents"],
            "judged": dict(led.judged), "stats": led.fallback_stats()}


@pytest.fixture(scope="module")
def escalate_run():
    """The same replica under a recording tracer, with one request that
    debits an account which must not be overdrawn past its credits: the
    headroom proof fails, the fast tier falls back `limit_only`, and the
    request is dispatched again to the converging tier."""
    tracer = Tracer(pid=0)
    cluster = Cluster(seed=11, replica_count=1,
                      tracer_factory=lambda i: tracer,
                      state_machine_factory=_device_machine)
    client = cluster.client(9)
    for op, body in (
            (Operation.create_accounts, [
                Account(id=1, ledger=1, code=1, flags=int(
                    AccountFlags.debits_must_not_exceed_credits)),
                Account(id=2, ledger=1, code=1)]),
            (Operation.create_transfers, [
                Transfer(id=10 + k, debit_account_id=1, credit_account_id=2,
                         amount=5, ledger=1, code=1) for k in range(3)])):
        client.request(op, multi_batch.encode(
            [b"".join(e.pack() for e in body)], 128))
        assert cluster.run(4000, until=lambda: client.idle), \
            cluster.debug_status()
    led = cluster.replicas[0].state_machine.led
    assert led.judged_kinds["single"]["escalated"] == 1
    return {"events": tracer.chrome_dict()["traceEvents"]}


TWO_PHASE_PAIRS = 3   # requests of pendings, each followed by its posts
TWO_PHASE_WIDTH = 120
TWO_PHASE_STAGES = ("flush_columns", "flush_two_phase")


@pytest.fixture(scope="module")
def two_phase_run():
    """The same replica under a recording tracer, fed the benchmark's
    two-phase deployment (its own generator and configuration file, a
    quarter of the resolutions made voids so that all three counters
    move): pairs of a request of pendings and the request that posts or
    voids each of them. No checkpoint falls inside the run."""
    config = json.loads((REPO / "chipbench" / "configs"
                         / "tb_bench_twophase_1r.json").read_text())
    config["transfers"]["two_phase"].update(post_share=0.75, void_share=0.25)
    dep = Deployment(config, seed=35, accounts_cut=64)
    tracer = Tracer(pid=0)
    cluster = Cluster(seed=5, replica_count=1,
                      tracer_factory=lambda i: tracer,
                      state_machine_factory=_device_machine)
    client = cluster.client(9)
    sent = []
    requests = dep.account_requests(TWO_PHASE_WIDTH) + [
        dep.transfer_request(0, k, TWO_PHASE_WIDTH)
        for k in range(2 * TWO_PHASE_PAIRS)]
    for k, request in enumerate(requests):
        client.request(getattr(Operation, request.operation),
                       wire.encode_one(request.payload, 128))
        assert cluster.run(4000, until=lambda: client.idle), \
            cluster.debug_status()
        sent.append(Sent("window", 0, request, float(k), float(k) + 0.5,
                         results=np.frombuffer(wire.decode_one(
                             client.replies[-1].body, 16), wire.RESULT)))
    replica = cluster.replicas[0]
    assert replica.superblock.op_checkpoint == 0
    return {"replica": replica, "tracer": tracer, "sent": sent,
            "events": tracer.chrome_dict()["traceEvents"]}


# ------------------------------------------------------------ (a) the spans

# The grid's spans carry no `op`: their own test below.
OP_STAGES = sorted(s for s, c in STAGE_CHILDREN.items()
                   if not c[0].startswith("grid_"))
GRID_PARENTS = {"grid_read": ("flush_account_reads", "lookup_tree"),
                "grid_verify": ("flush_account_reads", "lookup_tree"),
                "grid_write": ("compact_beat", "checkpoint_forest")}


@pytest.mark.parametrize("stage", OP_STAGES)
def test_every_child_span_lies_inside_its_parent_and_carries_its_op(
        traced_run, two_phase_run, host_run, escalate_run, stage):
    for child in STAGE_CHILDREN[stage]:
        # The host mirror's spans open only where it judges a request,
        # an escalation only where a limit breaches the headroom proof.
        run = (host_run if child in HOST_CHILDREN else escalate_run
               if stage == "execute_escalate" else two_phase_run
               if stage in TWO_PHASE_STAGES else traced_run)
        spans = [e for e in run["events"] if e["ph"] == "X"]
        parents = {e["args"]["op"]: e for e in spans if e["name"] == stage}
        assert parents
        found = [e for e in spans if e["name"] == child
                 and e["args"]["op"] in parents]
        assert found, f"no {child} span under any {stage}"
        for e in found:
            p = parents[e["args"]["op"]]
            if not (p["ts"] <= e["ts"]
                    and e["ts"] + e["dur"] <= p["ts"] + p["dur"] + 1e-3):
                if stage == "execute_escalate":
                    # The op's proof-gated dispatch, which fell back,
                    # ends before its escalation starts.
                    assert e["ts"] + e["dur"] <= p["ts"], e
                    continue
                # flush_columns / flush_objects also run under a
                # checkpoint's flush, for the same op as a commit_compact.
                assert e["name"] in ("flush_columns", "flush_objects"), e
                assert stage == "commit_compact"


@pytest.mark.parametrize("child", sorted(GRID_PARENTS))
def test_grid_spans_lie_inside_their_callers(traced_run, child):
    spans = [e for e in traced_run["events"] if e["ph"] == "X"]
    found = [e for e in spans if e["name"] == child]
    assert found, f"no {child} span in the traced run"
    owners = set()
    for e in found:
        owner = [p["name"] for p in spans if p["name"] in GRID_PARENTS[child]
                 and p["ts"] <= e["ts"]
                 and e["ts"] + e["dur"] <= p["ts"] + p["dur"] + 1e-3]
        assert owner, f"{child} at {e['ts']} lies in no caller's span"
        owners.update(owner)
    # The run's reads: the flush's after the checkpoint (the lookup's
    # rows are in the memtable again by then); its writes: the
    # checkpoint's table writes (no bar ends in it).
    want = {"grid_write": "checkpoint_forest"}.get(
        child, "flush_account_reads")
    assert want in owners


def test_grid_counters_add_up_in_a_served_run(traced_run):
    replica = traced_run["replica"]
    grid = replica.durable.grid
    assert grid.tracer is traced_run["tracer"]
    stats = grid.held_stats()
    assert stats["reads"] == (stats["cache_hits"] + stats["readahead_hits"]
                              + stats["device_reads"])
    assert stats["device_reads"] > 0 and stats["blocks_written"] > 0
    # The record says the cache's size (one block here) and that it
    # evicted: the misses above are the cache's, not the grid's.
    assert stats["cache_blocks"] == 1 and stats["cache_evictions"] > 0
    # Every value block the flush's reads searched was asked of the grid.
    assert 0 < replica.durable.account_reads["blocks_searched"] \
        <= stats["reads"]
    # One grid_read and one grid_verify a batched read that sent blocks
    # to the device, each over that batch's blocks: never one a block.
    missed = [n for n in traced_run["grid_batches"] if n]
    assert missed
    for name in ("grid_read", "grid_verify"):
        assert [e["args"]["blocks"] for e in traced_run["events"]
                if e["name"] == name] == missed


def test_two_phase_spans_open_only_in_an_op_that_holds_such_a_row(
        traced_run, two_phase_run):
    names = {e["name"] for e in traced_run["events"]}
    assert "flush_columns" in names
    assert not names & {"flush_two_phase", "memtable_fold"}

    spans = [e for e in two_phase_run["events"] if e["ph"] == "X"]
    by_op = {name: {e["args"]["op"]: e for e in spans if e["name"] == name}
             for name in ("commit_execute", "flush_columns",
                          "flush_two_phase", "memtable_fold")}
    transfer_ops = sorted(
        op for op, e in by_op["commit_execute"].items()
        if e["args"]["operation"] == int(Operation.create_transfers))
    assert len(transfer_ops) == 2 * TWO_PHASE_PAIRS
    # Every request of the run holds two-phase rows; only a request of
    # posts and voids reads its pendings by key, so only it folds.
    assert sorted(by_op["flush_two_phase"]) == transfer_ops
    assert sorted(by_op["memtable_fold"]) == transfer_ops[1::2]
    for op, fold in by_op["memtable_fold"].items():
        outer = by_op["flush_two_phase"][op]
        assert outer["ts"] <= fold["ts"] and \
            fold["ts"] + fold["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    for op, loop in by_op["flush_two_phase"].items():
        outer = by_op["flush_columns"][op]
        assert outer["ts"] <= loop["ts"] and \
            loop["ts"] + loop["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    got = children_share(two_phase_run["events"])
    assert got["flush_columns"]["count"] == 2 * TWO_PHASE_PAIRS
    assert 0.0 < got["flush_columns"]["share_mean"] < 1.0
    assert got["flush_two_phase"]["children_mean_ms"]["memtable_fold"] > 0.0


def test_two_phase_counters_equal_the_references_rows(two_phase_run):
    """`start` prints DurableState.two_phase_rows as the shutdown
    record's `two_phase` block: the rows the plain reference holds by
    flag after replaying the same request bytes."""
    order, _ = check.ordered(two_phase_run["sent"])
    reference = StateMachineOracle()
    assert check.replay(reference, order) == 0
    flags = np.array([t.flags for t in reference.transfers.values()])
    want = {"pending": int((flags & F_PENDING != 0).sum()),
            "posted": int((flags & F_POST != 0).sum()),
            "voided": int((flags & F_VOID != 0).sum())}
    durable = two_phase_run["replica"].durable
    assert durable.two_phase_rows == want
    assert want["voided"] > 0 and want["posted"] > want["voided"]
    assert want["pending"] == want["posted"] + want["voided"]
    assert sum(want.values()) == len(reference.transfers)
    # The fold counted what it folded: each resolving request's own run
    # and its pendings' run, in `transfers` and in `xfer_by_ts`.
    assert durable.rows_put["folded"] == 2 * sum(want.values())


def test_checkpoint_flush_holds_a_flush_pass_of_its_own(traced_run):
    spans = [e for e in traced_run["events"] if e["ph"] == "X"]
    (ckpt,) = [e for e in spans if e["name"] == "checkpoint_flush"]
    inside = [e["name"] for e in spans
              if e["name"] in ("flush_columns", "flush_objects")
              and ckpt["ts"] <= e["ts"] < ckpt["ts"] + ckpt["dur"]]
    # The replica pops the queued columns before a checkpoint: its flush
    # is the object path alone.
    assert inside == ["flush_objects"]


@pytest.mark.parametrize("stage,share", [
    ("commit_checkpoint", 0.95), ("commit_compact", 0.90),
    ("commit_execute", 0.90)])
def test_children_account_for_their_parent(traced_run, stage, share):
    events = traced_run["events"]
    got = children_share(events, keep_operation(
        events, int(Operation.create_transfers)))[stage]
    # The acceptance holds every occurrence of a chip run to the share
    # (`python -m tigerbeetle_tpu.trace.span_tree` over its span trace); a test host shared
    # with five other workers preempts where it likes, so here the
    # median occurrence is held to it, and each has children at all.
    assert got["share_median"] >= share, got
    assert got["share_min"] > 0.0, got


def test_host_judged_requests_open_the_host_spans_by_route(host_run):
    """One request the device judged, one it handed to the mirror after
    its dispatch fell back, one the hard regime routed there with no
    dispatch: the `judged` counters and the spans agree."""
    assert host_run["judged"] == {
        "create_accounts_fast": 1, "create_transfers_fast": 1,
        "host_fallback": 1, "host_mirror": 1}
    assert host_run["stats"]["host_fallbacks"] == 2
    assert host_run["stats"]["causes"] == {"e4_overflow": 1}
    spans = [e for e in host_run["events"] if e["ph"] == "X"]
    ops = {e["args"]["op"]: e["args"]["operation"] for e in spans
           if e["name"] == "commit_execute"}
    by_name = {name: [e for e in spans if e["name"] == name]
               for name in (*HOST_CHILDREN, "execute_dispatch")}
    judged = by_name["execute_host_judge"]
    assert [e["args"]["route"] for e in judged] == ["fallback", "mirror"]
    host_ops = [e["args"]["op"] for e in judged]
    assert {ops[op] for op in host_ops} == {int(Operation.create_transfers)}
    assert [e["args"]["op"] for e in by_name["execute_mirror_drain"]] \
        == host_ops
    # A push after each host judgement, and in a pulse (the oracle's
    # expiry runs on the mirror too).
    pushes = by_name["execute_mirror_push"]
    assert [e["args"]["op"] for e in pushes
            if e["args"]["op"] in host_ops] == host_ops
    assert {ops[e["args"]["op"]] for e in pushes
            if e["args"]["op"] not in host_ops} <= {int(Operation.pulse)}
    # Both transfers of the overflowing request fail (the first request
    # left a debit of 5), so it pushes nothing; the last pushes its
    # transfer, its two accounts and its history row.
    assert [e["args"]["rows"] for e in pushes
            if e["args"]["op"] in host_ops] == [0, 4]
    fallback_op, mirror_op = host_ops
    dispatched = [e["args"]["op"] for e in by_name["execute_dispatch"]]
    assert fallback_op in dispatched and mirror_op not in dispatched


def test_an_op_has_one_dispatch_and_the_tier_is_named(traced_run):
    for rec in stage_occurrences(traced_run["events"], "commit_execute"):
        if rec["args"]["operation"] == int(Operation.create_transfers):
            assert rec["children"]["execute_dispatch"] > 0
    dispatches = [e for e in traced_run["events"]
                  if e["name"] == "execute_dispatch"]
    assert {e["args"]["tier"] for e in dispatches} == {
        "create_accounts_fast", "create_transfers_fast"}
    # create_accounts' dispatch carries its own op too, not a stale one.
    executes = {e["args"]["op"] for e in traced_run["events"]
                if e["name"] == "commit_execute"}
    assert {e["args"]["op"] for e in dispatches} <= executes


# --------------------------------------- (b) the tracer survives a rebuild

def test_tracer_survives_the_state_setters_ledger_rebuild(traced_run):
    from tigerbeetle_tpu.oracle.state_machine import StateMachineOracle

    # Replica.open() installs a restored state through the setter.
    replica, tracer = traced_run["replica"], traced_run["tracer"]
    assert replica.state_machine.tracer is tracer
    assert replica.state_machine.led.tracer is tracer
    assert replica.durable.tracer is tracer
    # ... and DurableState hands it to the grid it builds (again at a
    # rollback's rebuild, vsr/replica.py, which builds a DurableState).
    assert replica.durable.grid.tracer is tracer

    sm = _device_machine()
    assert isinstance(sm.led.tracer, NullTracer)
    sm.tracer = tracer
    first = sm.led
    from tigerbeetle_tpu.ops.lazy_mirror import LazyTransferDict
    fresh = StateMachineOracle()
    fresh.transfers = LazyTransferDict()
    sm.state = fresh
    assert sm.led is not first and sm.led.tracer is tracer


# ------------------------------------------- (c) the null tracer's silence

class _CountingNull(NullTracer):
    def __init__(self):
        self.now_calls = 0
        self.recorded = 0

    def now_ns(self) -> int:
        self.now_calls += 1
        return 0

    def record_span(self, *a, **kw) -> None:
        self.recorded += 1


class _NoClock:
    def __getattr__(self, name):
        raise AssertionError(f"the served path read time.{name} "
                             "with tracing off")


def test_null_tracer_records_nothing_and_reads_no_clock(monkeypatch):
    import tigerbeetle_tpu.lsm.grid as grid_mod
    import tigerbeetle_tpu.lsm.tree as tree_mod
    import tigerbeetle_tpu.ops.ledger as ledger_mod
    import tigerbeetle_tpu.state_machine as sm_mod
    import tigerbeetle_tpu.trace.tracer as tracer_mod
    import tigerbeetle_tpu.vsr.durable as durable_mod

    # The two modules that used to time commits, and the grid and the
    # trees, import no clock at all.
    for mod in (sm_mod, durable_mod, grid_mod, tree_mod):
        assert not any(getattr(mod, n, None) is __import__("time")
                       for n in dir(mod)), mod.__name__
    monkeypatch.setattr(ledger_mod, "_time", _NoClock())
    # The tracer module's clock: what a grid span would read.
    monkeypatch.setattr(tracer_mod, "_time", _NoClock())
    cluster = Cluster(seed=4, replica_count=1,
                      state_machine_factory=_device_machine)
    replica = cluster.replicas[0]
    assert isinstance(replica.tracer, NullTracer)
    assert not isinstance(replica.tracer, Tracer)
    assert replica.tracer.now_ns() == 0
    # Every grid path runs: batched reads that miss, writes, counters.
    _tiny_grid_cache(replica)
    _drive_past_checkpoint(cluster)
    assert replica.superblock.op_checkpoint > 0
    assert replica.state_machine.led.tracer is replica.tracer
    grid = replica.durable.grid
    assert grid.tracer is replica.tracer
    assert grid.device_reads > 0 and grid.blocks_written > 0


class _Bus:
    def __init__(self, tracer):
        self.tracer, self.woke_ns = tracer, 0

    def poll(self, timeout):
        self.woke_ns = self.tracer.now_ns()


class _Replica:
    commit_min = 0

    def __init__(self, stop, work_s):
        self.stop, self.work_s = stop, list(work_s)

    def tick(self):
        import time

        time.sleep(self.work_s.pop(0))
        if not self.work_s:
            self.stop.append(1)


def test_serve_records_busy_turns_of_a_millisecond_or_more(capsys):
    from tigerbeetle_tpu.main import LOOP_BUSY_MIN_NS, serve

    tracer, stop = Tracer(), []
    serve(_Bus(tracer), _Replica(stop, [0.0, 0.004, 0.0, 0.003]),
          tracer, stop)
    turns = [e for e in tracer.events if e["name"] == "loop_busy"]
    # The two turns that worked are there (an idle one may be too, on a
    # host that preempted it for a millisecond).
    assert 2 <= len(turns) <= 4
    assert all(e["dur"] * 1e3 >= LOOP_BUSY_MIN_NS for e in turns)
    assert sum(e["dur"] for e in turns) >= 7000
    assert capsys.readouterr().out.startswith("commit=0")

    null, stop = _CountingNull(), []
    serve(_Bus(null), _Replica(stop, [0.002, 0.002]), null, stop)
    assert null.recorded == 0 and null.now_ns() == 0


def test_host_gc_spans_are_on_the_tracers_clock_and_removable():
    tracer = Tracer()
    before = tracer.now_ns()
    remove = install_gc_spans(tracer)
    try:
        gc.collect(0)
        gc.collect(2)
    finally:
        remove()
    after = tracer.now_ns()
    seen = [e for e in tracer.events if e["name"] == "host_gc"]
    assert {e["args"]["generation"] for e in seen} >= {0, 2}
    for e in seen:
        start_ns = e["ts"] * 1000.0 - tracer._epoch_ns
        assert before - 1000 <= start_ns <= after
    n = len(seen)
    gc.collect()
    assert len([e for e in tracer.events if e["name"] == "host_gc"]) == n


# -------------------------------------------------- (d) the row counters

def test_durable_rows_count_what_the_paths_put(traced_run):
    rows = traced_run["replica"].durable.rows_put
    assert rows["column"] == traced_run["created"]
    assert rows["object"] == 0 and rows["checkpoints"] == 1
    # The checkpoint's drain takes in clean what each op's column flush
    # had made durable, so its own flush puts none of those rows again:
    # every row created goes into the object tree once.
    assert rows["object_at_checkpoint"] == 0
    assert traced_run["transfer_puts"] == traced_run["created"]
    # The column rows entered the memtables as runs, and nothing read
    # them back by key: none was folded into a dict.
    assert rows["run"] == rows["column"] and rows["folded"] == 0
    # The tracer's counter holds every path's rows (tag `path`).
    assert traced_run["tracer"].counters["durable_rows_put"] == \
        rows["column"] + rows["run"]


# ------------------------------------------------------- the jit tiers

def test_every_per_batch_tier_is_a_named_program():
    import jax.numpy as jnp

    from tigerbeetle_tpu.ops import fast_kernels as fk

    tiers = [n for n in dir(fk) if n.startswith("create_transfers_")
             and n.endswith("_jit") and "_super" not in n
             and "_chain" not in n]
    assert len(tiers) >= 7
    for n in tiers:
        assert getattr(fk, n).__name__ == n[:-len("_jit")]
    named = fk._tier_jit("create_transfers_probe", lambda x, k: x * k, k=2)
    assert "module @jit_create_transfers_probe" in \
        named.lower(jnp.ones(2)).as_text()


# ------------------------------------------------- the benchmark's readers

def _reader(name):
    path = REPO / "chipbench" / "layer_metrics" / (name + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _context(spans: dict, shutdown=None, dropped=0):
    return {"spans": {"spans": {k: (np.array([s for s, _ in v], float),
                                    np.array([d for _, d in v], float))
                                for k, v in spans.items()},
                      "dropped_events": dropped},
            "window": {"wall_t0": 100.0, "wall_t1": 110.0, "seconds": 10.0},
            "shutdown": shutdown or {"fallback_stats": {}}}


SYNTHETIC = {
    # two ops in the window, one before it
    "commit_execute": [(90.0, 1.0), (101.0, 1.0), (105.0, 1.0)],
    "execute_decode": [(90.1, 0.1), (101.1, 0.1), (105.1, 0.3)],
    "execute_encode": [(101.8, 0.1), (105.8, 0.1)],
    "execute_stage": [(101.2, 0.05), (105.2, 0.05)],
    # the second op escalates: two dispatches
    "execute_dispatch": [(101.3, 0.4), (105.3, 0.2), (105.5, 0.2)],
    "execute_delta_fetch": [(101.7, 0.05), (105.7, 0.15)],
    "commit_compact": [(102.0, 1.0), (106.0, 1.0)],
    # a third flush pass runs under the checkpoint, not under a compact
    "flush_columns": [(102.0, 0.5), (106.0, 0.3)],
    # both ops hold two-phase rows; only the second reads by key
    "flush_two_phase": [(102.1, 0.2), (106.05, 0.1)],
    "memtable_fold": [(106.06, 0.04)],
    "flush_objects": [(102.5, 0.1), (106.3, 0.1), (107.3, 1.0)],
    "compact_beat": [(102.7, 0.2), (106.5, 0.4)],
    "commit_checkpoint": [(107.0, 2.0)],
    "checkpoint_mirror_drain": [(107.0, 0.25)],
    "checkpoint_flush": [(107.25, 1.25)],
    "checkpoint_forest": [(108.5, 0.25)],
    "checkpoint_superblock": [(108.75, 0.25)],
    # the thread is busy from 101 to 109.5, and a turn straddles the end
    "loop_busy": [(90.0, 1.0), (101.0, 8.5), (109.75, 1.0)],
    "host_gc": [(50.0, 1.0), (107.5, 0.5)],
}


@pytest.mark.parametrize("name,want", [
    ("execute_decode_ms", 200.0), ("execute_encode_ms", 100.0),
    ("execute_stage_ms", 50.0), ("execute_dispatch_ms", 400.0),
    ("execute_delta_fetch_ms", 100.0), ("flush_columns_ms", 400.0),
    ("flush_objects_ms", 100.0), ("compact_beat_ms", 300.0),
    ("checkpoint_drain_ms", 250.0), ("checkpoint_flush_ms", 1250.0),
    ("checkpoint_forest_ms", 250.0), ("checkpoint_superblock_ms", 250.0),
    ("replica_busy_share", 87.5), ("replica_protocol_ms", 1375.0),
    ("host_gc_window_share", 5.0),
    ("flush_two_phase_ms", 150.0), ("memtable_fold_ms", 20.0)])
def test_span_readers_on_a_synthetic_trace(name, want):
    read = _reader(name)
    assert read(_context(SYNTHETIC)) == pytest.approx(want)
    # A ring that dropped events, no span trace, or a program without
    # the span (the parent commit): nothing to read, and no raise.
    assert read(_context(SYNTHETIC, dropped=1)) is None
    assert read({**_context({}), "spans": None}) is None
    old = {k: v for k, v in SYNTHETIC.items()
           if k.startswith("commit_")}
    assert read(_context(old)) is None


GRID_RECORD = {"blocks": 8192, "held_at_checkpoint": 100,
               "held_peak": 200, "reads": 1000, "cache_hits": 300,
               "readahead_hits": 100, "device_reads": 600,
               "readahead_submitted": 400, "readahead_wasted": 250,
               "blocks_written": 50, "scrub_reads": 0}
GRID_SPANS = {"grid_read": [(95.0, 1.0), (102.2, 0.5), (109.5, 1.0)],
              "grid_verify": [(102.7, 0.25), (106.1, 0.25)],
              "grid_write": [(107.5, 0.5)]}


@pytest.mark.parametrize("name,want", [
    # 0.5 s and the half of the last span that lies in the window
    ("grid_read_window_share", 10.0),
    ("grid_verify_window_share", 5.0),
    ("grid_write_window_share", 5.0),
    ("grid_cache_miss_share", 60.0),
    ("grid_readahead_used_share", 25.0)])
def test_grid_readers_on_a_synthetic_context(name, want):
    read = _reader(name)
    spans = dict(SYNTHETIC, **GRID_SPANS)
    assert read(_context(spans, {"grid": GRID_RECORD})) == \
        pytest.approx(want)
    # The parent's record: a grid block without the counters, or none.
    old = {k: GRID_RECORD[k] for k in ("blocks", "held_at_checkpoint",
                                       "held_peak")}
    assert read(_context(spans, {"grid": old})) is None
    assert read(_context(spans)) is None
    if name.endswith("_window_share"):
        # A ring that dropped events, or no span trace: nothing.
        assert read(_context(spans, {"grid": GRID_RECORD},
                             dropped=1)) is None
        assert read({**_context({}, {"grid": GRID_RECORD}),
                     "spans": None}) is None
        # The counters are there, no such span in the run: 0.
        assert read(_context(SYNTHETIC, {"grid": GRID_RECORD})) == 0.0


@pytest.mark.parametrize("record, want", [
    # nothing read; nothing read ahead (a host without the native engine)
    ({"reads": 0, "readahead_submitted": 0}, None)])
def test_grid_counter_readers_find_nothing_where_nothing_was_counted(
        record, want):
    grid = dict(GRID_RECORD, **record)
    for name in ("grid_cache_miss_share", "grid_readahead_used_share"):
        assert _reader(name)(_context({}, {"grid": grid})) is want


def test_host_gc_share_reads_zero_when_the_hook_saw_no_pause_in_the_window():
    quiet = dict(SYNTHETIC, host_gc=[(50.0, 1.0)])
    assert _reader("host_gc_window_share")(_context(quiet)) == 0.0


def test_checkpoint_object_rows_reads_the_shutdown_record():
    read = _reader("checkpoint_object_rows")
    rows = {"column": 900, "object": 0, "object_at_checkpoint": 600,
            "checkpoints": 3}
    assert read(_context({}, {"durable_rows": rows})) == 200.0
    assert read(_context({}, {"fallback_stats": {}})) is None
    assert read(_context({}, {"durable_rows": dict(rows, checkpoints=0)})) \
        is None


@pytest.mark.parametrize("shutdown, want", [
    ({"two_phase": {"pending": 500, "posted": 400, "voided": 80},
      "stores": {"transfer_rows": 1000, "t_cap": 4096}}, 98.0),
    # a single-phase cell: the control that its traffic is what its file says
    ({"two_phase": {"pending": 0, "posted": 0, "voided": 0},
      "stores": {"transfer_rows": 1000, "t_cap": 4096}}, 0.0),
    # the parent's record has no such block; an empty store has no share
    ({"stores": {"transfer_rows": 1000, "t_cap": 4096}}, None),
    ({"fallback_stats": {}}, None),
    ({"two_phase": {"pending": 0, "posted": 0, "voided": 0},
      "stores": {"transfer_rows": 0, "t_cap": 4096}}, None)])
def test_two_phase_event_share_reads_the_shutdown_record(shutdown, want):
    got = _reader("two_phase_event_share")(_context({}, shutdown))
    assert got == want


@pytest.mark.parametrize("shutdown, want", [
    ({"forest": {"deepest_level": 1, "tables": 40, "compaction": {
        "jobs": 12, "rows_in": 25_000, "rows_out": 21_000,
        "passed_sorted": 4_000}},
      "stores": {"transfer_rows": 1000, "t_cap": 4096}}, 25.0),
    # no tree left level 0: no job ran
    ({"forest": {"deepest_level": 0, "tables": 9, "compaction": {
        "jobs": 0, "rows_in": 0, "rows_out": 0, "passed_sorted": 0}},
      "stores": {"transfer_rows": 1000, "t_cap": 4096}}, 0.0),
    # the parent's record: a forest block without the counters, or none
    ({"forest": {"deepest_level": 1, "tables": 40},
      "stores": {"transfer_rows": 1000, "t_cap": 4096}}, None),
    ({"stores": {"transfer_rows": 1000, "t_cap": 4096}}, None),
    ({"fallback_stats": {}}, None),
    ({"forest": {"deepest_level": 0, "tables": 9, "compaction": {
        "jobs": 0, "rows_in": 0, "rows_out": 0, "passed_sorted": 0}},
      "stores": {"transfer_rows": 0, "t_cap": 4096}}, None)])
def test_compaction_rows_per_transfer_reads_the_shutdown_record(
        shutdown, want):
    got = _reader("compaction_rows_per_transfer")(_context({}, shutdown))
    assert got == want


def test_every_new_per_layer_entry_has_its_reader_file():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for entry in bench["per_layer"]:
        assert (REPO / "chipbench" / "layer_metrics"
                / (entry["name"] + ".py")).exists(), entry["name"]
