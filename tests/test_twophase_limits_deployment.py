"""The deployment `tb_bench_twophase_limits_1r` served in process:
upstream's benchmark load reserved, then captured (each request of
pendings followed by the request that posts every one of them), on
customer accounts that must not be overdrawn (every second account
flagged `debits_must_not_exceed_credits`, funded once from the operator
account beside it).

One device-engine replica on the small layout, over a file `format`
made, fed the requests `chipbench.traffic.Deployment` builds from the
configuration's own file: the accounts cut, the requests narrower than
the small wire, across a checkpoint and a restart from the file at that
checkpoint's op. The comparison is the benchmark's own
(`chipbench.check.judge`): every reply, every account and a sample of
transfers against the plain reference's replay of the same request
bytes. Beside it, the counters that say who judged each kind of request
(`judged_kinds`) and the escalations' spans; then the readers of the
cell's per-layer metrics, on synthetic contexts.
"""

import collections
import importlib.util
import json
import os
import time

import numpy as np
import pytest

from chipbench import check, run, wire
from chipbench.reference.ledger_types import CreateTransferStatus
from chipbench.traffic import Deployment
from chipbench.window import Sent
from tigerbeetle_tpu import main as tb_main
from tigerbeetle_tpu.state_machine import StateMachine
from tigerbeetle_tpu.testing.cluster import Cluster
from tigerbeetle_tpu.trace import Tracer
from tigerbeetle_tpu.types import Operation
from tigerbeetle_tpu.vsr.storage import FileStorage

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "chipbench", "configs")
NAME = "tb_bench_twophase_limits_1r"
CELL = "twophase_limits.full_batch_1s"
CLUSTER_ID = 0xC1A57E12  # testing.cluster.Cluster's own
# 32 of the configuration's 10,000 accounts, 480 events a request: the
# hot customer accounts' funding runs low within a few pairs, so some
# pendings are declined and their posts find no pending.
ACCOUNTS = 32
A_CAP, T_CAP = 1 << 9, 1 << 14  # `start --small` gives its stores 2^14 rows
WIDTH = 480
RESTART_AT_OP = 16       # the first checkpoint
LAST_OP = 32             # the second
SESSION = 0
KINDS = {"accounts", "single", "pending", "post_void", "mixed"}


def _config(name: str) -> dict:
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def _bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


class _Served:
    """The replica under a recording tracer (one across the restart),
    the requests it was sent with what came back, and the device
    ledger's counters on either side of the restart."""

    def __init__(self, path: str, seed: int):
        self.dep = Deployment(_config(NAME), seed, accounts_cut=ACCOUNTS)
        self.path = path
        assert tb_main.main(
            ["format", f"--cluster={CLUSTER_ID}", "--replica=0",
             "--replica-count=1", "--small", path]) == 0
        self.layout = tb_main._data_file_layout(
            tb_main.build_parser().parse_args(
                ["start", "--addresses=127.0.0.1:1", "--replica=0",
                 "--small", path]))
        self.tracer = Tracer(pid=0)
        self.cluster = Cluster(
            seed=46, replica_count=1, layout=self.layout,
            tracer_factory=lambda i: self.tracer,
            state_machine_factory=lambda: StateMachine(
                engine="device", a_cap=A_CAP, t_cap=T_CAP))
        self._reopen()
        self.client = self.cluster.client(5)
        self.sent: list[Sent] = []
        self.judged: list[dict] = []
        self.kinds: list[dict] = []

    @property
    def replica(self):
        return self.cluster.replicas[0]

    def _reopen(self) -> None:
        self.cluster.crash(0)
        old = self.cluster.storages[0]
        if isinstance(old, FileStorage):
            old.sync()
            old.close()
        # Synchronous IO: the simulator's clock does not wait for a
        # worker thread's write.
        self.cluster.storages[0] = FileStorage(
            self.path, layout=self.layout, async_grid=False)
        self.cluster.restart(0)

    def _keep_counters(self) -> None:
        led = self.replica.state_machine.led
        self.judged.append(dict(led.judged))
        self.kinds.append(json.loads(json.dumps(led.judged_kinds)))

    def request(self, request) -> Sent:
        one = Sent("window", SESSION, request, time.monotonic(),
                   wall_send=time.time())
        self.client.request(getattr(Operation, request.operation),
                            wire.encode_one(request.payload, 128))
        assert self.cluster.run(4000, until=lambda: self.client.idle), \
            self.cluster.debug_status()
        one.t_reply = time.monotonic()
        one.results = np.frombuffer(
            wire.decode_one(self.client.replies[-1].body, 16),
            dtype=wire.RESULT)
        self.sent.append(one)
        return one

    def run(self) -> None:
        for request in self.dep.account_requests(ACCOUNTS // 2):
            self.request(request)
        for request in self.dep.funding_requests(WIDTH):
            self.request(request)
        k, restarted = 0, False
        while self.replica.commit_min < LAST_OP:
            self.request(self.dep.transfer_request(SESSION, k, WIDTH))
            k += 1
            if self.replica.commit_min == RESTART_AT_OP:
                assert self.replica.superblock.op_checkpoint == RESTART_AT_OP
                self._keep_counters()
                self._reopen()
                assert self.replica.commit_min == RESTART_AT_OP
                restarted = True
        assert restarted
        self._keep_counters()

    @property
    def pairs(self) -> list[tuple[Sent, Sent]]:
        """(pendings, posts) in the order sent: the funding request is
        the first create_transfers, then the pairs."""
        transfers = [s for s in self.sent
                     if s.request.operation == "create_transfers"][1:]
        return list(zip(transfers[0::2], transfers[1::2]))

    def total(self, kind: str, key: str) -> int:
        return sum(k.get(kind, {}).get(key, 0) for k in self.kinds)

    def read_back(self, seed: int) -> dict:
        """Every account and a sample of transfer ids, the last
        request's among them, as `chipbench/run.py` `read_back` lays
        them out for the comparison."""
        sm = self.replica.state_machine
        ids = self.dep.account_ids()
        rng = np.random.default_rng(seed)
        pool = np.concatenate([s.request.ids for s in self.sent
                               if s.request.operation == "create_transfers"])
        chosen = np.concatenate([
            pool[rng.choice(len(pool), 2000, replace=False)], pool[-WIDTH:]])
        tids = check.int_ids(np.unique(chosen, axis=0))
        return {
            "accounts": [(ids, b"".join(
                a.pack() for a in sm.lookup_accounts(ids)))],
            "transfers": [(tids, b"".join(
                t.pack() for t in sm.lookup_transfers(tids)))]}


@pytest.fixture(scope="module", params=[4600000001, 2200000046])
def served(request, tmp_path_factory):
    run = _Served(str(tmp_path_factory.mktemp("twophase_limits")
                      / "0_0.tb"), request.param)
    run.run()
    run.events = [e for e in run.tracer.chrome_dict()["traceEvents"]
                  if e.get("ph") == "X"]
    yield run
    run.cluster.storages[0].close()


# ------------------------------------------------------ the configuration

def test_the_file_states_two_phase_on_accounts_that_cannot_overdraw():
    cfg = _config(NAME)
    assert cfg["name"] == NAME
    assert len(cfg["source"]) <= 200
    for words in ("benchmark_load.zig", "src/benchmark.zig",
                  "IS_TWO_PHASE_COMMIT", "debits_must_not_exceed_credits"):
        assert words in cfg["source"], words
    assert "format_args" not in cfg["server"] \
        and "start_args" not in cfg["server"]
    assert "preloaded_count" not in cfg["transfers"]
    (entry,) = [c for c in _bench()["configs"] if c["name"] == NAME]
    assert entry["source"] == cfg["source"]
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == [
        "replica_count", "transfer_count"]
    assert "chipbench/reference/ledger.py" in entry["why"]
    (cell,) = [w for w in _bench()["workloads"] if w["config"] == NAME]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        CELL, "full_batch_1s", 1)
    # The traffic file as it stands, servable as it is.
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           "full_batch_1s.json")) as f:
        run.servable(cfg, json.load(f))


@pytest.mark.parametrize("key, parent", [
    ("server", "tb_bench_limits_1r"),
    ("accounts", "tb_bench_limits_1r"),
    ("transfers", "tb_bench_twophase_1r")])
def test_each_block_is_its_parents_letter_for_letter(key, parent):
    assert _config(NAME)[key] == _config(parent)[key]


def test_the_guarantees_and_assumptions_are_the_union_of_the_parents():
    cfg = _config(NAME)
    limits, two_phase = _config("tb_bench_limits_1r"), \
        _config("tb_bench_twophase_1r")
    assert sorted(cfg) == sorted(limits)
    assert cfg["guarantees"] == {**two_phase["guarantees"],
                                 **limits["guarantees"]}
    assert "limits" in cfg["guarantees"]
    # Every entry of either parent, the same words, but where stated:
    # the two sources in one, the shape, the decline share recounted.
    restated = {"source", "shape", "exceeds_credits_share"}
    ours = {"pending_credit", "voids"}
    assert set(cfg["assumed"]) == \
        set(limits["assumed"]) | set(two_phase["assumed"]) | ours
    for parent in (limits, two_phase):
        for k, v in parent["assumed"].items():
            if k not in restated:
                assert cfg["assumed"][k] == v, k
    assert "1.5%" in cfg["assumed"]["exceeds_credits_share"]
    # The same cuts as both parents; replica_count in the same words.
    assert sorted(cfg["reduced"]) == sorted(limits["reduced"]) \
        == sorted(two_phase["reduced"])
    assert cfg["reduced"]["replica_count"] == \
        limits["reduced"]["replica_count"]


# ------------------------------------------------------------ the served run

def test_replies_accounts_and_transfers_equal_the_references_replay(served):
    numbers = check.judge(served.sent, served.read_back(seed=7))
    assert numbers == dict.fromkeys(check.LIMITS, 0)


def test_declined_pendings_leave_their_posts_no_pending(served):
    exceeds = int(CreateTransferStatus.exceeds_credits)
    not_found = int(CreateTransferStatus.pending_transfer_not_found)
    created = int(CreateTransferStatus.created)
    declined = 0
    for pendings, posts in served.pairs:
        over = pendings.results["status"] == exceeds
        declined += int(over.sum())
        # Every post of a declined pending finds none; no post exceeds.
        assert (posts.results["status"][over] == not_found).all()
        assert not (posts.results["status"] == exceeds).any()
        ok = pendings.results["status"] == created
        assert (posts.results["status"][ok] == created).all()
    assert 0 < declined < 0.5 * WIDTH * len(served.pairs)


def test_the_kinds_sum_to_what_judged_counts(served):
    for judged, kinds in zip(served.judged, served.kinds):
        assert set(kinds) <= KINDS
        by_tier = collections.Counter()
        for c in kinds.values():
            by_tier.update(c["by_tier"])
            assert c["requests"] == sum(c["by_tier"].values())
        assert by_tier == judged
    assert served.total("pending", "requests") == len(served.pairs)
    assert served.total("post_void", "requests") == len(served.pairs)
    assert served.total("single", "requests") == 1      # the funding
    assert served.total("accounts", "requests") == 2
    assert served.total("mixed", "requests") == 0


def test_pendings_escalate_and_posts_take_the_converging_tier(served):
    """Today's router: a request of pendings that breaches the headroom
    proof pays the fast dispatch and is dispatched again to the
    converging tier; the posts after it find the hysteresis set and go
    straight there, converge in the one round that finds no breach and
    clear it, so the next request of pendings pays the proof again. A
    request of pendings that passes the proof (a few, while the
    balances are deep) is judged fast, and so are its posts."""
    pairs = len(served.pairs)
    for k in served.kinds:
        pend, post = k["pending"], k["post_void"]
        assert pend["escalated"] == \
            pend["by_tier"].get("create_transfers_fixpoint", 0)
        assert pend["fixpoint_dispatches"] == pend["escalated"]
        assert post["by_tier"] == pend["by_tier"]
        assert post["escalated"] == 0
        assert post["fixpoint_rounds"] == post["fixpoint_dispatches"]
        assert set(pend["by_tier"]) <= {"create_transfers_fast",
                                        "create_transfers_fixpoint"}
    assert served.total("pending", "escalated") >= 0.75 * pairs
    assert served.total("pending", "fixpoint_rounds") > \
        served.total("pending", "fixpoint_dispatches")


def test_the_escalate_spans_count_the_escalations(served):
    escalated = sum(served.total(k, "escalated") for k in KINDS)
    spans = sorted((e for e in served.events
                    if e["name"] == "execute_escalate"),
                   key=lambda e: e["ts"])
    assert len(spans) == escalated > 0
    dispatches = [e for e in served.events if e["name"] == "execute_dispatch"]
    for esc in spans:
        inside = [d for d in dispatches if esc["ts"] <= d["ts"]
                  and d["ts"] + d["dur"] <= esc["ts"] + esc["dur"] + 1e-3]
        # The dispatch that judged lies inside; the wasted one before.
        assert [d["args"]["tier"] for d in inside] == [
            "create_transfers_fixpoint"]
        assert inside[0]["args"]["op"] == esc["args"]["op"]
        before = [d for d in dispatches if d["args"]["op"] == esc["args"]["op"]
                  and d["ts"] < esc["ts"]]
        assert [d["args"]["tier"] for d in before] == [
            "create_transfers_fast"]


# ------------------------------------------------------------- the readers

def _reader(name: str):
    path = os.path.join(ROOT, "chipbench", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


KINDS_RECORD = {
    "accounts": {"requests": 2, "by_tier": {"create_accounts_fast": 2},
                 "escalated": 0, "fixpoint_dispatches": 0,
                 "fixpoint_rounds": 0},
    "pending": {"requests": 25, "by_tier": {"create_transfers_fixpoint": 24,
                                            "create_transfers_fast": 1},
                "escalated": 20, "fixpoint_dispatches": 24,
                "fixpoint_rounds": 1800},
    "post_void": {"requests": 25,
                  "by_tier": {"create_transfers_fixpoint": 15,
                              "create_transfers_fast": 10},
                  "escalated": 0, "fixpoint_dispatches": 15,
                  "fixpoint_rounds": 15},
}


@pytest.mark.parametrize("name, want", [
    ("pending_escalated_share", 80.0),      # 20 of 25
    ("post_converging_share", 60.0),        # 15 of 25
    ("pending_fixpoint_rounds", 75.0)])     # 1,800 over 24 dispatches
def test_kind_readers_read_the_records_block(name, want):
    read = _reader(name)
    assert read({"shutdown": {"judged_kinds": KINDS_RECORD}}) == \
        pytest.approx(want)
    # The parent's program: no such block.
    assert read({"shutdown": {"judged": {"create_transfers_fast": 3}}}) \
        is None
    # A run with no request of the kind the reader reads.
    single = {"single": {"requests": 3,
                         "by_tier": {"create_transfers_fast": 3},
                         "escalated": 0, "fixpoint_dispatches": 0,
                         "fixpoint_rounds": 0}}
    assert read({"shutdown": {"judged_kinds": single}}) is None


def _span_context(spans: dict, dropped: int = 0) -> dict:
    by_name = {k: (np.array([s for s, _, _ in v]),
                   np.array([d for _, d, _ in v])) for k, v in spans.items()}
    return {"shutdown": {"judged_kinds": KINDS_RECORD},
            "window": {"wall_t0": 100.0, "wall_t1": 110.0},
            "spans": {"spans": by_name, "dropped_events": dropped,
                      "op": {"commit_execute": np.array(
                          [op for _, _, op in spans["commit_execute"]])},
                      "read_ops": np.array([], dtype=np.int64)}}


# Four create requests inside a window of 100..110 s, the first and
# third escalated, and one after it (op 5, escalated too).
SPANS = {
    "commit_execute": [(101.0, 0.2, 1), (102.0, 0.2, 2), (103.0, 0.2, 3),
                       (104.0, 0.2, 4), (120.0, 0.2, 5)],
    "execute_escalate": [(101.05, 0.1, 1), (103.05, 0.12, 3),
                         (120.05, 0.5, 5)],
}


def test_escalate_ms_is_the_mean_escalation_of_the_window():
    read = _reader("escalate_ms")
    assert read(_span_context(SPANS)) == pytest.approx(110.0)
    # A ring that dropped events, or no span trace: nothing to read.
    assert read(_span_context(SPANS, dropped=1)) is None
    assert read({**_span_context(SPANS), "spans": None}) is None
    # The parent's program, or a window with no escalation: no span.
    parent = {"commit_execute": SPANS["commit_execute"]}
    assert read(_span_context(parent)) is None


def test_the_cells_metrics_list_the_new_cell_alone():
    bench = _bench()
    tail = bench["per_layer"][-4:]
    assert [(e["name"], e["unit"], e["better"], e["layer"], e["moves"],
             e["workloads"]) for e in tail] == [
        ("pending_escalated_share", "%", "lower", "device ledger",
         "request_p50_ms", [CELL]),
        ("post_converging_share", "%", "lower", "device ledger",
         "accepted_tps", [CELL]),
        ("pending_fixpoint_rounds", "rounds", "lower", "kernels",
         "accepted_tps", [CELL]),
        ("escalate_ms", "ms", "lower", "device ledger", "request_p50_ms",
         [CELL])]
    by_name = {e["name"]: e for e in bench["end_to_end"]}
    for e in tail:
        assert "workloads" not in by_name[e["moves"]], e["name"]
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == NAME


# The accepted metrics whose spans or counters the cell's window opens:
# the converging tier's kernel, roofline and rounds, and the column
# flush with its two-phase loop.
@pytest.mark.parametrize("name", [
    "kernel_ms.create_transfers_fixpoint",
    "create_transfers_fixpoint_roofline", "fixpoint_rounds",
    "flush_two_phase_ms", "memtable_fold_ms", "flush_columns_ms",
    "execute_delta_fetch_ms", "flush_account_reads_ms"])
def test_the_accepted_metrics_it_reads_list_the_new_cell_last(name):
    (entry,) = [e for e in _bench()["per_layer"] if e["name"] == name]
    assert entry["workloads"][-1] == CELL
    assert entry["workloads"].count(CELL) == 1
