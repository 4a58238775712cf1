"""The deployment `tb_bench_many_accounts_1r` served in process (ISSUE
38): upstream's benchmark over a population that no longer sits in the
accounts tree's memtable and the served replica's object cache, under
the read mix (`chipbench/traffic/read_mix_1s.json`: every second request
a `lookup_accounts`).

One device-engine replica on the small layout, over a file `format`
made, fed the requests `chipbench.traffic.Deployment` builds from the
configuration's own file: the accounts cut to what `--small` holds, the
object cache attached a sixteenth of them, the requests narrower than
the small wire, through three checkpoints. The comparison is the
benchmark's own (`chipbench.check.judge`): every reply, every read's
rows at its place in the commit order, and every account and a sample
of transfers read back, against the plain reference's replay of the
same request bytes. Beside it, the spans and counters that say what an
account read costs the flush and a lookup: they have to count what the
requests say, and the mechanism has to work (rows read from tables,
lookups that miss the cache).
"""

import importlib.util
import json
import os
import time

import numpy as np
import pytest

from chipbench import check, wire
from chipbench.traffic import Deployment
from chipbench.window import Sent
from tigerbeetle_tpu import constants
from tigerbeetle_tpu import main as tb_main
from tigerbeetle_tpu.state_machine import StateMachine
from tigerbeetle_tpu.testing.cluster import Cluster
from tigerbeetle_tpu.trace import Tracer
from tigerbeetle_tpu.trace.span_tree import (children_share, keep_operation,
                                             stage_occurrences)
from tigerbeetle_tpu.types import Operation
from tigerbeetle_tpu.vsr.storage import FileStorage

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "chipbench", "configs")
CONFIG = os.path.join(CONFIGS, "tb_bench_many_accounts_1r.json")
MIX = os.path.join(ROOT, "chipbench", "traffic", "read_mix_1s.json")
CLUSTER_ID = 0xC1A57E12  # testing.cluster.Cluster's own
ACCOUNTS = 2000          # of the configuration's 1,000,000
A_CAP, T_CAP = 1 << 12, 1 << 14  # what `start --small` gives its stores
CACHE_SETS, CACHE_WAYS = 16, 8   # 128 accounts: a sixteenth of them
WIDTH = 400              # events a write: 24 of them stay under T_CAP
IDS = 452                # ids a lookup: the small wire's most
LAST_OP = 52             # past the third checkpoint (ops 16, 32, 48)
SESSION = 0


class _Served:
    """The replica under a recording tracer, and the requests it was
    sent with what came back."""

    def __init__(self, path: str, seed: int):
        with open(CONFIG) as f:
            self.config = json.load(f)
        with open(MIX) as f:
            self.mix = json.load(f)
        self.dep = Deployment(self.config, seed, accounts_cut=ACCOUNTS)
        assert tb_main.main(
            ["format", f"--cluster={CLUSTER_ID}", "--replica=0",
             "--replica-count=1", "--small", path]) == 0
        layout = tb_main._data_file_layout(
            tb_main.build_parser().parse_args(
                ["start", "--addresses=127.0.0.1:1", "--replica=0",
                 "--small", path]))
        self.tracer = Tracer(pid=0)
        self.cluster = Cluster(
            seed=38, replica_count=1, layout=layout,
            tracer_factory=lambda i: self.tracer,
            state_machine_factory=lambda: StateMachine(
                engine="device", a_cap=A_CAP, t_cap=T_CAP))
        self.cluster.crash(0)
        # Synchronous IO: the simulator's clock does not wait for a
        # worker thread's write.
        self.cluster.storages[0] = FileStorage(path, layout=layout,
                                               async_grid=False)
        self.cluster.restart(0)
        # The simulator turns the extra checks on; `start` serves
        # without them (a lookup's four tree reads of cache against
        # tree lie under no span), and the reference holds every answer.
        constants.set_verify(False)
        self.replica = self.cluster.replicas[0]
        # The served replica's cache, at a size the population outgrows
        # as 1,000,000 accounts outgrow `start`'s 8,192.
        self.replica.state_machine.attach_durable(
            self.replica.durable, cache_sets=CACHE_SETS, ways=CACHE_WAYS)
        self.client = self.cluster.client(5)
        self.sent: list[Sent] = []
        self.reads_before_first_checkpoint: dict = {}

    def request(self, request) -> Sent:
        one = Sent("window", SESSION, request, time.monotonic(),
                   wall_send=time.time())
        self.client.request(
            getattr(Operation, request.operation),
            wire.encode_one(request.payload, request.event_size))
        assert self.cluster.run(4000, until=lambda: self.client.idle), \
            self.cluster.debug_status()
        one.t_reply = time.monotonic()
        one.results = np.frombuffer(
            wire.decode_one(self.client.replies[-1].body,
                            request.result.itemsize), dtype=request.result)
        self.sent.append(one)
        return one

    def run(self) -> None:
        for request in self.dep.account_requests(500):
            self.request(request)
        interval = self.replica.options.checkpoint_interval
        k = 0
        while self.replica.commit_min < LAST_OP:
            self.request(self.dep.session_request(
                self.mix, SESSION, k, WIDTH, IDS))
            k += 1
            if self.replica.commit_min == interval - 1:
                self.reads_before_first_checkpoint = dict(
                    self.replica.durable.account_reads)
        assert self.replica.superblock.op_checkpoint == 48
        self.window_reads = dict(self.replica.durable.account_reads)
        self.window_cache = self.replica.state_machine.account_cache_stats()

    @property
    def writes(self) -> list[Sent]:
        return [s for s in self.sent
                if s.request.operation == "create_transfers"]

    @property
    def lookups(self) -> list[Sent]:
        return [s for s in self.sent if s.request.is_read]

    def read_back(self, seed: int) -> dict:
        """Every account through served lookups as wide as the wire
        admits, and a sample of transfer ids, the last write's among
        them, as `chipbench/run.py` `read_back` lays them out."""
        sm = self.replica.state_machine
        ids = self.dep.account_ids()
        accounts = []
        for i in range(0, len(ids), IDS):
            chunk = ids[i:i + IDS]
            self.client.request(Operation.lookup_accounts, wire.encode_one(
                wire.ids_payload(chunk), wire.ID_SIZE))
            assert self.cluster.run(4000, until=lambda: self.client.idle)
            accounts.append((chunk, wire.decode_one(
                self.client.replies[-1].body, 128)))
        rng = np.random.default_rng(seed)
        pool = np.concatenate([s.request.ids for s in self.writes])
        chosen = np.concatenate([
            pool[rng.choice(len(pool), 2000, replace=False)], pool[-WIDTH:]])
        tids = check.int_ids(np.unique(chosen, axis=0))
        return {"accounts": accounts,
                "transfers": [(tids, b"".join(
                    t.pack() for t in sm.lookup_transfers(tids)))]}


@pytest.fixture(scope="module", params=[3380000001, 2200000038])
def served(request, tmp_path_factory):
    run = _Served(str(tmp_path_factory.mktemp("many_accounts") / "0_0.tb"),
                  request.param)
    run.run()
    run.events = run.tracer.chrome_dict()["traceEvents"]
    run.numbers = check.judge(run.sent, run.read_back(seed=7))
    yield run
    run.cluster.storages[0].close()


# ------------------------------------------------------ the configuration

def _config(name: str) -> dict:
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_the_file_states_upstreams_benchmark_over_a_million_accounts():
    cfg = _config("tb_bench_many_accounts_1r")
    size = cfg["accounts"]["count"]
    assert size in (1_000_000, 500_000, 250_000)  # ISSUE 38's size rule
    # a cut below the million states the seconds that forced it
    assert ("account_count" in cfg["reduced"]) == (size != 1_000_000)
    a_cap = 1 << (size - 1).bit_length()
    assert cfg["server"]["start_args"] == [f"--account-capacity={a_cap}"]
    (grid,) = cfg["server"]["format_args"]
    blocks = int(grid.removeprefix("--grid-blocks="))
    assert blocks & (blocks - 1) == 0
    assert "--account-count" in cfg["source"]
    assert "NUM_ACCOUNTS" in cfg["source"]
    assert len(cfg["source"]) <= 200
    assert "preloaded_count" not in cfg["transfers"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [c for c in bench["configs"] if c["name"] == cfg["name"]]
    assert entry["source"] == cfg["source"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert "chipbench/reference/ledger.py" in entry["why"]
    (cell,) = [w for w in bench["workloads"] if w["config"] == cfg["name"]]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        "many_accounts.read_mix_1s", "read_mix_1s", 1)


@pytest.mark.parametrize("key", [
    "server", "accounts", "transfers", "guarantees", "assumed", "reduced"])
def test_every_other_shape_is_the_default_deployments(key):
    """Key by key against `tb_bench_default_1r.json`: the population,
    the two sizes it forces and the words that say so are the only
    differences."""
    many = _config("tb_bench_many_accounts_1r")
    default = _config("tb_bench_default_1r")
    assert sorted(many) == sorted(default)
    ours = {
        "server": ("format_args", "start_args"),
        "accounts": ("count",),
        "transfers": (),
        "guarantees": (),
        "assumed": ("account_count", "source", "sizes"),
        "reduced": ("transfer_count", "account_count"),
    }[key]
    a, b = dict(many[key]), dict(default[key])
    for k in ours:
        a.pop(k, None)
        b.pop(k, None)
    assert a == b
    for k in ours:
        if k != "account_count" or key != "reduced":
            assert k in many[key], k


# ------------------------------------------------------------ the served run

def test_replies_reads_accounts_and_transfers_equal_the_references_replay(
        served):
    assert served.numbers == dict.fromkeys(check.LIMITS, 0)
    assert len(served.writes) >= 20 and len(served.lookups) >= 20
    # a lookup answers a row per id found; 0.3% of the ids name no account
    rows = sum(len(s.results) for s in served.lookups)
    assert 0.98 * IDS * len(served.lookups) < rows < IDS * len(served.lookups)


def test_every_request_is_judged_on_the_device(served):
    stats = served.replica.state_machine.led.fallback_stats()
    creates = len(served.sent) - len(served.lookups)
    assert stats["fast_batches"] == creates
    assert stats["host_fallbacks"] == 0 and stats["causes"] == {}


def test_flush_reads_count_the_distinct_accounts_of_what_was_created(served):
    """`flush_reads` is the keys the column flush asked of the accounts
    tree: the distinct debit and credit accounts of each write's created
    events."""
    want = 0
    for s in served.writes:
        ev = np.frombuffer(s.request.payload, dtype=wire.TRANSFER)
        made = ev[s.results["status"] == wire.CREATED]
        want += len(np.unique(np.concatenate([
            np.stack([made["debit_lo"], made["debit_hi"]], axis=1),
            np.stack([made["credit_lo"], made["credit_hi"]], axis=1)]),
            axis=0))
    assert served.window_reads["flush_reads"] == want


def test_the_mechanism_works_rows_leave_the_memtable_and_the_cache(served):
    early, reads = served.reads_before_first_checkpoint, served.window_reads
    # Until the first checkpoint freezes it, the memtable holds every
    # account; afterwards a write's tail accounts lie in tables.
    assert early["flush_reads"] > 0
    assert early["flush_reads_from_tables"] == 0 == early["table_probes"]
    assert 0 < reads["flush_reads_from_tables"] < reads["flush_reads"]
    assert reads["table_probes"] >= reads["flush_reads_from_tables"]
    cache = served.window_cache
    ids = sum(s.request.n_events for s in served.lookups)
    assert cache["cache_hits"] + cache["cache_misses"] == ids
    assert cache["cache_misses"] > 0 and cache["cache_hits"] > 0
    # 2,000 accounts through a cache of 128
    assert cache["cache_evictions"] > 0


def test_a_lookups_child_spans_cover_its_commit_execute(served):
    lookups = keep_operation(served.events, int(Operation.lookup_accounts))
    got = children_share(served.events, lookups)["commit_execute"]
    assert got["count"] >= len(served.lookups)
    # A host shared with other test workers preempts where it likes: the
    # median lookup is held to the share, and the run as a whole.
    assert got["share_median"] >= 0.95, got
    assert got["share_mean"] >= 0.95, got
    for name in ("lookup_ids", "lookup_cache", "lookup_tree", "lookup_pack"):
        assert got["children_mean_ms"][name] > 0.0, name
    for name in ("execute_stage", "execute_dispatch", "execute_delta_fetch"):
        assert got["children_mean_ms"][name] == 0.0, name


def test_flush_account_reads_opens_once_a_write_inside_flush_columns(served):
    writes = keep_operation(served.events, int(Operation.create_transfers))
    occ = [r for r in stage_occurrences(served.events, "flush_columns")
           if writes("flush_columns", r)]
    assert len(occ) == len(served.writes)
    for r in occ:
        assert 0.0 < r["children"]["flush_account_reads"] < r["dur"]
        assert r["children"]["flush_two_phase"] == 0.0  # single-phase


# ------------------------------------------------------------- the readers

def _reader(name: str):
    path = os.path.join(ROOT, "chipbench", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


ACCOUNTS_BLOCK = {"cache_hits": 600, "cache_misses": 200,
                  "cache_evictions": 50, "flush_reads": 8000,
                  "flush_reads_from_tables": 5000, "table_probes": 12000}


@pytest.mark.parametrize("name, shutdown, want", [
    ("account_store_fill_share",
     {"stores": {"a_cap": 1 << 20, "account_rows": 1_000_000,
                 "t_cap": 1 << 21, "transfer_rows": 5}}, 100e6 / (1 << 20)),
    ("account_store_fill_share", {"fallback_stats": {}}, None),
    ("flush_account_table_read_share", {"accounts": ACCOUNTS_BLOCK}, 62.5),
    # the parent's record has no such block; a run that flushed nothing
    ("flush_account_table_read_share", {"stores": {}}, None),
    ("flush_account_table_read_share",
     {"accounts": dict(ACCOUNTS_BLOCK, flush_reads=0)}, None),
    ("lookup_cache_miss_share", {"accounts": ACCOUNTS_BLOCK}, 25.0),
    ("lookup_cache_miss_share", {"stores": {}}, None),
    ("lookup_cache_miss_share",
     {"accounts": dict(ACCOUNTS_BLOCK, cache_hits=0, cache_misses=0)}, None)])
def test_counter_readers_read_the_shutdown_record(name, shutdown, want):
    got = _reader(name)({"shutdown": shutdown})
    assert got == (want if want is None else pytest.approx(want))


# Two writes (ops 1, 3) and two lookups (ops 2, 4) inside a window of
# 100..110 s, and a lookup of the read-back after it (op 5).
SPANS = {
    "commit_execute": [(101.0, 0.2, 1), (102.0, 0.4, 2), (103.0, 0.2, 3),
                       (104.0, 0.6, 4), (120.0, 1.0, 5)],
    "commit_compact": [(101.3, 0.6, 1), (102.5, 0.01, 2), (103.3, 0.8, 3),
                       (104.7, 0.01, 4), (121.1, 0.01, 5)],
    "flush_columns": [(101.3, 0.5, 1), (103.3, 0.7, 3)],
    "flush_account_reads": [(101.35, 0.3, 1), (103.35, 0.5, 3)],
    "lookup_cache": [(102.0, 0.05, 2), (104.0, 0.07, 4), (120.0, 0.1, 5)],
    # the first lookup's ids all hit
    "lookup_tree": [(104.1, 0.3, 4), (120.1, 0.6, 5)],
    "lookup_pack": [(102.1, 0.3, 2), (104.4, 0.2, 4), (120.7, 0.3, 5)],
}


def _span_context(spans: dict, dropped: int = 0) -> dict:
    by_name = {k: (np.array([s for s, _, _ in v]),
                   np.array([d for _, d, _ in v])) for k, v in spans.items()}
    return {"window": {"wall_t0": 100.0, "wall_t1": 110.0},
            "spans": {"spans": by_name, "dropped_events": dropped,
                      "op": {k: np.array([op for _, _, op in spans[k]])
                             for k in ("commit_execute", "commit_compact")
                             if k in spans},
                      "read_ops": np.array([2, 4, 5])}}


@pytest.mark.parametrize("name, want", [
    ("flush_account_reads_ms", 400.0), ("lookup_cache_ms", 60.0),
    ("lookup_tree_ms", 150.0), ("lookup_pack_ms", 250.0)])
def test_span_readers_read_the_windows_ops_of_their_kind(name, want):
    read = _reader(name)
    assert read(_span_context(SPANS)) == pytest.approx(want)
    # A ring that dropped events, no span trace, or a program without
    # the span (the parent commit): nothing to read, and no raise.
    assert read(_span_context(SPANS, dropped=1)) is None
    assert read({**_span_context(SPANS), "spans": None}) is None
    parent = {k: v for k, v in SPANS.items()
              if k.startswith("commit_") or k == "flush_columns"}
    assert read(_span_context(parent)) is None
    if name.startswith("lookup_"):
        # a window with no read (every other cell) reports none
        writes = {k: [x for x in v if x[2] in (1, 3)]
                  for k, v in SPANS.items()}
        no_reads = _span_context({k: v for k, v in writes.items() if v})
        no_reads["spans"]["read_ops"] = np.array([], dtype=np.int64)
        assert read(no_reads) is None


def test_the_seven_metrics_are_appended_with_the_cells_that_report_them():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    tail = bench["per_layer"][-7:]
    reads = ["default.read_mix_1s", "many_accounts.read_mix_1s"]
    assert [(e["name"], e["layer"], e.get("workloads")) for e in tail] == [
        ("account_store_fill_share", "device ledger", None),
        ("flush_account_reads_ms", "durable flush", None),
        ("flush_account_table_read_share", "durable flush", None),
        ("lookup_tree_ms", "state machine", reads),
        ("lookup_pack_ms", "state machine", reads),
        ("lookup_cache_ms", "state machine", reads),
        ("lookup_cache_miss_share", "state machine", reads)]
    by_name = {e["name"]: e for e in bench["end_to_end"]}
    for e in tail:
        # every cell of a metric's list reports the metric it moves
        assert "workloads" not in by_name[e["moves"]], e["name"]
    # the accepted read metrics keep the one cell they named
    assert by_name["lookup_p50_ms"]["workloads"] == ["default.read_mix_1s"]
