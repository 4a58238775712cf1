"""The deployment `tb_bench_limits_1r` served in process: upstream's
benchmark load on customer accounts that must not be overdrawn (every
second account flagged `debits_must_not_exceed_credits`, funded once
from the operator accounts beside it).

One device-engine replica on the small layout, over a file `format`
made, fed the requests `chipbench.traffic.Deployment` builds from the
configuration's own file: the accounts cut, the requests narrower than
the small wire, across a checkpoint and a restart from the file at that
checkpoint's op. The comparison is the benchmark's own
(`chipbench.check.judge`): every reply, every account and a sample of
transfers against the plain reference's replay of the same request
bytes. Beside it, the spans and counters that say who judged each
request: the device's tiers and the host mirror, by route (one request
the device cannot judge, a payment sent twice, hands the ledger to the
mirror for a while). Then the readers of the cell's
per-layer metrics, on synthetic contexts.
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
from tigerbeetle_tpu.ops.ledger import DeviceLedger
from tigerbeetle_tpu.state_machine import StateMachine
from tigerbeetle_tpu.testing.cluster import Cluster
from tigerbeetle_tpu.trace import Tracer
from tigerbeetle_tpu.types import Operation
from tigerbeetle_tpu.vsr.storage import FileStorage

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "chipbench", "configs")
CONFIG = os.path.join(CONFIGS, "tb_bench_limits_1r.json")
CLUSTER_ID = 0xC1A57E12  # testing.cluster.Cluster's own
# 32 of the configuration's 10,000 accounts, 480 events a request: each
# limited account takes enough of a request's debits that, once its
# funding runs low, some cascades outgrow the 32 rounds an unrolled tier
# stops at (the cell's 8,189-event requests over 10,000 accounts outgrow
# them in nearly every request); the converging tier judges them all.
ACCOUNTS = 32
A_CAP, T_CAP = 1 << 9, 1 << 14  # `start --small` gives its stores 2^14 rows
WIDTH = 480
RESTART_AT_OP = 16       # the first checkpoint
LAST_OP = 32             # the second
SESSION = 0
HOST = ("host_mirror", "host_fallback")
DUPLICATE_AFTER = 3      # transfer requests before the one the host judges
DUPLICATE_STREAM = 0x7F00


def duplicate_request(dep: Deployment):
    """A payment sent twice in one request, the same id and fields: the
    reference answers the second `exists`. The device's tiers cannot
    judge an id that repeats inside a batch, so the host mirror judges
    the request and holds the hard regime for the requests after it."""
    rec = np.frombuffer(dep.transfer_request(DUPLICATE_STREAM, 0, 2).payload,
                        dtype=wire.TRANSFER).copy()
    rec[1] = rec[0]
    return Deployment._request("create_transfers", rec)


class _Served:
    """The replica under a recording tracer (one across the restart),
    the requests it was sent with what came back, and the device
    ledger's counters on either side of the restart."""

    def __init__(self, path: str, seed: int):
        with open(CONFIG) as f:
            self.config = json.load(f)
        self.dep = Deployment(self.config, seed, accounts_cut=ACCOUNTS)
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
            seed=44, replica_count=1, layout=self.layout,
            tracer_factory=lambda i: self.tracer,
            state_machine_factory=lambda: StateMachine(
                engine="device", a_cap=A_CAP, t_cap=T_CAP))
        self._reopen()
        self.client = self.cluster.client(5)
        self.sent: list[Sent] = []
        self.judged: list[dict] = []
        self.stats: list[dict] = []

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
        self.stats.append(led.fallback_stats())

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
            if k == DUPLICATE_AFTER:
                self.request(duplicate_request(self.dep))
            if self.replica.commit_min == RESTART_AT_OP:
                assert self.replica.superblock.op_checkpoint == RESTART_AT_OP
                self._keep_counters()
                self._reopen()
                assert self.replica.commit_min == RESTART_AT_OP
                restarted = True
        assert restarted
        self._keep_counters()

    @property
    def transfer_requests(self) -> list[Sent]:
        return [s for s in self.sent
                if s.request.operation == "create_transfers"]

    def total(self, key: str) -> int:
        return sum(j.get(key, 0) for j in self.judged)

    def read_back(self, seed: int) -> dict:
        """Every account and a sample of transfer ids, the last
        request's among them, as `chipbench/run.py` `read_back` lays
        them out for the comparison."""
        sm = self.replica.state_machine
        ids = self.dep.account_ids()
        rng = np.random.default_rng(seed)
        pool = np.concatenate([s.request.ids for s in self.transfer_requests])
        chosen = np.concatenate([
            pool[rng.choice(len(pool), 2000, replace=False)], pool[-WIDTH:]])
        tids = check.int_ids(np.unique(chosen, axis=0))
        return {
            "accounts": [(ids, b"".join(
                a.pack() for a in sm.lookup_accounts(ids)))],
            "transfers": [(tids, b"".join(
                t.pack() for t in sm.lookup_transfers(tids)))]}


@pytest.fixture(scope="module", params=[4400000001, 2200000044])
def served(request, tmp_path_factory):
    run = _Served(str(tmp_path_factory.mktemp("limits") / "0_0.tb"),
                  request.param)
    run.run()
    run.events = [e for e in run.tracer.chrome_dict()["traceEvents"]
                  if e.get("ph") == "X"]
    yield run
    run.cluster.storages[0].close()


# ------------------------------------------------------ the configuration

def _config(name: str) -> dict:
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def _bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_file_states_upstreams_benchmark_on_accounts_that_cannot_overdraw():
    cfg = _config("tb_bench_limits_1r")
    assert cfg["accounts"]["limit_flag"] == "debits_must_not_exceed_credits"
    assert "debits_must_not_exceed_credits" in cfg["source"]
    assert "benchmark_load.zig" in cfg["source"]
    assert len(cfg["source"]) <= 200
    assert "format_args" not in cfg["server"] \
        and "start_args" not in cfg["server"]
    assert "preloaded_count" not in cfg["transfers"]
    # the guarantee in the words of the harness's limits fixture
    fixture = os.path.join(ROOT, "chipbench", "tests", "fixtures",
                           "twophase_limits.json")
    with open(fixture) as f:
        assert cfg["guarantees"]["limits"] == \
            json.load(f)["guarantees"]["limits"]
    (entry,) = [c for c in _bench()["configs"] if c["name"] == cfg["name"]]
    assert entry["source"] == cfg["source"]
    assert entry["file"] == "chipbench/configs/tb_bench_limits_1r.json"
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert "chipbench/reference/ledger.py" in entry["why"]
    (cell,) = [w for w in _bench()["workloads"] if w["config"] == cfg["name"]]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        "limits.full_batch_1s", "full_batch_1s", 1)


@pytest.mark.parametrize("key", [
    "server", "accounts", "transfers", "guarantees", "assumed", "reduced"])
def test_every_other_shape_is_the_default_deployments(key):
    """Key by key against `tb_bench_default_1r.json`: the limit, its
    funding, the guarantee it adds and the words that say so are the
    only differences."""
    limits = _config("tb_bench_limits_1r")
    default = _config("tb_bench_default_1r")
    assert sorted(limits) == sorted(default)
    ours = {
        "server": (),
        "accounts": ("limited_every", "limit_flag", "funding_amount",
                     "funding_events_per_request"),
        "transfers": (),
        "guarantees": ("limits",),
        "assumed": ("limited_every", "funding_amount",
                    "funding_events_per_request", "clash_drift",
                    "exceeds_credits_share", "source"),
        # the same cuts, each said again for this file
        "reduced": tuple(default["reduced"]),
    }[key]
    a, b = dict(limits[key]), dict(default[key])
    for k in ours:
        assert k in limits[key], k
        a.pop(k)
        b.pop(k, None)
    assert a == b


def test_set_up_funds_every_customer_account_in_one_request():
    cfg = _config("tb_bench_limits_1r")
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           "full_batch_1s.json")) as f:
        mix = json.load(f)
    run.servable(cfg, mix)
    dep = Deployment(cfg, 4400000001)
    funding, widths = run.plan_setup(dep, mix, 8189, 8189, 500_000)
    assert [r.n_events for r in funding] == [5000] and widths == []
    ev = np.frombuffer(funding[0].payload, dtype=wire.TRANSFER)
    assert (ev["amount_lo"] == 20000).all()
    limited = dep.limited
    assert limited.sum() == 5000 and limited[0]
    credited = check.int_ids(np.stack([ev["credit_lo"], ev["credit_hi"]], 1))
    assert sorted(credited) == sorted(
        i for i, lim in zip(dep.account_ids(), limited) if lim)


# ------------------------------------------------------------ the served run

def test_replies_accounts_and_transfers_equal_the_references_replay(served):
    numbers = check.judge(served.sent, served.read_back(seed=7))
    assert numbers == dict.fromkeys(check.LIMITS, 0)


def test_some_payments_are_declined_for_exceeding_credits(served):
    exceeds = int(CreateTransferStatus.exceeds_credits)
    declined = sum(int((s.results["status"] == exceeds).sum())
                   for s in served.transfer_requests)
    assert 0 < declined < 0.5 * WIDTH * len(served.transfer_requests)


def test_both_the_host_mirror_and_a_device_tier_judge_requests(served):
    """The comparison above covers both paths: requests the device's
    converging limit tier judged and requests the host mirror judged, by
    both of its routes: the duplicate's request after its dispatch fell
    back, and the hard regime's requests after it."""
    device = served.total("create_transfers_fixpoint")
    host = sum(served.total(k) for k in HOST)
    assert device > 0 and host > 0
    assert served.total("host_fallback") == 1
    assert 0 < served.total("host_mirror") \
        <= DeviceLedger.MIRROR_PROBE_INTERVAL
    # One count a create request, whoever judged it.
    assert sum(sum(j.values()) for j in served.judged) == len(served.sent)
    assert sum(s["host_fallbacks"] for s in served.stats) == host
    # No limit cascade left the device: the one fallback is the
    # duplicate's collision, and every other request the host judged
    # was the hard regime's.
    causes = collections.Counter()
    for s in served.stats:
        causes.update(s["causes"])
    assert causes == {"e2_collision": 1}
    assert "e3_limit" not in causes
    # The device judged every request outside that stretch, by the
    # plain tier or the converging one.
    assert {k for j in served.judged for k in j} <= {
        *HOST, "create_accounts_fast", "create_transfers_fast",
        "create_transfers_fixpoint"}


def test_the_host_spans_count_what_the_route_counters_count(served):
    spans = served.events
    ops = {e["args"]["op"]: e["args"]["operation"] for e in spans
           if e["name"] == "commit_execute"}
    creates = {int(Operation.create_accounts),
               int(Operation.create_transfers)}
    judge = [e for e in spans if e["name"] == "execute_host_judge"]
    by_route = collections.Counter(e["args"]["route"] for e in judge)
    assert by_route == {"mirror": served.total("host_mirror"),
                        "fallback": served.total("host_fallback")}
    host_ops = sorted(e["args"]["op"] for e in judge)
    assert len(set(host_ops)) == len(host_ops)
    drains = sorted(e["args"]["op"] for e in spans
                    if e["name"] == "execute_mirror_drain")
    pushes = sorted(e["args"]["op"] for e in spans
                    if e["name"] == "execute_mirror_push"
                    and ops.get(e["args"]["op"]) in creates)
    assert drains == pushes == host_ops
    # A request the device judged: the tier of its last dispatch is the
    # key it counts under.
    last_tier = {}
    for e in sorted((e for e in spans if e["name"] == "execute_dispatch"),
                    key=lambda e: e["ts"]):
        last_tier[e["args"]["op"]] = e["args"]["tier"]
    device_ops = [op for op, o in ops.items()
                  if o in creates and op not in set(host_ops)]
    got = collections.Counter(last_tier[op] for op in device_ops)
    want = collections.Counter()
    for j in served.judged:
        want.update({k: v for k, v in j.items() if k not in HOST})
    assert got == want


def test_a_host_judged_requests_children_cover_its_commit_execute(served):
    from tigerbeetle_tpu.trace.span_tree import children_share

    host_ops = {e["args"]["op"] for e in served.events
                if e["name"] == "execute_host_judge"}
    got = children_share(served.events, lambda stage, r: (
        stage == "commit_execute" and r["args"].get("op") in host_ops))
    share = got["commit_execute"]
    assert share["count"] == len(host_ops)
    # A host shared with other test workers preempts where it likes: the
    # median request is held to the share.
    assert share["share_median"] >= 0.9, share
    for name in ("execute_decode", "execute_host_judge",
                 "execute_mirror_push", "execute_encode"):
        assert share["children_mean_ms"][name] > 0.0, name


# ------------------------------------------------------------- the readers

def _reader(name: str):
    path = os.path.join(ROOT, "chipbench", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# Four create requests (ops 1-4) inside a window of 100..110 s, the
# second and fourth judged by the host mirror, and one after it (op 5).
SPANS = {
    "commit_execute": [(101.0, 0.2, 1), (102.0, 0.9, 2), (103.0, 0.2, 3),
                       (104.0, 0.7, 4), (120.0, 1.0, 5)],
    "execute_dispatch": [(101.05, 0.1, 1), (103.05, 0.1, 3),
                         (120.05, 0.1, 5)],
    "execute_host_judge": [(102.1, 0.6, 2), (104.1, 0.4, 4),
                           (120.2, 0.5, 5)],
    "execute_mirror_push": [(102.75, 0.1, 2), (104.55, 0.05, 4),
                            (120.8, 0.1, 5)],
}
JUDGED = {"shutdown": {"judged": {"host_mirror": 2,
                                  "create_transfers_fixpoint": 3}}}


def _span_context(spans: dict, dropped: int = 0, shutdown=JUDGED) -> dict:
    by_name = {k: (np.array([s for s, _, _ in v]),
                   np.array([d for _, d, _ in v])) for k, v in spans.items()}
    return {**shutdown,
            "window": {"wall_t0": 100.0, "wall_t1": 110.0},
            "spans": {"spans": by_name, "dropped_events": dropped,
                      "op": {"commit_execute": np.array(
                          [op for _, _, op in spans["commit_execute"]])},
                      "read_ops": np.array([], dtype=np.int64)}}


@pytest.mark.parametrize("name, want", [
    ("host_judge_ms", 250.0),           # (600 + 400) ms over 4 requests
    ("mirror_push_ms", 75.0),           # (100 + 50) ms over 2 pushes
    ("host_judged_window_share", 50.0)])
def test_host_span_readers_read_the_windows_requests(name, want):
    read = _reader(name)
    assert read(_span_context(SPANS)) == pytest.approx(want)
    # A ring that dropped events, or no span trace: nothing to read.
    assert read(_span_context(SPANS, dropped=1)) is None
    assert read({**_span_context(SPANS), "spans": None}) is None
    # The parent's program: no such span and no `judged` block.
    parent = {"commit_execute": SPANS["commit_execute"],
              "execute_dispatch": SPANS["execute_dispatch"]}
    assert read(_span_context(parent, shutdown={"shutdown": {}})) is None
    # This program, with every request of the window judged on the
    # device: the shares and means read 0, a mean per push nothing.
    device_only = _span_context(parent)
    assert read(device_only) == (None if name == "mirror_push_ms" else 0.0)


def _device(module_counts: dict, op_seconds: dict) -> dict:
    return {"device": {"module_counts": module_counts,
                       "op_seconds": op_seconds},
            "device_kind": "TPU v5 lite",
            "window": {"events_per_request": 8189}}


FIXPOINT_TRACE = _device(
    {"jit_create_transfers_fast": 4, "jit_create_transfers_fixpoint": 3,
     "jit_create_transfers_fixpoint_deep": 1, "jit__xfer_delta_gather": 4},
    {"jit_create_transfers_fast/%fusion.29 fusion": 0.056,
     "jit_create_transfers_fixpoint/%fusion.3 fusion": 0.120,
     "jit_create_transfers_fixpoint/%while.6 while": 0.110,
     "jit_create_transfers_fixpoint/%sort.2 sort": 0.040,
     "jit_create_transfers_fixpoint_deep/%fusion.3 fusion": 0.080,
     "jit_create_transfers_fixpoint_deep/%while.9 while": 0.070,
     "jit__xfer_delta_gather/%fusion.1 fusion": 0.008})


def test_fixpoint_kernel_reader_counts_a_while_body_once():
    read = _reader("kernel_ms.create_transfers_fixpoint")
    # (120 + 40 + 80) ms of ops under 4 fixpoint modules, while left out
    assert read(FIXPOINT_TRACE) == pytest.approx(60.0)
    assert read({"device": None}) is None
    no_fixpoint = _device({"jit_create_transfers_fast": 4},
                          {"jit_create_transfers_fast/%fusion.29 fusion":
                           0.056})
    assert read(no_fixpoint) is None


def test_fixpoint_roofline_is_the_least_bytes_over_the_peak_over_the_time():
    from chipbench.kernel_bytes import create_transfers_least_bytes

    read = _reader("create_transfers_fixpoint_roofline")
    least_s = create_transfers_least_bytes(8189) / 819e9
    assert read(FIXPOINT_TRACE) == pytest.approx(100.0 * least_s / 0.060)
    assert read({**FIXPOINT_TRACE, "device": None}) is None
    assert read(_device({}, {})) is None


def test_fixpoint_rounds_reads_the_records_mean_a_dispatch():
    read = _reader("fixpoint_rounds")
    record = {"dispatches": 4, "rounds": 250, "rounds_max": 137}
    assert read({"shutdown": {"fixpoint": record}}) == pytest.approx(62.5)
    # No converging dispatch in the run, or the parent's program: no block.
    assert read({"shutdown": {"fixpoint": {
        "dispatches": 0, "rounds": 0, "rounds_max": 0}}}) is None
    assert read({"shutdown": {}}) is None


RECORDED = os.path.join(ROOT, "chipbench", "tests", "recorded",
                        "trace.xplane.pb.gz")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_ops_without_while_sum_to_the_union_on_a_chip_trace(
        tmp_path, monkeypatch):
    """On a trace recorded on the chip (cell 1's fast tier), the
    op events of a module less its `while` ops sum to the union of its
    op intervals, which is what `kernel_ms.create_transfers` reads a
    dispatch by: the fixpoint reader's way of counting, checked on the
    one module such a trace holds."""
    import gzip

    from chipbench import fixpoint_dispatch, trace_reduce

    path = tmp_path / "trace.xplane.pb"
    with gzip.open(RECORDED) as src:
        path.write_bytes(src.read())
    summary = trace_reduce.device_summary(
        trace_reduce.reduce_xplane(str(path)), trace_reduce.KERNEL_MODULES)
    monkeypatch.setattr(fixpoint_dispatch, "PREFIX",
                        "jit_create_transfers_fast")
    d = summary["dispatch_seconds"]
    assert fixpoint_dispatch.dispatch_seconds(summary) == pytest.approx(
        sum(d) / len(d), rel=1e-3)


def test_the_cells_metrics_are_appended_with_the_cell_that_reports_them():
    bench = _bench()
    names = [e["name"] for e in bench["per_layer"]]
    at = names.index("host_judge_ms")
    tail = bench["per_layer"][at:at + 6]
    cell = ["limits.full_batch_1s"]
    # The converging tier's metrics name every cell it judges in: the
    # two-phase limits cell's pendings and posts too.
    converging = cell + ["twophase_limits.full_batch_1s"]
    assert [(e["name"], e["layer"], e["moves"], e.get("workloads"))
            for e in tail] == [
        ("host_judge_ms", "device ledger", "request_p50_ms", cell),
        ("mirror_push_ms", "device ledger", "request_p50_ms", cell),
        ("host_judged_window_share", "device ledger", "accepted_tps", cell),
        ("kernel_ms.create_transfers_fixpoint", "kernels", "accepted_tps",
         converging),
        ("create_transfers_fixpoint_roofline", "kernels", "accepted_tps",
         converging),
        ("fixpoint_rounds", "kernels", "accepted_tps", converging)]
    by_name = {e["name"]: e for e in bench["end_to_end"]}
    for e in tail:
        assert "workloads" not in by_name[e["moves"]], e["name"]
    # The accepted metrics that may read nothing in the new cell name the
    # accepted cells that report what they move, so no accepted cell is
    # dropped from them.
    accepted = [w["name"] for w in bench["workloads"]
                if w["name"] != "limits.full_batch_1s"]
    lists = {e["name"]: e.get("workloads") for e in bench["per_layer"]}
    for name in ("execute_delta_fetch_ms", "flush_columns_ms",
                 "flush_account_reads_ms"):
        assert lists[name] == accepted, name
    # The grid's read-ahead is read in the cells that were accepted when
    # the limits cell came, and in no cell added since.
    cells = [w["name"] for w in bench["workloads"]]
    assert lists["grid_readahead_used_share"] == \
        cells[:cells.index("limits.full_batch_1s")]
