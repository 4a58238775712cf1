"""The deployment `tb_bench_twophase_1r` served in process (ISSUE 35):
upstream's benchmark with every transfer two-phase, a request of
pendings and then the request that posts each of them.

One device-engine replica on the small layout, over a file `format`
made, fed the requests `chipbench.traffic.Deployment` builds from the
configuration's own file (the accounts cut to fit the small stores,
the requests narrower than the small wire so that the pairs fit the
store), across a checkpoint and a restart from the file at a checkpoint
op that falls between a request of pendings and the request that posts
them. The comparison is the benchmark's own (`chipbench.check.judge`):
every reply, every account and a sample of transfers against the plain
reference's replay of the same request bytes.
"""

import json
import os
import time

import numpy as np
import pytest

from chipbench import check, wire
from chipbench.reference.ledger import StateMachineOracle
from chipbench.reference.ledger_types import CreateTransferStatus
from chipbench.traffic import F_PENDING, F_POST, F_VOID, Deployment
from chipbench.window import Sent
from tigerbeetle_tpu import main as tb_main
from tigerbeetle_tpu.state_machine import StateMachine
from tigerbeetle_tpu.testing.cluster import Cluster
from tigerbeetle_tpu.types import Operation
from tigerbeetle_tpu.vsr.storage import FileStorage

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "chipbench", "configs",
                      "tb_bench_twophase_1r.json")
CLUSTER_ID = 0xC1A57E12  # testing.cluster.Cluster's own
ACCOUNTS = 256           # of the configuration's 10,000
A_CAP, T_CAP = 1 << 9, 1 << 14  # `start --small` gives its stores 2^14 rows
WIDTH = 400              # events a request: 30 requests stay under T_CAP
RESTART_AT_OP = 16       # the first checkpoint
LAST_OP = 32             # the second
SESSION = 0


class _Served:
    """The replica, the requests it was sent with what came back, and
    the device ledger's counters on either side of the restart."""

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
        self.cluster = Cluster(
            seed=35, replica_count=1, layout=self.layout,
            state_machine_factory=lambda: StateMachine(
                engine="device", a_cap=A_CAP, t_cap=T_CAP))
        self._reopen()
        self.client = self.cluster.client(5)
        self.sent: list[Sent] = []
        self.ledger_stats: list[dict] = []
        self.two_phase_rows: list[dict] = []

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
        self.ledger_stats.append(
            self.replica.state_machine.led.fallback_stats())
        self.two_phase_rows.append(dict(self.replica.durable.two_phase_rows))

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
        # Two requests of accounts, so that the first checkpoint falls
        # after an odd number of transfer requests.
        for request in self.dep.account_requests(ACCOUNTS // 2):
            self.request(request)
        assert self.dep.funding_requests(WIDTH) == []  # nothing is funded
        k, restarted = 0, False
        while self.replica.commit_min < LAST_OP:
            self.request(self.dep.transfer_request(SESSION, k, WIDTH))
            k += 1
            if self.replica.commit_min == RESTART_AT_OP:
                assert self.replica.superblock.op_checkpoint == RESTART_AT_OP
                # Between a request of pendings and the request that
                # posts them: the posts read rows the file alone holds.
                assert k % 2 == 1
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
        tids = [(int(h) << 64) | int(l) for l, h in np.unique(chosen, axis=0)]
        return {
            "accounts": [(ids, b"".join(
                a.pack() for a in sm.lookup_accounts(ids)))],
            "transfers": [(tids, b"".join(
                t.pack() for t in sm.lookup_transfers(tids)))]}


@pytest.fixture(scope="module", params=[3350000001, 2200000035])
def served(request, tmp_path_factory):
    run = _Served(str(tmp_path_factory.mktemp("twophase") / "0_0.tb"),
                  request.param)
    run.run()
    yield run
    run.cluster.storages[0].close()


def test_the_file_states_upstreams_first_benchmark():
    with open(CONFIG) as f:
        cfg = json.load(f)
    assert cfg["transfers"]["two_phase"]["post_share"] == 1.0
    assert cfg["transfers"]["two_phase"]["void_share"] == 0.0
    assert cfg["accounts"]["limited_every"] == 0
    assert "preloaded_count" not in cfg["transfers"]
    assert sorted(cfg["guarantees"]) == [
        "acknowledged_means", "consistency", "replicas", "results"]
    assert "format_args" not in cfg["server"] \
        and "start_args" not in cfg["server"]
    assert len(cfg["source"]) <= 200


def test_replies_accounts_and_transfers_equal_the_references_replay(served):
    numbers = check.judge(served.sent, served.read_back(seed=7))
    assert numbers == dict.fromkeys(check.LIMITS, 0)


def test_every_pair_is_a_request_of_pendings_and_the_request_that_posts_them(
        served):
    requests = served.transfer_requests
    pairs = list(zip(requests[0::2], requests[1::2]))
    assert len(pairs) >= 8
    created = int(CreateTransferStatus.created)
    assert created == wire.CREATED
    not_found = int(CreateTransferStatus.pending_transfer_not_found)
    n_failed = 0
    for pendings, posts in pairs:
        sent_p = np.frombuffer(pendings.request.payload, dtype=wire.TRANSFER)
        sent_r = np.frombuffer(posts.request.payload, dtype=wire.TRANSFER)
        assert (sent_p["flags"] == F_PENDING).all()
        assert (sent_r["flags"] == F_POST).all()
        assert not (sent_r["flags"] & F_VOID).any()
        assert (sent_r["pending_lo"] == sent_p["id_lo"]).all()
        assert (sent_r["amount_lo"] == sent_p["amount_lo"]).all()
        ok = pendings.results["status"] == created
        # The post of a created pending is created; the post of one
        # built to fail finds no pending.
        assert (posts.results["status"][ok] == created).all()
        assert (posts.results["status"][~ok] == not_found).all()
        n_failed += int((~ok).sum())
    # About 1% of the pendings are built to fail.
    assert 0 < n_failed < 0.03 * WIDTH * len(pairs)


def test_one_dispatch_a_request_and_no_host_fallback(served):
    before, after = served.ledger_stats
    requests = len(served.sent)  # accounts and transfers: all creates
    assert before["fast_batches"] + after["fast_batches"] == requests
    for stats in served.ledger_stats:
        assert stats["host_fallbacks"] == 0 and stats["causes"] == {}
        assert stats["fixpoint_batches"] == 0
        assert stats["deep_fixpoint_batches"] == 0
        assert stats["escalations"] == 0


def test_two_phase_counters_equal_the_references_rows(served):
    """The shutdown record's `two_phase` block is
    `DurableState.two_phase_rows`; a restart starts it anew, so the two
    halves add up to what the reference holds."""
    order, _ = check.ordered(served.sent)
    reference = StateMachineOracle()
    assert check.replay(reference, order) == 0
    flags = np.array([t.flags for t in reference.transfers.values()])
    want = {"pending": int((flags & F_PENDING != 0).sum()),
            "posted": int((flags & F_POST != 0).sum()),
            "voided": int((flags & F_VOID != 0).sum())}
    got = {k: sum(rows[k] for rows in served.two_phase_rows) for k in want}
    assert got == want
    assert want["voided"] == 0 and want["posted"] > 0
    # The run ends on a request of pendings that nothing has posted yet.
    assert len(served.transfer_requests) % 2 == 1
    assert 0 < want["pending"] - want["posted"] <= WIDTH
    assert sum(want.values()) == len(reference.transfers)
    rows = served.replica.durable.rows_put
    # Each request of posts folded its own run and its pendings' run of
    # `transfers` and `xfer_by_ts`; nothing else reads by key.
    assert rows["folded"] > 0 and rows["object"] == 0
