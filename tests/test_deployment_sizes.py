"""`start` takes the device stores' capacities, `format` the grid's
size, and the data file carries that size in its length (ISSUE 31).

(a) no flag: today's capacities and layout, and a file the parent's
    `format` sequence made opens;
(b) a device-engine replica over a larger grid and a `t_cap` above
    `--small`'s serves more transfers than the `--small` store holds,
    across two checkpoints and a restart from the file, and answers as
    the sequential oracle does;
(c) each refusal is made in words with a non-zero exit;
(d) `format` leaves the grid unallocated;
(e) what every configuration of the benchmark states for `format` and
    `start` is taken by the program's own parser;
(f) the shutdown record's `stores`, `grid` and `forest` blocks equal
    counts taken another way.
"""

import glob
import json
import os
import struct

import numpy as np
import pytest

from tigerbeetle_tpu import main as tb_main
from tigerbeetle_tpu import multi_batch
from tigerbeetle_tpu.clients.common import events_max
from tigerbeetle_tpu.constants import HEADER_SIZE
from tigerbeetle_tpu.lsm.forest import Forest, chain_next, chain_payload
from tigerbeetle_tpu.lsm.grid import Grid, MemoryDevice
from tigerbeetle_tpu.lsm.manifest_level import SNAPSHOT_LATEST
from tigerbeetle_tpu.lsm.table import TableInfo
from tigerbeetle_tpu.lsm.tree import BAR_LENGTH
from tigerbeetle_tpu.ops.warmup import (WARM_ACCOUNTS, WARM_SIZES,
                                        WARM_TRANSFERS, capacity_error)
from tigerbeetle_tpu.state_machine import StateMachine
from tigerbeetle_tpu.testing.cluster import Cluster
from tigerbeetle_tpu.types import Account, Operation, Transfer
from tigerbeetle_tpu.vsr import durable as durable_mod
from tigerbeetle_tpu.vsr.replica import Replica, _split_root
from tigerbeetle_tpu.vsr.storage import (TEST_LAYOUT, FileStorage,
                                         LayoutError, StorageLayout,
                                         layout_of_file, with_grid_blocks)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL_T_CAP = 1 << 14  # what `start --small` gives its transfer store
FORMAT = ["format", "--cluster=0", "--replica=0", "--replica-count=1"]
START = ["start", "--addresses=127.0.0.1:1", "--replica=0"]


def _parse(argv):
    return tb_main.build_parser().parse_args(argv)


# ------------------------------------------ (a) no flag: as the parent

@pytest.mark.parametrize("small, caps", [
    (False, (1 << 17, 1 << 21)), (True, (1 << 12, SMALL_T_CAP))])
def test_no_flag_gives_todays_capacities(small, caps):
    args = _parse(START + (["--small"] if small else []) + ["x.tb"])
    assert args.account_capacity is None and args.transfer_capacity is None
    assert tb_main._store_capacities(args) == caps
    assert capacity_error(*caps) is None


@pytest.mark.parametrize("small, base", [
    (False, StorageLayout()), (True, TEST_LAYOUT)])
def test_no_flag_gives_todays_layout(small, base, tmp_path):
    path = str(tmp_path / "absent.tb")
    flags = ["--small"] if small else []
    fmt = tb_main._data_file_layout(_parse(FORMAT + flags + [path]),
                                    formatting=True)
    assert fmt == base and fmt.grid_block_count == base.grid_block_count
    # A file of the parent's length reads as the parent's layout: the
    # production file is 1,686,388,736 B (PERF.md §4).
    assert layout_of_file(base, base.size) is base
    assert StorageLayout().size == 1_686_388_736
    assert tb_main._data_file_layout(_parse(START + flags + [path])) == base


def test_a_file_formatted_by_the_parents_sequence_opens(tmp_path, capsys):
    """The parent's cmd_format, statement for statement, against
    today's: the same bytes, and the file opens through the helper."""
    old, new = str(tmp_path / "old.tb"), str(tmp_path / "new.tb")
    storage = FileStorage(old, layout=TEST_LAYOUT, create=True)
    Replica.format(storage, cluster=0, replica_id=0, replica_count=1)
    storage.sync()
    storage.close()
    assert tb_main.main(FORMAT + ["--small", new]) == 0
    with open(old, "rb") as f, open(new, "rb") as g:
        assert f.read() == g.read()
    assert tb_main._data_file_layout(
        _parse(START + ["--small", old])) is TEST_LAYOUT
    assert tb_main.main(["inspect", "--small", "--integrity", old]) == 0
    assert tb_main.main(["multiversion", "--small", old]) == 0
    assert "0 fault(s)" in capsys.readouterr().out


def test_the_warm_up_creates_what_the_floor_says():
    assert WARM_SIZES == (513, 4097)
    assert (WARM_ACCOUNTS, WARM_TRANSFERS) == (3, 9220)
    assert capacity_error(8, SMALL_T_CAP) is None


# ------------------------ (b) a larger deployment, served and restarted

GRID_BLOCKS = 3 * TEST_LAYOUT.grid_block_count
T_CAP = 2 * SMALL_T_CAP
ACCOUNTS = 96
CLUSTER_ID = 0xC1A57E12  # testing.cluster.Cluster's own
LAST_OP = 48             # a checkpoint op: the third checkpoint
# The restart comes right at the second checkpoint, where the file's
# checkpoint alone holds the state. (A WAL suffix would not be replayed
# from a file: the native WAL scan takes a prepare that carries a
# client's trace context for unrecognizable, in the parent as here —
# PERF.md §7, program fault 7. This file is about sizes.)
RESTART_AT_OP = 32


def _stream(rng, first_id: int, n: int) -> list[Transfer]:
    """n transfers between the accounts; about 1% cannot succeed (the
    same account on both sides, or a credit account that is not
    there)."""
    debit = rng.integers(1, ACCOUNTS + 1, n)
    credit = (debit + rng.integers(1, ACCOUNTS, n) - 1) % ACCOUNTS + 1
    fail = rng.random(n)
    credit = np.where(fail < 0.005, debit, credit)
    credit = np.where(fail > 0.995, 10_000, credit)
    amount = rng.integers(1, 1000, n)
    return [Transfer(id=first_id + i, debit_account_id=int(debit[i]),
                     credit_account_id=int(credit[i]), amount=int(amount[i]),
                     ledger=1, code=1, user_data_64=first_id)
            for i in range(n)]


class _Served:
    """One device-engine replica over a file `format --grid-blocks`
    made, every committed prepare replayed through a sequential oracle
    machine at the prepare's own timestamp."""

    def __init__(self, path: str):
        self.path = path
        assert tb_main.main(
            ["format", f"--cluster={CLUSTER_ID}", "--replica=0",
             "--replica-count=1", "--small", f"--grid-blocks={GRID_BLOCKS}",
             path]) == 0
        self.layout = self._layout()
        self.cluster = Cluster(
            seed=31, replica_count=1, layout=self.layout,
            state_machine_factory=lambda: StateMachine(
                engine="device", a_cap=1 << 9, t_cap=T_CAP))
        # The cluster formatted a memory file of its own; the replica
        # under test serves from the one on disk.
        self._reopen()
        self.client = self.cluster.client(5)
        self.oracle = StateMachine(engine="oracle")
        self.replayed_op = self.replica.commit_min
        self.mismatches = []
        self.created = 0
        self.held_samples = []
        self.n_max = events_max(Operation.create_transfers,
                                self.layout.message_size_max - HEADER_SIZE)

    def _layout(self) -> StorageLayout:
        return tb_main._data_file_layout(_parse(START + ["--small",
                                                         self.path]))

    def _reopen(self) -> None:
        self.cluster.crash(0)
        old = self.cluster.storages[0]
        if isinstance(old, FileStorage):
            old.sync()
            old.close()
        # Synchronous IO: the simulator's clock does not wait for a
        # worker thread's write.
        self.cluster.storages[0] = FileStorage(
            self.path, layout=self._layout(), async_grid=False)
        self.cluster.restart(0)

    @property
    def replica(self):
        return self.cluster.replicas[0]

    def request(self, operation, events) -> None:
        body = multi_batch.encode([b"".join(e.pack() for e in events)], 128)
        self.client.request(operation, body)
        assert self.cluster.run(4000, until=lambda: self.client.idle), \
            self.cluster.debug_status()
        reply = self.client.replies[-1]
        while self.replayed_op < self.replica.commit_min:
            self.replayed_op += 1
            prepare = self.replica.journal.read_prepare(self.replayed_op)
            op = Operation(prepare.header.operation)
            if op not in (Operation.create_accounts,
                          Operation.create_transfers):
                continue
            want = self.oracle.commit(op, prepare.body,
                                      prepare.header.timestamp)
            if want != reply.body:
                self.mismatches.append(self.replayed_op)
        grid = self.replica.durable.grid
        self.held_samples.append(grid.block_count - sum(grid.free))

    def run(self) -> None:
        rng = np.random.default_rng(31)
        self.request(Operation.create_accounts,
                     [Account(id=i, ledger=1, code=1)
                      for i in range(1, ACCOUNTS + 1)])
        restarted = False
        while self.replica.commit_min < LAST_OP:
            self.request(Operation.create_transfers,
                         _stream(rng, 1_000_000 + self.created, self.n_max))
            self.created += self.n_max
            if self.replica.commit_min == RESTART_AT_OP:
                assert self.replica.superblock.op_checkpoint == RESTART_AT_OP
                self._reopen()
                assert self.replica.commit_min == RESTART_AT_OP
                restarted = True
        assert restarted and self.replica.commit_min == LAST_OP


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    run = _Served(str(tmp_path_factory.mktemp("deploy") / "0_0.tb"))
    run.run()
    yield run
    run.cluster.storages[0].close()


def test_the_file_carries_its_grid_size(served):
    assert served.layout.grid_block_count == GRID_BLOCKS
    assert served.layout == with_grid_blocks(TEST_LAYOUT, GRID_BLOCKS)
    assert os.path.getsize(served.path) == served.layout.size
    assert served.replica.durable.grid.block_count == GRID_BLOCKS


def test_more_transfers_than_the_small_store_holds_answer_as_the_oracle(
        served):
    """Every reply of the run (accounts, then every create_transfers
    request, before and after the restart) byte for byte the oracle's,
    at a row count the `--small` store could not hold."""
    assert served.mismatches == []
    rows = len(served.oracle.state.transfers)
    assert SMALL_T_CAP < rows <= served.created < T_CAP
    assert served.replica.superblock.op_checkpoint == LAST_OP
    assert served.replica.state_machine.led.fallbacks == 0


def test_accounts_and_transfers_read_back_as_the_oracle_holds_them(served):
    sm, want = served.replica.state_machine, served.oracle.state
    got = sm.lookup_accounts(list(range(1, ACCOUNTS + 1)))
    assert [a.pack() for a in got] == \
        [want.accounts[i].pack() for i in range(1, ACCOUNTS + 1)]
    rng = np.random.default_rng(5)
    ids = sorted(want.transfers)
    sample = [ids[i] for i in rng.choice(len(ids), 2000, replace=False)]
    sample += ids[-served.n_max:]  # the last acknowledged request
    got = sm.lookup_transfers(sample)
    assert [t.pack() for t in got] == \
        [want.transfers[i].pack() for i in sample]
    failed = [i for i in range(1_000_000, 1_000_000 + served.created)
              if i not in want.transfers]
    assert failed and sm.lookup_transfers(failed[:50]) == []


# --------------------------------------------------- (c) the refusals

@pytest.fixture()
def small_file(tmp_path):
    path = str(tmp_path / "0_0.tb")
    assert tb_main.main(FORMAT + ["--small", path]) == 0
    return path


@pytest.mark.parametrize("argv, words", [
    (FORMAT + ["--small", "--grid-blocks=0"], "at least one block"),
    (FORMAT + ["--small", "--grid-blocks=-4"], "at least one block"),
    (FORMAT + ["--small", "--grid-blocks=1000000"], "free set"),
    (FORMAT + ["--grid-blocks=16777216"], "free set"),
    (START + ["--small", "--transfer-capacity=5000"], "power of two"),
    (START + ["--small", "--transfer-capacity=8192"], "at least 16384"),
    (START + ["--transfer-capacity=0"], "power of two"),
    (START + ["--small", "--account-capacity=100"], "power of two"),
    (START + ["--small", "--account-capacity=4"], "at least 8"),
])
def test_a_size_the_program_cannot_take_is_refused_in_words(
        argv, words, tmp_path, capsys):
    path = str(tmp_path / "refused.tb")
    assert tb_main.main(argv + [path]) == 1
    out = capsys.readouterr().out
    assert out.startswith("error: ") and words in out
    assert not os.path.exists(path)


@pytest.mark.parametrize("command", [
    START + ["--small"], ["inspect", "--small"], ["multiversion", "--small"],
    ["recover", "--replica=0", "--replica-count=1", "--small", "x.aof"],
    ["inspect"], START])
def test_a_length_that_fits_no_whole_grid_is_refused_in_words(
        command, small_file, capsys):
    """Cut short or grown by a part of a block (`--small` commands), or
    read with the other layout (the last two)."""
    if "--small" in command:
        with open(small_file, "ab") as f:
            f.write(b"\0" * 100)
    assert tb_main.main(command + [small_file]) == 1
    out = capsys.readouterr().out
    assert out.startswith("error: ") and "holds no whole grid" in out


def test_the_layout_helpers_refuse_by_raising():
    with pytest.raises(LayoutError, match="holds no whole grid"):
        layout_of_file(TEST_LAYOUT, TEST_LAYOUT.zone_offsets["grid"])
    with pytest.raises(LayoutError, match="free set"):
        layout_of_file(TEST_LAYOUT, TEST_LAYOUT.zone_offsets["grid"]
                       + (1 << 20) * TEST_LAYOUT.grid_block_size)
    assert layout_of_file(
        TEST_LAYOUT, TEST_LAYOUT.size + TEST_LAYOUT.grid_block_size
    ).grid_block_count == TEST_LAYOUT.grid_block_count + 1


# --------------------------------- (d) the grid zone is left unwritten

def test_format_leaves_the_grid_unallocated(tmp_path, capsys):
    path = str(tmp_path / "sparse.tb")
    blocks = 16 * TEST_LAYOUT.grid_block_count  # a grid of 256 MiB
    assert tb_main.main(FORMAT + ["--small", f"--grid-blocks={blocks}",
                                  path]) == 0
    out = capsys.readouterr().out
    layout = with_grid_blocks(TEST_LAYOUT, blocks)
    stat = os.stat(path)
    assert stat.st_size == layout.size
    assert f"a grid of {blocks} blocks" in out and "left unwritten" in out
    assert f"{stat.st_size} B long" in out
    probe = str(tmp_path / "probe")
    with open(probe, "wb") as f:
        f.truncate(1 << 24)
    if os.stat(probe).st_blocks * 512 >= 1 << 24:
        pytest.skip("this file system reports a file's length as allocated")
    # Superblock, WAL headers, one root, one manifest block: no more
    # than the zones before the grid, and a block of the grid.
    assert stat.st_blocks * 512 <= \
        layout.zone_offsets["grid"] + layout.grid_block_size
    assert stat.st_blocks * 512 < layout.size // 16


# ------------- (e) what the benchmark's configurations ask the parser

def _stated_args():
    out = []
    for path in sorted(glob.glob(os.path.join(ROOT, "chipbench", "configs",
                                              "*.json"))):
        with open(path) as f:
            server = json.load(f)["server"]
        name = os.path.basename(path)[:-len(".json")]
        out.append(pytest.param("format", server.get("format_args", []),
                                id=f"{name}-format"))
        out.append(pytest.param("start", server.get("start_args", []),
                                id=f"{name}-start"))
    return out


@pytest.mark.parametrize("command, stated", _stated_args())
def test_the_programs_parser_takes_what_a_configuration_states(
        command, stated):
    """As chipbench/server.py builds the two command lines: the
    harness's own arguments, then the configuration's as they stand. A
    flag the parser does not know exits (SystemExit) and fails this."""
    if command == "format":
        args = _parse(FORMAT + stated + ["0_0.tigerbeetle"])
        layout = tb_main._data_file_layout(args, formatting=True)
        assert layout is not None
        assert layout_of_file(StorageLayout(), layout.size) == layout
    else:
        args = _parse(START + ["--engine=device"] + stated
                      + ["0_0.tigerbeetle"])
        assert capacity_error(*tb_main._store_capacities(args)) is None


# ---------------- (f) the shutdown record's blocks, counted another way

def test_grid_held_counts_follow_the_free_set():
    grid = Grid(MemoryDevice(64 * 512), block_size=512, block_count=64)
    assert grid.held_stats() == {"blocks": 64, "held_at_checkpoint": 0,
                                 "held_peak": 0}
    written = [grid.write_block(bytes([i]) * 8).index for i in range(10)]
    for index in written[:3]:
        grid.release(index)
    reservation = grid.reserve(5)
    grid.write_block(b"r", reservation)
    assert grid.held() == 15
    blob = grid.checkpoint_free_set()
    # Before the frees landed 15 were held; the checkpoint's own free
    # set holds the 7 written and kept (a live reservation is free in
    # it, written or not).
    assert grid.held_stats() == {"blocks": 64, "held_at_checkpoint": 7,
                                 "held_peak": 15}
    grid.forfeit(reservation)
    grid.write_block(b"x")
    grid.checkpoint_free_set()
    assert grid.held_stats()["held_at_checkpoint"] == 9
    assert grid.held_stats()["held_peak"] == 15
    fresh = Grid(MemoryDevice(64 * 512), block_size=512, block_count=64)
    fresh.restore_free_set(blob)
    assert fresh.held_stats() == {"blocks": 64, "held_at_checkpoint": 7,
                                  "held_peak": 7}


NO_COMPACTION = {"jobs": 0, "rows_in": 0, "rows_out": 0, "passed_sorted": 0}


def _persisted_root(served) -> bytes:
    sb = served.replica.superblock
    return served.cluster.storages[0].read(
        "snapshot", sb.snapshot_slot * served.layout.snapshot_size_max,
        sb.snapshot_size)


def test_stores_block_equals_the_oracles_counts(served):
    stats = served.replica.state_machine.led.store_stats()
    assert stats == {"a_cap": 1 << 9, "t_cap": T_CAP,
                     "account_rows": len(served.oracle.state.accounts),
                     "transfer_rows": len(served.oracle.state.transfers)}
    assert stats["account_rows"] == ACCOUNTS
    assert stats["transfer_rows"] > SMALL_T_CAP


def test_grid_block_equals_the_persisted_free_set(served):
    """The run stopped at a checkpoint op, so the root in the snapshot
    slot is that checkpoint's: its free set, decoded from the file."""
    stats = served.replica.durable.grid.held_stats()
    forest_root, _ = _split_root(_persisted_root(served))
    assert stats["blocks"] == GRID_BLOCKS
    assert stats["held_at_checkpoint"] == \
        len(durable_mod.allocated_blocks(forest_root))
    # Between ops the test counted the free list itself; a checkpoint
    # counts before its frees land, so its peak is no lower than any
    # count taken after an op, and the grid was never near full.
    assert max(served.held_samples) <= stats["held_peak"] < GRID_BLOCKS
    assert stats["held_peak"] >= stats["held_at_checkpoint"]
    assert stats["held_peak"] > TEST_LAYOUT.grid_block_count // 8


def test_forest_block_equals_the_persisted_manifests(served):
    """Live tables and the deepest level holding one, decoded from the
    manifest chain the last checkpoint wrote (lsm.tree.manifest_pack's
    layout), against Forest.depth_stats over the live trees."""
    forest_root, _ = _split_root(_persisted_root(served))
    grid = served.replica.durable.grid
    payload, link = b"", durable_mod.checkpoint_manifest(forest_root)
    while link is not None:
        raw = grid.read_block(*link)
        payload += chain_payload(raw)
        link = chain_next(raw)
    tables, deepest = 0, -1
    (n_trees,) = struct.unpack_from("<I", payload)
    pos = 4
    for _ in range(n_trees):
        name_len, size = struct.unpack_from("<HI", payload, pos)
        tree = payload[pos + 6 + name_len:pos + 6 + name_len + size]
        pos += 6 + name_len + size
        (n_levels,) = struct.unpack_from("<B", tree, 8)
        tpos = 9
        for level in range(n_levels):
            (n_entries,) = struct.unpack_from("<I", tree, tpos + 8)
            tpos += 12
            for _ in range(n_entries):
                (snapshot_max,) = struct.unpack_from("<Q", tree, tpos + 8)
                _, tpos = TableInfo.unpack(tree, tpos + 24)
                if snapshot_max == SNAPSHOT_LATEST:
                    tables += 1
                    deepest = max(deepest, level)
    stats = served.replica.durable.forest.depth_stats()
    compaction = stats.pop("compaction")
    assert stats == {"deepest_level": deepest, "tables": tables}
    assert tables > 0 and deepest == 0  # 23,000 rows leave level 0 to no tree
    # No tree left level 0, so no job ran (the freezes and their
    # flushes are not compaction's rows).
    assert compaction == NO_COMPACTION


def test_forest_depth_follows_a_table_down_the_levels():
    grid = Grid(MemoryDevice(256 * 4096), block_size=4096, block_count=256)
    forest = Forest(grid, {"a": (8, 8), "b": (8, 8)})
    assert forest.depth_stats() == {"deepest_level": -1, "tables": 0,
                                    "compaction": NO_COMPACTION}
    for name, tree in forest.trees.items():
        for i in range(10):
            tree.put(i.to_bytes(8, "big"), name.encode() * 8)
    forest.checkpoint()
    assert forest.depth_stats() == {"deepest_level": 0, "tables": 2,
                                    "compaction": NO_COMPACTION}
    tree = forest.trees["b"]
    table = tree.levels[0][0]
    tree.levels[0].remove(table, snapshot=tree.beat)
    tree.levels[3].insert(table, snapshot=tree.beat)
    assert forest.depth_stats() == {"deepest_level": 3, "tables": 2,
                                    "compaction": NO_COMPACTION}


def test_compaction_counters_move_once_a_tree_reaches_level_1(served):
    """The last test of the file: it serves the replica on, a few
    transfers a request, to the end of the bar in which the trees'
    second jobs install (level 0 holds a table a freeze and a table a
    checkpoint; a tree's first job finds level 1 empty). Before any
    job the counters are all zero (the test above); from then on they
    say what the jobs read and wrote."""
    forest = served.replica.durable.forest
    assert forest.depth_stats()["compaction"] == NO_COMPACTION
    rng = np.random.default_rng(36)
    while served.replica.commit_min < 5 * BAR_LENGTH - 1:
        served.request(Operation.create_transfers,
                       _stream(rng, 1_000_000 + served.created, 8))
        served.created += 8
    assert served.mismatches == []
    stats = forest.depth_stats()
    compaction = stats["compaction"]
    assert stats["deepest_level"] == 1 and compaction["jobs"] >= 2
    assert 0 < compaction["rows_out"] < compaction["rows_in"]
    assert 0 < compaction["passed_sorted"] < compaction["rows_in"]
    assert compaction == {
        key: sum(tree.compaction[key] for tree in forest.trees.values())
        for key in NO_COMPACTION}
    # A tree keyed by timestamp compacts into an empty range of level 1:
    # its rows pass as they stand. One keyed by account interleaves
    # with what level 1 holds, and the accounts' own rows meet their
    # older selves there.
    by_ts = forest.trees["xfer_by_ts"].compaction
    assert by_ts["jobs"] == 2
    assert by_ts["passed_sorted"] == by_ts["rows_in"] == by_ts["rows_out"]
    by_dr = forest.trees["xfer_by_dr"].compaction
    assert by_dr["jobs"] == 2
    assert by_dr["passed_sorted"] < by_dr["rows_in"] == by_dr["rows_out"]
    accounts = forest.trees["accounts"].compaction
    assert accounts["jobs"] == 2
    # (the first job moved 96 rows down, the second met them with 96)
    assert (accounts["rows_in"], accounts["rows_out"]) == \
        (3 * ACCOUNTS, 2 * ACCOUNTS)
