"""Drives the rest of a run with the chip look skipped (a `--small`
server on the CPU) and sees `correct` come out false: once for the
control of each guarantee, once for each fault planted under the timed
path; each in a cell of BENCHMARK.json and in the fixture cell that
states `start_args` and a preload (the rehearsal leaves the arguments
out and sends eight preload requests); and in the cell whose mix states
reads, where the `stale_read` control and one row altered where a lookup
is answered come out not correct too. Run by hand (each case boots a
server, about 35 s):

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests/test_faults.py -q -p no:cacheprovider
"""

import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from chipbench import check, control, wire  # noqa: E402
from chipbench.run import run_cell  # noqa: E402
from chipbench.server import BenchFailure  # noqa: E402
from chipbench.traffic import STREAM_PRELOAD  # noqa: E402

FAULTY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "faulty_launcher.py")
CELLS = "chipbench/tests/fixtures/cells.json"
CASES = pytest.mark.parametrize("cell,preloaded", [
    ("default.b1024_4s", False), ("preload.b1024_4s", True)])


def drop_a_preloaded_requests_rows(readback: dict) -> int:
    """The read-back with the rows of preload request 0 gone from the
    sampled transfers' reply; how many went."""
    (ids, reply), = readback["transfers"]
    rows = np.frombuffer(reply, dtype=wire.TRANSFER)
    gone = ((rows["id_hi"] & np.uint64(0xFFFF)) == STREAM_PRELOAD) \
        & ((rows["id_lo"] >> np.uint64(16)) == 0)
    readback["transfers"] = [(ids, rows[~gone].tobytes())]
    return int(gone.sum())


@CASES
def test_sound_run_is_correct_and_every_control_is_not(cell, preloaded):
    kept = {}

    def keep(sent, readback):
        kept["sent"], kept["readback"] = sent, readback

    result = run_cell(cell, 2026093001, 5.0, False, rehearse=True,
                      tamper=keep, cells=CELLS)
    assert result["correct"], result["compared"]
    assert all(v["value"] == 0 for v in result["compared"].values())
    w = result["window"]
    assert (w["preloaded_transfers"] > 0) == preloaded
    assert (w["preloaded_ids_read_back"] > 0) == preloaded
    if preloaded:
        setup_transfers = [s for s in kept["sent"] if s.phase == "setup"
                           and s.request.operation == "create_transfers"]
        assert len(setup_transfers) == 8 + 2  # preload, then warm
        assert sum(s.request.n_events for s in setup_transfers[:8]) == \
            w["preloaded_transfers"]
        assert w["transfers_created_whole_run"] == sum(
            s.created for s in kept["sent"]
            if s.request.operation == "create_transfers")
        assert w["preload_seconds"] > 0
        sent, readback = copy.deepcopy((kept["sent"], kept["readback"]))
        assert drop_a_preloaded_requests_rows(readback) > 0
        numbers = check.judge(sent, readback)
        assert not check.verdict(numbers)
        assert numbers["transfer_mismatches"] > 0
        assert numbers["result_mismatches"] == 0
    sent, readback = copy.deepcopy((kept["sent"], kept["readback"]))
    control.lost_write(sent, readback)
    numbers = check.judge(sent, readback)
    assert not check.verdict(numbers)
    assert numbers["account_mismatches"] > 0
    assert numbers["transfer_mismatches"] > 0


@pytest.mark.parametrize("fault,number", [
    ("answer_altered", "result_mismatches"),
    ("state_unchanged", "account_mismatches"),
])
@CASES
def test_fault_under_the_timed_path_is_not_correct(fault, number, cell,
                                                   preloaded, monkeypatch):
    monkeypatch.setenv("CHIPBENCH_FAULT", fault)
    result = run_cell(cell, 2026093002, 5.0, False, rehearse=True,
                      launcher=FAULTY, cells=CELLS)
    assert not result["correct"]
    assert result["compared"][number]["value"] > 0


READ_CELL = "default.read_mix_1s"


def test_sound_read_mix_is_correct_and_a_stale_read_is_not():
    kept = {}

    def keep(sent, readback):
        kept["sent"], kept["readback"] = sent, readback

    result = run_cell(READ_CELL, 2026100401, 5.0, False, rehearse=True,
                      tamper=keep)
    assert result["correct"], result["compared"]
    assert all(v["value"] == 0 for v in result["compared"].values())
    assert result["window"]["answered_by_operation"] == {
        "create_transfers": 4, "lookup_accounts": 4}
    assert set(result["metrics"]) == {
        "accepted_tps", "request_p50_ms", "request_p98_ms", "lookup_p50_ms",
        "setup_s"}
    reads = [s for s in kept["sent"] if s.request.is_read]
    assert [s.phase for s in reads] == ["setup"] + ["window"] * 4
    # rows came back, the unknown ids' left out, and balances had moved
    assert all(0 < len(s.results) <= s.request.n_events for s in reads)
    assert any(len(s.results) < s.request.n_events for s in reads)
    assert all(s.results["debits_posted_lo"].any() for s in reads)
    sent, readback = copy.deepcopy((kept["sent"], kept["readback"]))
    control.stale_read(sent, readback)
    numbers = check.judge(sent, readback)
    assert not check.verdict(numbers)
    assert numbers["read_mismatches"] > 0
    assert all(v == 0 for k, v in numbers.items() if k != "read_mismatches")
    sent, readback = copy.deepcopy((kept["sent"], kept["readback"]))
    control.lost_write(sent, readback)
    numbers = check.judge(sent, readback)
    assert numbers["account_mismatches"] > 0
    assert numbers["transfer_mismatches"] > 0
    assert numbers["read_mismatches"] == 0


def test_a_row_altered_where_a_lookup_is_answered_is_not_correct(monkeypatch):
    monkeypatch.setenv("CHIPBENCH_FAULT", "row_altered")
    result = run_cell(READ_CELL, 2026100402, 5.0, False, rehearse=True,
                      launcher=FAULTY)
    assert not result["correct"]
    assert result["compared"]["read_mismatches"]["value"] == 1
    assert all(v["value"] == 0 for k, v in result["compared"].items()
               if k != "read_mismatches")


def test_an_argument_the_program_refuses_fails_in_its_own_words(tmp_path):
    """Not a rehearsal (that leaves the arguments out): `format` writes
    a production data file, `start` exits at its argument parser before
    it touches a device, and the run fails with what it said."""
    with open(os.path.join(ROOT, CELLS)) as f:
        cells = json.load(f)
    with open(os.path.join(ROOT, cells["configs"][0]["file"])) as f:
        config = json.load(f)
    config["server"]["start_args"].append("--no-such-capacity=4194304")
    cells["configs"][0]["file"] = str(tmp_path / "config.json")
    (tmp_path / "config.json").write_text(json.dumps(config))
    (tmp_path / "cells.json").write_text(json.dumps(cells))
    with pytest.raises(BenchFailure, match="unrecognized arguments: "
                                           "--no-such-capacity=4194304"):
        run_cell("preload.full_batch_1s", 1, 1.0, False,
                 cells=str(tmp_path / "cells.json"))
