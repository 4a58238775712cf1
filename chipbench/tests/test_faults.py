"""Drives the rest of a run with the chip look skipped (a `--small`
server on the CPU) and sees `correct` come out false: once for the
control of each guarantee, once for each fault planted under the timed
path. Run by hand (each case boots a server, about 35 s):

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests/test_faults.py -q -p no:cacheprovider
"""

import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import check, control  # noqa: E402
from chipbench.run import run_cell  # noqa: E402

FAULTY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "faulty_launcher.py")
CELL = "default.b1024_4s"


def test_sound_run_is_correct_and_every_control_is_not():
    kept = {}

    def keep(sent, readback):
        kept["sent"], kept["readback"] = sent, readback

    result = run_cell(CELL, 2026093001, 5.0, False, rehearse=True, tamper=keep)
    assert result["correct"], result["compared"]
    assert all(v["value"] == 0 for v in result["compared"].values())
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
def test_fault_under_the_timed_path_is_not_correct(fault, number, monkeypatch):
    monkeypatch.setenv("CHIPBENCH_FAULT", fault)
    result = run_cell(CELL, 2026093002, 5.0, False, rehearse=True,
                      launcher=FAULTY)
    assert not result["correct"]
    assert result["compared"][number]["value"] > 0
