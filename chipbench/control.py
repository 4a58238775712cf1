#!/usr/bin/env python3
"""The control of the comparison: the plain reference put in the
program's place with one stated guarantee broken. It has to come out
NOT correct; the program's own answers, read in the same process, have
to come out correct.

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 --seconds <s>

The system states no numeric precision, so the control breaks a
guarantee of the configuration file:

  lost_write   "an acknowledged write is read back": the control
               acknowledges each session's last window request and then
               forgets it; its lookups answer from the state without it.
  stale_read   (traffic with reads) "strict serializability: a read
               sees every write acknowledged before it was sent": the
               control answers each read of the run from the state before
               the last write ahead of it in the commit order.
  no_limits    (configurations with balance limits) "the event that
               would pass the limit answers exceeds_credits": the control
               judges every event with the limit flags ignored.

Each run drives a real window on the chip (same entry, sizes and load
as the cell, a shorter window), keeps the requests, the commit order
and the timestamps, and swaps only the answers. Prints one JSON line
per seed with the program's numbers and each control's.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from chipbench import check, wire  # noqa: E402
from chipbench.reference.ledger import StateMachineOracle  # noqa: E402
from chipbench.reference.ledger_types import Account  # noqa: E402


def _rows(ref, s) -> np.ndarray:
    return np.frombuffer(b"".join(check.read_rows(ref, s)),
                         dtype=s.request.result)


def _control_answers(order: list, *, skip=()):
    """The broken reference's answers to the requests in commit order:
    {id(request): a write's RESULT records, a read's rows}, and its
    final state."""
    ref = StateMachineOracle()
    answers = {}
    for s in order:
        if id(s) in skip:
            continue
        if s.request.is_read:
            answers[id(s)] = _rows(ref, s)
            continue
        want = check.apply(ref, s)
        rec = np.zeros(len(want), dtype=wire.RESULT)
        rec["timestamp"] = [w.timestamp for w in want]
        rec["status"] = [int(w.status) for w in want]
        answers[id(s)] = rec
    return answers, ref


def _lookups(ref, readback: dict) -> dict:
    return {
        "accounts": [(ids, b"".join(a.pack() for a in ref.lookup_accounts(ids)))
                     for ids, _ in readback["accounts"]],
        "transfers": [(ids, b"".join(t.pack() for t in ref.lookup_transfers(ids)))
                      for ids, _ in readback["transfers"]]}


def lost_write(sent: list, readback: dict) -> None:
    order, _ = check.ordered(sent)
    last = {s.session: s for s in order
            if s.phase == "window" and not s.request.is_read}
    _, ref = _control_answers(order, skip={id(s) for s in last.values()})
    readback.update(_lookups(ref, readback))


def no_limits(sent: list, readback: dict) -> None:
    order, _ = check.ordered(sent)
    kept = Account.debits_exceed_credits
    Account.debits_exceed_credits = lambda self, amount: False
    try:
        answers, ref = _control_answers(order)
    finally:
        Account.debits_exceed_credits = kept
    for s in order:
        s.results = answers[id(s)]
    readback.update(_lookups(ref, readback))


def stale_read(sent: list, readback: dict) -> None:
    order, _ = check.ordered(sent)
    ref = StateMachineOracle()
    for i, s in enumerate(order):
        if s.request.is_read:
            continue
        # the reads that follow this write see the state before it
        for r in order[i + 1:]:
            if not r.request.is_read:
                break
            r.results = _rows(ref, r)
        check.apply(ref, s)


CONTROLS = {"lost_write": lost_write, "no_limits": no_limits,
            "stale_read": stale_read}


def main(argv=None) -> int:
    from chipbench.run import run_cell
    from chipbench.server import BenchFailure

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=15.0)
    p.add_argument("--cells", default=None)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    ok = True
    for seed in (int(x) for x in args.seeds.split(",")):
        kept = {}

        def keep(sent, readback):
            kept["sent"], kept["readback"] = sent, readback

        try:
            result = run_cell(args.workload, seed, args.seconds, False,
                              rehearse=args.rehearse, tamper=keep,
                              cells=args.cells)
        except BenchFailure as e:
            print(f"[control] seed {seed}: FAILED: {e}", file=sys.stderr)
            return 1
        line = {"workload": args.workload, "seed": seed,
                "device": result["device"],
                "events_compared": sum(s.request.n_events
                                       for s in kept["sent"]),
                "program": {k: v["value"]
                            for k, v in result["compared"].items()},
                "program_correct": result["correct"],
                "metrics": result["metrics"], "window": result["window"]}
        ok &= result["correct"]
        limited = any(
            np.frombuffer(s.request.payload, dtype=wire.ACCOUNT)["flags"].any()
            for s in kept["sent"] if s.request.operation == "create_accounts")
        reads = any(s.request.is_read for s in kept["sent"])
        for name, control in CONTROLS.items():
            if (name == "no_limits" and not limited) or \
                    (name == "stale_read" and not reads):
                continue
            sent, readback = copy.deepcopy((kept["sent"], kept["readback"]))
            control(sent, readback)
            numbers = check.judge(sent, readback)
            line[name] = numbers
            line[name + "_correct"] = check.verdict(numbers)
            ok &= not line[name + "_correct"]
        print(json.dumps(line), flush=True)
    print(f"[control] {'every control failed and every program run passed' if ok else 'A CONTROL PASSED OR A PROGRAM RUN FAILED'}",
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
