"""How much of each commit stage its child spans account for.

The three commit stages that hold most of a request's time
(`commit_execute`, `commit_compact`, `commit_checkpoint`) are split into
child spans, one catalog event per phase (trace/event.py), and
`flush_columns` once more (`flush_account_reads`, its reads of the
accounts' previous rows, and, where an op holds two-phase rows,
`flush_two_phase` with `memtable_fold` inside it); a served lookup's
`commit_execute` holds `lookup_ids`, `lookup_cache`, `lookup_tree` and
`lookup_pack`; a request the device ledger hands to its host mirror
`execute_mirror_drain`, `execute_host_judge` and `execute_mirror_push`;
an escalated request's `execute_escalate` holds its second
`execute_dispatch` (which `commit_execute` counts once, as a dispatch);
the account reads (`flush_account_reads`,
`lookup_tree`) hold the grid's `grid_read` and `grid_verify`, and
`compact_beat` and `checkpoint_forest` its `grid_write`. A child
carries no pointer to its parent: it belongs to the parent occurrence
that contains it in time, on the same pid. This module lays the children
over their parents and says what share of each parent they cover — the
residual is time under the stage that no span names yet.

    python -m tigerbeetle_tpu.trace.span_tree <chrome trace>.json [operation]

prints, per stage, the number of occurrences, the least, mean and median
covered share, the children's mean milliseconds, and the occurrence with
the largest residual. With an operation's name (`create_transfers`)
only the `commit_execute` and `commit_compact` spans of that operation's
ops are counted.
"""

from __future__ import annotations

import json
import statistics
import sys

STAGE_CHILDREN: dict = {
    "commit_execute": (
        "execute_decode", "execute_stage", "execute_dispatch",
        "execute_delta_fetch", "execute_encode",
        # A lookup's: ids from bytes, the cache loop, Tree.get_many for
        # the misses (only where an id missed), the rows packed.
        "lookup_ids", "lookup_cache", "lookup_tree", "lookup_pack",
        # A request the host mirror answered: the drain before it, its
        # judgement, the push of what it changed back to the device.
        "execute_mirror_drain", "execute_host_judge", "execute_mirror_push"),
    "commit_compact": (
        "flush_columns", "flush_objects", "flush_cache_upsert",
        "compact_beat"),
    "commit_checkpoint": (
        "checkpoint_wal_barrier", "checkpoint_mirror_drain",
        "checkpoint_flush", "checkpoint_forest", "checkpoint_superblock"),
    # The previous-row reads of the chunk's distinct accounts, in every
    # op, and what two-phase rows cost the column flush: those two open
    # only in an op whose delta holds such a row (a single-phase trace
    # has no flush_two_phase occurrence).
    "flush_columns": ("flush_account_reads", "flush_two_phase"),
    "flush_two_phase": ("memtable_fold",),
    # The re-dispatch to a fixpoint tier after a `limit_only` fallback:
    # the dispatch that judged the request. It is counted under
    # commit_execute as any dispatch, so the escalation is not listed
    # there beside it.
    "execute_escalate": ("execute_dispatch",),
    # The grid's block work under its callers (lsm/grid.py, lsm/tree.py):
    # a batched read's device fan-out and checksums where a block missed
    # the cache, and the trees' block writes. These carry no `op`.
    "flush_account_reads": ("grid_read", "grid_verify"),
    "lookup_tree": ("grid_read", "grid_verify"),
    "compact_beat": ("grid_write",),
    "checkpoint_forest": ("grid_write",),
}


def stage_occurrences(events: list, stage: str) -> list:
    """One record per occurrence of `stage` among Chrome "X" events:
    its args, start, duration (us) and the summed duration of each of
    its children (those of STAGE_CHILDREN[stage] that start inside it on
    the same pid)."""
    names = STAGE_CHILDREN[stage]
    spans = [e for e in events if e.get("ph") == "X"]
    children = [e for e in spans if e["name"] in names]
    out = []
    for p in spans:
        if p["name"] != stage:
            continue
        lo, hi = p["ts"], p["ts"] + p["dur"]
        by_child = dict.fromkeys(names, 0.0)
        for c in children:
            if c["pid"] == p["pid"] and lo <= c["ts"] < hi:
                by_child[c["name"]] += c["dur"]
        out.append({"args": p.get("args", {}), "ts": lo, "dur": p["dur"],
                    "children": by_child,
                    "covered": sum(by_child.values())})
    return out


def children_share(events: list, keep=None) -> dict:
    """stage -> summary of how far its children cover it, over the
    occurrences `keep(record)` admits (default: all)."""
    out = {}
    for stage, names in STAGE_CHILDREN.items():
        occ = [r for r in stage_occurrences(events, stage)
               if keep is None or keep(stage, r)]
        if not occ:
            continue
        shares = [r["covered"] / r["dur"] if r["dur"] else 1.0 for r in occ]
        worst = max(occ, key=lambda r: r["dur"] - r["covered"])
        total = sum(r["dur"] for r in occ)
        out[stage] = {
            "count": len(occ),
            "share_min": min(shares),
            "share_mean": sum(r["covered"] for r in occ) / total
            if total else 1.0,
            "share_median": statistics.median(shares),
            "mean_ms": total / len(occ) / 1e3,
            "children_mean_ms": {
                n: sum(r["children"][n] for r in occ) / len(occ) / 1e3
                for n in names},
            "worst_residual": {
                "op": worst["args"].get("op"),
                "residual_ms": (worst["dur"] - worst["covered"]) / 1e3,
                "dur_ms": worst["dur"] / 1e3},
        }
    return out


def keep_operation(events: list, operation: int):
    """A `keep` for children_share: the commit_execute and
    commit_compact occurrences of the ops that executed `operation`
    (a wire operation code), the children those ops own, and every
    checkpoint."""
    ops = {e["args"]["op"] for e in events
           if e.get("name") == "commit_execute"
           and e["args"].get("operation") == operation}
    return (lambda stage, r: stage in ("commit_checkpoint",
                                       "checkpoint_forest")
            or r["args"].get("op") in ops)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) not in (1, 2):
        print(__doc__, file=sys.stderr)
        return 2
    with open(argv[0]) as f:
        events = json.load(f)["traceEvents"]
    keep = None
    if len(argv) == 2:
        from ..types import Operation

        keep = keep_operation(events, int(Operation[argv[1]]))
    print(json.dumps(children_share(events, keep), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
