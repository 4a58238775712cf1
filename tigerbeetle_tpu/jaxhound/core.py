"""jaxhound core: census + lint primitives over jax compile artifacts.

reference: src/copyhound.zig:1-9 — the reference hunts large memcpys and
monomorphization bloat in LLVM IR; the TPU-native analog inspects XLA
artifacts: per-kernel HLO instruction counts, fusion counts, and the
largest temp buffers. Compile bloat here is the same disease copyhound
hunts there — generated code growing without anyone noticing.

This module holds the trace-level machinery (heavy-op census, scan-body
census, telemetry census, budget-trail resolvers, closure/while/gather
lints, lowered-artifact analysis). The whole-stack static passes —
device determinism, host determinism, retrace budget, sharding spec —
live in sibling modules of this package; `python -m
tigerbeetle_tpu.jaxhound --help` is the operator entry point.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import Callable

# ------------------------------------------------------------- op budgets
# Heavy-op classes (gathers, scatters, sorts, scans: what the kernels'
# time goes to — they have no matmul content). jaxpr-primitive ->
# budget class.
# segment_* reductions lower through scatter-add/min/max; associative
# scans and lax.scan/while are the 'scan' class.
HEAVY_CLASSES = {
    "sort": "sort",
    "gather": "gather",
    "scatter": "scatter",
    "scatter-add": "segment_sum",
    "scatter-max": "segment_sum",
    "scatter-min": "segment_sum",
    "scatter-mul": "segment_sum",
    "scan": "scan",
    "while": "scan",
    "cumsum": "scan",
    "cummax": "scan",
    "cummin": "scan",
    "cumprod": "scan",
    "reduce_window": "scan",
    "reduce_window_sum": "scan",
    "reduce_window_max": "scan",
    "reduce_window_min": "scan",
    # Cross-device collectives (the partitioned exchange's op class):
    # each is an ICI round trip billed like a heavy op, and the
    # partitioned tiers pin their count so the exchange cannot silently
    # grow (opbudget lint: none of these may move whole-state operands).
    "psum": "collective",
    "pmin": "collective",
    "pmax": "collective",
    "all_gather": "collective",
    "all_to_all": "collective",
    "ppermute": "collective",
    "reduce_scatter": "collective",
}
HEAVY_CLASS_ORDER = ("sort", "gather", "scatter", "segment_sum", "scan",
                     "collective")


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * dtype.itemsize


def _walk_jaxpr(jaxpr, visit) -> None:
    """Depth-first over a jaxpr and every sub-jaxpr (pjit/cond/scan/
    shard_map/...). Params carry bodies either as ClosedJaxpr (pjit,
    scan — has .jaxpr) or as a raw Jaxpr (shard_map — has .eqns
    directly); both forms recurse."""
    for eqn in jaxpr.eqns:
        visit(eqn)
        for sub in eqn.params.values():
            subs = sub if isinstance(sub, (list, tuple)) else (sub,)
            for s in subs:
                if hasattr(s, "eqns"):  # raw Jaxpr param (shard_map)
                    _walk_jaxpr(s, visit)
                    continue
                inner = getattr(s, "jaxpr", None)
                if inner is not None:
                    _walk_jaxpr(inner if hasattr(inner, "eqns") else s,
                                visit)


def heavy_census(closed_jaxpr) -> dict:
    """Per-class heavy-op counts + heavy operand bytes of a traced fn.

    Input: a ClosedJaxpr (jax.make_jaxpr(fn)(*args)). Counts the
    primitives in HEAVY_CLASSES recursively (one count per *executed*
    op instance in the unrolled program — a scan body counts once, like
    the dispatch layer sees it) and sums the operand bytes those ops
    read (the bytes-dependent part of a heavy op's cost).
    Deterministic: no XLA compile, trace-level only.

    The collective class is ALSO broken out by operand bytes
    (`collective_operand_bytes`): collectives bill ICI traffic, not
    HBM reads, so the partitioned budgets pin their byte mass
    separately — including inside lax.scan bodies, where the fused
    partitioned-chain route runs the whole exchange (scan_body_census
    inherits the key; one iteration's exchange bytes, amortized x1 in
    the program like every other body op)."""
    counts = collections.Counter({c: 0 for c in HEAVY_CLASS_ORDER})
    nbytes = [0]
    coll_bytes = [0]

    def visit(eqn):
        cls = HEAVY_CLASSES.get(eqn.primitive.name)
        if cls is None:
            return
        counts[cls] += 1
        b = 0
        for v in eqn.invars:
            b += _aval_bytes(getattr(v, "aval", None))
        nbytes[0] += b
        if cls == "collective":
            coll_bytes[0] += b

    _walk_jaxpr(closed_jaxpr.jaxpr, visit)
    out = {"heavy": {c: counts[c] for c in HEAVY_CLASS_ORDER}}
    out["heavy_total"] = sum(out["heavy"].values())
    out["heavy_operand_bytes"] = nbytes[0]
    out["collective_operand_bytes"] = coll_bytes[0]
    return out


def scan_bodies(closed_jaxpr) -> list:
    """Every lax.scan body (ClosedJaxpr) anywhere in the program, in
    visit order. The scan-form chain dispatch's whole point is that the
    body lowers ONCE regardless of the scan length W — these are the
    jaxprs the dispatch layer re-executes per iteration."""
    bodies: list = []

    def visit(eqn):
        if eqn.primitive.name == "scan":
            inner = eqn.params.get("jaxpr")
            if inner is not None:
                bodies.append(inner)

    _walk_jaxpr(closed_jaxpr.jaxpr, visit)
    return bodies


def scan_body_census(closed_jaxpr) -> dict:
    """heavy_census of the LARGEST lax.scan body in the program (by
    heavy total) — the chain route's per-ITERATION op mass. The
    whole-window scan dispatch executes this body once per window
    iteration (body ops x 1 in the program, x W at runtime), so the
    op-budget gate pins the BODY census alongside the whole-program one
    (which counts the body once plus the outer scan op). The census
    covers every heavy class INCLUDING collectives (the fused
    partitioned chain runs the psum exchange inside its scan body) and
    carries their operand-byte mass as collective_operand_bytes —
    state_gathers() recurses into scan bodies with the same classing,
    so a whole-state collective inside a scan cannot hide from the
    lint either. Returns a zero census when the program holds no
    scan."""
    best = None
    for b in scan_bodies(closed_jaxpr):
        c = heavy_census(b)
        if best is None or c["heavy_total"] > best["heavy_total"]:
            best = c
    if best is None:
        best = {"heavy": {c: 0 for c in HEAVY_CLASS_ORDER},
                "heavy_total": 0, "heavy_operand_bytes": 0,
                "collective_operand_bytes": 0}
    return best


# The telemetry plane's pack marker: parallel/partitioned.py stacks its
# u32 telemetry words through a named, non-inlined jit wrapper so the
# pack survives tracing as a `pjit` equation carrying this name — the
# lanes are then a CENSUSABLE CLASS of their own instead of dissolving
# into the surrounding elementwise soup.
TELEMETRY_PACK_NAME = "_telemetry_pack"


def telemetry_census(closed_jaxpr) -> dict:
    """Census of the device-telemetry lanes in a traced program.

    Finds every `pjit` equation named TELEMETRY_PACK_NAME (anywhere —
    including inside the fused chain route's scan body) and reports:
    `sites` (pack call sites in the program), `lanes` (telemetry words
    per pack — the widest site), and `ops` (equation count inside the
    largest pack body). The op-budget gate pins `lanes` so the
    telemetry block cannot grow a word without a committed budget bump,
    and bounds `ops` so 'just one more derived metric' cannot smuggle
    real compute into the observability plane."""
    sites = []

    def visit(eqn):
        if eqn.primitive.name != "pjit":
            return
        if eqn.params.get("name") != TELEMETRY_PACK_NAME:
            return
        inner = eqn.params.get("jaxpr")
        n_ops = len(inner.jaxpr.eqns) if inner is not None else 0
        sites.append((len(eqn.invars), n_ops))

    _walk_jaxpr(closed_jaxpr.jaxpr, visit)
    return {
        "sites": len(sites),
        "lanes": max((s[0] for s in sites), default=0),
        "ops": max((s[1] for s in sites), default=0),
    }


# Repo-relative perf/ dir: this file lives two levels below the repo
# root (tigerbeetle_tpu/jaxhound/core.py).
_DEFAULT_PERF_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "perf")


def _newest_round_path(perf_dir: str | None, prefix: str) -> str:
    """Resolve the newest committed `<prefix>_r<N>.json` budget file
    (highest round number) under perf_dir."""
    if perf_dir is None:
        perf_dir = _DEFAULT_PERF_DIR
    paths = glob.glob(os.path.join(perf_dir, f"{prefix}_r*.json"))
    best = None
    best_round = -1
    for p in paths:
        m = re.search(rf"{prefix}_r(\d+)\.json$", os.path.basename(p))
        if m and int(m.group(1)) > best_round:
            best_round = int(m.group(1))
            best = p
    if best is None:
        raise FileNotFoundError(
            f"no {prefix}_r*.json under {perf_dir!r}")
    return best


def newest_budget_path(perf_dir: str | None = None) -> str:
    """Path of the NEWEST committed perf/opbudget_r*.json (highest
    round number). The budget trail is append-oriented — every round
    that moves a pinned census commits a new file — so consumers
    (smokes, the gate) resolve the head dynamically instead of
    hardcoding a round that silently goes stale."""
    return _newest_round_path(perf_dir, "opbudget")


def newest_tracebudget_path(perf_dir: str | None = None) -> str:
    """Path of the NEWEST committed perf/tracebudget_r*.json — the
    retrace-budget trail (compiled-signature pins for the dispatch
    route matrix), same append-oriented regime as the op-budget
    trail."""
    return _newest_round_path(perf_dir, "tracebudget")


def newest_membudget_path(perf_dir: str | None = None) -> str:
    """Path of the NEWEST committed perf/membudget_r*.json — the
    static-allocation memory-budget trail (per-component resident
    bytes for the serving ledger, trace/memwatch.py), same
    append-oriented regime as the op-budget trail."""
    return _newest_round_path(perf_dir, "membudget")


# ----------------------------------------------------------- static lints

CLOSURE_CONST_LIMIT = 4096  # bytes; PERF.md: ~64 ms/call at 0.5 MB


def _collect_consts(closed_jaxpr) -> list:
    """Every closed-over constant anywhere in the program: the
    top-level ClosedJaxpr's consts PLUS the consts of every sub-
    ClosedJaxpr (pjit/cond bodies keep their own const list — a
    constant baked inside a nested jit never surfaces in the outer
    `.consts`, so a top-level-only scan misses exactly the chain /
    partitioned-chain bodies). Raw Jaxpr params (shard_map) carry no
    const list of their own; their constvars are threaded from an
    enclosing ClosedJaxpr, which this walk does see."""
    consts = list(closed_jaxpr.consts)

    def visit(eqn):
        for sub in eqn.params.values():
            subs = sub if isinstance(sub, (list, tuple)) else (sub,)
            for s in subs:
                if hasattr(s, "consts"):  # nested ClosedJaxpr
                    consts.extend(s.consts)

    _walk_jaxpr(closed_jaxpr.jaxpr, visit)
    return consts


def closure_constants(closed_jaxpr) -> list[tuple[str, int]]:
    """(dtype/shape label, bytes) of every closed-over constant above
    CLOSURE_CONST_LIMIT — including constants closed inside scan/cond/
    pjit sub-jaxprs (a lookup table baked into the chain body is just
    as poisonous as one at top level). A baked-in constant is part of
    the program, not an operand the caller can keep resident and
    donate, so serving-path entries must take every table as an
    argument."""
    out = []
    for c in _collect_consts(closed_jaxpr):
        shape = getattr(c, "shape", ())
        dtype = getattr(c, "dtype", None)
        if dtype is None:
            continue
        n = 1
        for d in shape:
            n *= int(d)
        size = n * dtype.itemsize
        if size > CLOSURE_CONST_LIMIT:
            out.append((f"{dtype}{list(shape)}", size))
    return out


def while_ops(closed_jaxpr) -> int:
    """Count of while/fori loops anywhere in the program. One executed
    lax.while_loop degrades every later dispatch in the process to
    5-8 ms (PERF.md round-2 finding) — serving-path lowerings must stay
    straight-line."""
    n = [0]

    def visit(eqn):
        if eqn.primitive.name == "while":
            n[0] += 1

    _walk_jaxpr(closed_jaxpr.jaxpr, visit)
    return n[0]


# Whole-state gather threshold: the partitioned exchange moves compact
# per-event bundles (a few MB at N_PAD=8192); any collective whose
# operand is larger than this is moving ledger STORE rows, which is
# exactly the regression the partitioned layout exists to prevent.
STATE_GATHER_LIMIT = 16 << 20  # bytes


def state_gathers(closed_jaxpr, limit: int = STATE_GATHER_LIMIT) -> list:
    """(primitive, operand_bytes) for every cross-device collective whose
    per-device operand exceeds `limit` — the 'exchange regressed into a
    whole-state all_gather' lint for partitioned serving entries."""
    hits: list = []

    def visit(eqn):
        if HEAVY_CLASSES.get(eqn.primitive.name) != "collective":
            return
        nbytes = sum(_aval_bytes(getattr(v, "aval", None))
                     for v in eqn.invars)
        if nbytes > limit:
            hits.append((eqn.primitive.name, nbytes))

    _walk_jaxpr(closed_jaxpr.jaxpr, visit)
    return hits


def donated_inputs(lowered) -> int:
    """Number of donated parameters reported by a lowered artifact.
    State-carrying entries must donate their ledger buffers
    (donate_argnums) or every dispatch pays a full state copy. Donation
    appears as input->output aliasing (`tf.aliasing_output`) when
    resolvable at lowering time, or as a `jax.buffer_donor` mark (e.g.
    sharded programs) when the pairing is deferred to the runtime."""
    text = lowered.as_text()
    return (len(re.findall(r"tf\.aliasing_output", text))
            + len(re.findall(r"jax\.buffer_donor", text)))


def analyze_lowered(lowered) -> dict:
    """Instruction histogram + size stats from a lowered jax computation."""
    text = lowered.as_text()
    ops = collections.Counter()
    # StableHLO prints ops in two forms: pretty ('%3 = stablehlo.add %0,
    # %2 : ...') and generic ('%9 = "stablehlo.scatter"(%0, ...) ...');
    # match the op name in either (also '%cst = stablehlo.constant ...').
    op_re = re.compile(r"%[\w#]+(?::\d+)? = \"?([\w]+\.[\w.]+)\"?[ (<]")
    for line in text.splitlines():
        match = op_re.match(line.strip())
        if match:
            ops[match.group(1)] += 1
    compiled = lowered.compile()
    stats = {}
    unavailable = []
    try:
        analysis = compiled.cost_analysis()
        if isinstance(analysis, list):
            analysis = analysis[0]
        if analysis:
            stats = {k: analysis[k] for k in
                     ("flops", "bytes accessed", "optimal_seconds")
                     if k in analysis}
    except Exception as e:  # backend-dependent; record WHY it failed
        unavailable.append(f"cost_analysis: {type(e).__name__}: {e}")
    try:
        mem = compiled.memory_analysis()
        stats["temp_bytes"] = getattr(mem, "temp_size_in_bytes", None)
        stats["argument_bytes"] = getattr(mem, "argument_size_in_bytes", None)
        stats["output_bytes"] = getattr(mem, "output_size_in_bytes", None)
    except Exception as e:
        unavailable.append(f"memory_analysis: {type(e).__name__}: {e}")
    out = {
        "instructions": sum(ops.values()),
        "top_ops": ops.most_common(12),
        "stats": stats,
    }
    if unavailable:
        # Consumers (report()) render "n/a: <reason>" instead
        # of mistaking a swallowed backend failure for zero cost.
        out["stats_unavailable"] = "; ".join(unavailable)
    return out


def kernels() -> dict[str, Callable[[], "object"]]:
    """Lowerable entry points (thunks so nothing compiles until asked)."""

    def transfers_fast():
        import jax
        import numpy as np

        from ..ops.batch import transfers_to_arrays
        from ..ops.fast_kernels import create_transfers_fast
        from ..ops.ledger import init_state, pad_transfer_events
        from ..types import Transfer

        state = init_state(1 << 10, 1 << 12)
        ev = pad_transfer_events(transfers_to_arrays(
            [Transfer(id=1, debit_account_id=1, credit_account_id=2,
                      amount=1, ledger=1, code=1)]))
        return jax.jit(create_transfers_fast).lower(
            state, ev, np.uint64(1000), np.int32(1))

    def accounts_fast():
        import jax
        import numpy as np

        from ..ops.fast_kernels import create_accounts_fast
        from ..ops.ledger import init_state, pad_account_events
        from ..ops.batch import accounts_to_arrays
        from ..types import Account

        state = init_state(1 << 10, 1 << 12)
        ev = pad_account_events(accounts_to_arrays(
            [Account(id=1, ledger=1, code=1)]))
        return jax.jit(create_accounts_fast).lower(
            state, ev, np.uint64(1000), np.int32(1))

    return {
        "create_transfers_fast": transfers_fast,
        "create_accounts_fast": accounts_fast,
    }


def report(kernel: str | None = None) -> list[str]:
    registry = kernels()
    if kernel is not None and kernel not in registry:
        raise KeyError(
            f"unknown kernel {kernel!r}; available: {sorted(registry)}")
    lines = []
    for name, thunk in registry.items():
        if kernel and name != kernel:
            continue
        info = analyze_lowered(thunk())
        lines.append(f"{name}: {info['instructions']} HLO instructions")
        for op, count in info["top_ops"]:
            lines.append(f"  {op:<24} {count}")
        for key, value in info["stats"].items():
            if value is not None:
                lines.append(f"  {key}: {value}")
        if info.get("stats_unavailable"):
            lines.append(f"  stats: n/a ({info['stats_unavailable']})")
    return lines
