#!/usr/bin/env python3
"""One cell of the benchmark, on the served path.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Formats a data file, boots `start --engine=<the configuration's>`
(production layout, one replica) as the only process on the chip, loads
the configuration's
accounts over TCP, sends a few un-timed requests of the cell's own
traffic (and one un-timed lookup where the mix states reads), measures
a closed-loop window of about `--seconds` (a fixed number of requests:
`--seconds` times the mix's stated rate), reads the
state back, stops the server in order, replays every answer through the
plain reference, and prints the contract's one JSON line last on
stdout. This process never starts a JAX backend.

A cell is an entry of BENCHMARK.json's `workloads`; its configuration
is chipbench/configs/<config>.json, its traffic mix
chipbench/traffic/<traffic>.json, each end-to-end metric
chipbench/e2e_metrics/<name>.py and each per-layer metric
chipbench/layer_metrics/<name>.py: adding one is adding files and
entries, editing none. A configuration states the sizes its server is
formatted and started with (`server.format_args`, `server.start_args`:
appended to the two command lines as they stand) and the state that
exists before the window (`transfers.preloaded_count`, sent through
the served path in set-up). A traffic mix may state reads (`reads`:
the last of every `every` requests of a session is a `lookup_accounts`
as wide as the wire admits, its ids drawn by the configuration's key
skew); each is held against the plain reference at its place in the
commit order (`read_mismatches`). The request percentiles read every
answered request of the window, of any operation; the lookups' own
median stands beside them (`lookup_p50_ms`).
What a file asks for and the harness cannot serve (an open loop, three
replicas, a guarantee no comparison holds the program to, an argument
the harness itself puts on the command line, a read the comparison
cannot hold or cannot place) fails the run; no key is read by nothing.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import collections  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from chipbench import check, trace_reduce, wire  # noqa: E402
from chipbench.server import (HARNESS_ARGS, BenchFailure, Server,  # noqa: E402
                              format_data_file, free_port)
from chipbench.traffic import STREAM_PRELOAD, STREAM_WARM, Deployment  # noqa: E402
from chipbench.window import Sent, StoreBudget, run_window, send  # noqa: E402

HERE = os.path.join(ROOT, "chipbench")
BOOT_TIMEOUT_S = 1000      # a cold warm-up compiles for minutes
SETUP_REPLY_TIMEOUT_S = 900.0  # and so may a cell's first un-timed request or lookup
PROFILE_TIMEOUT_S = 120.0  # for the profiler to start, and to write its trace
# The rehearsal's `--small` server: TEST_LAYOUT and start's small caps;
# of a preload it sends eight requests, a quarter of its store.
REHEARSAL = {"accounts": 2000, "transfers": 1 << 14, "preload_requests": 8}


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


def rss_bytes() -> int:
    """This process's resident set now (Linux; 0 where /proc is not)."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except OSError:
        return 0


def load_cell(workload: str,
              cells: str | None = None) -> tuple[dict, dict, dict, dict]:
    """`cells` names a file of further `configs` and `workloads`: the
    builder's scratch cells and the tests' fixtures, never the
    driver's, which runs what BENCHMARK.json holds."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if cells:
        with open(os.path.join(ROOT, cells)) as f:
            more = json.load(f)
        bench = dict(bench, configs=bench["configs"] + more["configs"],
                     workloads=bench["workloads"] + more["workloads"])
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise BenchFailure(f"no workload {workload!r} in BENCHMARK.json "
                           f"(has: {sorted(by_name)})")
    cell = by_name[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    return bench, cell, config, mix


def load_reader(kind: str, name: str):
    """The reader of one metric: chipbench/<kind>/<name>.py's `read`."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(kind: str, entries: list, workload: str, context: dict) -> dict:
    """Each metric of `entries` that this cell reports, through its
    reader; a reader that finds nothing to read leaves its metric out."""
    out = {}
    for entry in entries:
        if workload not in entry.get("workloads", [workload]):
            continue
        value = load_reader(kind, entry["name"])(context)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def servable(config: dict, mix: dict) -> None:
    """Fail on what a configuration or a mix asks for and this harness
    cannot serve: it drives closed loops against one server process."""
    if mix["loop"] != "closed":
        raise BenchFailure(f"traffic {mix['name']!r} asks for a "
                           f"{mix['loop']!r} loop; the harness drives closed "
                           "loops only")
    reads = mix.get("reads")
    if reads is not None:
        if set(reads) != {"every"} or not isinstance(reads["every"], int) \
                or reads["every"] < 2:
            raise BenchFailure(
                f"traffic {mix['name']!r}: `reads` is {reads!r}; it states "
                "`every` alone, n >= 2: the last of every n requests is a "
                "`lookup_accounts` as wide as the wire admits, ids by the "
                "configuration's key skew (what the generator makes and "
                "the comparison holds)")
        if mix["sessions"] != 1:
            raise BenchFailure(
                f"traffic {mix['name']!r} asks for reads from "
                f"{mix['sessions']} sessions; a read's reply names no "
                "prepare, so its place among another session's concurrent "
                "writes cannot be known: reads need one session")
        if config["transfers"].get("two_phase"):
            raise BenchFailure(
                f"traffic {mix['name']!r} asks for reads on the two-phase "
                f"configuration {config['name']!r}, whose request k + 1 "
                "resolves request k: a read in a write's place would leave "
                "pendings unresolved")
    server = config["server"]
    if server["replica_count"] != 1:
        raise BenchFailure(f"configuration {config['name']!r} asks for "
                           f"{server['replica_count']} replicas; the harness "
                           "starts one server process")
    if server["small_layout"]:
        raise BenchFailure(f"configuration {config['name']!r} asks for the "
                           "small layout: that is the rehearsal's, no cell's")
    for key in ("format_args", "start_args"):
        for arg in server.get(key, []):
            name = arg.split("=", 1)[0]
            # argparse takes any unambiguous prefix of a flag for it
            if not arg.startswith("--") or len(name) < 3 or any(
                    owned.startswith(name) for owned in HARNESS_ARGS):
                raise BenchFailure(
                    f"configuration {config['name']!r} puts {arg!r} in "
                    f"server.{key}: each entry is one `--flag` or "
                    f"`--flag=value`, and {', '.join(HARNESS_ARGS)} and the "
                    "path are the harness's own")
    if config["guarantees"]["replicas"] != server["replica_count"]:
        raise BenchFailure("the guarantees name another number of replicas "
                           "than the server block")
    unheld = sorted(set(config["guarantees"]) - set(check.GUARANTEES))
    if unheld:
        raise BenchFailure(f"configuration {config['name']!r} states "
                           f"guarantees {unheld} that no compared number "
                           "holds the program to")


def plan_setup(dep: Deployment, mix: dict, n_max: int, n_req: int,
               capacity: int, preload_cut: int | None = None,
               ) -> tuple[list, list[int]]:
    """The set-up's transfers before anything boots: the funding
    requests and the width of each preload request, held as a whole
    (the warm requests with them) against the same budget the run
    counts by. `preload_cut` is the rehearsal's."""
    preload = dep.config["transfers"].get("preloaded_count", 0)
    if preload_cut is not None:
        preload = min(preload, preload_cut)
    try:
        widths = dep.preload_widths(preload, n_max)
    except ValueError as e:
        raise BenchFailure(str(e))
    funding = dep.funding_requests(n_max)
    dry = StoreBudget(capacity, n_req)
    for n in ([r.n_events for r in funding] + widths
              + [n_req] * mix["warm_requests"]):
        if not dry.reserve(n):
            raise BenchFailure(
                f"set-up alone would pass transfer_count: {dry.created} "
                f"transfers and a request of {n} more, and a run may create "
                f"{capacity} less one request of {n_req}")
        dry.settle(n, n)
    return funding, widths


def wait_for(path: str, what: str) -> None:
    deadline = time.monotonic() + PROFILE_TIMEOUT_S
    while not os.path.exists(path):
        if time.monotonic() > deadline:
            raise BenchFailure(f"{what} within {PROFILE_TIMEOUT_S:.0f}s")
        time.sleep(0.02)


def lookup(client, operation, ids: list[int]) -> bytes | None:
    """One lookup request; the reply's rows, or None if unanswered."""
    try:
        reply = client.request(operation,
                               wire.encode_one(wire.ids_payload(ids), 16),
                               timeout_s=SETUP_REPLY_TIMEOUT_S)
        return wire.decode_one(reply, 128)
    except (TimeoutError, ValueError, OSError) as e:
        say(f"{operation.name}: no usable reply ({type(e).__name__}: {e})")
        return None


def read_back(client, Operation, dep: Deployment, sent: list,
              n_lookup: int, seed: int) -> dict:
    """Every account, and a sample of transfer ids drawn from the seed
    with each session's last acknowledged request in it."""
    out = {"accounts": [], "transfers": []}
    ids = dep.account_ids()
    for i in range(0, len(ids), n_lookup):
        chunk = ids[i:i + n_lookup]
        out["accounts"].append(
            (chunk, lookup(client, Operation.lookup_accounts, chunk)))
    transfers = [s for s in sent
                 if s.request.operation == "create_transfers"
                 and s.error is None]
    if transfers:
        rng = np.random.default_rng([seed & ((1 << 63) - 1), 0x10CC])
        last = {s.session: s for s in transfers if s.phase == "window"}
        parts = [s.request.ids[-(n_lookup // (2 * len(last))):]
                 for s in last.values()]
        pool = np.concatenate([s.request.ids for s in transfers])
        take = n_lookup - sum(len(p) for p in parts)
        parts.append(pool[rng.choice(len(pool), size=min(take, len(pool)),
                                     replace=False)])
        chosen = np.unique(np.concatenate(parts), axis=0)
        tids = check.int_ids(chosen)[:n_lookup]
        out["transfers"].append(
            (tids, lookup(client, Operation.lookup_transfers, tids)))
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearse: bool = False, tamper=None,
             launcher: str | None = None, cells: str | None = None) -> dict:
    """The whole run; returns the result line's object. `rehearse` runs
    a `--small` server on whatever backend JAX has and skips the look
    for a chip (the caller fails the run afterwards); `tamper(sent,
    readback)` lets the control and the fault tests put other answers
    in the program's place before the comparison; `launcher` lets the
    fault tests start the server with its timed path broken; `cells`
    as in `load_cell`."""
    bench, cell, config, mix = load_cell(workload, cells)
    servable(config, mix)
    # The program's client library and admission rules: the system
    # under test, imported here; none of them starts a JAX backend.
    from tigerbeetle_tpu.clients.common import events_max
    from tigerbeetle_tpu.constants import HEADER_SIZE
    from tigerbeetle_tpu.types import Operation
    from tigerbeetle_tpu.vsr.client import Client
    from tigerbeetle_tpu.vsr.storage import TEST_LAYOUT, StorageLayout

    layout = TEST_LAYOUT if rehearse else StorageLayout()
    body_max = layout.message_size_max - HEADER_SIZE
    n_max = events_max(Operation.create_transfers, body_max)
    n_lookup = events_max(Operation.lookup_accounts, body_max)
    n_req = mix["events_per_request"]
    n_req = n_max if n_req == "wire_max" else min(int(n_req), n_max)
    reads = mix.get("reads")
    quota = max(1, round(seconds * mix["requests_per_second_per_session"]))
    capacity = (REHEARSAL["transfers"] if rehearse
                else config["transfers"]["transfer_count"])
    dep = Deployment(config, seed,
                     accounts_cut=REHEARSAL["accounts"] if rehearse else None)
    # The rehearsal's server is `--small` whatever the configuration
    # states: its sizes are left out and its preload is cut.
    stated = [config["server"].get(k, [])
              for k in ("format_args", "start_args")]
    format_args, start_args = ([], []) if rehearse else stated
    funding, preload_widths = plan_setup(
        dep, mix, n_max, n_req, capacity,
        REHEARSAL["preload_requests"] * n_max if rehearse else None)
    preload = sum(preload_widths)
    say(f"cell {workload}: config {config['name']}, traffic {mix['name']} "
        f"({mix['sessions']} sessions x {quota} requests x {n_req} events, "
        f"closed loop"
        + (f", the last of every {reads['every']} a lookup_accounts of "
           f"{n_lookup} ids" if reads else "") + "), "
        f"seed {seed}, {seconds}s, trace {int(trace)}; messages of "
        f"{layout.message_size_max} B; format_args {format_args}, "
        f"start_args {start_args}; {preload} transfers preloaded in "
        f"{len(preload_widths)} requests before the window; a run may create "
        f"{capacity} transfers (the configuration's transfer_count: what it "
        "states its stores hold, set-up and preload included)")
    if rehearse:
        say("rehearsal: a --small server; the configuration's format_args "
            f"{stated[0]} and start_args {stated[1]} are left out, its "
            f"preload cut from {config['transfers'].get('preloaded_count', 0)}"
            f" to {preload}, its accounts to {dep.n}, its transfers to "
            f"{capacity}")

    workdir = os.path.join(ROOT, "scratch", "chipbench", workload)
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    data_path = os.path.join(workdir, "0_0.tigerbeetle")
    span_path = os.path.join(workdir, "spans.json") if trace else None
    for line in format_data_file(data_path, small=rehearse,
                                 extra=format_args):
        say("format: " + line)
    stat = os.stat(data_path)
    say(f"data file as formatted: {stat.st_size} B, {stat.st_blocks * 512} B "
        "of them allocated")
    port = free_port()
    server = Server(port, data_path, workdir, small=rehearse,
                    span_trace=span_path, profile=trace,
                    engine=config["server"]["engine"], launcher=launcher,
                    start_args=start_args)
    clients: list = []
    sent: list[Sent] = []
    try:
        m = server.wait_line(
            r"^device: platform=(\S+) kind='([^']*)' count=(\d+)", 600)
        device = {"platform": m.group(1), "kind": m.group(2),
                  "count": int(m.group(3))}
        say(f"server device: {device}")
        if not rehearse and (device["platform"] != "tpu"
                             or device["count"] < cell["chips"]):
            raise BenchFailure(
                f"the cell asks for {cell['chips']} TPU chip(s); JAX "
                f"reports {device}. No fallback to another backend.")
        say(server.wait_line(r"^compile cache: ", 60).string)
        m = server.wait_line(r"^kernels warm in ([0-9.]+)s.*", BOOT_TIMEOUT_S)
        say(m.string)
        server.wait_line(r"^replica 0 listening", 120)
        say(f"boot to listening {time.monotonic() - T_PROCESS_START:.1f}s "
            "after process start")

        addr = [("127.0.0.1", port)]
        clients = [Client(cluster=0, client_id=0xC0FFEE + i,
                          replica_addresses=addr)
                   for i in range(mix["sessions"])]
        c0 = clients[0]
        budget = StoreBudget(capacity, n_req)

        def setup(request) -> None:
            if request.operation == "create_transfers" and \
                    not budget.reserve(request.n_events):
                raise BenchFailure("set-up alone would pass transfer_count")
            one = send(c0, getattr(Operation, request.operation),
                       Sent("setup", -1, request, 0.0),
                       timeout_s=SETUP_REPLY_TIMEOUT_S)
            sent.append(one)
            if one.error is not None:
                raise BenchFailure(f"set-up {request.operation}: {one.error}")
            if request.operation == "create_transfers":
                budget.settle(request.n_events, one.created)

        for request in dep.account_requests(n_max):
            setup(request)
        for request in funding:
            setup(request)
        # The state the deployment holds before the window, through the
        # served path like everything else.
        rss_before, t_preload = rss_bytes(), time.monotonic()
        for k, n in enumerate(preload_widths):
            setup(dep.transfer_request(STREAM_PRELOAD, k, n))
        preload_s = time.monotonic() - t_preload
        if preload_widths:
            rss_after = rss_bytes()
            say(f"preload: {preload} transfers in {len(preload_widths)} "
                f"requests, {preload_s:.3f}s ({preload / preload_s:.0f} "
                f"transfers/s), {budget.created} created so far; the "
                f"harness's resident set {rss_before} -> {rss_after} B "
                f"({(rss_after - rss_before) / len(preload_widths):.0f} B "
                "a held request)")
        # Un-timed requests of the cell's own traffic: every shape the
        # window uses is compiled (or loaded) before it.
        for k in range(mix["warm_requests"]):
            setup(dep.transfer_request(STREAM_WARM, k, n_req))
        if reads:  # so that the first timed read is not the path's first use
            setup(dep.lookup_request(STREAM_WARM, mix["warm_requests"],
                                     n_lookup))
        say(f"set-up: {dep.n} accounts, {len(sent)} requests, "
            f"{budget.created} transfers created")

        if trace:
            # The profiler brackets the whole window: every checkpoint
            # of it is in the trace, whatever the wall clock says.
            open(os.path.join(workdir, "profile.go"), "w").close()
            wait_for(os.path.join(workdir, "profile.started"),
                     "the launcher did not start the profiler")
        open(os.path.join(workdir, "mark.window_begin"), "w").close()
        time.sleep(0.05)  # the launcher polls for the mark every 20 ms
        setup_s = time.monotonic() - T_PROCESS_START
        wall_t0 = time.time()
        window, t0, t1, cut = run_window(
            clients, Operation,
            lambda s, k: dep.session_request(mix, s, k, n_req, n_lookup),
            quota, seconds, budget)
        wall_t1 = wall_t0 + (t1 - t0)
        open(os.path.join(workdir, "mark.window_end"), "w").close()
        if trace:
            open(os.path.join(workdir, "profile.stop"), "w").close()
            wait_for(os.path.join(workdir, "profile.json"),
                     "the launcher did not write the profiler's trace")
        sent += window
        if cut:
            say(f"WINDOW CUT at {t1 - t0:.1f}s: the sessions had not sent "
                f"their {quota} requests after 3 x {seconds}s. The rate "
                "is over what was sent.")
        if budget.exhausted:
            say(f"WINDOW ENDED EARLY after {t1 - t0:.1f}s of {seconds}s: "
                f"{budget.created} transfers created and a run may create "
                f"{capacity} (the configuration's transfer_count, which "
                "states what its stores hold, set-up and preload included); "
                "the next request could have passed it. The rate is over "
                "the shortened window.")

        rss_window = rss_bytes()
        readback = read_back(c0, Operation, dep, sent, n_lookup, seed)
        for c in clients:
            c.close()
        clients = []
        shutdown, device_record = server.stop()
    finally:
        for c in clients:
            c.close()
        server.kill()
        if os.path.exists(data_path):
            os.remove(data_path)  # its size is said above; logs and traces stay

    # The harness stayed off the device: the chip had one owner.
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        raise BenchFailure("the harness's own process started a JAX backend")
    if any(name.startswith("tigerbeetle_tpu.ops") for name in sys.modules):
        raise BenchFailure("the harness's own process imported device code")

    # ---- the comparison (the server is stopped, its memory peak read)
    t_ref = time.monotonic()
    if tamper is not None:
        tamper(sent, readback)
    numbers = check.judge(sent, readback)
    correct = check.verdict(numbers)
    ref_s = time.monotonic() - t_ref
    rss_compared = rss_bytes()

    answered = [s for s in window if s.error is None]
    if not answered:
        raise BenchFailure("no request of the window was answered")
    # The request percentiles read every answered request, of any
    # operation; the lookups' seconds stand beside them as well.
    secs = [s.seconds for s in answered]
    lookup_secs = [s.seconds for s in answered if s.request.is_read]
    by_operation = dict(collections.Counter(
        s.request.operation for s in answered))
    window_s = t1 - t0
    created = sum(s.created for s in answered)
    marks = device_record.get("marks", {})
    compiles_in_window = (
        marks["window_end"]["compiles"] - marks["window_begin"]["compiles"]
        if {"window_begin", "window_end"} <= set(marks) else None)
    fb = shutdown["fallback_stats"]
    say(f"window: {window_s:.3f}s, {len(window)} requests "
        f"({len(answered)} answered), {sum(s.request.n_events for s in answered)} "
        f"events, {created} created; request seconds in send order per "
        f"session: " + json.dumps({
            str(n): [round(s.seconds, 4) for s in window if s.session == n]
            for n in range(mix["sessions"])}))
    say(f"latency sample: {len(secs)} requests (p95 has "
        f"{int(len(secs) * 0.05)} beyond it, p98 {int(len(secs) * 0.02)}; "
        f"the longest {round(max(secs), 4)}s)"
        + (f"; {len(lookup_secs)} of them lookups, the longest "
           f"{round(max(lookup_secs), 4)}s" if lookup_secs else ""))
    say(f"compiles inside the window: {compiles_in_window} "
        f"(after listening, whole run: {shutdown['compiles_after_listening']})")
    say(f"server shutdown record: {json.dumps(shutdown, sort_keys=True)}")
    events = sum(s.request.n_events for s in sent)
    say(f"reference replay and comparison: {ref_s:.1f}s for {events} events "
        f"({events / ref_s:.0f} events/s); the harness's resident set "
        f"{rss_window} B after the window, {rss_compared} B after the "
        f"comparison, peak "
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024} B")

    context = {
        "server_lines": server.lines, "shutdown": shutdown, "marks": marks,
        "compiles_in_window": compiles_in_window, "setup_s": setup_s,
        "window": {"wall_t0": wall_t0, "wall_t1": wall_t1,
                   "seconds": window_s, "requests": len(answered),
                   "request_seconds": secs, "lookup_seconds": lookup_secs,
                   "created": created,
                   "events_per_request": n_req,
                   "create_requests_answered":
                       sum(1 for s in sent if s.error is None and
                           s.request.operation.startswith("create_"))},
        "read_operations": {int(o) for o in Operation
                            if o.name.startswith("lookup_")},
        "device_kind": device["kind"],
        "memory_peak_bytes": device_record["memory_peak_bytes"],
        "spans": None, "device": None, "profile": None,
    }
    metrics = read_metrics("e2e_metrics", bench["end_to_end"], workload,
                           context)
    result = {
        "correct": correct, "attempted": len(window),
        "failed": len(window) - len(answered), "metrics": metrics,
        "device": dict(device,
                       memory_peak_bytes=device_record["memory_peak_bytes"]),
    }
    if trace:
        result["breakdown"] = read_traces(workdir, span_path, context,
                                          need_device=not rehearse)
        if context["device"] is not None:
            result["device"]["busy_s"] = context["device"]["busy_s"]
            result["device"]["window_s"] = context["profile"]["seconds"]
        result["metrics"] = read_metrics("layer_metrics", bench["per_layer"],
                                         workload, context)
        say("end-to-end metrics of this traced run (not the ones judged): "
            + json.dumps(metrics))
    result["window"] = {
        "seconds": window_s, "requests_per_session": quota,
        "answered_by_operation": by_operation,
        "cut_at_hard_stop": cut,
        "ended_early_at_store_capacity": budget.exhausted,
        "transfers_created_whole_run": budget.created,
        "preloaded_transfers": preload, "preload_seconds": preload_s,
        "preloaded_ids_read_back": sum(
            1 for ids, _ in readback["transfers"] for i in ids
            if (i >> 64) & 0xFFFF == STREAM_PRELOAD),
        "compiles_in_window": compiles_in_window,
        "host_fallbacks": fb["host_fallbacks"],
        "fallback_causes": fb["causes"],
        "device_batches": {k: fb[k] for k in (
            "fast_batches", "fixpoint_batches", "deep_fixpoint_batches",
            "escalations")},
        "mirror_regime": shutdown["mirror_regime"],
        "reference_seconds": ref_s}
    result["compared"] = check.compared(numbers)
    return result


def read_traces(workdir: str, span_path: str, context: dict,
                need_device: bool) -> dict:
    """Reduce the profiler's trace and the program's spans into the
    context the per-layer readers see; returns the breakdown. Only the
    CPU rehearsal may find no device plane (its readers then return
    nothing)."""
    with open(os.path.join(workdir, "profile.json")) as f:
        prof = json.load(f)
    xplane = trace_reduce.find_xplane(os.path.join(workdir, "profile"))
    if xplane is None:
        raise BenchFailure("the traced run left no .xplane.pb")
    xp = trace_reduce.reduce_xplane(xplane)
    spans = trace_reduce.load_spans(span_path, context["read_operations"])
    context["spans"] = spans
    if not xp["devices"] and not need_device:
        say("rehearsal: the profiler's trace has no TPU device plane; "
            f"span ring dropped {spans['dropped_events']} events")
        return {"device_ops": [], "idle_gaps": []}
    if not xp["devices"]:
        raise BenchFailure("the profiler's trace has no TPU device plane")
    if xp["anchor_ns"] is None:
        raise BenchFailure("the profiler's trace has no chipbench_anchor")
    summary = trace_reduce.device_summary(xp, trace_reduce.KERNEL_MODULES)
    if summary["busy_s"] <= 0:
        raise BenchFailure("no operation ran on the device in the traced window")
    anchor_wall_s = prof["anchor_wall_ns"] / 1e9
    t0_ns = xp["anchor_ns"]
    t1_ns = t0_ns + (prof["stop_call_wall_ns"] - prof["anchor_wall_ns"])
    context["device"] = summary
    context["profile"] = {
        "wall_t0": anchor_wall_s, "wall_t1": prof["stop_call_wall_ns"] / 1e9,
        "seconds": (prof["stop_call_wall_ns"] - prof["anchor_wall_ns"]) / 1e9}
    gaps = trace_reduce.idle_gaps(summary["busy_intervals_ns"], t0_ns, t1_ns,
                                  xp["anchor_ns"], anchor_wall_s,
                                  spans["spans"])
    say(f"traced window {context['profile']['seconds']:.2f}s, device busy "
        f"{summary['busy_s']:.4f}s, {len(summary['dispatch_seconds'])} "
        f"create_transfers dispatches; modules run: "
        f"{json.dumps(summary['module_counts'])}; xplane "
        f"{os.path.getsize(xplane)} B; span ring dropped "
        f"{spans['dropped_events']} events")
    return {"device_ops": trace_reduce.top(summary["op_seconds"]),
            "idle_gaps": trace_reduce.top(gaps)}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--cells", default=None,
                   help="a file of further configs and workloads (the "
                        "builder's scratch cells); the driver gives none")
    p.add_argument("--rehearse", action="store_true",
                   help="CPU rehearsal: a --small server on any backend; "
                        "passes everything, then fails at the device check")
    args = p.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), rehearse=args.rehearse,
                          cells=args.cells)
        if args.rehearse:
            say("rehearsal line (NOT a result): " + json.dumps(result))
            if not result["correct"]:
                raise BenchFailure("the rehearsal's comparison came out "
                                   f"not correct: {result['compared']}")
            raise BenchFailure(
                "the rehearsal passed every step before it, and fails at "
                f"the device check: a --small server on "
                f"{result['device']['platform']!r} is no cell. No result.")
    except (BenchFailure, ImportError, FileNotFoundError) as e:
        print(f"[chipbench] FAILED: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1
    line = json.dumps(result)
    print("[chipbench] compared (value, limit): "
          + json.dumps(result["compared"]), file=sys.stderr, flush=True)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
