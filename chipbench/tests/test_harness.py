"""Tests of the benchmark's own arithmetic. Run by hand on the CPU (not
in tier 1):

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q -p no:cacheprovider
"""

import copy
import functools
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import (check, control, kernel_bytes, peaks, run,  # noqa: E402
                       server, trace_reduce, wire)
from chipbench.server import BenchFailure  # noqa: E402
from chipbench.traffic import (STREAM_FUNDING, STREAM_PRELOAD,  # noqa: E402
                               STREAM_WARM, Deployment)
from chipbench.window import Sent, StoreBudget  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(ROOT, "chipbench", "configs")
# The generator's two-phase and balance-limit parameters have no cell
# (PERF.md, Open questions); a fixture keeps them and the no_limits
# control under test.
TWOPHASE = os.path.join(HERE, "fixtures", "twophase_limits.json")
# tb_bench_default_1r with `start_args` and `preloaded_count` stated:
# the builder's scratch cell on the chip (PERF.md, PR 29), in no
# BENCHMARK.json.
PRELOAD = os.path.join(HERE, "fixtures", "preload_args.json")
CELLS = "chipbench/tests/fixtures/cells.json"


def config(name):
    path = name if os.path.isabs(name) else os.path.join(CONFIGS, name + ".json")
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["tb_bench_default_1r", TWOPHASE])
def test_same_seed_same_bytes(name):
    """The same --seed gives byte-identical request bodies, whatever
    order they are asked for in; another seed gives others."""
    a = Deployment(config(name), 4000000123)
    b = Deployment(config(name), 4000000123)
    c = Deployment(config(name), 4000000124)
    for stream, k in [(0, 0), (3, 7), (0, 1), (1, 0)]:
        ra = a.transfer_request(stream, k, 8189)
        assert ra.payload == b.transfer_request(stream, k, 8189).payload
        assert ra.payload != c.transfer_request(stream, k, 8189).payload
        assert len(ra.payload) == 8189 * 128
    assert [r.payload for r in a.account_requests(8189)] == \
        [r.payload for r in b.account_requests(8189)]
    assert [r.payload for r in a.funding_requests(8189)] == \
        [r.payload for r in b.funding_requests(8189)]


def test_no_id_repeats_and_both_limbs():
    d = Deployment(config(TWOPHASE), 7)
    ids = np.concatenate([d.transfer_request(s, k, 1024).ids
                          for s in range(4) for k in range(4)]
                         + [r.ids for r in d.funding_requests(8189)])
    assert len(np.unique(ids, axis=0)) == len(ids)
    assert (ids[:, 1] != 0).all() and (ids[:, 0] != 0).all()
    assert len(set(d.account_ids())) == d.n
    assert any(i >> 64 for i in d.account_ids())


def test_two_phase_resolves_the_request_before():
    d = Deployment(config(TWOPHASE), 11)
    pend = np.frombuffer(d.transfer_request(0, 4, 8189).payload, wire.TRANSFER)
    res = np.frombuffer(d.transfer_request(0, 5, 8189).payload, wire.TRANSFER)
    assert (pend["flags"] == 2).all()
    assert (res["pending_lo"] == pend["id_lo"]).all()
    post = res["flags"] == 4
    assert set(np.unique(res["flags"])) == {4, 8}
    assert 0.85 < post.mean() < 0.95
    assert (res["amount_lo"][post] == pend["amount_lo"][post]).all()


def served_by_the_reference(dep, n_req, n_requests):
    """A run's requests with the plain reference in the server's place:
    what run_cell hands the comparison, without a server."""
    from chipbench.reference.ledger import StateMachineOracle

    ref, sent, ts = StateMachineOracle(), [], 10 ** 18
    requests = (dep.account_requests(8189) + dep.funding_requests(8189)
                + [dep.transfer_request(0, k, n_req) for k in range(n_requests)])
    for i, request in enumerate(requests):
        window = i >= len(requests) - n_requests
        s = Sent("window" if window else "setup", 0 if window else -1,
                 request, float(i), float(i) + 0.5)
        ts += request.n_events
        s.ts = ts
        want = check.apply(ref, s)
        s.results = np.zeros(len(want), dtype=wire.RESULT)
        s.results["timestamp"] = [w.timestamp for w in want]
        s.results["status"] = [int(w.status) for w in want]
        sent.append(s)
    tids = [(int(h) << 64) | int(l) for l, h in sent[-1].request.ids]
    asked = {"accounts": [(dep.account_ids(), None)],
             "transfers": [(tids, None)]}
    return sent, control._lookups(ref, asked)


def test_controls_fail_what_the_reference_itself_passes():
    """no_limits has no cell to run in since the two-phase cell went;
    here it and lost_write break a run the reference itself served."""
    dep = Deployment(config(TWOPHASE), 13, accounts_cut=2000)
    sent, readback = served_by_the_reference(dep, 1024, 6)
    assert check.verdict(check.judge(sent, readback))
    assert (sent[-2].results["status"] != wire.CREATED).sum() > 20
    for name, fails in [("no_limits", "result_mismatches"),
                        ("lost_write", "transfer_mismatches")]:
        broken = copy.deepcopy((sent, readback))
        control.CONTROLS[name](*broken)
        numbers = check.judge(*broken)
        assert not check.verdict(numbers), name
        assert numbers[fails] > 0, (name, numbers)


def test_what_the_harness_cannot_serve_fails():
    cfg = config("tb_bench_default_1r")
    with open(os.path.join(ROOT, "chipbench", "traffic", "b1024_4s.json")) as f:
        mix = json.load(f)
    run.servable(cfg, mix)
    for change in [lambda c, m: m.update(loop="open"),
                   lambda c, m: c["server"].update(replica_count=3),
                   lambda c, m: c["server"].update(small_layout=True),
                   lambda c, m: c["guarantees"].update(replicas=3),
                   lambda c, m: c["guarantees"].update(
                       durable_across_restart="read back after a restart")]:
        c, m = copy.deepcopy((cfg, mix))
        change(c, m)
        with pytest.raises(BenchFailure):
            run.servable(c, m)


def test_default_command_lines_are_the_parents():
    """tb_bench_default_1r states neither key: `format` and `start` get
    the literal lists the parent of PR 29 built."""
    srv = config("tb_bench_default_1r")["server"]
    assert "format_args" not in srv and "start_args" not in srv
    assert server.format_argv("/d/0_0.tigerbeetle", small=False) == [
        sys.executable, "-m", "tigerbeetle_tpu", "format", "--cluster=0",
        "--replica=0", "--replica-count=1", "/d/0_0.tigerbeetle"]
    assert server.start_argv(3001, "/d/0_0.tigerbeetle", engine=srv["engine"],
                             small=False, span_trace=None) == [
        "start", "--addresses=127.0.0.1:3001", "--replica=0",
        "--engine=device", "/d/0_0.tigerbeetle"]
    # the traced rehearsal's, as the parent built it
    assert server.start_argv(7, "p", engine="device", small=True,
                             span_trace="s.json") == [
        "start", "--addresses=127.0.0.1:7", "--replica=0", "--engine=device",
        "--small", "--trace", "s.json", "p"]
    assert server.format_argv("p", small=True)[-2:] == ["--small", "p"]


def test_a_configurations_arguments_land_after_the_harness_own():
    extra = ["--grid-blocks=65536", "--verbose"]
    assert server.format_argv("p", small=False, extra=extra)[-4:] == [
        "--replica-count=1", *extra, "p"]
    assert server.start_argv(7, "p", engine="device", small=False,
                             span_trace="s.json", extra=extra)[-5:] == [
        "--trace", "s.json", *extra, "p"]
    _, _, cfg, mix = run.load_cell("preload.full_batch_1s", CELLS)
    assert cfg["server"]["start_args"] == ["--trace-emit-interval=10.0"]
    run.servable(cfg, mix)


@pytest.mark.parametrize("key", ["format_args", "start_args"])
@pytest.mark.parametrize("arg", [
    "--cluster=1", "--replica=1", "--replica-count=3",
    "--addresses=127.0.0.1:1", "--engine=oracle", "--small", "--trace=x.json",
    "--eng=oracle", "--trac=x.json", "0_0.tigerbeetle", "-x", "--"])
def test_an_argument_the_harness_owns_is_refused(key, arg):
    _, _, cfg, mix = run.load_cell("preload.full_batch_1s", CELLS)
    cfg["server"][key] = ["--trace-emit-interval=10.0", arg]
    with pytest.raises(BenchFailure, match="the harness's own"):
        run.servable(cfg, mix)


def test_preload_same_seed_same_bytes_and_no_id_twice():
    """The preload is a stream of its own beside funding, warm and the
    sessions: the same bytes for the same seed, no id twice within it or
    across them."""
    cfg = config(PRELOAD)
    a, b = Deployment(cfg, 4000000123), Deployment(cfg, 4000000123)
    widths = a.preload_widths(cfg["transfers"]["preloaded_count"], 8189)
    assert widths == [8189] * 8
    assert a.preload_widths(20_000, 8189) == [8189, 8189, 3622]
    assert a.preload_widths(0, 8189) == []
    pre = [a.transfer_request(STREAM_PRELOAD, k, n)
           for k, n in enumerate(widths)]
    assert [r.payload for r in pre] == [
        b.transfer_request(STREAM_PRELOAD, k, n).payload
        for k, n in enumerate(widths)]
    assert pre[0].payload != Deployment(cfg, 4000000124).transfer_request(
        STREAM_PRELOAD, 0, 8189).payload
    lim = Deployment(config(TWOPHASE), 4000000123)  # has funding requests
    others = ([a.transfer_request(STREAM_WARM, k, 8189) for k in range(2)]
              + [a.transfer_request(s, k, 1024)
                 for s in range(4) for k in range(4)])
    ids = np.concatenate([r.ids for r in pre + others])
    assert len(np.unique(ids, axis=0)) == len(ids) == 8 * 8189 + 2 * 8189 + 16 * 1024
    streams = {int(r.ids[0, 1]) & 0xFFFF
               for r in pre + others + lim.funding_requests(8189)}
    assert streams == {STREAM_PRELOAD, STREAM_WARM, STREAM_FUNDING, 0, 1, 2, 3}
    # the event shape is the deployment's own: skew, amounts, fail shares
    rec = np.frombuffer(pre[3].payload, wire.TRANSFER)
    win = np.frombuffer(a.transfer_request(0, 3, 8189).payload, wire.TRANSFER)
    assert rec["amount_lo"].min() >= 1 and rec["amount_lo"].max() < 1000
    assert abs(int((rec["ledger"] != 1).sum())
               - int((win["ledger"] != 1).sum())) < 30
    hot = a.id_lo[0]
    assert (rec["debit_lo"] == hot).sum() > 400 < (win["debit_lo"] == hot).sum()


def test_two_phase_preload_is_whole_pairs():
    d = Deployment(config(TWOPHASE), 5)
    assert d.preload_widths(4 * 8189, 8189) == [8189] * 4
    for count in (3 * 8189, 2 * 8189 + 5):
        with pytest.raises(ValueError, match="whole pairs"):
            d.preload_widths(count, 8189)


def test_preload_counts_against_transfer_count():
    cfg = config(PRELOAD)
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           "full_batch_1s.json")) as f:
        mix = json.load(f)
    dep = Deployment(cfg, 9)
    funding, widths = run.plan_setup(dep, mix, 8189, 8189, 500_000)
    assert funding == [] and sum(widths) == 65_512
    # the rehearsal's cut: eight requests of its own narrower wire
    assert run.plan_setup(dep, mix, 509, 509, 1 << 14, 8 * 509)[1] == [509] * 8
    cfg["transfers"]["preloaded_count"] = 600_000
    with pytest.raises(BenchFailure,
                       match="set-up alone would pass transfer_count"):
        run.plan_setup(Deployment(cfg, 9), mix, 8189, 8189, 500_000)
    # 58 preload + 2 warm requests fit under 500,000 less one request; 59 do not
    cfg["transfers"]["preloaded_count"] = 58 * 8189
    run.plan_setup(Deployment(cfg, 9), mix, 8189, 8189, 500_000)
    cfg["transfers"]["preloaded_count"] = 59 * 8189
    with pytest.raises(BenchFailure, match="set-up alone"):
        run.plan_setup(Deployment(cfg, 9), mix, 8189, 8189, 500_000)


def test_new_per_layer_readers_on_a_hand_made_context():
    """compact_beat_max_ms is the longest beat inside a commit_compact
    of the window; device_memory_share the launcher's peak over the
    chip's HBM, and nothing where the backend keeps no peak."""
    arr = np.array
    spans = {"commit_compact": (arr([9.0, 10.0, 11.0, 12.0, 40.0]),
                                arr([0.5, 0.5, 0.9, 0.5, 0.5])),
             # before the window; two in op 10; the freeze in op 11; one
             # outside any commit_compact; one after the window
             "compact_beat": (arr([9.1, 10.1, 10.2, 11.05, 12.7, 40.1]),
                              arr([2.0, 0.003, 0.004, 0.84, 3.0, 5.0]))}
    context = {"spans": {"spans": spans, "dropped_events": 0},
               "window": {"wall_t0": 9.5, "wall_t1": 30.0},
               "memory_peak_bytes": 2_925_394_432,
               "device_kind": "TPU v5 lite"}
    longest = run.load_reader("layer_metrics", "compact_beat_max_ms")
    mean = run.load_reader("layer_metrics", "compact_beat_ms")
    share = run.load_reader("layer_metrics", "device_memory_share")
    assert longest(context) == pytest.approx(840.0)
    assert mean(context) == pytest.approx(1e3 * (0.003 + 0.004 + 0.84) / 3)
    assert share(context) == pytest.approx(18.28371520)
    assert share(dict(context, memory_peak_bytes=None)) is None
    assert longest(dict(context, spans=None)) is None
    context["spans"]["dropped_events"] = 1
    assert longest(context) is None


def test_every_metric_of_the_benchmark_has_its_reader():
    """End-to-end and per-layer metrics are found by file; the
    end-to-end readers on a hand-made window."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for kind, key in [("e2e_metrics", "end_to_end"),
                      ("layer_metrics", "per_layer")]:
        for entry in bench[key]:
            assert callable(run.load_reader(kind, entry["name"]))
    secs = [0.2] * 45 + [4.0, 6.0, 8.0]
    context = {"setup_s": 50.0,
               "window": {"seconds": 30.0, "created": 390_000,
                          "request_seconds": secs}}
    got = run.read_metrics("e2e_metrics", bench["end_to_end"],
                           "default.full_batch_1s", context)
    assert set(got) == {"accepted_tps", "request_p50_ms", "request_p98_ms",
                        "setup_s"}  # p95 only where its cell is listed
    assert got["accepted_tps"]["value"] == 13_000.0
    assert got["request_p50_ms"]["value"] == pytest.approx(200.0)
    # 0.98 x 47 = 46.06: the second longest and a little of the longest,
    # inside the stalled three
    assert got["request_p98_ms"]["value"] == pytest.approx(
        1e3 * (6.0 + 0.06 * 2.0))
    assert "request_p95_ms" in run.read_metrics(
        "e2e_metrics", bench["end_to_end"], "default.b1024_4s", context)
    # the read mix's cell: the request percentiles read every request's
    # seconds as in any cell, the lookups' own median stands beside them
    context["window"]["lookup_seconds"] = [0.05, 0.07, 0.04, 0.30]
    got = run.read_metrics("e2e_metrics", bench["end_to_end"],
                           "default.read_mix_1s", context)
    assert set(got) == {"accepted_tps", "request_p50_ms", "request_p98_ms",
                        "lookup_p50_ms", "setup_s"}
    assert got["lookup_p50_ms"]["value"] == pytest.approx(60.0)
    assert got["request_p50_ms"]["value"] == pytest.approx(200.0)
    context["window"]["lookup_seconds"] = []
    assert "lookup_p50_ms" not in run.read_metrics(
        "e2e_metrics", bench["end_to_end"], "default.read_mix_1s", context)


# ------------------------------------------------- a mix that states reads

def mix(name):
    with open(os.path.join(ROOT, "chipbench", "traffic", name + ".json")) as f:
        return json.load(f)


def test_read_mix_same_seed_same_bytes_and_its_writes_are_cell_1s():
    """Request k of a session under read_mix_1s is a lookup where
    k % 2 == 1 and full_batch_1s's own write at the same k otherwise;
    the same seed gives the same bytes, another seed others."""
    cfg, reads, plain = (config("tb_bench_default_1r"), mix("read_mix_1s"),
                         mix("full_batch_1s"))
    assert {k: v for k, v in reads.items()
            if k not in ("name", "why", "reads", "requests_per_second_why")} \
        == {k: v for k, v in plain.items()
            if k not in ("name", "why", "requests_per_second_why")}
    a, b, c = (Deployment(cfg, 4000000123), Deployment(cfg, 4000000123),
               Deployment(cfg, 4000000124))
    kinds = []
    for k in range(8):
        ra = a.session_request(reads, 0, k, 8189, 7278)
        assert ra.payload == b.session_request(reads, 0, k, 8189, 7278).payload
        assert ra.payload != c.session_request(reads, 0, k, 8189, 7278).payload
        kinds.append(ra.operation)
        if ra.is_read:
            assert (ra.event_size, ra.result.itemsize) == (16, 128)
            assert len(ra.payload) == 7278 * 16 and ra.n_events == 7278
            assert ra.payload == wire.ids_payload(check.int_ids(ra.ids))
        else:
            assert (ra.event_size, ra.result.itemsize) == (128, 16)
            assert ra.payload == a.session_request(
                plain, 0, k, 8189, 0).payload
            assert ra.payload == a.transfer_request(0, k, 8189).payload
    assert reads["reads"] == {"every": 2}  # YCSB A's share, by requests
    assert kinds == ["create_transfers", "lookup_accounts"] * 4
    every4 = dict(reads, reads={"every": 4})
    assert [a.session_request(every4, 0, k, 8189, 7278).is_read
            for k in range(8)] == [False, False, False, True] * 2
    # the ids are the deployment's accounts by its key skew, repeats
    # kept, its unknown_account share of them ids that no account has
    r = a.lookup_request(0, 3, 7278)
    known = set(a.account_ids())
    asked = check.int_ids(r.ids)
    unknown = sum(1 for i in asked if i not in known)
    assert 5 <= unknown <= 45  # 0.3% of 7278 is 22
    assert len(set(asked)) < len(asked)
    assert asked.count(a.account_ids()[0]) > 400  # the hot account


def sent_of(request, t_send, t_reply, results):
    return Sent("window", 0, request, t_send, t_reply, results=results)


def test_a_read_is_held_at_its_place_in_the_commit_order():
    """Write, read, write from one caller: the read has to see the
    first write and not the second. One answered from any other place
    is counted, under `read_mismatches` and no other number."""
    from chipbench.reference.ledger import StateMachineOracle

    dep = Deployment(config("tb_bench_default_1r"), 17, accounts_cut=500)
    ref, sent, seen = StateMachineOracle(), [], []
    read = dep.lookup_request(0, 3, 300)

    def rows_now():
        return control._rows(ref, sent_of(read, 0, 0, None))

    for i, request in enumerate(dep.account_requests(8189)
                                + [dep.transfer_request(0, 0, 400), read,
                                   dep.transfer_request(0, 1, 400)]):
        seen.append(rows_now())  # what the read would see before this one
        s = sent_of(request, float(i), i + 0.5, seen[-1])
        if not request.is_read:
            s.ts = 10 ** 18 + 1000 * (i + 1)
            want = check.apply(ref, s)
            s.results = np.zeros(len(want), dtype=wire.RESULT)
            s.results["timestamp"] = [w.timestamp for w in want]
            s.results["status"] = [int(w.status) for w in want]
        sent.append(s)
    before_first, before_read, before_second = seen[-3:]
    after_second = rows_now()
    asked = {"accounts": [(dep.account_ids(), None)], "transfers": []}
    readback = control._lookups(ref, asked)
    order, _ = check.ordered(sent)
    assert [s.request.operation for s in order[-3:]] == [
        "create_transfers", "lookup_accounts", "create_transfers"]
    numbers = check.judge(sent, readback)
    assert check.verdict(numbers), numbers
    assert before_read.tobytes() == before_second.tobytes()
    for stale in (before_first, after_second):
        assert stale.tobytes() != before_read.tobytes()
        sent[-2].results = stale
        numbers = check.judge(sent, readback)
        assert numbers["read_mismatches"] > 0
        assert not check.verdict(numbers)
        assert all(v == 0 for k, v in numbers.items()
                   if k != "read_mismatches")
    # a row missing from the reply counts, and so does a surplus one
    sent[-2].results = before_read[:-1]
    assert check.judge(sent, readback)["read_mismatches"] == 1
    # the stale_read control answers from before the first write
    sent[-2].results = before_read
    control.stale_read(sent, readback)
    assert sent[-2].results.tobytes() == before_first.tobytes()
    assert check.judge(sent, readback)["read_mismatches"] > 0
    # a read sent while a write was still unanswered takes its place
    # after the last write answered before it
    sent[-2].results = before_first
    sent[-2].t_send, sent[-2].t_reply = sent[-3].t_send + 0.1, sent[-3].t_reply + 0.1
    order, _ = check.ordered(sent)
    assert [s.request.operation for s in order[-3:]] == [
        "lookup_accounts", "create_transfers", "create_transfers"]
    assert check.judge(sent, readback)["read_mismatches"] == 0


@pytest.mark.parametrize("change,words", [
    (lambda c, m: m.update(sessions=2), "reads need one session"),
    (lambda c, m: m["reads"].update(operation="lookup_transfers"),
     "`every` alone"),
    (lambda c, m: m["reads"].update(every=1), "n >= 2"),
    (lambda c, m: c["transfers"].update(
        two_phase={"post_share": 1.0, "void_share": 0.0}),
     "would leave pendings unresolved"),
])
def test_reads_the_comparison_cannot_hold_or_place_are_refused(change, words):
    cfg, m = config("tb_bench_default_1r"), mix("read_mix_1s")
    run.servable(cfg, m)
    change(cfg, m)
    with pytest.raises(BenchFailure, match=words):
        run.servable(cfg, m)


def test_per_op_means_read_the_writes_and_a_reads_execute_stands_apart(
        tmp_path):
    """Three ops in the window, the second a lookup (its `commit_execute`
    names operation 140): the per-op means read ops 7 and 9, a child span
    under the lookup belongs to no kept parent, `lookup_execute_ms` reads
    op 8, and what every op owes (its compaction beat, the protocol's
    work) is read over all three."""
    def x(name, ts, dur, **args):
        return {"name": name, "ph": "X", "ts": ts * 1e6, "dur": dur * 1e6,
                "args": args}

    events = [x("loop_busy", 9.9, 0.9), x("loop_busy", 11.0, 1.0)]
    for op, t, operation, execute in [(6, 5.0, 147, 0.5), (7, 10.0, 147, 0.02),
                                      (8, 10.3, 140, 0.04),
                                      (9, 11.0, 147, 0.03)]:
        events += [x("journal_write", t - 0.01, 0.001 * op, op=op),
                   x("commit_execute", t, execute, op=op, operation=operation),
                   x("execute_encode", t + 0.001, 0.004, op=op),
                   # the bar's long beat falls to the lookup's op
                   x("commit_compact", t + 0.1, 0.05 if operation == 147
                     else 0.12, op=op),
                   x("compact_beat", t + 0.101, 0.002 if operation == 147
                     else 0.1, op=op)]
    events.append(x("commit_checkpoint", 10.35, 0.2, op=8))
    path = tmp_path / "spans.json"
    path.write_text(json.dumps({"traceEvents": events,
                                "metadata": {"dropped_events": 0}}))
    context = {"window": {"wall_t0": 9.5, "wall_t1": 30.0}}
    reader = functools.partial(run.load_reader, "layer_metrics")
    for read_operations, want in [
            ({140}, {"commit_execute_ms": 25.0, "lookup_execute_ms": 40.0,
                     "journal_write_ms": 8.0, "commit_compact_ms": 50.0,
                     "execute_encode_ms": 4.0, "checkpoint_ms": 200.0,
                     "compact_beat_ms": 104 / 3, "compact_beat_max_ms": 100.0}),
            # with no operation named a read, every op is a write's
            ((), {"commit_execute_ms": 30.0, "lookup_execute_ms": None,
                  "journal_write_ms": 8.0, "commit_compact_ms": 220 / 3,
                  "execute_encode_ms": 4.0, "checkpoint_ms": 200.0,
                  "compact_beat_ms": 104 / 3, "compact_beat_max_ms": 100.0})]:
        context["spans"] = trace_reduce.load_spans(str(path), read_operations)
        for name, value in want.items():
            got = reader(name)(context)
            assert got == (pytest.approx(value) if value else None), name
        # 1.9 s busy, less 0.09 + 0.22 + 0.2 staged, over three ops
        assert reader("replica_protocol_ms")(context) == pytest.approx(
            1e3 * (1.9 - 0.51) / 3)


def test_least_bytes_hand_worked():
    """Per event: 128 in + 16 out + 2 x (64 + 2 x 128) account and
    balance rows + 160 + 200 rows out + 4 x 24 hash slots = 1240 B."""
    assert kernel_bytes.create_transfers_bytes_per_event() == 1240
    assert kernel_bytes.create_transfers_least_bytes(1024) == 1_269_760
    assert kernel_bytes.create_transfers_least_bytes(8189) == 10_154_360


def test_unknown_device_kind_is_an_error():
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v9")


def test_store_budget_ends_a_window_before_the_store_fills():
    b = StoreBudget(capacity=10_000, request_events=1024)
    sent = 0
    while b.reserve(1024):
        b.settle(1024, 1024)
        sent += 1
    assert b.exhausted
    assert b.created == sent * 1024 <= 10_000 - 1024
    # In-flight requests count as if every event were created.
    b2 = StoreBudget(capacity=4096, request_events=1024)
    assert b2.reserve(1024) and b2.reserve(1024) and b2.reserve(1024)
    assert not b2.reserve(1024)


def test_wire_trailer():
    body = wire.encode_one(b"x" * 256, 128)
    assert len(body) == 256 + 128 and body[-2:] == b"\x01\x00"
    assert wire.decode_one(wire.encode_one(b"y" * 32, 16), 16) == b"y" * 32
    with pytest.raises(ValueError):
        wire.decode_one(b"\x00" * 32, 16)


def test_commit_order_sees_real_time_contradictions():
    class S:
        def __init__(self, ts, t_send, t_reply):
            self.ts, self.t_send, self.t_reply = ts, t_send, t_reply
    good = [S(1, 0.0, 1.0), S(2, 0.5, 2.0), S(3, 2.1, 3.0)]
    assert check.commit_order(good)[1] == 0
    bad = [S(2, 0.0, 1.0), S(1, 1.5, 2.0)]  # replied first, ordered second
    assert check.commit_order(bad)[1] == 1
    assert check.commit_order([S(1, 0, 1), S(1, 0, 1)])[1] == 1


def test_prepare_timestamp_is_exact_at_1e18():
    n = 1024
    ts = 1_790_000_000_123_456_789
    rec = np.zeros(n, dtype=wire.RESULT)
    rec["timestamp"] = ts - n + 1 + np.arange(n)
    rec["timestamp"][5] = 7  # one wrong event does not move it
    assert check.prepare_timestamp(rec) == ts


# ----------------------------------------------------- trace reduction

def test_union_and_gaps():
    start = np.array([0.0, 5e8, 4e8, 2e9])
    dur = np.array([1e8, 2e8, 2e8, 1e9])
    seconds, merged = trace_reduce.union_seconds(start, dur)
    assert seconds == pytest.approx(0.1 + 0.3 + 1.0)
    assert merged == [(0.0, 1e8), (4e8, 7e8), (2e9, 3e9)]
    spans = {"commit_checkpoint": (np.array([100.7]), np.array([1.3]))}
    gaps = trace_reduce.idle_gaps(merged, 0.0, 3.5e9, 0.0, 100.0, spans)
    # gaps: .1-.4 none, .7-2.0 checkpoint, 3.0-3.5 none
    assert gaps["commit_checkpoint"] == pytest.approx(1.3)
    assert gaps["none"] == pytest.approx(0.3 + 0.5)
    # Gaps between the ops of one dispatch go under one name, unsearched.
    merged = [(0.0, 1e6), (1e6 + 5e3, 2e6), (2e6 + 4e3, 3e6)]
    gaps = trace_reduce.idle_gaps(merged, 0.0, 3e6, 0.0, 100.0, spans)
    assert gaps == {trace_reduce.BETWEEN_OPS: pytest.approx(9e-6)}


RECORDED = os.path.join(HERE, "recorded")


@pytest.mark.skipif(not os.path.exists(os.path.join(RECORDED, "expected.json")),
                    reason="no recorded trace")
def test_recorded_trace_reduces_to_the_recorded_numbers():
    """A small trace recorded on the chip (TPU v5 lite), reduced by
    today's code, gives the numbers written down when it was recorded."""
    import gzip
    import shutil
    import tempfile

    with open(os.path.join(RECORDED, "expected.json")) as f:
        want = json.load(f)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "recorded.xplane.pb")
        with gzip.open(os.path.join(RECORDED, "trace.xplane.pb.gz")) as src, \
                open(path, "wb") as dst:
            shutil.copyfileobj(src, dst)
        xp = trace_reduce.reduce_xplane(path)
    assert xp["anchor_ns"] is not None
    assert len(xp["devices"]) == want["devices"]
    summary = trace_reduce.device_summary(xp, trace_reduce.KERNEL_MODULES)
    assert summary["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert len(summary["dispatch_seconds"]) == want["dispatches"]
    assert summary["module_counts"]["jit_create_transfers_fast"] == 16
    assert sum(summary["dispatch_seconds"]) == pytest.approx(
        want["dispatch_seconds_sum"], rel=1e-9)
    # A second witness: the `XLA Modules` line's own durations bound the
    # union of the ops under them, and here lie within 1% of it.
    assert sum(summary["dispatch_seconds"]) <= want["module_seconds_sum"]
    assert sum(summary["dispatch_seconds"]) == pytest.approx(
        want["module_seconds_sum"], rel=0.01)
    assert xp["anchor_ns"] == want["anchor_ns"]
    spans = trace_reduce.load_spans(os.path.join(RECORDED, "spans.json"))
    assert spans["dropped_events"] == 0
    for name, (count, total) in want["spans"].items():
        start, dur = spans["spans"][name]
        assert len(dur) == count
        assert float(dur.sum()) == pytest.approx(total, rel=1e-9)
