"""The SLO-grade latency plane: Prometheus exposition + endpoint, SLO
engine + burn rates, critical-path attribution, and the
scraped-vs-offline p99 parity acceptance."""

import dataclasses
import json
import urllib.request

import pytest

from tigerbeetle_tpu.metrics import (MetricsServer, parse_prometheus,
                                     render_prometheus)
from tigerbeetle_tpu.trace import Event, Tracer
from tigerbeetle_tpu.trace.histogram import REL_ERROR, Histogram
from tigerbeetle_tpu.trace.merge import critical_path, span_quantile
from tigerbeetle_tpu.trace.slo import burn_rates, evaluate, load_objectives


def _tracer_with_latency_series():
    t = Tracer(pid=0)
    for route, tier in (("chain", "scan"), ("chain", "scan"),
                        ("per_batch", "fallback")):
        with t.span(Event.window_commit, route=route, tier=tier):
            pass
    with t.span(Event.serving_dispatch, what="window"):
        pass
    t.count(Event.serving_retries, 3)
    t.gauge(Event.bus_pool_used, 7)
    t.observe(Event.serving_replay_windows, 4)
    return t


# ---------------------------------------------------------- exposition

def test_render_parse_round_trip():
    t = _tracer_with_latency_series()
    text = render_prometheus(t)
    parsed = parse_prometheus(text)  # raises on any malformed line
    assert parsed["tb_tpu_serving_retries_total"] == [({}, 3.0)]
    assert parsed["tb_tpu_bus_pool_used"] == [({}, 7.0)]
    # Span histograms carry the _us unit suffix and the partition tags.
    counts = dict((frozenset(lab.items()), v) for lab, v
                  in parsed["tb_tpu_window_commit_us_count"])
    assert counts[frozenset({("route", "chain"),
                             ("tier", "scan")}.union())] == 2.0
    assert counts[frozenset({("route", "per_batch"),
                             ("tier", "fallback")})] == 1.0
    # +Inf bucket == series count for every series.
    for lab, v in parsed["tb_tpu_window_commit_us_bucket"]:
        if lab.get("le") == "+Inf":
            assert v == counts[frozenset(
                (k, x) for k, x in lab.items() if k != "le")]
    # Histogram-kind events keep their declared unit (no _us).
    assert parsed["tb_tpu_serving_replay_windows_count"] == [({}, 1.0)]
    assert "tb_tpu_serving_replay_windows_us_count" not in parsed


def test_exemplar_render_parse_round_trip():
    """ISSUE 15 satellite: a traced span stamps its series' exemplar;
    the rendered exposition carries an OpenMetrics exemplar suffix on
    exactly one in-range bucket line per series, and parse_prometheus
    returns it (labels + value) under __exemplars__."""
    from tigerbeetle_tpu.trace.context import fmt_trace_id, mint_context

    t = _tracer_with_latency_series()  # untraced spans: no exemplars
    ctx = mint_context(7, 1)
    tid = fmt_trace_id(ctx.trace_id)
    with t.span(Event.window_commit, ctx=ctx, route="chain",
                tier="scan"):
        pass
    assert any(ex["trace_id"] == tid for ex in t.exemplars.values())
    text = render_prometheus(t)
    parsed = parse_prometheus(text)
    exemplars = parsed["__exemplars__"]["tb_tpu_window_commit_us_bucket"]
    assert len(exemplars) == 1  # one suffixed bucket line per series
    labels, ex_labels, ex_value = exemplars[0]
    assert labels["route"] == "chain" and labels["tier"] == "scan"
    assert ex_labels == {"trace_id": tid}
    # OpenMetrics: the exemplar lies within its bucket's bounds.
    assert ex_value > 0
    if labels["le"] != "+Inf":
        assert ex_value <= float(labels["le"])
    # The stripped text (no suffixes) parses to the identical series —
    # the suffix never perturbs the sample itself.
    base = parse_prometheus(
        "\n".join(ln.partition(" # ")[0] for ln in text.splitlines()))
    assert base["tb_tpu_window_commit_us_bucket"] \
        == parsed["tb_tpu_window_commit_us_bucket"]
    assert "__exemplars__" not in base


def test_exemplar_merge_keeps_slowest_sample():
    from tigerbeetle_tpu.trace.context import fmt_trace_id

    from tigerbeetle_tpu.trace.context import TraceContext

    def traced(pid, dur_us, raw_tid):
        t = Tracer(pid=pid)
        t.record_span(Event.window_commit, t.now_ns(),
                      int(dur_us * 1_000), route="chain", tier="scan",
                      ctx=TraceContext(trace_id=raw_tid))
        return t

    slow_tid = fmt_trace_id(0xABC)
    parsed = parse_prometheus(render_prometheus(
        [traced(0, 50.0, 0x123), traced(1, 9_000.0, 0xABC)]))
    exemplars = parsed["__exemplars__"]["tb_tpu_window_commit_us_bucket"]
    assert len(exemplars) == 1
    _, ex_labels, ex_value = exemplars[0]
    assert ex_labels["trace_id"] == slow_tid  # the p99 candidate wins
    assert ex_value == pytest.approx(9_000.0, rel=0.01)


def test_render_merges_tracers():
    a = _tracer_with_latency_series()
    b = _tracer_with_latency_series()
    parsed = parse_prometheus(render_prometheus([a, b]))
    assert parsed["tb_tpu_serving_retries_total"] == [({}, 6.0)]
    total = sum(v for _, v in parsed["tb_tpu_window_commit_us_count"])
    assert total == 6.0  # histograms merged losslessly across tracers


def test_metrics_server_scrape():
    t = _tracer_with_latency_series()
    srv = MetricsServer(lambda: render_prometheus(t), port=0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=5) as r:
            assert r.status == 200
            assert "version=0.0.4" in r.headers["Content-Type"]
            parsed = parse_prometheus(r.read().decode())
        assert "tb_tpu_window_commit_us_bucket" in parsed
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/nope", timeout=5)
    finally:
        srv.close()


# ---------------------------------------------------------- SLO engine

def test_load_objectives_committed_file():
    cfg = load_objectives()
    names = {o.name for o in cfg["objectives"]}
    assert "chain_window_p99_ms" in names
    assert cfg["burn_window_runs"] >= 1
    assert 0.0 < cfg["burn_budget"] < 1.0


def test_dead_slo_rejected(tmp_path):
    def _write(objective):
        p = tmp_path / "slo.json"
        p.write_text(json.dumps({"objectives": [objective]}))
        return str(p)

    with pytest.raises(ValueError, match="no_such_event"):
        load_objectives(_write({"name": "x", "event": "no_such_event",
                                "threshold": 1.0}))
    with pytest.raises(ValueError, match="counter"):
        load_objectives(_write({"name": "x", "event": "serving_retries",
                                "threshold": 1.0}))
    with pytest.raises(ValueError, match="histogram dimensions"):
        load_objectives(_write({"name": "x", "event": "window_commit",
                                "tags": {"bogus": "y"},
                                "threshold": 1.0}))
    p = tmp_path / "empty.json"
    p.write_text(json.dumps({"objectives": []}))
    with pytest.raises(ValueError, match="no objectives"):
        load_objectives(str(p))


def test_evaluate_and_breach_counter():
    t = _tracer_with_latency_series()
    cfg = load_objectives()
    rows = evaluate(t, cfg["objectives"], emit_to=t)
    by_name = {r["name"]: r for r in rows}
    # Sub-millisecond no-op spans sit far under the ms thresholds.
    assert by_name["chain_window_p99_ms"]["ok"] is True
    assert by_name["chain_window_p99_ms"]["count"] == 2
    # replay histogram: 4 windows vs the "windows"-unit threshold.
    assert by_name["recovery_replay_windows_max"]["value"] == 4
    assert "slo_breach" not in t.counters
    # Forced breach: every objective's threshold below any value.
    forced = [dataclasses.replace(o, threshold=-1.0)
              for o in cfg["objectives"]]
    rows2 = evaluate(t, forced, emit_to=t)
    breached = [r for r in rows2 if r["ok"] is False]
    assert breached and t.counters["slo_breach"] == len(breached)
    # An objective whose series is empty is unknown, not a breach.
    empty = Tracer(pid=1)
    rows3 = evaluate(empty, cfg["objectives"])
    assert all(r["ok"] is None and r["value"] is None for r in rows3)


def test_burn_rates_and_badges():
    def run(ok):
        return [{"name": "o", "ok": ok}]

    burn = burn_rates([run(True), run(False), run(False), run(True)],
                      window_runs=4, budget=0.25)["o"]
    assert burn["burn_rate"] == 0.5
    assert burn["breaches"] == 2
    assert burn["breached_now"] is False
    assert burn["badge"] is True  # burn 0.5 > budget 0.25
    # Latest-run breach raises the badge regardless of burn.
    burn2 = burn_rates([run(True)] * 7 + [run(False)],
                       window_runs=8, budget=0.5)["o"]
    assert burn2["breached_now"] is True and burn2["badge"] is True
    # Unknown runs don't consume error budget.
    burn3 = burn_rates([run(None), run(None), run(True)],
                       window_runs=8, budget=0.25)["o"]
    assert burn3["evaluated"] == 1 and burn3["badge"] is False


# ------------------------------------------------------- critical path

def _span(name, ts, dur, pid=0, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "pid": pid,
            "tid": 0, "args": args}


def test_critical_path_serving_windows():
    # 10 windows; the slowest is dominated by serving_dispatch.
    events = []
    t = 0.0
    for i in range(10):
        dur = 10_000.0 if i == 9 else 1_000.0
        events.append(_span("window_commit", t, dur, route="chain"))
        events.append(_span("serving_dispatch", t + 100,
                            dur * 0.8, what="window"))
        t += dur + 500.0
    cp = critical_path({"traceEvents": events}, quantile=0.9)
    assert cp["window_event"] == "window_commit"
    assert cp["windows_total"] == 10 and cp["windows_analyzed"] == 1
    assert cp["p99_owner"] == "serving_dispatch"
    assert cp["stage_share"]["serving_dispatch"] == pytest.approx(
        0.8, abs=0.02)
    assert sum(cp["stage_share"].values()) == pytest.approx(1.0, abs=0.01)


def test_critical_path_synthesized_commit_groups():
    # No window spans: per-(pid, op) commit groups become the windows,
    # and only the group's own members are attributed (an interleaved
    # neighbor op's spans must not leak in).
    events = []
    for op in range(5):
        base = op * 10_000.0
        dur = 8_000.0 if op == 4 else 1_000.0
        events.append(_span("commit_execute", base, dur * 0.25, op=op))
        events.append(_span("commit_checkpoint", base + dur * 0.25,
                            dur * 0.75, op=op))
    cp = critical_path({"traceEvents": events}, quantile=0.8)
    assert cp["window_event"] == "commit_op"
    assert cp["p99_owner"] == "commit_checkpoint"
    assert cp["windows_total"] == 5


def test_critical_path_empty():
    assert critical_path({"traceEvents": []}) is None


# ---------------------------------------------------------- live parity

def _serve_windows(tracer, windows=7):
    """A seeded supervisor run of `windows` commit windows of two
    batches each, traced."""
    from tigerbeetle_tpu.serving import RetryPolicy, ServingSupervisor
    from tigerbeetle_tpu.types import Account, Transfer

    n_accounts, per_batch = 32, 64

    # epoch_interval past the run length: an epoch verification costs
    # an order of magnitude more than a window and would own p99.
    sup = ServingSupervisor(
        a_cap=1 << 9, t_cap=1 << 12, epoch_interval=2 * windows + 1,
        retry=RetryPolicy(max_retries=2, base_delay_s=1e-3,
                          max_delay_s=4e-3, deadline_s=30.0),
        seed=1234, tracer=tracer)
    ts = 1_000
    sup.create_accounts([Account(id=i, ledger=1, code=1)
                         for i in range(1, n_accounts + 1)], ts)
    next_id = 1_000_000
    for _ in range(windows):
        batches, stamps = [], []
        for _ in range(2):
            batches.append([Transfer(
                id=i, debit_account_id=i % n_accounts + 1,
                credit_account_id=(i % n_accounts + 1) % n_accounts + 1,
                amount=1 + i % 7, ledger=1, code=1)
                for i in range(next_id, next_id + per_batch)])
            next_id += per_batch
            ts += per_batch + 10
            stamps.append(ts)
        sup.create_transfers_window(batches, stamps)


def test_endpoint_p99_matches_offline_trace():
    """Acceptance: the endpoint's per-route window histogram p99 agrees
    with the offline (merged-trace) exact quantile within the histogram
    error bound."""
    t = Tracer(pid=0)
    _serve_windows(t)
    parsed = parse_prometheus(render_prometheus(t))
    # The supervisor tagged every window_commit span with its route.
    routes = {lab.get("route")
              for lab, _ in parsed["tb_tpu_window_commit_us_count"]}
    assert routes and None not in routes
    exact = span_quantile(t.chrome_dict(), "window_commit", 0.99)[""]
    merged = Histogram()
    for key, (name, _tags) in t.histogram_series.items():
        if name == "window_commit":
            merged.merge(t.histograms[key])
    got_ms = merged.quantile(0.99) / 1000.0
    assert abs(got_ms - exact) / exact <= 2 * REL_ERROR


# --------------------------------------------- vortex cluster scrape

@pytest.mark.integration
def test_vortex_metrics_endpoint(tmp_path):
    """Acceptance: curl /metrics on a running vortex cluster yields
    Prometheus-parseable output whose commit histograms agree with the
    offline merged trace within the histogram error bound."""
    from tigerbeetle_tpu.main import _parse_addresses
    from tigerbeetle_tpu.testing.vortex import VortexSupervisor
    from tigerbeetle_tpu.types import Account, Transfer
    from tigerbeetle_tpu.vsr.client import Client

    import time

    supervisor = VortexSupervisor(str(tmp_path), replica_count=3,
                                  seed=5, trace=True, metrics=True)
    try:
        client = Client(cluster=supervisor.cluster, client_id=13,
                        replica_addresses=_parse_addresses(
                            supervisor.addresses))
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            try:
                client.create_accounts([Account(id=1, ledger=1, code=1),
                                        Account(id=2, ledger=1, code=1)])
                break
            except TimeoutError:
                continue
        else:
            raise AssertionError("cluster never became available")
        for i in range(8):
            client.create_transfers([Transfer(
                id=100 + i, debit_account_id=1, credit_account_id=2,
                amount=1 + i, ledger=1, code=1)])
        # Live scrape: parseable, and the commit pipeline fed span
        # histograms on every replica. A backup that joined late (slow
        # jax import in its process) exposes commit-free metrics until
        # it finishes replaying — wait for cluster-wide catch-up first.
        supervisor.wait_caught_up()
        for i in range(3):
            parsed = parse_prometheus(supervisor.scrape_metrics(i))
            assert parsed["tb_tpu_commit_execute_us_count"][0][1] > 0
            assert parsed["tb_tpu_commits_total"][0][1] > 0
        client.close()
    finally:
        supervisor.shutdown()
    merged = supervisor.collect_merged_trace()
    # Offline parity: the merged cluster-wide histogram p99 vs the
    # exact nearest-rank p99 over the same merged trace's spans.
    hmeta = merged["metadata"]["histograms"]["commit_execute"]
    p99_hist_ms = Histogram.from_dict(hmeta).quantile(0.99) / 1000.0
    p99_exact_ms = span_quantile(merged, "commit_execute", 0.99)[""]
    assert abs(p99_hist_ms - p99_exact_ms) / p99_exact_ms <= 2 * REL_ERROR
