"""Op-budget ledger + jaxhound static-lint unit tests (quick tier).

The budgets themselves are enforced by scripts/gate.py running
`perf/opbudget.py --check --lint` (a full-tier census); these tests pin
the MACHINERY — census classification, packed-layout round-trips, the
donation/while/closure detectors — and the committed budget file's
shape, so a regression in the measuring stick is caught by the cheap
tier before the gate trusts it.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tigerbeetle_tpu import jaxhound

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# r07: historical pin for the round-7 reduction-campaign assertions.
# r09: the LIVE budget file perf/opbudget.py --check enforces (r08's
# tiers carried forward + the fused partitioned_chain tiers).
BUDGET_PATH = os.path.join(REPO, "perf", "opbudget_r07.json")
BUDGET_PATH_LIVE = os.path.join(REPO, "perf", "opbudget_r09.json")


# ------------------------------------------------------------- census

def test_heavy_census_classifies_primitives():
    def f(x, idx, seg):
        g = x[idx]                                   # gather
        s = jnp.sort(x)                              # sort
        ss = jax.ops.segment_sum(x, seg, num_segments=4)  # scatter-add
        sc = jnp.zeros_like(x).at[idx].set(x)        # scatter
        return g.sum() + s.sum() + ss.sum() + sc.sum()

    x = jnp.arange(8, dtype=jnp.float32)
    idx = jnp.zeros(8, dtype=jnp.int32)
    cj = jax.make_jaxpr(f)(x, idx, idx)
    c = jaxhound.heavy_census(cj)
    assert c["heavy"]["gather"] >= 1
    assert c["heavy"]["sort"] == 1
    assert c["heavy"]["segment_sum"] == 1
    assert c["heavy"]["scatter"] == 1
    assert c["heavy_total"] == sum(c["heavy"].values())
    assert c["heavy_operand_bytes"] > 0


def test_heavy_census_recurses_into_scan():
    def f(x):
        idx = jnp.zeros(2, dtype=jnp.int32)

        def body(c, xi):
            return c + x[idx].sum(), xi  # gather inside the body
        c, _ = jax.lax.scan(body, jnp.float32(0), x)
        return c

    cj = jax.make_jaxpr(f)(jnp.arange(4, dtype=jnp.float32))
    c = jaxhound.heavy_census(cj)
    assert c["heavy"]["scan"] == 1
    assert c["heavy"]["gather"] >= 1


def test_scan_body_census_counts_body_once():
    """The chain route's gate number: the scan BODY census is the
    per-iteration op mass — body ops x 1 in the program regardless of
    the scan length (the whole-window dispatch's point)."""
    def mk(w):
        def f(x, idx):
            def body(c, xi):
                g = c[idx]                       # 1 gather / iteration
                s = jnp.sort(c)                  # 1 sort / iteration
                return c + g.sum() + s.sum() + xi.sum(), ()
            c, _ = jax.lax.scan(
                body, x, jnp.zeros((w, 4), jnp.float32))
            return c
        return jax.make_jaxpr(f)(jnp.arange(8, dtype=jnp.float32),
                                 jnp.zeros(8, jnp.int32))

    bodies = [jaxhound.scan_body_census(mk(w)) for w in (2, 8, 32)]
    assert bodies[0]["heavy_total"] == bodies[1]["heavy_total"] \
        == bodies[2]["heavy_total"]
    assert bodies[0]["heavy"]["gather"] >= 1
    assert bodies[0]["heavy"]["sort"] == 1
    # Whole-program census = body (once) + the outer scan op.
    whole = jaxhound.heavy_census(mk(32))
    assert whole["heavy_total"] == bodies[0]["heavy_total"] + 1
    # No scan -> zero census, not an error.
    empty = jaxhound.scan_body_census(
        jax.make_jaxpr(lambda x: x + 1)(jnp.zeros(4)))
    assert empty["heavy_total"] == 0


def test_chain_body_census_within_plain_budget():
    """Acceptance pin: the committed chain BODY budget stays at or
    under the per-batch plain tier's, and the whole-program chain
    census is depth-independent (body + 1 scan at every committed
    depth)."""
    with open(BUDGET_PATH) as f:
        d = json.load(f)
    b = d["budget"]
    assert (b["chain_body_w8"]["heavy_total"]
            <= b["plain"]["heavy_total"])
    for w in (2, 8, 32):
        assert (b[f"chain_w{w}"]["heavy_total"]
                == b["chain_body_w8"]["heavy_total"] + 1), w


def test_heavy_census_counts_collectives_inside_shard_map():
    """The partitioned tiers' gate number: the census must descend into
    a shard_map body (raw Jaxpr param, not ClosedJaxpr) and classify
    the exchange collectives, and state_gathers must flag any
    collective whose operand exceeds the whole-state threshold."""
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P


    from jax import shard_map
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))

    def body(a):
        return jax.lax.psum(a, "x")

    try:
        f = shard_map(body, mesh=mesh, in_specs=(P("x"),),
                      out_specs=P(), check_vma=False)
    except TypeError:
        f = shard_map(body, mesh=mesh, in_specs=(P("x"),),
                      out_specs=P(), check_rep=False)
    cj = jax.make_jaxpr(f)(jnp.zeros((8, 8), jnp.float32))
    c = jaxhound.heavy_census(cj)
    assert c["heavy"]["collective"] >= 1
    hits = jaxhound.state_gathers(cj, limit=8)
    assert hits and any("psum" in name for name, _ in hits)
    assert jaxhound.state_gathers(cj, limit=1 << 20) == []


def test_scan_body_census_counts_collectives_and_bytes():
    """The fused partitioned-chain route runs its psum exchange INSIDE
    the scan body: the body census must count the collective class and
    carry its operand-byte mass (collective_operand_bytes), and
    state_gathers must still flag an oversized collective through the
    scan — collectives in scan bodies must not escape either check."""
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P


    from jax import shard_map
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))

    def body(a, xs):
        def step(c, x):
            return c + jax.lax.psum(x, "x"), ()
        c, _ = jax.lax.scan(step, a, xs)
        return c

    try:
        f = shard_map(body, mesh=mesh, in_specs=(P("x"), P()),
                      out_specs=P("x"), check_vma=False)
    except TypeError:
        f = shard_map(body, mesh=mesh, in_specs=(P("x"), P()),
                      out_specs=P("x"), check_rep=False)
    cj = jax.make_jaxpr(f)(jnp.zeros((8,), jnp.float32),
                           jnp.zeros((4, 8), jnp.float32))
    whole = jaxhound.heavy_census(cj)
    assert whole["heavy"]["collective"] >= 1
    assert whole["collective_operand_bytes"] > 0
    bodyc = jaxhound.scan_body_census(cj)
    assert bodyc["heavy"]["collective"] >= 1
    assert bodyc["collective_operand_bytes"] > 0
    # The collective's bytes are a subset of the body's heavy bytes.
    assert (bodyc["collective_operand_bytes"]
            <= bodyc["heavy_operand_bytes"])
    hits = jaxhound.state_gathers(cj, limit=8)
    assert hits and any("psum" in name for name, _ in hits)
    # A collective-free scan censuses zero collective bytes.
    def plain(x):
        def step(c, xi):
            return c + jnp.sort(xi), ()
        c, _ = jax.lax.scan(step, x, jnp.zeros((4, 8), jnp.float32))
        return c

    clean = jaxhound.scan_body_census(
        jax.make_jaxpr(plain)(jnp.zeros((8,), jnp.float32)))
    assert clean["heavy"]["collective"] == 0
    assert clean["collective_operand_bytes"] == 0
    assert clean["heavy"]["sort"] == 1


# ----------------------------------------------------------- lints

def test_while_detector_sees_searchsorted_scan_method():
    def f(a, q):
        return jnp.searchsorted(a, q)  # default method lowers to while

    a = jnp.arange(64, dtype=jnp.uint64)
    low = jax.jit(f).lower(a, a[:4])
    assert low.as_text().count("stablehlo.while") >= 1

    def g(a, q):
        return jnp.searchsorted(a, q, method="sort")

    low2 = jax.jit(g).lower(a, a[:4])
    assert low2.as_text().count("stablehlo.while") == 0


def test_donated_inputs_counts_aliased_params():
    def f(state, y):
        return {k: v + y for k, v in state.items()}

    state = {"a": jnp.zeros(4), "b": jnp.zeros(4)}
    donated = jaxhound.donated_inputs(
        jax.jit(f, donate_argnums=0).lower(state, jnp.float32(1)))
    assert donated == 2
    undonated = jaxhound.donated_inputs(
        jax.jit(f).lower(state, jnp.float32(1)))
    assert undonated == 0


def test_closure_constant_detector():
    big = jnp.arange(4096, dtype=jnp.uint64)  # 32 KiB baked constant

    def f(x):
        return big[x]

    consts = jaxhound.closure_constants(
        jax.make_jaxpr(f)(jnp.zeros(4, jnp.int32)))
    assert consts and consts[0][1] == 4096 * 8

    def g(x):
        return x + 1  # no large consts

    assert jaxhound.closure_constants(
        jax.make_jaxpr(g)(jnp.zeros(4, jnp.int32))) == []


# ----------------------------------------------- packed store layouts

def test_packed_layout_roundtrip_transfers():
    from tigerbeetle_tpu.ops.ev_layout import (
        XF_NCOLS, XF_P32_POS, XF_PSTAT_COL32, XF_U64_IDX, narrow, pack32,
        widen, xf_col, xf_named, xf_rows32)

    # The host writes the packed u64 matrix; the store holds its u32
    # view (columns 2c / 2c+1 = low / high half of u64 column c).
    m = np.zeros((3, XF_NCOLS), dtype=np.uint64)
    m[:, XF_U64_IDX["ts"]] = [7, 8, (9 << 32) | 1]
    # ud32 above 2^31 (sign-sensitive), pstat/dr_row as i32 views.
    col, half = XF_P32_POS["ud32"]
    m[:, col] |= np.uint64(0xDEADBEEF) << np.uint64(32 * half)
    col, half = XF_P32_POS["timeout"]
    m[:, col] |= np.uint64(17) << np.uint64(32 * half)
    col, half = XF_P32_POS["pstat"]
    m[:, col] |= np.uint64(2) << np.uint64(32 * half)
    xfr = {"u32": narrow(m)}
    assert xfr["u32"].dtype == np.uint32
    assert xfr["u32"].shape == (3, 2 * XF_NCOLS)
    assert (widen(xfr["u32"]) == m).all()
    assert (xfr["u32"][:, XF_PSTAT_COL32] == 2).all()
    assert list(xf_col(xfr, "ud32")) == [0xDEADBEEF] * 3
    assert xf_col(xfr, "ud32").dtype == np.uint32
    assert list(xf_col(xfr, "timeout")) == [17] * 3
    named = xf_named(xfr)
    assert named["pstat"].dtype == np.int32
    assert list(named["pstat"]) == [2, 2, 2]
    assert list(named["ts"]) == [7, 8, (9 << 32) | 1]
    # The device path (arithmetic, no view) agrees with the host view,
    # both ways, and rows built from named columns are the same bytes.
    dev = {"u32": jnp.asarray(xfr["u32"])}
    for k, v in xf_named(dev).items():
        assert v.dtype == named[k].dtype, k
        assert (np.asarray(v) == named[k]).all(), k
    assert (np.asarray(widen(dev["u32"])) == m).all()
    assert (np.asarray(narrow(jnp.asarray(m))) == xfr["u32"]).all()
    assert (np.asarray(xf_rows32(xf_named(dev))) == xfr["u32"]).all()
    # pack32 zero-extends signed inputs (no sign smear into the partner).
    w = pack32(np.array([-1], dtype=np.int32),
               np.array([5], dtype=np.int32))
    assert int(w[0]) == (5 << 32) | 0xFFFFFFFF


def test_packed_layout_roundtrip_events_negative_p_row():
    from tigerbeetle_tpu.ops.ledger import init_state
    from tigerbeetle_tpu.ops.ev_layout import ev_col, ev_named

    evr = init_state(1 << 6, 1 << 6)["events"]
    p_row = np.asarray(ev_col(evr, "p_row"))
    assert p_row.dtype == np.int32
    assert (p_row == -1).all()  # the init sentinel survives packing
    tflags = np.asarray(ev_col(evr, "tflags"))
    assert (tflags == np.uint32(0xFFFFFFFF)).all()
    named = ev_named(evr)
    assert named["dr_row"].dtype == np.int32


def test_packed_layout_accounts_flags_isolated_from_code():
    from tigerbeetle_tpu.ops.ev_layout import (
        AC_FLAGS_COL32, AC_NCOLS, AC_P32_POS, ac_named, ac_rows32, narrow,
        pack32)

    m = np.zeros((2, AC_NCOLS), dtype=np.uint64)
    col, _ = AC_P32_POS["code"]
    assert AC_P32_POS["flags"][0] == col, \
        "flags shares its packed u64 word with code (the durable row " \
        "format); in the store it is a u32 column of its own"
    m[:, col] = pack32(np.array([77, 78], dtype=np.uint32),
                       np.array([0x10, 0x20], dtype=np.uint32))
    bal = np.arange(32, dtype=np.uint64).reshape(2, 16)
    rows = {"u32": narrow(m), "bal": narrow(bal)}
    assert list(rows["u32"][:, AC_FLAGS_COL32]) == [0x10, 0x20]
    named = ac_named(rows)
    assert list(named["code"]) == [77, 78]
    assert list(named["flags"]) == [0x10, 0x20]
    assert (named["bal"] == bal).all()  # widened to its u64 view
    dev = ac_named({"u32": jnp.asarray(rows["u32"])})
    assert (np.asarray(ac_rows32(dev)) == rows["u32"]).all()


# ------------------------------------------------- committed budgets

def test_budget_file_covers_core_tiers():
    with open(BUDGET_PATH_LIVE) as f:
        d = json.load(f)
    for tier in ("per_event_plain", "plain", "fixpoint_8",
                 "balancing_8", "imported", "super_plain_s4",
                 "super_deep24_s4", "sharded_plain", "sharded_fixpoint",
                 "chain_w2", "chain_w8", "chain_w32", "chain_body_w8",
                 "partitioned_plain", "partitioned_fixpoint",
                 "partitioned_chain_w2", "partitioned_chain_w8",
                 "partitioned_chain_w32", "partitioned_chain_body"):
        assert tier in d["budget"], tier
        b = d["budget"][tier]
        assert b["heavy_total"] == sum(b["heavy"].values())
        assert b["heavy_operand_bytes"] > 0
    # post must not exceed budget (the gate's invariant, pinned here
    # against hand-edits that would silently loosen it backwards).
    for tier, b in d["budget"].items():
        post = d["post"][tier]
        assert post["heavy_total"] <= b["heavy_total"], tier
    # The partitioned tiers' exchange is budget-pinned: a bounded,
    # NONZERO collective count (two psum exchange rounds + the merged
    # bad-flag reduction), never a whole-state gather (run_lints).
    for tier in ("partitioned_plain", "partitioned_fixpoint",
                 "partitioned_chain_body"):
        assert 0 < d["budget"][tier]["heavy"]["collective"] <= 8, tier


def test_partitioned_chain_budget_is_amortized_x1():
    """Acceptance pin for the fused route: the scan-BODY op count
    equals the per-batch partitioned tier (the window amortizes
    dispatch, it adds no per-prepare op mass), the whole-program census
    is flat in W (body + the one outer scan op at every committed
    depth), and the exchange's ICI byte mass is pinned nonzero inside
    the scan body (collective_operand_bytes in the post census)."""
    with open(BUDGET_PATH_LIVE) as f:
        d = json.load(f)
    b = d["budget"]
    body = b["partitioned_chain_body"]["heavy_total"]
    assert body == b["partitioned_plain"]["heavy_total"]
    for w in (2, 8, 32):
        assert b[f"partitioned_chain_w{w}"]["heavy_total"] == body + 1, w
    post = d["post"]["partitioned_chain_body"]
    assert post["heavy"]["collective"] >= 1
    assert post["collective_operand_bytes"] > 0


def test_campaign_hit_the_15pct_reduction():
    with open(BUDGET_PATH) as f:
        d = json.load(f)
    pre = d["pre"]["per_event_plain"]["heavy_total"]
    post = d["post"]["per_event_plain"]["heavy_total"]
    assert post <= 0.85 * pre, (pre, post)
    # The full plain tier rode along.
    assert (d["post"]["plain"]["heavy_total"]
            <= 0.85 * d["pre"]["plain"]["heavy_total"])


def test_check_budgets_flags_excess(monkeypatch):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "tb_opbudget_test", os.path.join(REPO, "perf", "opbudget.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(mod.BUDGET_PATH) as f:
        budgets = json.load(f)["budget"]
    ok = {t: {"heavy_total": b["heavy_total"],
              "heavy": dict(b["heavy"]),
              "heavy_operand_bytes": b["heavy_operand_bytes"]}
          for t, b in budgets.items()}
    assert mod.check_budgets(current=ok) == []
    bad = {t: dict(c, heavy=dict(c["heavy"])) for t, c in ok.items()}
    tier = "plain"
    bad[tier]["heavy_total"] += 1
    bad[tier]["heavy"]["gather"] += 1
    fails = mod.check_budgets(current=bad)
    assert any(tier in f and "heavy_total" in f for f in fails)
    assert any(tier in f and "gather" in f for f in fails)
