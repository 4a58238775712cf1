"""jaxhound 2.0 static-pass unit tests (quick tier).

The full passes over the serving-entry registry are the gate's
`static` leg (testing/static_smoke.py); these tests pin the PASS
MACHINERY on small synthetic programs — every rule must RED on its
injected violation and stay clean on the paired sanctioned form — plus
the committed tracebudget file's schema and the satellite fixes
(closure-constant recursion into scan/pjit bodies, explicit
stats_unavailable instead of a silent except).
"""

import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tigerbeetle_tpu.jaxhound import (
    core, determinism, hostdet, retrace, shardspec)
from tigerbeetle_tpu.jaxhound.registry import Entry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACEBUDGET_PATH = os.path.join(REPO, "perf", "tracebudget_r01.json")


@pytest.fixture(scope="module", autouse=True)
def _release_compiles():
    """This module compiles a few dozen throwaway fixture programs;
    drop them from jax's process-global caches afterwards so the live
    latency bench (test_metrics.py runs next in alphabetical order)
    doesn't inherit the allocation/GC pressure."""
    yield
    import gc

    jax.clear_caches()
    gc.collect()


# ------------------------------------------- closure-const recursion

def test_closure_constant_inside_scan_body_is_caught():
    """Satellite: a lookup table baked into a lax.scan BODY never
    surfaces in the top-level consts — the recursive collector must
    find it anyway."""
    table = jnp.arange(4096, dtype=jnp.int32)  # 16 KiB > 4 KiB limit

    def f(x):
        def body(c, xi):
            return c + table[xi], xi
        c, _ = jax.lax.scan(body, jnp.int32(0), x)
        return c

    cj = jax.make_jaxpr(f)(jnp.zeros(4, jnp.int32))
    big = core.closure_constants(cj)
    assert big, "oversized const inside the scan body not reported"
    assert any(size >= 4096 * 4 for _label, size in big)


def test_closure_constant_inside_nested_jit_is_caught():
    """pjit bodies keep their own const list (unlike scan, whose
    consts hoist): the nested-jit case is the one a top-level-only
    scan provably misses."""
    table = jnp.arange(4096, dtype=jnp.int32)

    @jax.jit
    def inner(x):
        return x + table[x]

    cj = jax.make_jaxpr(lambda x: inner(x) * 2)(jnp.zeros(4, jnp.int32))
    assert not cj.consts or all(
        getattr(c, "nbytes", 0) < 4096 * 4 for c in cj.consts), \
        "fixture broke: const hoisted to top level, nested case untested"
    assert core.closure_constants(cj), \
        "oversized const inside a nested jit not reported"


def test_small_consts_stay_clean():
    def f(x):
        return x + jnp.arange(8, dtype=jnp.int32)  # 32 B, under limit

    assert core.closure_constants(jax.make_jaxpr(f)(
        jnp.zeros(8, jnp.int32))) == []


# ------------------------------------------------- stats_unavailable

def test_analyze_lowered_reports_stats_unavailable():
    """Satellite: a failing cost/memory analysis must surface as an
    explicit `stats_unavailable` reason, not a silent pass."""

    class _Compiled:
        def cost_analysis(self):
            raise RuntimeError("backend says no")

        def memory_analysis(self):
            raise RuntimeError("backend says no")

    class _Lowered:
        def as_text(self):
            return ("func.func public @main() {\n"
                    "  %0 = stablehlo.constant dense<1> : tensor<i32>\n"
                    "}\n")

        def compile(self):
            return _Compiled()

    info = core.analyze_lowered(_Lowered())
    assert "stats_unavailable" in info
    assert "cost_analysis" in info["stats_unavailable"]
    assert "backend says no" in info["stats_unavailable"]


def test_analyze_lowered_real_entry_has_no_unavailable():
    low = jax.jit(lambda x: x * 2).lower(jnp.zeros(8, jnp.int32))
    info = core.analyze_lowered(low)
    assert "stats_unavailable" not in info


# ------------------------------------------------- device determinism

def test_float_psum_reds_int_psum_clean():
    mk = lambda dt: jax.make_jaxpr(  # noqa: E731
        lambda x: jax.lax.psum(x, "i"),
        axis_env=[("i", 2)])(jnp.ones(4, dt))
    red = determinism.findings_for(mk(jnp.float32), "t")
    assert any("float_collective" in f for f in red)
    assert determinism.findings_for(mk(jnp.int32), "t") == []


def test_baked_prng_key_reds_threaded_key_clean():
    baked = jax.make_jaxpr(
        lambda x: x + jax.random.uniform(jax.random.PRNGKey(0), (4,))
    )(jnp.ones(4))
    assert any("rng_no_key" in f
               for f in determinism.findings_for(baked, "t"))
    threaded = jax.make_jaxpr(
        lambda k, x: x + jax.random.uniform(k, (4,))
    )(jax.random.PRNGKey(0), jnp.ones(4))
    assert determinism.findings_for(threaded, "t") == []


def test_baked_key_inside_scan_body_reds():
    """The recursion must carry derived-ness INTO sub-jaxprs: a key
    built from a constant inside a scan body is still baked."""
    def f(x):
        def body(c, xi):
            r = jax.random.uniform(jax.random.PRNGKey(7), (4,),
                                   dtype=jnp.float32)
            return c + r.sum(), xi
        c, _ = jax.lax.scan(body, jnp.float32(0), x)
        return c

    cj = jax.make_jaxpr(f)(jnp.zeros(3, jnp.float32))
    assert any("rng_no_key" in f_ for f_ in
               determinism.findings_for(cj, "t"))


def test_host_callback_reds():
    def f(x):
        return jax.pure_callback(
            lambda v: np.asarray(v) * 2, jax.ShapeDtypeStruct(
                (4,), jnp.float32), x)

    cj = jax.make_jaxpr(f)(jnp.ones(4, jnp.float32))
    assert any("host_callback" in f_ for f_ in
               determinism.findings_for(cj, "t"))


def test_float_scatter_dup_reds_int_and_unique_clean():
    idx = jnp.zeros((4, 1), jnp.int32)

    def add(x, u):
        return x.at[idx[:, 0]].add(u)

    red = determinism.findings_for(
        jax.make_jaxpr(add)(jnp.ones(8, jnp.float32),
                            jnp.ones(4, jnp.float32)), "t")
    assert any("float_scatter_dup" in f for f in red)
    clean_int = determinism.findings_for(
        jax.make_jaxpr(add)(jnp.ones(8, jnp.int32),
                            jnp.ones(4, jnp.int32)), "t")
    assert not any("float_scatter_dup" in f for f in clean_int)

    def add_unique(x, u):
        return x.at[idx[:, 0]].add(u, unique_indices=True,
                                   indices_are_sorted=True)

    clean_uni = determinism.findings_for(
        jax.make_jaxpr(add_unique)(jnp.ones(8, jnp.float32),
                                   jnp.ones(4, jnp.float32)), "t")
    assert not any("float_scatter_dup" in f for f in clean_uni)


# --------------------------------------------------- host determinism

def test_wall_clock_fixture_reds_and_pragma_suppresses():
    red = hostdet.scan_source(
        "import time\n\ndef f():\n    return time.time()\n", "fx.py")
    assert red == ["fx.py:4: wall_clock: time.time() read"]
    ok = hostdet.scan_source(
        "import time\n\ndef f():\n    return time.time()"
        "  # jaxhound: allow(wall_clock)\n", "fx.py")
    assert ok == []
    # A pragma for a DIFFERENT rule must not suppress.
    wrong = hostdet.scan_source(
        "import time\n\ndef f():\n    return time.time()"
        "  # jaxhound: allow(env_read)\n", "fx.py")
    assert len(wrong) == 1


def test_module_alias_and_injected_provider():
    red = hostdet.scan_source(
        "import time as _t\n\ndef f():\n    return _t.monotonic()\n",
        "fx.py")
    assert any("wall_clock" in f for f in red)
    # Injected providers (self.time.…) are the sanctioned pattern.
    ok = hostdet.scan_source(
        "class C:\n    def f(self):\n"
        "        return self.time.monotonic()\n", "fx.py")
    assert ok == []


def test_unseeded_random_reds_seeded_clean():
    red = hostdet.scan_source(
        "import random\n\ndef f():\n    return random.random()\n",
        "fx.py")
    assert any("unseeded_random" in f for f in red)
    ok = hostdet.scan_source(
        "import random\n\ndef f():\n"
        "    return random.Random(7).random()\n", "fx.py")
    assert ok == []
    red_np = hostdet.scan_source(
        "import numpy\n\ndef f():\n"
        "    return numpy.random.randint(3)\n", "fx.py")
    assert any("unseeded_random" in f for f in red_np)
    ok_np = hostdet.scan_source(
        "import numpy\n\ndef f():\n"
        "    return numpy.random.default_rng(7).integers(3)\n", "fx.py")
    assert ok_np == []


def test_set_iteration_reds_sorted_clean():
    red = hostdet.scan_source(
        "def f(xs):\n    return [x for x in set(xs)]\n", "fx.py")
    assert any("set_iteration" in f for f in red)
    ok = hostdet.scan_source(
        "def f(xs):\n    return [x for x in sorted(set(xs))]\n",
        "fx.py")
    assert ok == []


def test_env_read_reds():
    red = hostdet.scan_source(
        "import os\n\ndef f():\n    return os.environ['X']\n", "fx.py")
    assert any("env_read" in f for f in red)
    red2 = hostdet.scan_source(
        "import os\n\ndef f():\n    return os.getenv('X')\n", "fx.py")
    assert any("env_read" in f for f in red2)


def test_host_pass_over_real_scope_is_clean():
    assert hostdet.run(REPO) == []


# ------------------------------------------------------ retrace audit

def _entry(make_args, depths=(1, 2, 8, 32)):
    return Entry(name="t", route="flat", jit_fn=None, raw_fn=None,
                 make_args=make_args, depths=depths)


def test_canonical_signature_normalizes_window_axis():
    canon, fails = retrace.canonical_signature(_entry(
        lambda d: (np.zeros((d, 16), np.int32), np.uint64(5))))
    assert fails == []
    assert canon[0][0] == ("W", 16)
    # One digest regardless of which depth you look at.
    assert retrace.signature_digest(canon)


def test_polymorphic_dtype_reds():
    _, fails = retrace.canonical_signature(_entry(
        lambda d: (np.zeros(8, np.int32 if d < 8 else np.int64),)))
    assert any("polymorphic_dtype" in f for f in fails)


def test_weak_type_flap_reds():
    # A Python scalar at one depth only: weak_type flaps across W.
    _, fails = retrace.canonical_signature(_entry(
        lambda d: (7 if d == 1 else np.int32(7),)))
    assert any("weak_type_leak" in f for f in fails)


def test_non_window_axis_variation_reds():
    _, fails = retrace.canonical_signature(_entry(
        lambda d: (np.zeros((d * 2, 4), np.int32),)))
    assert any("polymorphic_shape" in f for f in fails)


def test_weak_scan_carry_reds_pinned_clean():
    def weak(x):
        def body(c, xi):
            return c + 1, xi  # Python-int carry: weak int32
        c, _ = jax.lax.scan(body, 0, x)
        return c

    cj = jax.make_jaxpr(weak)(jnp.zeros(3, jnp.int32))
    assert any("weak_carry" in f for f in retrace.weak_carries(cj, "t"))

    def pinned(x):
        def body(c, xi):
            return c + 1, xi
        c, _ = jax.lax.scan(body, jnp.int32(0), x)
        return c

    cj2 = jax.make_jaxpr(pinned)(jnp.zeros(3, jnp.int32))
    assert retrace.weak_carries(cj2, "t") == []


def test_cache_probe_counts_misses():
    calls = jax.jit(lambda x: x + 1)
    a1 = (np.zeros(8, np.int32),)
    a2 = (np.zeros(16, np.int32),)
    # same sig twice -> [<=1, 0]; new sig -> <=1. No overruns = clean.
    assert retrace.cache_probe(calls, [a1, a1, a2]) == []


def test_budget_drift_reds():
    table = {"e": {"route": "flat", "depths": [1], "n_signatures": 1,
                   "n_leaves": 2, "digest": "a" * 16}}
    import json as _json
    import tempfile
    with tempfile.NamedTemporaryFile(
            "w", suffix="_r01.json", delete=False) as f:
        _json.dump({"entries": {"e": dict(table["e"], digest="b" * 16),
                                "gone": dict(table["e"])}}, f)
        path = f.name
    try:
        fails = retrace.check_budget({}, budget_path=path, table=table)
    finally:
        os.unlink(path)
    assert any("digest" in f for f in fails)          # drifted entry
    assert any("missing from the registry" in f for f in fails)


def test_committed_tracebudget_schema():
    """The committed pin itself: every entry carries the full schema,
    one canonical signature each, and the chain/partitioned-chain
    entries span the whole W matrix."""
    with open(TRACEBUDGET_PATH) as f:
        doc = json.load(f)
    assert doc["round"] == 1
    assert doc["matrix"]["depths"] == [1, 2, 8, 32]
    entries = doc["entries"]
    assert len(entries) >= 18
    routes = set()
    for name, e in entries.items():
        assert set(e) == {"route", "depths", "n_signatures",
                          "n_leaves", "digest"}, name
        assert e["n_signatures"] == 1, name
        assert re.fullmatch(r"[0-9a-f]{16}", e["digest"]), name
        assert e["n_leaves"] > 0, name
        routes.add(e["route"])
        if e["route"] in ("chain", "partitioned_chain"):
            assert e["depths"] == [1, 2, 8, 32], name
    assert routes >= {"flat", "chain", "sharded", "partitioned",
                      "partitioned_chain"}
    assert core.newest_tracebudget_path().endswith(
        os.path.basename(TRACEBUDGET_PATH))


def test_newest_round_path_family(tmp_path):
    """One generalized `_newest_round_path` helper behind all three
    budget-trail resolvers (op / trace / mem): each picks the highest
    committed round of ITS prefix, ignores the others' files, and the
    public helpers resolve the repo's committed heads."""
    for name in ("opbudget_r02.json", "opbudget_r11.json",
                 "tracebudget_r01.json", "membudget_r01.json",
                 "membudget_r03.json", "membudget_r02.json"):
        (tmp_path / name).write_text("{}")
    d = str(tmp_path)
    assert core._newest_round_path(d, "opbudget").endswith(
        "opbudget_r11.json")
    assert core._newest_round_path(d, "tracebudget").endswith(
        "tracebudget_r01.json")
    assert core._newest_round_path(d, "membudget").endswith(
        "membudget_r03.json")
    with pytest.raises(FileNotFoundError):
        core._newest_round_path(d, "nosuchbudget")
    # The committed heads resolve (and the membudget one is a valid
    # static-allocation budget the memwatch plane can audit against).
    for helper, prefix in (
            (core.newest_budget_path, "opbudget"),
            (core.newest_tracebudget_path, "tracebudget"),
            (core.newest_membudget_path, "membudget")):
        path = helper()
        assert os.path.basename(path).startswith(prefix + "_r"), path
        assert os.path.exists(path), path
    from tigerbeetle_tpu.trace import load_budget
    budget = load_budget()
    assert budget["components"] and budget["total_bytes"] == \
        sum(budget["components"].values())
    assert budget["profiler"]["overhead_ratio_max"] == 1.05


# ---------------------------------------------------- sharding verify

@pytest.fixture(scope="module")
def mesh8():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (virtual) devices")
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:8]), ("batch",))


def _sharded_jit(mesh, spec):
    from jax import shard_map
    from jax.sharding import NamedSharding
    sh = NamedSharding(mesh, spec)
    return jax.jit(
        shard_map(lambda s: s + 1, mesh=mesh, in_specs=spec,
                  out_specs=spec),
        in_shardings=sh, out_shardings=sh, donate_argnums=0)


def test_replicated_donated_state_reds(mesh8):
    from jax.sharding import PartitionSpec as P
    x = np.zeros((8, 128), np.int64)
    fails = shardspec.verify_lowered(
        _sharded_jit(mesh8, P()).lower(x), 1, "neg")
    assert any("donated" in f for f in fails)
    assert any("shard_map results" in f for f in fails)


def test_batch_sharded_state_clean(mesh8):
    from jax.sharding import PartitionSpec as P
    x = np.zeros((8, 128), np.int64)
    assert shardspec.verify_lowered(
        _sharded_jit(mesh8, P("batch")).lower(x), 1, "pos") == []


def test_split_main_args_survives_quoted_shardings():
    text = ('func.func public @main(%arg0: tensor<8x4xi32> '
            '{jax.buffer_donor = true, sdy.sharding = '
            '#sdy.sharding<@mesh, [{"batch"}, {}]>}, '
            '%arg1: tensor<4xi32> {sdy.sharding = '
            '#sdy.sharding<@mesh, [{}]>}) -> (tensor<4xi32>) {')
    args = shardspec.split_main_args(text)
    assert len(args) == 2
    assert "batch" in args[0] and "arg1" in args[1]
    assert shardspec.arg_axis_sharded(args[0])
    assert not shardspec.arg_axis_sharded(args[1])


def test_partitioned_entries_are_really_checked(mesh8):
    """Not vacuously clean: the verifier sees every donated state leaf
    of the real partitioned entries sharded on the mesh axis, going in
    and coming back out of shard_map."""
    from tigerbeetle_tpu.jaxhound.registry import entries
    for name, entry in entries(True).items():
        if entry.route not in ("partitioned", "partitioned_chain"):
            continue
        text = entry.lower().as_text()
        args = shardspec.split_main_args(text)
        donated = [a for a in args if "jax.buffer_donor" in a
                   or "tf.aliasing_output" in a]
        assert len(donated) >= entry.n_state_leaves > 0, name
        assert all(shardspec.arg_axis_sharded(a) for a in donated), name
        outs = shardspec.manual_out_shardings(text)
        assert sum('{"' in d for d in outs) >= entry.n_state_leaves, name
        assert shardspec.verify_lowered(
            entry.lower(), entry.n_state_leaves, name) == []


# --------------------------------------------------------------- CLI

def test_cli_host_pass_json(capsys):
    from tigerbeetle_tpu.jaxhound.cli import main
    rc = main(["--pass", "host", "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["passes"]["host"]["ok"] is True


def test_cli_rejects_unknown_pass():
    from tigerbeetle_tpu.jaxhound.cli import main
    with pytest.raises(SystemExit):
        main(["--pass", "nonsense"])
