"""Static-analysis smoke: the gate's `static` leg.

Runs jaxhound 2.0's four whole-stack passes over the FULL serving-entry
registry (flat, chain, sharded, partitioned, partitioned-chain — 8
virtual devices required for the mesh tiers):

  1. device determinism (jaxhound/determinism.py) over every entry's
     jaxpr at the representative depth;
  2. host determinism (jaxhound/hostdet.py) AST lint over the commit
     path's host modules, pragma allowlist honored;
  3. retrace/recompile audit (jaxhound/retrace.py): canonical-signature
     unification across W∈{1,2,8,32} vs the committed
     perf/tracebudget_r*.json head, weak-typed scan carries, and a live
     jit-cache-miss probe on a flat entry (re-drive must cost zero);
  4. sharding-spec verification (jaxhound/shardspec.py) of every
     partitioned entry's lowered artifact.

Then proves each pass can actually fail — NEGATIVE injected-violation
proofs, one per pass, each of which must RED on a synthetic violation
and stay clean on its paired fixed form:

  determinism  a float32 psum jaxpr (vs int32 clean) and a baked
               PRNGKey (vs threaded-key clean);
  host         a fixture module reading the wall clock via `time.time`
               (vs the same line under `# jaxhound: allow(wall_clock)`);
  retrace      an entry whose arg dtype drifts with W (polymorphic
               RED) and a tampered budget digest (drift RED);
  sharding     a donated shard_map state arg lowered replicated
               (in_specs=P()) vs the P("batch") layout clean.

Prints each pass's verdict, then raises on any RED — a
silently-passing verifier never gates anything.

Run via ``scripts/gate.py`` (skip with --no-static) or directly:
``python -c "from tigerbeetle_tpu.testing import static_smoke;
static_smoke.static_smoke()"``.
"""

from __future__ import annotations

import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _negative_proofs(entries) -> dict[str, bool]:
    """name -> ok; each proof plants one violation that must RED its
    pass (and checks the paired clean form stays clean)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ..jaxhound import determinism, hostdet, retrace, shardspec
    from ..jaxhound.registry import Entry

    out: dict[str, bool] = {}

    # -- determinism: float collective + baked RNG key ------------------
    psum_f = jax.make_jaxpr(lambda x: jax.lax.psum(x, "i"),
                            axis_env=[("i", 2)])(jnp.ones(4, jnp.float32))
    psum_i = jax.make_jaxpr(lambda x: jax.lax.psum(x, "i"),
                            axis_env=[("i", 2)])(jnp.ones(4, jnp.int32))
    baked = jax.make_jaxpr(
        lambda x: x + jax.random.uniform(jax.random.PRNGKey(0), (4,))
    )(jnp.ones(4))
    threaded = jax.make_jaxpr(
        lambda k, x: x + jax.random.uniform(k, (4,))
    )(jax.random.PRNGKey(0), jnp.ones(4))
    out["determinism_float_collective"] = (
        any("float_collective" in f
            for f in determinism.findings_for(psum_f, "neg"))
        and not determinism.findings_for(psum_i, "pos"))
    out["determinism_baked_key"] = (
        any("rng_no_key" in f
            for f in determinism.findings_for(baked, "neg"))
        and not determinism.findings_for(threaded, "pos"))

    # -- host: wall-clock fixture, pragma suppression -------------------
    red_src = ("import time\n\ndef f():\n"
               "    return time.time()\n")  # tidy:allow (lint fixture)
    ok_src = ("import time\n\ndef f():\n    return time.time()"  # tidy:allow
              "  # jaxhound: allow(wall_clock)\n")
    out["host_wall_clock"] = (
        any("wall_clock" in f
            for f in hostdet.scan_source(red_src, "fixture.py"))
        and not hostdet.scan_source(ok_src, "fixture.py"))

    # -- retrace: polymorphic dtype across W + tampered budget digest ---
    poly = Entry(
        name="neg_poly", route="flat", jit_fn=None, raw_fn=None,
        make_args=lambda d: (np.zeros(
            8, np.int32 if d < 8 else np.int64),),
        depths=(1, 2, 8, 32))
    _, poly_fails = retrace.canonical_signature(poly)
    tampered_table, _ = retrace.audit(
        {"create_transfers_fast_jit":
         entries["create_transfers_fast_jit"]})
    tampered_table["create_transfers_fast_jit"]["digest"] = "0" * 16
    drift = retrace.check_budget({}, table=dict(tampered_table))
    out["retrace_polymorphic"] = any(
        "polymorphic_dtype" in f for f in poly_fails)
    out["retrace_budget_drift"] = any("digest" in f for f in drift)

    # -- sharding: donated state lowered replicated ---------------------
    mesh = Mesh(np.array(jax.devices()[:8]), ("batch",))

    def _mk(spec):
        sh = NamedSharding(mesh, spec)
        return jax.jit(
            shard_map(lambda s: s + 1, mesh=mesh,
                      in_specs=spec, out_specs=spec),
            in_shardings=sh, out_shardings=sh, donate_argnums=0)

    x = np.zeros((8, 128), np.int64)
    red = shardspec.verify_lowered(_mk(P()).lower(x), 1, "neg")
    clean = shardspec.verify_lowered(_mk(P("batch")).lower(x), 1, "pos")
    out["sharding_replicated_donor"] = bool(red) and not clean
    return out


def static_smoke() -> None:
    import jax

    from ..jaxhound import (
        determinism, hostdet, registry, retrace, shardspec)

    n_dev = len(jax.devices())
    assert n_dev >= 8, (
        f"static smoke needs >= 8 devices for the mesh tiers, got "
        f"{n_dev}; run under "
        "XLA_FLAGS=--xla_force_host_platform_device_count=8")

    entries = registry.entries()
    print(f"[static] registry: {len(entries)} entries", flush=True)
    traces = {n: e.trace() for n, e in entries.items()}

    passes: dict[str, list[str]] = {}
    passes["determinism"] = determinism.run(traces)
    passes["host"] = hostdet.run(REPO)

    retrace_fails: list[str] = []
    table, audit_fails = retrace.audit(entries)
    retrace_fails.extend(audit_fails)
    try:
        retrace_fails.extend(retrace.check_budget(entries, table=table))
    except FileNotFoundError as e:
        retrace_fails.append(f"tracebudget: {e}")
    for name, cj in traces.items():
        retrace_fails.extend(retrace.weak_carries(cj, name))
    # Live cache probe: re-driving a flat entry at an already-compiled
    # signature must cost zero jit-cache misses.
    probe = entries["create_transfers_fast_jit"]
    retrace_fails.extend(
        f"create_transfers_fast_jit: {f}" for f in retrace.cache_probe(
            probe.jit_fn, [probe.make_args(1), probe.make_args(1)]))
    passes["retrace"] = retrace_fails

    passes["sharding"] = shardspec.run(entries)

    negatives = _negative_proofs(entries)

    reds: list[str] = []
    for name, fails in passes.items():
        print(f"[static] pass {name}: "
              + ("clean" if not fails else f"{len(fails)} RED"),
              flush=True)
        reds.extend(f"{name}: {f}" for f in fails)
    for name, ok in negatives.items():
        print(f"[static] negative {name}: "
              + ("reds as required" if ok else "FAILED TO RED"),
              flush=True)
        if not ok:
            reds.append(f"negative proof {name}: injected violation "
                        "did not RED (the pass cannot fail)")
    assert not reds, "[static] RED:\n  " + "\n  ".join(reds)
    print("[static] GREEN", flush=True)


if __name__ == "__main__":
    static_smoke()
