"""Device determinism pass: nondeterminism hazards in serving jaxprs.

The system's load-bearing invariant is byte-for-byte determinism
across replicas (ARCHITECTURE.md "Fault model & recovery": corrupted
state is repaired from peers precisely because every replica computes
identical bytes). This pass walks every registered serving entry's
jaxpr — recursing into scan/cond/pjit/shard_map sub-jaxprs — and REDs
on the four hazard classes that can silently break bit-parity:

  rng_no_key      an RNG primitive whose operands are all baked
                  (literals / closed-over constants, never derived
                  from an input): the key is compiled into the
                  program, so a retrace or a different backend mints
                  different bits than the replica that traced first.
                  A key THREADED from an argument is fine — the
                  caller owns reproducibility. The legacy stateful
                  `rng_uniform` is always a RED.
  host_callback   pure_callback / io_callback / debug_callback in a
                  serving lowering: the host round trip escapes the
                  deterministic replay envelope entirely.
  float_collective a cross-device collective on floating-point
                  operands: float psum is summation-order-dependent
                  across mesh topologies, so the same window commits
                  different bytes on a 2x4 vs an 8x1 mesh. The
                  partitioned exchange must stay integer (the PR 8/9
                  bodies do — this pass proves it stays that way).
  float_scatter_dup a scatter-family op on float operands with
                  neither sorted nor unique indices: duplicate index
                  combination order is unspecified, so FP accumulation
                  order — and the committed bytes — can vary.

Findings are strings prefixed with the rule name; an empty list means
the entry is determinism-clean.
"""

from __future__ import annotations

import numpy as np

from .core import HEAVY_CLASSES

# Key-threading RNG primitives (jax.random's functional family): legal
# ONLY when the key/seed operand is derived from an input.
RNG_PRIMS = frozenset({
    "threefry2x32", "rng_bit_generator", "random_seed", "random_wrap",
    "random_unwrap", "random_bits", "random_fold_in", "random_gamma",
    "random_clone",
})
# Legacy stateful RNG: nondeterministic by construction.
RNG_ALWAYS_RED = frozenset({"rng_uniform"})
# Host round trips: never allowed in a serving lowering.
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
})


def _is_float(aval) -> bool:
    dtype = getattr(aval, "dtype", None)
    return dtype is not None and np.issubdtype(dtype, np.floating)


def _sub_jaxprs(eqn):
    """(inner_jaxpr, inner_invars) for every sub-jaxpr carried by an
    equation's params — ClosedJaxpr (pjit/scan/cond) or raw Jaxpr
    (shard_map/while) alike."""
    out = []
    for sub in eqn.params.values():
        subs = sub if isinstance(sub, (list, tuple)) else (sub,)
        for s in subs:
            inner = getattr(s, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                out.append(inner)  # ClosedJaxpr (pjit/scan/cond)
            elif hasattr(s, "eqns"):
                out.append(s)  # raw Jaxpr (shard_map/while)
    return out


def _check_jaxpr(jaxpr, derived: set, findings: list, where: str) -> None:
    """One jaxpr level: local input-derived dataflow + hazard checks,
    then recursion. `derived` holds the Vars (identity-keyed) known to
    flow from this level's inputs; constvars and literal-fed chains
    stay outside it — an RNG primitive fed ONLY by those is baked."""
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        eqn_derived = any(v in derived for v in eqn.invars
                          if hasattr(v, "aval") and not hasattr(v, "val"))
        if prim in RNG_ALWAYS_RED:
            findings.append(
                f"rng_no_key: stateful `{prim}` in {where} "
                "(nondeterministic by construction)")
        elif prim in RNG_PRIMS and not eqn_derived:
            findings.append(
                f"rng_no_key: `{prim}` in {where} consumes a baked "
                "key/seed (literal or closed-over constant) — thread "
                "the key through an argument")
        if prim in CALLBACK_PRIMS:
            findings.append(
                f"host_callback: `{prim}` in {where} — host round "
                "trips escape the deterministic replay envelope")
        if HEAVY_CLASSES.get(prim) == "collective" and any(
                _is_float(getattr(v, "aval", None)) for v in eqn.invars):
            findings.append(
                f"float_collective: `{prim}` on floating operands in "
                f"{where} — summation order varies across mesh "
                "topologies; the exchange must stay integer")
        if (prim.startswith("scatter") and eqn.invars
                and _is_float(getattr(eqn.invars[0], "aval", None))
                and not eqn.params.get("unique_indices", False)
                and not eqn.params.get("indices_are_sorted", False)):
            findings.append(
                f"float_scatter_dup: `{prim}` on float operands with "
                f"unsorted, non-unique indices in {where} — duplicate "
                "combination order is unspecified")
        if eqn_derived:
            derived.update(eqn.outvars)
        for inner in _sub_jaxprs(eqn):
            # Positional derived-ness transfer, aligned from the END
            # (cond carries a leading predicate the branches don't
            # see); on a count mismatch fall back to all-derived —
            # conservative against false REDs.
            inner_derived = set()
            n_in, n_out = len(eqn.invars), len(inner.invars)
            if n_in >= n_out:
                for ov, iv in zip(eqn.invars[n_in - n_out:],
                                  inner.invars):
                    if not hasattr(ov, "val") and ov in derived:
                        inner_derived.add(iv)
            else:
                inner_derived.update(inner.invars)
            _check_jaxpr(inner, inner_derived, findings,
                         f"{where}/{prim}")


def findings_for(closed_jaxpr, name: str = "entry") -> list[str]:
    """Device-determinism findings for one traced program (empty =
    clean)."""
    findings: list[str] = []
    _check_jaxpr(closed_jaxpr.jaxpr, set(closed_jaxpr.jaxpr.invars),
                 findings, name)
    return findings


def run(jaxprs: dict) -> list[str]:
    """Run the pass over `name -> ClosedJaxpr`; returns RED strings."""
    fails = []
    for name, cj in jaxprs.items():
        fails.extend(f"{name}: {f}" for f in findings_for(cj, name))
    return fails
