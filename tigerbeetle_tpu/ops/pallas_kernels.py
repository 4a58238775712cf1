"""Pallas TPU prototypes for the serving kernel's fusion frontier.

PERF.md's path to 10M tps replaces the dominant op groups with
megakernels. This module holds the first one — the fused two-choice hash
probe (`ht_lookup_fused`) keeping the packed table VMEM-resident — plus
the adoption gate. The XLA path stays the default everywhere:

- the cost-model doctrine (ARCHITECTURE.md) demands a REAL-hardware
  profile before a hand-scheduled kernel replaces XLA's lowering — a
  Pallas kernel that loses to the native gather path is a regression;
- VMEM residency bounds applicability: the packed table must fit the
  ~16 MiB v5e budget (capacity gate below).

Tests run the kernel in interpreter mode on CPU, which pins its
semantics and nothing else: put to the TPU compiler (a described v5e,
PR 23) the kernel is refused at Mosaic lowering, so TB_PALLAS=1 makes
`ht_lookup_auto` raise rather than serve from the XLA lookup in
silence. The prototype is off the serving path until it compiles.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from .hash_table import (
    LANES, SLOTS, _buckets, bucket_rows, ht_buckets, match_bucket)

# VMEM working-set budget for the ungridded fused probe (v5e has ~16 MiB
# per core): packed table + key/bucket inputs + the two gathered
# (N, 3*SLOTS) row blocks must all fit.
VMEM_BUDGET_BYTES = 12 * (1 << 20)


def pallas_enabled() -> bool:
    return os.environ.get("TB_PALLAS", "") == "1"


def probe_fusable(table: dict, n: int = 8192) -> bool:
    """Admission gate: the WHOLE working set — table plus this batch's
    inputs, outputs, and both gathered row blocks — fits VMEM."""
    packed = table["packed"]
    table_bytes = packed.size * packed.dtype.itemsize
    per_event = (
        8 + 8          # key hi/lo
        + 4 + 4        # bucket indices
        + 2 * 3 * SLOTS * 8  # two gathered packed rows
        + 1 + 4        # found + val outputs
    )
    return table_bytes + n * per_event <= VMEM_BUDGET_BYTES


def _probe_kernel(khi_ref, klo_ref, b1_ref, b2_ref, table_ref,
                  found_ref, val_ref):
    """One fused pass: both bucket gathers + slot match + value select.

    The table rides in VMEM for the whole batch; the per-event work is
    two row gathers from VMEM plus elementwise lane matching — no HBM
    round-trips for intermediates (the XLA path materializes each
    (N, SLOTS) bucket view in HBM). Match semantics come from
    hash_table.match_bucket — the shared source of truth."""
    k_hi = khi_ref[:]
    k_lo = klo_ref[:]
    querying = ~((k_hi == 0) & (k_lo == 0))
    found = jnp.zeros(k_hi.shape, dtype=jnp.bool_)
    val = jnp.full(k_hi.shape, -1, dtype=jnp.int32)
    lanes = table_ref[:].reshape(-1, LANES)
    for rows_ref in (b1_ref, b2_ref):
        g = bucket_rows(lanes, rows_ref[:])
        hit, lane_val = match_bucket(g, k_hi, k_lo, querying)
        found = found | hit
        val = jnp.where(hit, lane_val, val)
    found_ref[:] = found
    val_ref[:] = val


def ht_lookup_fused(table: dict, k_hi, k_lo, *, interpret: bool = False):
    """Fused ht_lookup: same contract as hash_table.ht_lookup.

    interpret=True runs the Pallas interpreter (CPU differential tests);
    on TPU the kernel compiles via Mosaic. Bucket indices are computed
    OUTSIDE the kernel (cheap elementwise XLA, fuses with the callers'
    key prep) so the kernel body is pure probe."""
    from jax.experimental import pallas as pl

    b = ht_buckets(table)
    b1, b2 = _buckets(k_hi, k_lo, b)
    n = k_hi.shape[0]
    out_shape = (
        jax.ShapeDtypeStruct((n,), jnp.bool_),
        jax.ShapeDtypeStruct((n,), jnp.int32),
    )
    return pl.pallas_call(
        _probe_kernel,
        out_shape=out_shape,
        interpret=interpret,
    )(k_hi, k_lo, b1, b2, table["packed"])


def ht_lookup_auto(table: dict, k_hi, k_lo):
    """The serving kernels' lookup: the XLA path. TB_PALLAS=1 asks for
    the fused probe, which the TPU compiler REFUSES (compiled for a
    described v5e, PR 23: the in-kernel `jnp.take` row gather fails
    Mosaic lowering with "Shape mismatch in input, indices and output",
    before the 64-bit operands are even reached) — so the request
    raises instead of being served by the XLA lookup under the
    prototype's name."""
    from .hash_table import ht_lookup

    if pallas_enabled():
        raise NotImplementedError(
            "TB_PALLAS=1: ht_lookup_fused does not compile for TPU "
            "(Mosaic refuses its row gather); it runs in interpreter "
            "mode only — unset TB_PALLAS to serve from the XLA lookup")
    return ht_lookup(table, k_hi, k_lo)
