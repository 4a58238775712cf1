"""Pallas prototype kernels: interpreter-mode differentials against the
XLA implementations (semantics pinned before the first on-chip window
profiles them — see PERF.md and ops/pallas_kernels.py's adoption gate).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from tigerbeetle_tpu.ops import hash_table as HT
from tigerbeetle_tpu.ops import pallas_kernels as pk
from tigerbeetle_tpu.ops.pallas_kernels import (
    ht_lookup_fused,
    probe_fusable,
)


def _filled_table(cap=1 << 12, n_keys=1500, seed=3):
    rng = np.random.default_rng(seed)
    table = HT.ht_init(cap)
    k_hi = rng.integers(0, 1 << 63, n_keys, dtype=np.uint64)
    k_lo = rng.integers(1, 1 << 63, n_keys, dtype=np.uint64)
    # Unique keys (ht contract).
    seen = set()
    for i in range(n_keys):
        while (int(k_hi[i]), int(k_lo[i])) in seen:
            k_lo[i] += 1
        seen.add((int(k_hi[i]), int(k_lo[i])))
    vals = np.arange(n_keys, dtype=np.int32)
    table, ok = HT.ht_insert(table, jnp.asarray(k_hi), jnp.asarray(k_lo),
                             jnp.asarray(vals),
                             jnp.ones(n_keys, dtype=bool))
    assert bool(ok)
    return table, k_hi, k_lo, vals


def test_fused_probe_matches_xla_lookup():
    table, k_hi, k_lo, vals = _filled_table()
    rng = np.random.default_rng(7)
    # Query mix: present keys, absent keys, and zero sentinels.
    q_hi = np.concatenate([k_hi[:800],
                           rng.integers(0, 1 << 63, 300, dtype=np.uint64),
                           np.zeros(20, dtype=np.uint64)])
    q_lo = np.concatenate([k_lo[:800],
                           rng.integers(0, 1 << 63, 300, dtype=np.uint64),
                           np.zeros(20, dtype=np.uint64)])
    want_f, want_v = HT.ht_lookup(table, jnp.asarray(q_hi),
                                  jnp.asarray(q_lo))
    got_f, got_v = ht_lookup_fused(table, jnp.asarray(q_hi),
                                   jnp.asarray(q_lo), interpret=True)
    assert (np.asarray(got_f) == np.asarray(want_f)).all()
    assert (np.asarray(got_v) == np.asarray(want_v)).all()
    # Found keys resolve to their inserted values.
    assert (np.asarray(got_v)[:800] == vals[:800]).all()


def test_vmem_gate():
    small = HT.ht_init(1 << 12)
    assert probe_fusable(small)
    huge = HT.ht_init(1 << 21)  # (2^18+1) * 48 u32 ≈ 50 MB
    assert not probe_fusable(huge)


def test_tb_pallas_raises_instead_of_silent_xla(monkeypatch):
    """The TPU compiler refuses the prototype, so asking for it must not
    be answered by the XLA lookup under its name."""
    table = HT.ht_init(1 << 6)
    k = jnp.arange(1, 9, dtype=jnp.uint64)
    monkeypatch.delenv("TB_PALLAS", raising=False)
    found, _ = pk.ht_lookup_auto(table, k, k)
    assert not bool(found.any())
    monkeypatch.setenv("TB_PALLAS", "1")
    with pytest.raises(NotImplementedError, match="TB_PALLAS"):
        pk.ht_lookup_auto(table, k, k)
