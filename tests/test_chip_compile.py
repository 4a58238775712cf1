"""The served path's jit entries, compiled for a described TPU v5e at
production caps (`start` without --small: a_cap 2^17, t_cap 2^21) and
the 8192-row batch bucket — the chip's compiler asked without the chip.

Nothing here runs on a device: a compile that passes says the chip's
compiler accepts the program and how much device memory it plans, never
that results or times are right (chip_smoke.py shows those). The plain
create_transfers tier is also held to what PR 32 was for: the stores
are updated IN PLACE — the compiler plans no temporary the size of a
store and no pass over one beside the aliased scatters (`_in_place`).
Each
compile costs tens of seconds, so tier 1 keeps three entries (the plain
create_transfers tier, the scan-form chain window at the replica's
window depth, create_accounts) and the rest are marked slow:

    pytest tests/test_chip_compile.py -m "slow or not slow" --durations=0

The topology is described inside the module-scoped fixture, in the
test's own process and only after a test of this file has started:
libtpu admits one process at a time, so under pytest-xdist only the
worker that is handed this file may load it.
"""

import time

import numpy as np
import pytest

A_CAP = 1 << 17
T_CAP = 1 << 21
T_CAP_FILL = 1 << 22  # chipbench's tb_hbm_fill_1r
N_PAD = 8192
WINDOW_DEPTH = 8  # Replica.COMMIT_WINDOW_MAX


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / lock held: cannot be described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep them out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _cases():
    """name -> (jit entry, abstract args builder(sharding)). Built
    inside a test: nothing of the package's device code is imported
    while this file is collected. The shapes are the ones `start` warms
    and serves (ops/warmup.py), at production caps."""
    import jax

    from tigerbeetle_tpu.ops import fast_kernels as fk
    from tigerbeetle_tpu.ops import ledger, warmup

    def batch(n_pad, t_cap=T_CAP):
        return lambda s: warmup.batch_args(A_CAP, t_cap, n_pad, s)

    gather = jax.jit(ledger._xfer_delta_gather, static_argnums=(3, 4))

    def gather_args(size):
        return lambda s: (warmup.abstract_state(A_CAP, T_CAP, s),
                          *warmup.abstract((np.int32(0), np.int32(0)), s),
                          size, size)

    return {
        "create_transfers_fast@8192": (
            fk.create_transfers_fast_jit, batch(N_PAD)),
        "create_transfers_chain@W8x8192": (
            fk.create_transfers_chain_jit,
            lambda s: warmup.window_args(
                A_CAP, T_CAP, WINDOW_DEPTH, N_PAD, s,
                stack=ledger.stack_chain_window)),
        "create_accounts_fast@8192": (
            fk.create_accounts_fast_jit,
            lambda s: warmup.batch_args(A_CAP, T_CAP, sharding=s,
                                        accounts=True)),
        "create_transfers_fast@1024": (
            fk.create_transfers_fast_jit, batch(1024)),
        "create_transfers_fixpoint@1024": (
            fk.create_transfers_fixpoint_jit, batch(1024)),
        "create_transfers_fixpoint@8192": (
            fk.create_transfers_fixpoint_jit, batch(N_PAD)),
        "create_transfers_super@K2x8192": (
            fk.create_transfers_super_jit,
            lambda s: warmup.window_args(A_CAP, T_CAP, 2, N_PAD, s)),
        "create_transfers_super@K8x8192": (
            fk.create_transfers_super_jit,
            lambda s: warmup.window_args(A_CAP, T_CAP, WINDOW_DEPTH,
                                         N_PAD, s)),
        "xfer_delta_gather@8192": (gather, gather_args(N_PAD)),
        "xfer_delta_gather@65536": (gather, gather_args(8 * N_PAD)),
        "create_transfers_fast@8192,t_cap=2^22": (
            fk.create_transfers_fast_jit, batch(N_PAD, T_CAP_FILL)),
    }


TIER1 = ("create_transfers_fast@8192", "create_transfers_chain@W8x8192",
         "create_accounts_fast@8192")


def compile_case(name, sharding):
    """Lower + compile one case for the described chip. Returns
    (seconds, memory_analysis, optimised HLO text)."""
    entry, make_args = _cases()[name]
    t0 = time.monotonic()
    compiled = entry.lower(*make_args(sharding)).compile()
    return (time.monotonic() - t0, compiled.memory_analysis(),
            compiled.as_text())


# Cases whose stores must be updated in place, and their t_cap.
IN_PLACE = {"create_transfers_fast@8192": T_CAP,
            "create_transfers_fast@8192,t_cap=2^22": T_CAP_FILL}
TEMP_LIMIT = 256 << 20  # 2,065 MiB before PR 32, 102 MiB with it


def store_sized_dims(t_cap):
    """Every dimension an array the size of a store or a transfer
    table can have: the row counts (t_cap+1 rows, b+1 buckets) and
    their products with the column counts, whole and halved."""
    from tigerbeetle_tpu.ops import ev_layout as L
    from tigerbeetle_tpu.ops.hash_table import ROW, STRIDE, ht_buckets
    from tigerbeetle_tpu.ops import warmup

    state = warmup.abstract_state(A_CAP, t_cap)
    b = ht_buckets(state["xfer_ht"])
    rows = {state["transfers"]["u32"].shape[0],
            state["events"]["u32"].shape[0], b + 1, b + 2, (b + 2) // 2}
    widths = {1, ROW, ROW // 2, STRIDE, STRIDE // 2, L.XF_NCOLS,
              2 * L.XF_NCOLS, L.EV_NCOLS, 2 * L.EV_NCOLS}
    return {r * w for r in rows for w in widths}


def passes_over_stores(hlo_text, t_cap):
    """Instructions of the optimised HLO that copy, relayout, split,
    combine or loop over an array the size of a store: [(op, line)].
    The aliased scatters (and the fusions they sit in) are the program's
    work and are not among them."""
    import re

    dims = store_sized_dims(t_cap)
    shape = re.compile(r"\w+\[([\d,]+)\]")
    found = []
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if not m:
            continue
        result, op = m.groups()
        target = re.search(r'custom_call_target="(X64\w+)"', line)
        if target:
            op = target.group(1)
        if op not in ("while", "copy", "reshape") \
                and not op.startswith("X64"):
            continue
        if any(int(d) in dims for s in shape.findall(result)
               for d in s.split(",")):
            found.append((op, line.strip()[:200]))
    return found


def _check(name, one_chip):
    seconds, mem, hlo = compile_case(name, one_chip)
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(f"{name}: compiled for {one_chip} in {seconds:.1f}s, "
          f"args {mem.argument_size_in_bytes >> 20} MiB, "
          f"temps {mem.temp_size_in_bytes >> 20} MiB")
    # One v5e chip holds 16 GB; the donated state is aliased in place.
    assert total < 15 * (1 << 30), (name, total)
    if name in IN_PLACE:
        assert mem.temp_size_in_bytes < TEMP_LIMIT, (
            name, mem.temp_size_in_bytes >> 20)
        assert passes_over_stores(hlo, IN_PLACE[name]) == []


@pytest.mark.parametrize("name", TIER1)
def test_served_entry_compiles_for_v5e(name, one_chip):
    _check(name, one_chip)


SLOW = ("create_transfers_fast@1024", "create_transfers_fixpoint@1024",
        "create_transfers_fixpoint@8192",
        "create_transfers_super@K2x8192", "create_transfers_super@K8x8192",
        "xfer_delta_gather@8192", "xfer_delta_gather@65536",
        "create_transfers_fast@8192,t_cap=2^22")


@pytest.mark.slow
@pytest.mark.parametrize("name", SLOW)
def test_served_entry_compiles_for_v5e_slow(name, one_chip):
    _check(name, one_chip)


def test_case_lists_cover_every_case():
    assert sorted(TIER1 + SLOW) == sorted(_cases())
    assert set(IN_PLACE) <= set(_cases())


def test_the_in_place_check_sees_a_pass_over_a_store():
    """The reader is held to lines of the kind the u64 layout compiled
    to (PR 32's parent: `X64Combine.58`, `while.6`, `copy.1982`,
    `reshape.2`), and to what it must let through."""
    t1 = T_CAP + 1
    bad = f"""
  %X64Combine.58 = u64[1048577,24]{{0,1}} custom-call(%a, %b), custom_call_target="X64Combine"
  %while.6 = (s32[], u32[{t1 * 20}]{{0}}, u32[1,20,{t1}]{{2,1,0}}) while(%tuple.1), condition=%c, body=%b
  %copy.1982 = u32[24,1048577]{{1,0:T(8,128)}} copy(%x)
  %reshape.2 = u32[25165848]{{0}} reshape(%y)
"""
    assert [op for op, _ in passes_over_stores(bad, T_CAP)] == [
        "X64Combine", "while", "copy", "reshape"]
    fine = f"""
  %fusion.38 = u32[{t1},40]{{0,1:T(8,128)}} fusion(%p, %i, %u), kind=kLoop, calls=%scatter
  %copy.7 = u32[8192,40]{{1,0}} copy(%z)
  %X64Combine.3 = u64[131073,8]{{0,1}} custom-call(%a, %b), custom_call_target="X64Combine"
  %while.1 = (s32[], u32[8192,48]{{1,0}}) while(%tuple.2), condition=%c, body=%b
"""
    assert passes_over_stores(fine, T_CAP) == []


def test_warm_set_is_among_the_compiled_cases():
    """What `start` warms is what these tests put to the chip's
    compiler (the warm set's names are keys of the case table)."""
    from tigerbeetle_tpu.ops import warmup

    assert set(warmup.warm_set(1 << 10, 1 << 12)) <= set(_cases())


@pytest.mark.slow
def test_partitioned_window_compiles_for_v5e_2x2(topo):
    """chip_smoke.py --four-chips' fused window step, for a mesh of the
    four described chips: collectives over u64 lanes, state sharded."""
    from tigerbeetle_tpu.testing import partitioned_smoke as ps

    mesh, router = ps.build_router(topo.devices)
    with mesh:
        compiled = router._chain_step("plain").lower(
            *ps.abstract_chain_args(mesh)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 15 * (1 << 30)
    assert "all-reduce" in compiled.as_text()
