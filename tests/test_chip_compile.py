"""The served path's jit entries, compiled for a described TPU v5e at
production caps (`start` without --small: a_cap 2^17, t_cap 2^21) and
the 8192-row batch bucket — the chip's compiler asked without the chip.

Nothing here runs on a device: a compile that passes says the chip's
compiler accepts the program and how much device memory it plans, never
that results or times are right (chip_smoke.py shows those). Each
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


def abstract(tree, sharding):
    """Shapes of `tree` placed on the described device."""
    import jax

    def one(x):
        x = np.asarray(x) if not hasattr(x, "shape") else x
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    return jax.tree.map(one, tree)


def production_state(sharding):
    import jax

    from tigerbeetle_tpu.ops.ledger import init_state

    return abstract(jax.eval_shape(lambda: init_state(A_CAP, T_CAP)),
                    sharding)


def _empty_transfers():
    from tigerbeetle_tpu.ops.batch import transfers_to_arrays

    return transfers_to_arrays([])


def batch_args(sharding, n_pad=N_PAD):
    """(state, padded events, timestamp, n) for the per-batch tiers."""
    from tigerbeetle_tpu.ops.ledger import pad_transfer_events

    ev = pad_transfer_events(_empty_transfers(), n_pad)
    return (production_state(sharding), abstract(ev, sharding),
            abstract(np.uint64(1), sharding),
            abstract(np.int32(0), sharding))


def super_args(sharding, depth, n_pad=N_PAD):
    """The replica's all-or-nothing commit window: `depth` prepares
    flattened into one superbatch."""
    from tigerbeetle_tpu.ops.ledger import stack_superbatch

    ev_s, seg = stack_superbatch([_empty_transfers()] * depth,
                                 [10 ** 12] * depth, n_pad)
    return (production_state(sharding), abstract(ev_s, sharding),
            abstract(seg, sharding))


def chain_args(sharding, depth, n_pad=N_PAD):
    from tigerbeetle_tpu.ops.ledger import stack_chain_window

    ev_c, seg_c = stack_chain_window([_empty_transfers()] * depth,
                                     [10 ** 12] * depth, n_pad)
    return (production_state(sharding), abstract(ev_c, sharding),
            abstract(seg_c, sharding))


def accounts_args(sharding):
    from tigerbeetle_tpu.ops.batch import accounts_to_arrays
    from tigerbeetle_tpu.ops.ledger import pad_account_events

    ev = pad_account_events(accounts_to_arrays([]))
    return (production_state(sharding), abstract(ev, sharding),
            abstract(np.uint64(1), sharding),
            abstract(np.int32(0), sharding))


def _fk():
    from tigerbeetle_tpu.ops import fast_kernels

    return fast_kernels


def _ledger():
    from tigerbeetle_tpu.ops import ledger

    return ledger


def _delta_gather_jit():
    import jax

    return jax.jit(_ledger()._xfer_delta_gather, static_argnums=(3, 4))


# name -> (jit entry thunk, args builder). Thunks: nothing of the
# package's device code is imported while this file is collected.
CASES = {
    "create_transfers_fast@8192": (
        lambda: _fk().create_transfers_fast_jit, batch_args),
    "create_transfers_chain@W8x8192": (
        lambda: _fk().create_transfers_chain_jit,
        lambda s: chain_args(s, WINDOW_DEPTH)),
    "create_accounts_fast@8192": (
        lambda: _fk().create_accounts_fast_jit, accounts_args),
    "create_transfers_fast@1024": (
        lambda: _fk().create_transfers_fast_jit,
        lambda s: batch_args(s, 1024)),
    "create_transfers_fixpoint@1024": (
        lambda: _fk().create_transfers_fixpoint_jit,
        lambda s: batch_args(s, 1024)),
    "create_transfers_fixpoint@8192": (
        lambda: _fk().create_transfers_fixpoint_jit, batch_args),
    "create_transfers_fixpoint_deep@8192": (
        lambda: _fk().create_transfers_fixpoint_deep_jit, batch_args),
    "create_transfers_super@K2x8192": (
        lambda: _fk().create_transfers_super_jit,
        lambda s: super_args(s, 2)),
    "create_transfers_super@K8x8192": (
        lambda: _fk().create_transfers_super_jit,
        lambda s: super_args(s, WINDOW_DEPTH)),
    "xfer_delta_gather@8192": (
        _delta_gather_jit,
        lambda s: (production_state(s), abstract(np.int32(0), s),
                   abstract(np.int32(0), s), N_PAD, N_PAD)),
    "xfer_delta_gather@65536": (
        _delta_gather_jit,
        lambda s: (production_state(s), abstract(np.int32(0), s),
                   abstract(np.int32(0), s), 8 * N_PAD, 8 * N_PAD)),
}

TIER1 = ("create_transfers_fast@8192", "create_transfers_chain@W8x8192",
         "create_accounts_fast@8192")


def compile_case(name, sharding):
    """Lower + compile one case for the described chip. Returns
    (seconds, memory_analysis)."""
    entry, make_args = CASES[name]
    t0 = time.monotonic()
    compiled = entry().lower(*make_args(sharding)).compile()
    return time.monotonic() - t0, compiled.memory_analysis()


def _check(name, one_chip):
    seconds, mem = compile_case(name, one_chip)
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(f"{name}: compiled for {one_chip} in {seconds:.1f}s, "
          f"args {mem.argument_size_in_bytes >> 20} MiB, "
          f"temps {mem.temp_size_in_bytes >> 20} MiB")
    # One v5e chip holds 16 GB; the donated state is aliased in place.
    assert total < 15 * (1 << 30), (name, total)


@pytest.mark.parametrize("name", TIER1)
def test_served_entry_compiles_for_v5e(name, one_chip):
    _check(name, one_chip)


@pytest.mark.slow
@pytest.mark.parametrize("name", [n for n in CASES if n not in TIER1])
def test_served_entry_compiles_for_v5e_slow(name, one_chip):
    _check(name, one_chip)
