"""tigerbeetle_tpu — a TPU-native distributed financial-transactions framework.

A brand-new implementation of the capabilities of TigerBeetle (double-entry
accounting, VSR consensus, LSM storage, deterministic simulation testing),
designed TPU-first: the batched create_transfers/create_accounts validation
hot loop runs as a JAX batch-verification kernel over device-resident
struct-of-arrays state, while consensus, journaling, and block storage are
host-side components behind the same generic StateMachine boundary the
reference uses (reference: src/testing/cluster.zig:70).

u128 balances require exact 64-bit limb arithmetic, so the package enables
jax_enable_x64 at import.
"""

import jax

jax.config.update("jax_enable_x64", True)

from . import constants, types  # noqa: E402

__version__ = "0.1.0"
__all__ = ["constants", "types"]
