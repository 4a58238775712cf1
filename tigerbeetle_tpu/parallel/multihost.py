"""Multi-host mesh: jax.distributed initialization + the 2-process leg.

The partitioned route shards ledger state by account/transfer range
over a device mesh; nothing in the route cares whether those devices
hang off one host. This module supplies the multi-controller plumbing
that stretches the mesh 8 -> 8xN:

  - ``init_multihost``: idempotent ``jax.distributed.initialize``
    wrapper (coordinator address + process count + process id from
    args or the standard env vars). Every process runs the SAME
    program; after init, ``jax.devices()`` is the GLOBAL device list
    and a mesh built over it spans hosts — shard_map + psum inside it
    become cross-host collectives with no change to the partitioned
    step itself.
  - ``global_mesh``: the 1-D partitioned mesh over the global device
    list.
  - ``two_process_smoke``: the gate's local multi-controller leg — two
    coordinator-connected processes on this host, each owning half the
    virtual CPU mesh, drive one fused partitioned-chain window and
    check oracle parity on the replicated results. Environments
    without multi-process support (no distributed runtime, no CPU
    cross-process collectives) SKIP gracefully: only a parity break is
    a red, never a missing capability.

Production deployment (one process per TPU host, coordinator =
host 0) is documented in docs/operating/cluster.md "Multi-host mesh".
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import time

_INITIALIZED = False


def init_multihost(coordinator_address: str | None = None,
                   num_processes: int | None = None,
                   process_id: int | None = None) -> bool:
    """Bring up the multi-controller runtime. Returns True when
    distributed init succeeded (or already ran), False when the
    runtime is unavailable in this environment — callers treat False
    as "single-host mesh", not an error. Arguments default to the
    standard JAX env vars (JAX_COORDINATOR_ADDRESS, JAX_NUM_PROCESSES,
    JAX_PROCESS_ID); with none present and no args, this is a no-op
    single-process True."""
    global _INITIALIZED
    if _INITIALIZED:
        return True
    if coordinator_address is None:
        coordinator_address = os.environ.get("JAX_COORDINATOR_ADDRESS")
    if coordinator_address is None:
        # Single-process: nothing to initialize, the local mesh IS the
        # global mesh.
        return True
    try:
        import jax

        # CPU cross-process collectives need an explicit impl (gloo)
        # where supported; harmless no-op elsewhere.
        try:
            jax.config.update("jax_cpu_collectives_implementation",
                              "gloo")
        except Exception:
            pass
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=(num_processes
                           if num_processes is not None else
                           int(os.environ.get("JAX_NUM_PROCESSES", 1))),
            process_id=(process_id if process_id is not None else
                        int(os.environ.get("JAX_PROCESS_ID", 0))))
        _INITIALIZED = True
        return True
    except Exception as e:  # runtime absent / backend refuses: skip
        print(f"[multihost] distributed init unavailable: {e!r}",
              flush=True)
        return False


def global_mesh(axis: str = "batch"):
    """The 1-D partitioned mesh over the GLOBAL device list (after
    init_multihost, that spans every connected process's devices)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()), (axis,))


# ------------------------------------------------ 2-process local leg

_WORKER = r"""
import os, sys
pid = int(sys.argv[1]); nproc = int(sys.argv[2]); coord = sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4").strip()
try:
    from tigerbeetle_tpu.parallel import multihost
    if not multihost.init_multihost(coord, nproc, pid):
        print("MULTIHOST_SKIP: distributed init unavailable",
              flush=True)
        sys.exit(0)
    import jax
    import numpy as np
    if len(jax.devices()) != 4 * nproc:
        print(f"MULTIHOST_SKIP: global device list is "
              f"{len(jax.devices())}, expected {4 * nproc}", flush=True)
        sys.exit(0)
    from tigerbeetle_tpu.oracle import StateMachineOracle
    from tigerbeetle_tpu.ops.batch import transfers_to_arrays
    from tigerbeetle_tpu.parallel.partitioned import PartitionedRouter
    from tigerbeetle_tpu.types import Account, Transfer

    mesh = multihost.global_mesh()
    oracle = StateMachineOracle()
    oracle.create_accounts(
        [Account(id=i, ledger=1, code=1) for i in range(1, 17)], 50)
    router = PartitionedRouter(mesh, a_cap=1 << 8, t_cap=1 << 9)
    state = router.from_oracle(oracle)
    rng = np.random.default_rng(31)
    nid, ts = 10 ** 6, 10 ** 9
    window, tss = [], []
    for _ in range(2):  # W=2: one fused cross-host dispatch
        evs = []
        for _ in range(6):
            dr, cr = (int(x) for x in rng.choice(
                np.arange(1, 17), 2, replace=False))
            evs.append(Transfer(id=nid, debit_account_id=dr,
                                credit_account_id=cr,
                                amount=int(rng.integers(1, 20)),
                                ledger=1, code=1))
            nid += 1
        ts += 300
        window.append(evs)
        tss.append(ts)
    state, results = router.step_window(
        state, [transfers_to_arrays(e) for e in window], tss)
except AssertionError:
    raise  # parity breaks are a RED, not a skip
except Exception as e:
    print(f"MULTIHOST_SKIP: {e!r}"[:300], flush=True)
    sys.exit(0)
# The route and parity asserts run OUTSIDE the skip net: once the
# runtime is up, a wrong answer must fail the leg.
assert router.window_routes.get("partitioned_chain") == 1, \
    router.window_routes
assert router.host_fallbacks == 0, router.stats()
for evs, t, (st, rts) in zip(window, tss, results):
    want = oracle.create_transfers(evs, t)
    got = [(int(rts[i]), int(st[i])) for i in range(len(evs))]
    assert got == [(r.timestamp, int(r.status)) for r in want], got
print(f"MULTIHOST_OK process={pid}", flush=True)
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _smoke_attempt(timeout: float) -> str:
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _WORKER, str(pid), "2", coord],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for pid in range(2)]
    deadline = time.time() + timeout
    outs = []
    for p in procs:
        left = max(1.0, deadline - time.time())
        try:
            out, _ = p.communicate(timeout=left)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            # A wedged coordinator handshake is an environment
            # limitation, not a ledger bug: skip, loudly.
            return "skipped: 2-process leg timed out (coordinator " \
                   "handshake unavailable?)"
        outs.append(out or "")
    for p, out in zip(procs, outs):
        if p.returncode != 0:
            if "AssertionError" in out:
                # A parity/route break with the runtime UP: a real red.
                raise RuntimeError(
                    f"multihost 2-process leg RED "
                    f"(rc={p.returncode}):\n" + out[-2000:])
            # Transport-layer crashes (the CPU gloo backend aborts on a
            # TCP race now and then) are an environment limitation.
            return ("skipped: worker crashed in the multi-process "
                    f"runtime (rc={p.returncode}): " + out[-200:])
    if all("MULTIHOST_OK" in o for o in outs):
        return "ok"
    reason = next((line for o in outs for line in o.splitlines()
                   if line.startswith("MULTIHOST_SKIP")),
                  "MULTIHOST_SKIP: no marker")
    return "skipped: " + reason.split(":", 1)[-1].strip()


def two_process_smoke(timeout: float = 300.0, attempts: int = 2) -> str:
    """Run the 2-process multi-controller leg on this host: two
    processes, 4 virtual CPU devices each, one coordinator, one fused
    partitioned-chain window over the 8-device GLOBAL mesh. Returns
    "ok" (route green across processes) or "skipped: <reason>"
    (multi-process init/collectives unavailable here — flaky transport
    crashes retry once before skipping). Raises on a parity red."""
    last = "skipped: not attempted"
    for _ in range(attempts):
        last = _smoke_attempt(timeout)
        if last == "ok":
            return last
    return last


if __name__ == "__main__":
    print(f"[multihost] {two_process_smoke()}")
