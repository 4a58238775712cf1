"""Sharding-spec verifier: the partitioned layout, statically proven.

The partitioned tiers exist so that ledger STORE rows never move: each
device owns an account range, state stays resident under
`P("batch")`, and only compact per-event bundles cross the ICI. The
runtime-shape complement (`core.state_gathers`) catches a collective
moving whole-state operands; THIS pass catches the quieter failure —
a donated state leaf whose sharding silently degrades to replicated
(an in_specs/out_specs edit, a lost NamedSharding on the fixture, an
out_shardings default) so every device suddenly holds, copies, and
donates the WHOLE ledger again.

It parses the lowered StableHLO of each partitioned entry — as JAX 0.9
lowers it, with Shardy: shardings are `sdy.sharding =
#sdy.sharding<@mesh, [{"batch"}, {}, {}]>` attrs on the @main
arguments (a dimension is sharded when its axis list names an axis;
`[{}, {}]` or `@empty_mesh` is replicated), and shard_map is one
`sdy.manual_computation` whose `out_shardings=[...]` say how each
result leaves it — and asserts:

  - every `jax.buffer_donor` input (the donated state leaves) carries
    an `sdy.sharding` that shards at least one dimension;
  - the donated-and-sharded input count >= the state leaf count (no
    leaf slipped out of the donated set into replicated-land);
  - shard_map returns at least as many axis-sharded results
    (`out_shardings` entries) as there are state leaves — the state
    comes BACK sharded, not gathered;
  - no state-sized operand is silently replicated: any @main input
    without an axis sharding whose byte size reaches the largest
    sharded state leaf is flagged (a whole-state table passed
    replicated defeats the layout even if the named state is fine).

Findings are strings; empty = the layout holds.
"""

from __future__ import annotations

import re

# MLIR element type -> bytes (i1 stored as a byte for sizing purposes).
_ELEM_BYTES = {
    "i1": 1, "i8": 1, "ui8": 1, "i16": 2, "ui16": 2, "f16": 2,
    "bf16": 2, "i32": 4, "ui32": 4, "f32": 4, "i64": 8, "ui64": 8,
    "f64": 8,
}

_MAIN_RE = re.compile(
    r"func\.func\s+public\s+@main\((.*?)\)\s*->", re.S)
_TENSOR_RE = re.compile(r"tensor<([^>]*)>")
_SDY_ARG_RE = re.compile(
    r"sdy\.sharding\s*=\s*#sdy\.sharding<@\w+,\s*\[(.*?)\]>")
_SDY_ENTRY_RE = re.compile(r"<@\w+,\s*\[(.*?)\]>")


def _axis_sharded(dims: str) -> bool:
    """True when a Shardy dimension list like `{"batch"}, {}, {}` names
    a mesh axis on some dimension (`{}` everywhere = replicated)."""
    return '{"' in dims


def arg_axis_sharded(arg_decl: str) -> bool:
    m = _SDY_ARG_RE.search(arg_decl)
    return bool(m) and _axis_sharded(m.group(1))


def manual_out_shardings(text: str) -> list[str]:
    """The dimension lists of every `out_shardings=[...]` entry of the
    lowered module's sdy.manual_computation ops (shard_map's exits)."""
    out = []
    key = "out_shardings=["
    pos = text.find(key)
    while pos != -1:
        i = pos + len(key)
        depth, j = 1, i
        while depth and j < len(text):
            depth += {"[": 1, "]": -1}.get(text[j], 0)
            j += 1
        out.extend(_SDY_ENTRY_RE.findall(text[i:j - 1]))
        pos = text.find(key, j)
    return out


def tensor_nbytes(tensor_body: str) -> int:
    """Byte size of an MLIR `tensor<...>` body like '8x512x6xui64'."""
    parts = tensor_body.split("x")
    elem = parts[-1]
    n = 1
    for p in parts[:-1]:
        n *= int(p)
    return n * _ELEM_BYTES.get(elem, 1)


def split_main_args(text: str) -> list[str]:
    """The @main signature's argument declarations (attrs included),
    split at top-level commas."""
    m = _MAIN_RE.search(text)
    if m is None:
        return []
    body = m.group(1)
    args, depth, cur, in_str = [], 0, [], False
    for ch in body:
        if ch == '"':
            # Sharding attr values are quoted and hold UNBALANCED
            # brackets ("{devices=[8,1]<=[8]}"); bracket depth must
            # ignore string contents entirely.
            in_str = not in_str
        elif not in_str:
            if ch in "<{([":
                depth += 1
            elif ch in ">})]":
                depth -= 1
        if ch == "," and depth == 0 and not in_str:
            args.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    if cur and "".join(cur).strip():
        args.append("".join(cur).strip())
    return args


def verify_lowered(lowered, n_state_leaves: int,
                   name: str = "entry") -> list[str]:
    """Sharding-spec findings for one lowered partitioned entry."""
    text = lowered.as_text()
    args = split_main_args(text)
    fails: list[str] = []
    if not args:
        return [f"{name}: could not locate @main signature in the "
                "lowered artifact"]
    donated_sharded = 0
    sharded_sizes: list[int] = []
    arg_meta = []  # (index, nbytes, donated, devices_sharded)
    for i, a in enumerate(args):
        tm = _TENSOR_RE.search(a)
        nbytes = tensor_nbytes(tm.group(1)) if tm else 0
        # Donation lowers as `jax.buffer_donor = true` (unaliased
        # donor) or `tf.aliasing_output = N` (donor aliased to an
        # output) depending on whether XLA established the alias.
        donated = "jax.buffer_donor" in a or "tf.aliasing_output" in a
        devices = arg_axis_sharded(a)
        arg_meta.append((i, nbytes, donated, devices))
        if donated:
            if devices:
                donated_sharded += 1
                sharded_sizes.append(nbytes)
            else:
                fails.append(
                    f"{name}: donated input #{i} "
                    f"({tm.group(1) if tm else '?'}) carries no "
                    "axis sharding (replicated donated state — the "
                    "partitioned layout regressed)")
    if donated_sharded < n_state_leaves:
        fails.append(
            f"{name}: {donated_sharded} donated+sharded inputs < "
            f"{n_state_leaves} state leaves (a state leaf left the "
            "donated sharded set)")
    # Output side: shard_map exits through sdy.manual_computation; the
    # state must come back axis-sharded, leaf for leaf.
    out_sharded = sum(1 for dims in manual_out_shardings(text)
                      if _axis_sharded(dims))
    if out_sharded < n_state_leaves:
        fails.append(
            f"{name}: {out_sharded} axis-sharded shard_map results < "
            f"{n_state_leaves} state leaves (state is gathered, not "
            "returned sharded)")
    # Silent replication: any input as large as the biggest sharded
    # state leaf but carrying no devices sharding is whole-state mass
    # being re-shipped to every device.
    threshold = max(sharded_sizes, default=0)
    if threshold:
        for i, nbytes, donated, devices in arg_meta:
            if not devices and nbytes >= threshold:
                fails.append(
                    f"{name}: input #{i} ({nbytes} B) is state-sized "
                    "but replicated (no axis sharding) — a "
                    "whole-state operand is shipped to every device")
    return fails


def run(entries: dict) -> list[str]:
    """Run the verifier over the registry's partitioned entries
    (routes 'partitioned' and 'partitioned_chain')."""
    fails = []
    for name, entry in entries.items():
        if entry.route not in ("partitioned", "partitioned_chain"):
            continue
        fails.extend(verify_lowered(entry.lower(), entry.n_state_leaves,
                                    name))
    return fails
