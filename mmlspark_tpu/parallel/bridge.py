"""Host table <-> device array bridge.

Replaces the reference's quadruple-copy JNI boundary
(CNTKModel.scala:63-92: Row -> FloatVector -> Value -> evaluate ->
FloatVectorVector -> Row) with a single host->HBM transfer: numpy columns are
`jax.device_put` directly with a NamedSharding, so each device receives only
its shard (no full-batch replication, no per-row copies).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from mmlspark_tpu.parallel.mesh import (DATA_AXIS, MODEL_AXIS, batch_sharding,
                                        replicated)


def pad_to_multiple(arr: np.ndarray, multiple: int,
                    axis: int = 0) -> tuple[np.ndarray, int]:
    """Zero-pad `arr` along `axis` to a multiple; returns (padded, valid_count).

    Sharded arrays need a leading dim divisible by the mesh axis; static
    padded shapes also keep XLA from recompiling per remainder batch.
    """
    n = arr.shape[axis]
    rem = n % multiple
    if rem == 0:
        return arr, n
    pad = multiple - rem
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, pad)
    return np.pad(arr, widths), n


def shard_batch(arr: np.ndarray, mesh: Mesh, *, axis: str = DATA_AXIS) -> jax.Array:
    """Place a host batch onto the mesh, split along the leading dim."""
    padded, _ = pad_to_multiple(np.asarray(arr), mesh.shape[axis])
    return jax.device_put(padded, batch_sharding(mesh, axis=axis))


def shard_table_columns(table, columns: Sequence[str], mesh: Mesh,
                        *, axis: str = DATA_AXIS,
                        dtype=None) -> tuple[dict[str, jax.Array], int]:
    """Materialize table columns as sharded device arrays.

    Returns (column dict, valid row count) — rows beyond the count are
    padding introduced for divisibility.
    """
    out: dict[str, jax.Array] = {}
    valid = table.num_rows
    for c in columns:
        col = table[c]
        if col.dtype == object:
            raise TypeError(
                f"column '{c}' is an object column; tensorize it first")
        arr = col.astype(dtype) if dtype is not None else col
        padded, valid = pad_to_multiple(arr, mesh.shape[axis])
        out[c] = jax.device_put(padded, batch_sharding(mesh, axis=axis))
    return out, valid


def put_batch_parts(mesh: Mesh, *arrays: np.ndarray,
                    axis: str = DATA_AXIS) -> tuple:
    """device_put several row-aligned host arrays with the mesh batch
    sharding, one straight-to-sharded transfer each (no default-device
    hop).  Leading dims must already be shard-divisible — callers that
    pad rows carry per-array pad values (a true-length pads with 1, a
    liveness mask with False), so padding stays theirs.  The bucketed
    decode path stages prompts + true lengths + live masks in lockstep."""
    sharding = batch_sharding(mesh, axis=axis)
    for a in arrays:
        if a.shape[0] % mesh.shape[axis]:
            raise ValueError(
                f"leading dim {a.shape[0]} not divisible by the mesh "
                f"'{axis}' axis ({mesh.shape[axis]}); pad rows first "
                f"(pad_to_multiple)")
    return tuple(jax.device_put(a, sharding) for a in arrays)


def put_sharded(local: np.ndarray, sharding: NamedSharding) -> jax.Array:
    """Assemble a global device array from this process's local rows.

    Single-process: a plain `device_put`.  Multi-host (the replacement for
    the reference's per-node MPI data feed, CommandBuilders.scala:95-117):
    every process contributes only the rows its addressable devices hold, and
    `jax.make_array_from_process_local_data` stitches them into one global
    array — no host ever materializes the global batch.
    """
    if jax.process_count() == 1:
        return jax.device_put(local, sharding)
    return jax.make_array_from_process_local_data(sharding, local)


_gather_fns: dict[Mesh, Any] = {}
_GATHER_CACHE_MAX = 8  # a process uses a handful of meshes; bound the cache
# so churning through many short-lived meshes can't pin them (and their
# compiled executables) for the process lifetime


def gather_replicated(tree: Any, mesh: Mesh) -> Any:
    """All-gather a pytree to fully-replicated device arrays.

    Under multi-host, shards owned by other processes are not addressable;
    an XLA identity jit with fully-replicated output shardings performs the
    all-gather over ICI/DCN.  Every process must call this (it is a
    collective).  The jitted gather is cached per mesh so repeated
    checkpoints don't re-lower/re-compile.
    """
    if mesh not in _gather_fns:
        while len(_gather_fns) >= _GATHER_CACHE_MAX:
            _gather_fns.pop(next(iter(_gather_fns)))  # FIFO eviction
        _gather_fns[mesh] = jax.jit(lambda t: t,
                                    out_shardings=replicated(mesh))
    return _gather_fns[mesh](tree)


_snapshot_fn = None


def snapshot_tree(tree: Any) -> Any:
    """A defensive on-device copy with UNCHANGED shardings.

    The async-checkpoint snapshot (train/trainer.py): the writer thread
    device_gets the copy at its leisure, so the step loop donating the
    live state buffers to the next step never invalidates a pending
    write.  Single-process only — every shard is addressable, so no
    replication (cost: one device-local copy of the state bytes, not
    n_devices copies); multi-host saves keep `gather_replicated`, which
    the coordinator needs for addressability anyway.
    """
    global _snapshot_fn
    if _snapshot_fn is None:
        _snapshot_fn = jax.jit(lambda t: t)  # identity jit = fresh buffers
    return _snapshot_fn(tree)


def gather_to_host(tree: Any, mesh: Mesh) -> Any:
    """Fetch a pytree of (possibly cross-process sharded) arrays to host."""
    if jax.process_count() == 1:
        return jax.device_get(tree)
    return jax.device_get(gather_replicated(tree, mesh))


def reshard(x: Any, sharding: NamedSharding) -> jax.Array:
    """Re-lay-out an already-device-resident array (CheckpointData cache
    slices) onto `sharding` — an on-device transfer, never host-bounced
    (unlike `put_sharded`, which assembles from host rows per process)."""
    return jax.device_put(x, sharding)


def put_tree(tree: Any, shardings: Any) -> Any:
    """device_put every leaf onto its matching sharding (cold path: state
    init).  Hot-loop modules use this instead of raw `jax.device_put` —
    scripts/lint.py keeps transfers inside bridge.py/prefetch.py."""
    return jax.tree_util.tree_map(jax.device_put, tree, shardings)


def put_like(new: Any, old: Any, mesh: Optional[Mesh] = None) -> Any:
    """Place `new` with `old`'s sharding (checkpoint restore: host values
    re-committed onto the live state's layout); passthrough when `old`
    carries no sharding (plain host leaves).  With `mesh`, leaves whose
    live sharding is single-device (uncommitted scalars such as optax
    step counters) are committed mesh-replicated instead: copying the
    single-device placement would pin them to the default device, which
    a jitted step rejects when the mesh is a strict subset of the
    process's devices (elastic resume onto fewer chips)."""
    if not hasattr(old, "sharding"):
        return new
    sharding = old.sharding
    if mesh is not None and isinstance(sharding,
                                       jax.sharding.SingleDeviceSharding):
        sharding = replicated(mesh)
    return jax.device_put(new, sharding)


def put_tree_like(new_tree: Any, like_tree: Any,
                  mesh: Optional[Mesh] = None) -> Any:
    """Reshard-on-restore: commit a host pytree onto the shardings of a
    live tree built for the CURRENT mesh.  Checkpoints store gathered
    (full logical shape) arrays, so their global shapes are
    device-count-independent — a state saved under dp=N lands correctly
    on an M-device mesh because the target layout comes from the live
    state, never from the file (elastic resume, train/trainer.py).
    `mesh` promotes single-device leaves to mesh-replicated (put_like)."""
    return jax.tree_util.tree_map(lambda n, o: put_like(n, o, mesh),
                                  new_tree, like_tree)


def replicate_tree(tree: Any, mesh: Mesh) -> Any:
    """Replicate a pytree (model weights) across the mesh."""
    sharding = replicated(mesh)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding), tree)


def place_weights(variables: Any, mesh: Optional[Mesh] = None,
                  partition_rules: Optional[Sequence] = None, *,
                  replicate_only: bool = False) -> Any:
    """Put a weight tree on the device(s) ONCE, for every later jitted
    call to take as it is: off-mesh each leaf goes to the default device
    (a plain `jax.device_put`, dtype kept; no mesh is built, so the
    decode engine's `mesh is None` choices stand), on a data-only mesh
    the tree is replicated, and with a model axis > 1 it is sharded by
    `partition_rules` (None = DEFAULT_RULES; unmatched leaves replicate).
    `replicate_only` keeps a small tree (a speculative draft) whole on
    every device of any mesh.  A host tree handed to a jitted call
    instead is uploaded again on every call."""
    if mesh is None:
        return jax.tree_util.tree_map(jax.device_put, variables)
    if mesh.shape.get(MODEL_AXIS, 1) > 1 and not replicate_only:
        from mmlspark_tpu.parallel.partition import (UNMATCHED_REPLICATE,
                                                     shard_tree)
        return shard_tree(variables, mesh, partition_rules,
                          on_unmatched=UNMATCHED_REPLICATE)
    return replicate_tree(variables, mesh)


def stack_trees(trees: Sequence[Any]) -> Any:
    """Stack N structurally-identical host pytrees on a new leading
    population axis (train/sweep.py: member param/opt trees become ONE
    tree whose leaves carry shape (N, ...), so a single vmapped step
    trains every member).  Host-side by design — stacking happens once
    at init/restore, before the tree is committed to devices."""
    if not trees:
        raise ValueError("stack_trees needs at least one tree")
    return jax.tree_util.tree_map(
        lambda *leaves: np.stack([np.asarray(l) for l in leaves]), *trees)


def unstack_member(tree: Any, k: int) -> Any:
    """Slice member `k` out of a population-stacked pytree, returning
    host arrays of the member's unstacked shapes (the sweep winner's
    tree, ready for an ordinary ModelBundle)."""
    return jax.tree_util.tree_map(
        lambda leaf: np.asarray(jax.device_get(leaf))[k], tree)


def device_to_host(x: Any, valid: Optional[int] = None) -> np.ndarray:
    """Fetch a (possibly sharded) device array back to host, trimming padding."""
    arr = np.asarray(jax.device_get(x))
    if valid is not None:
        arr = arr[:valid]
    return arr
