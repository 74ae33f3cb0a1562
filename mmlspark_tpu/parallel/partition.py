"""Partition-rule registry: regex over param-tree paths -> PartitionSpec.

The tensor-parallel layer of the mesh story (docs/parallelism.md): where
`parallel/mesh.py` names the axes and `parallel/bridge.py` moves bytes,
this module decides WHICH axis each weight lives on.  A rule set is an
ordered sequence of ``(regex, PartitionSpec)`` pairs matched against the
'/'-joined path of every param-tree leaf — first match wins, exactly the
fmengine/fmtrainer `match_partition_rules` contract:

    >>> match_partition_rules({"mlp_up": {"kernel": w}})  # DEFAULT_RULES
    {'mlp_up': {'kernel': PartitionSpec(None, 'model')}}

Invariants (test-pinned in tests/test_partition.py):

  * scalar / size-1 leaves are NEVER sharded, whatever the rules say —
    a PartitionSpec over a scalar is meaningless and GSPMD rejects it;
  * rank-1 ``bias`` leaves are never sharded (the per-shard bias add is
    already free under any activation layout);
  * int8 ``kernel_scale`` leaves (quant/quantize.py layout) follow their
    kernel's OUTPUT-channel spec — a column-parallel kernel's scales ride
    the same axis, a row-parallel kernel's scales replicate;
  * an unmatched leaf follows the explicit ``on_unmatched`` policy:
    ``"raise"`` (the default — silent replication of a tensor you meant
    to shard is how HBM blows up at scale) or ``"replicate"``.

This module is also the ONE place `with_sharding_constraint` /
`NamedSharding` construction is allowed to live (scripts/lint.py forbids
both outside `parallel/`, the same seam as the bridge/device_put rule):
model code states WHERE a value should live via `shard_constraint(x,
spec)` and the mesh in scope decides whether that means anything — on a
1-D (or absent) mesh the hint is a no-op, so forwards stay portable.
"""

from __future__ import annotations

import contextlib
import re
import threading
from typing import Any, Iterable, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mmlspark_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, SEQ_AXIS

# -- rule sets ---------------------------------------------------------------

# One rule: (regex searched over the '/'-joined tree path, spec).
Rule = tuple[str, P]

UNMATCHED_RAISE = "raise"
UNMATCHED_REPLICATE = "replicate"

# The TransformerLM layout (models/definitions.py param names), per the
# standard Megatron split: column-parallel producers (qkv, mlp_up, lm_head)
# shard their OUTPUT channels over "model" so each chip computes its own
# heads / hidden slice; row-parallel consumers (proj, mlp_down) shard their
# INPUT channels so the activation never re-gathers between the pair (one
# psum at the block boundary, inserted by GSPMD).  Expert stacks (E, D, H)
# shard the expert axis — expert parallelism through the same registry.
# Embeddings, norms, the MoE router, and everything unnamed replicate.
DEFAULT_RULES: tuple = (
    (r"(qkv|mlp_up|lm_head)/kernel$", P(None, MODEL_AXIS)),
    (r"(proj|mlp_down)/kernel$", P(MODEL_AXIS, None)),
    (r"moe/(w_in|w_out)$", P(MODEL_AXIS, None, None)),
    (r".*", P()),
)

# Activation/cache hints for the transformer forward (shard_constraint
# call sites in models/definitions.py and models/generate.py): attention
# tensors carry heads on "model" at axis 2 of (B, S, H, D); the MLP hidden
# carries its channel slice on "model"; the decode KV cache (B, W, H, D)
# keeps batch on "data" and heads on "model" so every segment/merge
# program preserves the layout.
HEADS_SPEC = P(DATA_AXIS, None, MODEL_AXIS, None)
HIDDEN_SPEC = P(DATA_AXIS, None, MODEL_AXIS)
KV_CACHE_SPEC = P(DATA_AXIS, None, MODEL_AXIS, None)
KV_SCALE_SPEC = P(DATA_AXIS, None, MODEL_AXIS)

# Speculative decoding (models/generate.py): the DRAFT model's cache rides
# the data axis only — a draft sized for low latency rarely has a head
# count the mesh's model axis divides, and its whole forward is a
# rounding error next to the target's, so replicating its heads costs
# nothing while keeping the verify program (which runs the TARGET layout
# above) free to shard.  Draft params replicate for the same reason.
DRAFT_KV_CACHE_SPEC = P(DATA_AXIS, None, None, None)
DRAFT_KV_SCALE_SPEC = P(DATA_AXIS, None, None)

# Sequence-sharded decode (models/generate.py seq path): the KV cache's
# WINDOW axis splits over "seq" — each chip owns a contiguous slab of
# cache slots, the decode step merges per-shard softmax statistics
# (ops/attention.merge_attention_stats) instead of gathering the window.
# Heads stay unsharded: the seq engine path refuses model>1 meshes, so
# naming MODEL_AXIS here would only demote on the meshes that reach it.
SEQ_KV_CACHE_SPEC = P(DATA_AXIS, SEQ_AXIS, None, None)
SEQ_KV_SCALE_SPEC = P(DATA_AXIS, SEQ_AXIS, None)


def path_str(path: Sequence) -> str:
    """'/'-joined form of a jax tree_map_with_path key path — the string
    the rule regexes are matched against."""
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(parts)


_path_str = path_str  # internal alias (pre-public-name call sites)


def _axes_of(spec: P) -> set:
    axes = set()
    for entry in spec:
        if entry is None:
            continue
        for a in (entry if isinstance(entry, (tuple, list)) else (entry,)):
            axes.add(a)
    return axes


def _match(path: str, rules: Sequence[Rule], on_unmatched: str) -> P:
    for pattern, spec in rules:
        if re.search(pattern, path):
            return spec
    if on_unmatched == UNMATCHED_REPLICATE:
        return P()
    raise ValueError(
        f"no partition rule matched param path {path!r} "
        f"(on_unmatched='raise'; add a rule or a catch-all ('.*', P()))")


def leaf_spec(path: str, shape: Sequence[int], rules: Sequence[Rule],
              on_unmatched: str = UNMATCHED_RAISE) -> P:
    """The spec for ONE leaf: scalar/bias invariants first, then rules."""
    shape = tuple(int(s) for s in shape)
    if len(shape) == 0 or int(np.prod(shape)) == 1:
        return P()  # scalar leaves are always unsharded
    name = path.rsplit("/", 1)[-1]
    if name == "bias" and len(shape) == 1:
        return P()  # 1-D biases are never sharded
    if name.endswith("_scale"):
        # int8 kernel_scale (out,) follows its kernel's output-channel
        # axis: the last entry of the kernel's spec (quant/quantize.py
        # stores one scale per output channel, so a column-parallel
        # kernel's scales shard with it; row-parallel scales replicate)
        kernel_spec = _match(path[:-len("_scale")], rules, on_unmatched)
        last = kernel_spec[-1] if len(kernel_spec) else None
        return P(last) if last is not None else P()
    return _match(path, rules, on_unmatched)


def match_partition_rules(tree: Any, rules: Optional[Sequence[Rule]] = None,
                          *, on_unmatched: str = UNMATCHED_RAISE) -> Any:
    """A spec pytree (same structure as `tree`), first matching rule wins.

    `tree` leaves may be arrays or anything with a ``.shape`` (live jax
    Arrays, ShapeDtypeStructs, numpy) — only shapes are read.
    """
    if on_unmatched not in (UNMATCHED_RAISE, UNMATCHED_REPLICATE):
        raise ValueError(
            f"on_unmatched must be 'raise' or 'replicate', got "
            f"{on_unmatched!r}")
    rule_list = tuple(DEFAULT_RULES if rules is None else rules)
    for pattern, spec in rule_list:
        re.compile(pattern)  # surface a bad regex at the call site
        if not isinstance(spec, P):
            raise TypeError(f"rule for {pattern!r} must map to a "
                            f"PartitionSpec, got {type(spec).__name__}")

    def assign(path, leaf):
        shape = getattr(leaf, "shape", None)
        if shape is None:
            shape = np.shape(leaf)
        return leaf_spec(_path_str(path), shape, rule_list, on_unmatched)

    return jax.tree_util.tree_map_with_path(assign, tree)


def compatible_spec(spec: P, shape: Sequence[int],
                    mesh: Optional[Mesh]) -> P:
    """Demote `spec` to P() when `shape` cannot actually be tiled by it.

    A spec longer than the leaf's rank, or naming a mesh axis whose size
    does not divide the corresponding dim (or that the mesh lacks), would
    be a GSPMD error — the rule registry describes the flagship layout,
    but scoring/restore must also accept trees the rules were not written
    for (conv models, odd vocab sizes).  Demotion to replicated is always
    correct, merely less parallel.
    """
    shape = tuple(shape)
    if len(spec) == 0:
        return spec
    if mesh is None or len(spec) > len(shape):
        return P()
    for dim, entry in zip(shape, spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        size = 1
        for a in axes:
            if a not in mesh.shape:
                return P()
            size *= mesh.shape[a]
        if size and dim % size:
            return P()
    return spec


# -- NamedSharding construction (the sanctioned site) ------------------------

def named_sharding(mesh: Mesh, spec: P = P()) -> NamedSharding:
    """Construct a NamedSharding — the one allowed construction site
    outside mesh.py (scripts/lint.py keeps raw construction in parallel/)."""
    return NamedSharding(mesh, spec)


def tree_shardings(mesh: Mesh, tree: Any,
                   rules: Optional[Sequence[Rule]] = None, *,
                   on_unmatched: str = UNMATCHED_RAISE) -> Any:
    """NamedSharding pytree for `tree` under `rules` — specs demoted per
    leaf shape (compatible_spec), so the result is always placeable."""
    specs = match_partition_rules(tree, rules, on_unmatched=on_unmatched)

    def build(leaf, spec):
        shape = getattr(leaf, "shape", None) or np.shape(leaf)
        return NamedSharding(mesh, compatible_spec(spec, shape, mesh))

    return jax.tree_util.tree_map(build, tree, specs)


def make_shard_fns(mesh: Mesh, specs: Any) -> Any:
    """Per-leaf placement callables from a spec pytree (the fmengine
    `make_shard_and_gather_fns` shard half): each fn device_puts its leaf
    onto the mesh under its (shape-validated) spec."""

    def one(spec):
        def put(x):
            s = compatible_spec(spec, np.shape(x), mesh)
            return jax.device_put(x, NamedSharding(mesh, s))
        return put

    return jax.tree_util.tree_map(one, specs,
                                  is_leaf=lambda s: isinstance(s, P))


def make_gather_fns(mesh: Mesh, specs: Any) -> Any:
    """Per-leaf gather callables: sharded leaf -> full host np.ndarray.

    The checkpoint/bundle-save direction — gathered arrays carry their
    full logical shape, so what lands on disk is topology-portable
    (restore re-commits onto whatever mesh is live via
    bridge.put_tree_like).  Under multi-host the identity jit with
    replicated out_shardings performs the all-gather; single-process
    arrays are fully addressable and fetch directly.
    """
    rep = NamedSharding(mesh, P())

    def one(_spec):
        def gather(x):
            if isinstance(x, jax.Array) and not x.is_fully_addressable:
                x = jax.jit(lambda t: t, out_shardings=rep)(x)
            return np.asarray(jax.device_get(x))
        return gather

    return jax.tree_util.tree_map(one, specs,
                                  is_leaf=lambda s: isinstance(s, P))


def shard_tree(tree: Any, mesh: Mesh,
               rules: Optional[Sequence[Rule]] = None, *,
               on_unmatched: str = UNMATCHED_RAISE) -> Any:
    """Place a host pytree onto the mesh per the rule set (convenience
    over match_partition_rules + make_shard_fns)."""
    specs = match_partition_rules(tree, rules, on_unmatched=on_unmatched)
    fns = make_shard_fns(mesh, specs)
    return jax.tree_util.tree_map(lambda f, x: f(x), fns, tree)


def gather_tree(tree: Any, mesh: Mesh) -> Any:
    """Gather a (possibly sharded) pytree to full host arrays."""
    specs = jax.tree_util.tree_map(lambda _: P(), tree)
    fns = make_gather_fns(mesh, specs)
    return jax.tree_util.tree_map(lambda f, x: f(x), fns, tree)


# -- rule-set serialization (ModelBundle metadata round-trip) ----------------

def rules_to_json(rules: Sequence[Rule]) -> list:
    """JSON-able form: [[pattern, [axis|null|[axis,...], ...]], ...]."""
    out = []
    for pattern, spec in rules:
        entries = []
        for entry in spec:
            if isinstance(entry, (tuple, list)):
                entries.append(list(entry))
            else:
                entries.append(entry)
        out.append([pattern, entries])
    return out


def rules_from_json(data: Iterable) -> tuple:
    """Inverse of rules_to_json; tolerates JSON's lists-for-tuples."""
    rules = []
    for pattern, entries in data:
        spec_entries = [tuple(e) if isinstance(e, list) else e
                        for e in entries]
        rules.append((str(pattern), P(*spec_entries)))
    return tuple(rules)


# -- activation sharding hints (the sanctioned constraint site) --------------

_local = threading.local()


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    """Make `mesh` the target of shard_constraint hints traced inside.

    Wrapped around jit DISPATCH sites (Trainer step, TPUModel apply,
    DecodeEngine segments): tracing happens inside the first call, so the
    hints bake this mesh into that mesh's compiled program.  None is a
    no-op context (hints fall back to any ambient `jax.set_mesh` scope).
    """
    if mesh is None:
        yield None
        return
    stack = getattr(_local, "mesh_stack", None)
    if stack is None:
        stack = _local.mesh_stack = []
    stack.append(mesh)
    try:
        yield mesh
    finally:
        stack.pop()


def active_mesh():
    """The mesh shard_constraint hints currently target: the innermost
    use_mesh scope, else jax's ambient `jax.set_mesh` scope (seen as its
    AbstractMesh — the only form readable while tracing), else None."""
    stack = getattr(_local, "mesh_stack", None)
    if stack:
        return stack[-1]
    ambient = jax.sharding.get_abstract_mesh()
    return None if ambient.empty else ambient


def shard_constraint(x: Any, spec: P) -> Any:
    """`with_sharding_constraint` that degrades to identity off-mesh.

    The ONE sanctioned constraint call site (scripts/lint.py): forwards
    state where a value should live, and the mesh in scope decides what
    that means.  No active mesh, a mesh lacking the named axes, a
    shard_map region that holds them manually, or a shape the spec cannot
    tile -> the value passes through untouched, so the same module code
    runs on a laptop CPU and a dp x mp slice.
    """
    mesh = active_mesh()
    if mesh is None:
        return x
    axes = _axes_of(spec)
    if not axes or not axes.issubset(set(mesh.axis_names)):
        return x
    if axes & set(jax.sharding.get_abstract_mesh().manual_axes):
        return x  # inside shard_map the region's specs own these axes
    s = compatible_spec(spec, np.shape(x), mesh)
    if len(s) == 0 and len(spec) != 0:
        return x  # demoted: the hint cannot tile this shape on this mesh
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s))


def expert_constraint(x: Any, axis: str) -> Any:
    """MoE dispatch hint: expert-major slabs live on the expert axis
    (ops/moe.py's slot tensor) — axis-name form of shard_constraint."""
    return shard_constraint(x, P(axis))
