"""Weights from the seed for the LFM2-MoE reference's tree
(`lfm2_moe.shapes_for`): one jitted call makes the whole tree on the
device in float32, every leaf drawn by the rule of its name, so that no
part of the model is switched off:

  *_norm       1 + 0.1 normal
  embed        normal / sqrt(d): tied to the head, so logits come out of
               order 1 (normalized hidden states against rows of norm 1)
  expert_bias  0.1 normal: drawn, never zero, so that the choice (s + b)
               and the weights (s) differ
  conv_taps    normal / sqrt(K)
  any other    a matrix or a stack of matrices (E, in, out): normal /
               sqrt(in).  An expert stack's fan-in is ONE expert's (2048
               or 1792), not E x that: with the product of all axes but
               the last, as `weights.py` has it, every expert's output
               would shrink by sqrt(E) and the experts vanish from the
               comparison.  The router's logits (normalized input, fan-in
               d) come out of order 1.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.weights import seed_key


def _leaf(name: str, key, shape) -> jax.Array:
    normal = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("_norm"):
        return 1.0 + 0.1 * normal
    if name == "embed":
        return normal / math.sqrt(shape[-1])
    if name == "expert_bias":
        return 0.1 * normal
    if name == "conv_taps":
        return normal / math.sqrt(shape[0])
    if len(shape) in (2, 3):
        return normal / math.sqrt(shape[-2])
    raise ValueError(f"no rule for a leaf called {name!r} of shape {shape}")


def make_variables(shapes, seed: int):
    """`shapes`: the tree `lfm2_moe.shapes_for` gives.  Returns the same
    tree of float32 device arrays."""
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [str(path[-1].key) for path, _ in paths_leaves]
    dims = [tuple(leaf.shape) for _, leaf in paths_leaves]

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(names))
        return [_leaf(n, k, s) for n, k, s in zip(names, keys, dims)]

    return jax.tree_util.tree_unflatten(treedef, build(seed_key(seed)))
