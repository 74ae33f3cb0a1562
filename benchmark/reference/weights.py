"""Weights from the seed: one jitted call makes the whole tree on the
device, in float32 (the type both programs hold their parameters in).

The tree's structure (names and shapes) is given by the caller, who gets
it from `jax.eval_shape` of the program's `init`: no program code runs.
Every leaf is drawn by the rule of its last path name, so that no part of
a model is switched off (a zero-initialised BatchNorm scale would hide a
whole residual branch from the comparison):

  kernel     normal * sqrt(gain / fan_in), fan_in = product of all axes
             but the last; gain 2 under ReLU (4-d conv kernels), else 1
  embedding  normal
  scale      1 + 0.1 normal          bias, mean   0.1 normal
  var        uniform in [0.8, 1.2]
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number up to 2**63 (the driver's seeds
    pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _leaf(name: str, key, shape) -> jax.Array:
    normal = lambda: jax.random.normal(key, shape, jnp.float32)
    if name == "kernel":
        fan_in = math.prod(shape[:-1])
        gain = 2.0 if len(shape) == 4 else 1.0
        return normal() * math.sqrt(gain / fan_in)
    if name == "embedding":
        return normal()
    if name == "scale":
        return 1.0 + 0.1 * normal()
    if name in ("bias", "mean"):
        return 0.1 * normal()
    if name == "var":
        return jax.random.uniform(key, shape, jnp.float32, 0.8, 1.2)
    raise ValueError(f"no rule for a leaf called {name!r}")


def make_variables(shapes, seed: int):
    """`shapes`: a pytree of objects with `.shape` (dict of dicts down to
    leaves).  Returns the same tree of float32 device arrays."""
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [str(path[-1].key) for path, _ in paths_leaves]
    dims = [tuple(leaf.shape) for _, leaf in paths_leaves]

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(names))
        return [_leaf(n, k, s) for n, k, s in zip(names, keys, dims)]

    return jax.tree_util.tree_unflatten(treedef, build(seed_key(seed)))
