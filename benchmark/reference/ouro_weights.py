"""Weights from the seed for the Ouro reference's tree (`ouro.shapes_for`):
one jitted call makes the whole tree on the device in float32, every leaf
drawn by the rule of its name, so that no part of the model is switched
off and a wrong window, mask or pass moves the logits:

  *_norm     1 + 0.1 normal: the four gains of a layer and the final norm
             all differ from 1, so a norm left out or moved shows
  embed      normal: the stream starts at rms 1, the size of what each
             post-normed sub-layer adds to it
  wq, wk     normal * sqrt(2 / d): without a norm over q and k, q . k /
             sqrt(D) of a normalized input then has a standard deviation
             near 2.  (At normal / sqrt(d) it is 1, and a softmax over
             several hundred random keys is flat enough that reading
             another pass's window would move the logits by little.)
  exit_w     normal * 0.5 / sqrt(d), exit_b 0.2 normal: the gate's logit
             has a standard deviation near 0.5, so g_t lies in 0.2-0.8
             and the expected exit pass strictly between 1 and R
  any other  a matrix (in, out): normal / sqrt(in); the untied head's
             logits come out of order 1
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
        return normal
    if name == "exit_w":
        return normal * 0.5 / math.sqrt(shape[0])
    if name == "exit_b":
        return 0.2 * normal
    if name in ("wq", "wk"):
        return normal * math.sqrt(2.0 / shape[0])
    if len(shape) == 2:
        return normal / math.sqrt(shape[0])
    raise ValueError(f"no rule for a leaf called {name!r} of shape {shape}")


def make_variables(shapes, seed: int):
    """`shapes`: the tree `ouro.shapes_for` gives.  Returns the same tree
    of float32 device arrays."""
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [str(path[-1].key) for path, _ in paths_leaves]
    dims = [tuple(leaf.shape) for _, leaf in paths_leaves]

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(names))
        return [_leaf(n, k, s) for n, k, s in zip(names, keys, dims)]

    return jax.tree_util.tree_unflatten(treedef, build(seed_key(seed)))
