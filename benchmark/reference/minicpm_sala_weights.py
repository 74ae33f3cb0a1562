"""Weights from the seed for the MiniCPM-SALA reference's tree
(`minicpm_sala.shapes_for`): one jitted call makes the whole tree on the
device in float32, every leaf drawn by the rule of its name and of its
layer's kind, so that no part of the model is switched off and the
selection of a `minicpm4` layer decides something:

  embed        normal / 12: `scale_emb` brings the stream to order 1
  head         normal * 16 / sqrt(d): the logits come out of order 1 after
               the division by d / `dim_model_base` = 16
  *_norm       1 + 0.1 normal; but q_norm and k_norm of a `minicpm4` layer
               sqrt(3) (1 + 0.1 normal): q . k / sqrt(D) of two normalized
               heads has the standard deviation g_q g_k, so the attention
               logits' is near 3.  (At 1 a softmax over 6,000-30,000 random
               keys is nearly flat, every block carries the same share and
               reading any 97 of them gives the same answer.)
  any other    a matrix (in, out): normal / sqrt(in).  (A `minicpm4`
               layer's wo at twice that was tried, to weigh its output in
               the stream as a `lightning-attn` layer's: the blocks that
               bfloat16 selects otherwise than float32 then cost a served
               token up to 0.117 of logit where the float8 control starts
               at 0.186; plain, 0.032 where it starts at 0.155: PERF.md
               section 2.)
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.weights import seed_key


def _leaf(name: str, sparse: bool, key, shape) -> jax.Array:
    normal = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("_norm"):
        gain = math.sqrt(3.0) if sparse and name in ("q_norm",
                                                     "k_norm") else 1.0
        return gain * (1.0 + 0.1 * normal)
    if name == "embed":
        return normal / 12.0
    if name == "head":
        return normal * 16.0 / math.sqrt(shape[0])
    if len(shape) == 2:
        return normal / math.sqrt(shape[0])
    raise ValueError(f"no rule for a leaf called {name!r} of shape {shape}")


def make_variables(shapes, seed: int):
    """`shapes`: the tree `minicpm_sala.shapes_for` gives.  Returns the
    same tree of float32 device arrays."""
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [str(path[-1].key) for path, _ in paths_leaves]
    dims = [tuple(leaf.shape) for _, leaf in paths_leaves]
    # a layer without an output norm is a `minicpm4` layer
    sparse = [len(path) == 3 and "o_norm" not in shapes["params"][
        str(path[1].key)] for path, _ in paths_leaves]

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(names))
        return [_leaf(n, sp, k, s)
                for n, sp, k, s in zip(names, sparse, keys, dims)]

    return jax.tree_util.tree_unflatten(treedef, build(seed_key(seed)))
