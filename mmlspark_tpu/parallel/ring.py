"""Sequence-parallel execution: shard_map wrappers + LM train step.

The distributed face of ops/attention.py: sequences too long for one
device's HBM shard over the mesh `seq` axis; ring attention rotates K/V
blocks over ICI neighbor links (ppermute — the bandwidth-optimal pattern
for this topology) while Ulysses trades two all-to-alls for local dense
attention.  Everything composes with data parallelism: batch over `data`,
sequence over `seq`, weights replicated (TP composes via the trainer's
kernel sharding rule).

The reference has no analogue (SURVEY §5 "long-context: absent") — this is
the first-class long-context support the TPU build adds.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, PartitionSpec as P

from mmlspark_tpu.ops.attention import attention, ring_attention, ulysses_attention
from mmlspark_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS
from mmlspark_tpu.parallel.partition import named_sharding

def _shard_map(fn, *, mesh, in_specs, out_specs):
    """`shard_map` with replication checking off — the repo-wide seam.

    The replication checker has no rule for `checkpoint_name` (the remat
    tag the seq-parallel LM forward emits) or `pallas_call` (the flash
    kernel ring_flash rotates), so every sharded region here runs
    unchecked: out_specs state the replication facts the checker would
    otherwise verify.  With checking off, array types inside the region
    carry no varying-manual-axes either (ops/flash_attention
    `_in_manual_region` reads False there).
    """
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def seq_parallel_attention(mesh: Mesh, q, k, v, causal: bool = False,
                           impl: str = "ring",
                           data_axis: str = DATA_AXIS,
                           seq_axis: str = SEQ_AXIS):
    """Attention over (B, S, H, D) arrays with S sharded over `seq_axis`.

    A standalone entry point for scoring paths; training integrates via
    make_seq_parallel_lm_step (the model's attention runs inside the same
    shard_map region as the loss).
    """
    if impl == "ring":
        fn = functools.partial(ring_attention, axis_name=seq_axis,
                               causal=causal)
    elif impl == "ring_flash":
        from mmlspark_tpu.ops.attention import ring_flash_attention
        fn = functools.partial(ring_flash_attention, axis_name=seq_axis,
                               causal=causal)
    elif impl == "ulysses":
        fn = functools.partial(ulysses_attention, axis_name=seq_axis,
                               causal=causal)
    elif impl == "dense":
        # all-gather the sequence axis; correctness fallback
        def fn(ql, kl, vl):
            kg = jax.lax.all_gather(kl, seq_axis, axis=1, tiled=True)
            vg = jax.lax.all_gather(vl, seq_axis, axis=1, tiled=True)
            start = jax.lax.axis_index(seq_axis) * ql.shape[1]
            return attention(ql, kg, vg, causal=causal, q_offset=start)
    else:
        raise ValueError(f"unknown seq-parallel impl '{impl}'")

    spec = P(data_axis, seq_axis, None, None)
    wrapped = _shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec)
    return wrapped(q, k, v)


def make_seq_parallel_lm_step(module, tx: optax.GradientTransformation,
                              mesh: Mesh,
                              data_axis: str = DATA_AXIS,
                              seq_axis: str = SEQ_AXIS,
                              remat: bool = False) -> Callable:
    """Build a jitted LM train step with batch over `data` and sequence
    over `seq`.

    The whole loss runs inside one shard_map region: the module (a
    TransformerLM with attn='ring'|'ulysses' and seq_axis set) computes
    ring attention with the axis in scope, per-token losses are averaged
    with psum over both axes, and jax.grad differentiates straight through
    the collectives (ppermute/psum have registered transposes).  Params
    and optimizer state stay replicated.

    `remat=True` turns on block-boundary activation rematerialization
    (the module's own `remat` field — each TransformerBlock recomputes its
    activations in the backward): inside the ring loop that is the 32k+
    story, since the per-fold score blocks are what blow HBM at long
    S_local.  The `checkpoint_name` tags this emits inside the sharded
    region are exactly why `_shard_map` runs with replication checking
    off.
    """
    if remat and getattr(module, "remat", None) is False:
        module = module.clone(remat=True)

    def local_loss(params, tokens, targets, mask):
        logits = module.apply(params, tokens)          # (b_l, s_l, V)
        ll = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), targets)
        total = jax.lax.psum((ll * mask).sum(), (data_axis, seq_axis))
        denom = jax.lax.psum(mask.sum(), (data_axis, seq_axis))
        return total / jnp.maximum(denom, 1.0)

    tok_spec = P(data_axis, seq_axis)

    sharded_loss = _shard_map(
        local_loss, mesh=mesh,
        in_specs=(P(), tok_spec, tok_spec, tok_spec),
        out_specs=P())

    @jax.jit
    def step(params, opt_state, tokens, targets, mask):
        loss, grads = jax.value_and_grad(
            lambda p: sharded_loss(p, tokens, targets, mask))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step


def shard_tokens(tokens: np.ndarray, mesh: Mesh,
                 data_axis: str = DATA_AXIS,
                 seq_axis: str = SEQ_AXIS) -> jax.Array:
    """Place (B, S) token arrays with B over data, S over seq.

    Placement routes through `parallel/partition.named_sharding` — the
    one sanctioned NamedSharding construction seam (scripts/lint.py)."""
    return jax.device_put(
        tokens, named_sharding(mesh, P(data_axis, seq_axis)))
