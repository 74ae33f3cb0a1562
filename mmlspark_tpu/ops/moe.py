"""Mixture-of-Experts with expert parallelism (EP) over a mesh axis.

New-design headroom over the reference (which has no sparse/conditional
compute at all — SURVEY §2b): a Switch-style top-k MoE MLP.  Expert
parallelism follows the GSPMD recipe rather than hand-written collectives:
the stacked expert weights (E, D, H) are sharded over a mesh axis
(`expert_parallel_rules`), the dispatched slot tensor carries a matching
sharding constraint, and XLA inserts the all_to_all / all_gather traffic —
the "annotate shardings, let the compiler place collectives" discipline the
rest of the framework uses for TP/DP.

Design for XLA: everything is static-shape.  Routing uses the classic
dispatch/combine one-hot formulation (einsum-only — no gather/scatter, no
dynamic shapes) applied PER TOKEN GROUP, the Mesh-TF/GShard convention:
tokens are split into fixed groups of at most `group_size`, each group
routes independently with per-expert capacity
`C = ceil(G / E * capacity_factor * k)`, and tokens beyond an expert's
capacity within their group are dropped (their residual stream passes
through unchanged).  Grouping bounds the dispatch/combine tensors at
~`capacity_factor * k * T * group_size` float32 elements — LINEAR in the
token count T, where ungrouped routing would cost
`capacity_factor * T^2` (multiple GB per layer at long-context scale).

Observability: the router sows three values —

  * `"losses" / "moe_aux_loss"`: the Switch load-balance term
    `E * Σ_e f_e · p_e` (f = choice-1 dispatch frequency, p = mean router
    probability), to be weighted into the objective
    (TrainerConfig.aux_loss_weight);
  * `"losses" / "moe_z_loss"`: the router z-loss
    `z_loss_weight * mean(logsumexp(logits)^2)` — PRE-SCALED by
    `z_loss_weight` so the trainer's single aux_loss_weight knob applies
    to the sum of sown losses;
  * `"metrics" / "moe_overflow_fraction"`: the fraction of routing slots
    dropped by capacity this step, so capacity collapse is visible in
    training history instead of silently degrading quality.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from mmlspark_tpu.parallel.mesh import MODEL_AXIS


def topk_dispatch(router_logits: jax.Array, capacity: int, k: int = 1):
    """(dispatch (T,E,C), combine (T,E,C), aux_loss, z_loss, kept_fraction)
    from one group's router logits (T, E).

    float32 routing throughout (softmax statistics must not ride bf16).
    All j-th choices queue behind every (j-1)-th choice in an expert's
    capacity buffer (the GShard priority rule); within a choice, slots
    fill in token order (the deterministic Switch tie-break).  `combine`
    scales by the router gate — raw for k=1 (Switch), normalized over the
    k chosen gates for k>1 (GShard) — so
    `y = combine^T · expert(dispatch · x)` is the MoE forward.
    """
    t, e = router_logits.shape
    logits32 = router_logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits32, axis=-1)

    remaining = probs
    counts = jnp.zeros((e,), jnp.float32)    # slots consumed per expert
    parts = []                               # (onehot, gate, pos_value)
    for _ in range(k):
        expert_idx = jnp.argmax(remaining, axis=-1)            # (T,)
        onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)
        gate = jnp.take_along_axis(probs, expert_idx[:, None], 1)[:, 0]
        pos = (jnp.cumsum(onehot, axis=0) - 1.0 + counts[None, :])
        pos_val = (pos * onehot).sum(-1)                       # (T,)
        parts.append((onehot, gate, pos_val))
        counts = counts + onehot.sum(0)
        remaining = remaining * (1.0 - onehot)  # mask chosen for next choice

    if k > 1:
        denom = sum(g for _, g, _ in parts) + 1e-9
        parts = [(oh, g / denom, pv) for (oh, g, pv) in parts]

    dispatch = jnp.zeros((t, e, capacity), jnp.float32)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    kept_slots = 0.0
    for onehot, gate, pos_val in parts:
        within = (pos_val < capacity) & (pos_val >= 0)
        pos_oh = jax.nn.one_hot(pos_val.astype(jnp.int32), capacity,
                                dtype=jnp.float32)
        d_j = (onehot * within[:, None])[:, :, None] * pos_oh[:, None, :]
        dispatch = dispatch + d_j
        combine = combine + d_j * gate[:, None, None]
        kept_slots = kept_slots + d_j.sum()

    # load balance on choice-1 frequencies (the Switch definition)
    f = parts[0][0].mean(axis=0)
    p = probs.mean(axis=0)
    aux = e * jnp.sum(f * p)
    z = jnp.mean(jax.scipy.special.logsumexp(logits32, axis=-1) ** 2)
    kept_fraction = kept_slots / float(t * k)
    return dispatch, combine, aux, z, kept_fraction


def top1_dispatch(router_logits: jax.Array, capacity: int):
    """(dispatch (T,E,C), combine (T,E,C), aux_loss): the Switch top-1
    special case of `topk_dispatch` (kept as the stable one-group API)."""
    dispatch, combine, aux, _, _ = topk_dispatch(router_logits, capacity, 1)
    return dispatch, combine, aux


def _group_size(t: int, target: int) -> int:
    """Largest divisor of t that is <= target (static Python arithmetic —
    shapes stay known to XLA)."""
    target = max(1, min(t, target))
    for g in range(target, 0, -1):
        if t % g == 0:
            return g
    return 1


class MoEMLP(nn.Module):
    """Drop-in MLP replacement: router -> top-k experts -> combine.

    `expert_axis` names the mesh axis the (E, ...) tensors shard over; it
    only places a `with_sharding_constraint` on the slot tensor (harmless
    outside jit/mesh contexts where it is a no-op on CPU tests), the
    weight shardings themselves come from `expert_parallel_rules`.

    `group_size` caps the routing group (tokens route independently per
    group, GShard-style), bounding dispatch memory at
    ~capacity_factor * router_k * T * group_size floats.
    """

    d_model: int
    n_experts: int = 8
    mlp_ratio: int = 4
    capacity_factor: float = 1.25
    dtype: jnp.dtype = jnp.bfloat16
    expert_axis: Optional[str] = None
    group_size: int = 512
    router_k: int = 1                  # 1 = Switch, 2 = GShard top-2
    z_loss_weight: float = 1e-3

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, s, d = x.shape
        t = b * s
        e = self.n_experts
        k = self.router_k
        h = self.mlp_ratio * self.d_model
        gs = _group_size(t, self.group_size)
        g = t // gs
        capacity = max(1, int(np.ceil(gs / e * self.capacity_factor * k)))

        xf = x.reshape(t, d)
        logits = nn.Dense(e, dtype=jnp.float32, name="router")(
            xf.astype(jnp.float32))
        dispatch, combine, aux, z, kept = jax.vmap(
            lambda lg: topk_dispatch(lg, capacity, k))(
            logits.reshape(g, gs, e))
        self.sow("losses", "moe_aux_loss", aux.mean())
        self.sow("losses", "moe_z_loss", self.z_loss_weight * z.mean())
        self.sow("metrics", "moe_overflow_fraction", 1.0 - kept.mean())

        w_in = self.param(
            "w_in", nn.initializers.lecun_normal(), (e, d, h), jnp.float32)
        w_out = self.param(
            "w_out", nn.initializers.lecun_normal(), (e, h, d), jnp.float32)

        xg = xf.reshape(g, gs, d).astype(jnp.float32)
        slots = jnp.einsum("gtec,gtd->egcd", dispatch, xg).astype(self.dtype)
        if self.expert_axis is not None:
            from mmlspark_tpu.parallel.partition import expert_constraint
            slots = expert_constraint(slots, self.expert_axis)
        hmid = nn.relu(jnp.einsum("egcd,edh->egch", slots,
                                  w_in.astype(self.dtype)))
        out = jnp.einsum("egch,ehd->egcd", hmid, w_out.astype(self.dtype))
        y = jnp.einsum("gtec,egcd->gtd", combine, out.astype(jnp.float32))
        return y.astype(x.dtype).reshape(b, s, d)


def is_expert_stack(path, shape, axis_size: int = 1) -> bool:
    """True when a param-tree leaf at `path` with `shape` is a stacked
    expert tensor whose leading (expert) dim can shard over an axis of
    `axis_size` devices.  The ONE predicate shared by
    `expert_parallel_rules` and the Trainer's sharding rule
    (train/trainer.py::_param_sharding_rule), so placement logic cannot
    diverge.  Scoped to leaves living under an MoE module (a path
    component containing "moe"), not bare `w_in`/`w_out` names — an
    unrelated module reusing those names must not get its leading dim
    split across the mesh; and the expert count must divide the axis or
    the leaf falls back to the caller's default placement.
    """
    keys = [p.key if hasattr(p, "key") else str(p) for p in path]
    return (len(shape) == 3
            and bool(keys) and keys[-1] in ("w_in", "w_out")
            and any("moe" in k.lower() for k in keys[:-1])
            and axis_size > 0 and shape[0] % axis_size == 0)


def expert_parallel_rules(params: dict, mesh,
                          axis: str = MODEL_AXIS) -> dict:
    """NamedSharding tree for a param tree containing MoE experts: (E, ...)
    expert tensors shard their leading (expert) dim over `axis`
    (`is_expert_stack` decides what qualifies); everything else
    replicates.  Feed to `jax.device_put` / `jit(in_shardings=...)` —
    XLA then places the EP all_to_all traffic (GSPMD).  Construction goes
    through parallel/partition.py (the sanctioned NamedSharding site).
    """
    from jax.sharding import PartitionSpec as P

    from mmlspark_tpu.parallel.partition import named_sharding

    axis_size = mesh.shape.get(axis, 1)

    def rule(path, leaf):
        if is_expert_stack(path, leaf.shape, axis_size):
            return named_sharding(mesh, P(axis, None, None))
        return named_sharding(mesh, P())

    return jax.tree_util.tree_map_with_path(rule, params)


# --------------------------------------------------------------------------
# Experts as deployed: dropless token-choice routing
# --------------------------------------------------------------------------

def route_tokens(x: jax.Array, router_kernel: jax.Array,
                 router_bias: jax.Array, top_k: int):
    """Token-choice routing with a sigmoid score: `(chosen (T, k) int32,
    weights (T, k) float32, margin (T,) float32)`.

    Scores `s = sigmoid(x W_r)` are float32 (operands and accumulation);
    the `top_k` experts are those with the highest `s + bias` (the bias
    enters the choice only), and a token's weights are its chosen scores
    over their sum (+ 1e-6).  `margin` is the k-th selection score less
    the (k+1)-th: how close the token was to another choice."""
    with jax.named_scope("moe.route"):
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router_kernel.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        ranked, order = jax.lax.top_k(
            scores + router_bias.astype(jnp.float32), top_k + 1)
        chosen = order[:, :top_k]
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
        return chosen, weights, ranked[:, top_k - 1] - ranked[:, top_k]


def routed_experts(x: jax.Array, router_kernel: jax.Array,
                   router_bias: jax.Array, w1: jax.Array, w3: jax.Array,
                   w2: jax.Array, *, top_k: int, dtype=jnp.bfloat16,
                   valid: Optional[jax.Array] = None):
    """Dropless routed gated experts over `(T, d)` tokens: returns
    `(y (T, d), load (E,) float32)`.

    `y_t = sum over the token's top_k experts e of w_te * W2_e (silu(W1_e
    x_t) * W3_e x_t)` with `route_tokens`' choice and weights; `w1`, `w3`
    are stacked `(E, d, w)`, `w2` `(E, w, d)`.  The `T * top_k`
    assignments are sorted by expert and each projection is ONE grouped
    product (`jax.lax.ragged_dot`) over the sorted rows: no token is
    dropped, nothing is padded to a capacity, and a token's output does
    not depend on which other tokens share the call (each row meets its
    own experts' weights only).  The same function serves a prefill's
    thousands of tokens and a decode step's handful of rows.

    `load` counts the assignments each expert received, over the tokens
    `valid` marks (all, when None): what the serving counters read."""
    t, d = x.shape
    n_experts = w1.shape[0]
    chosen, weights, _ = route_tokens(x, router_kernel, router_bias, top_k)
    with jax.named_scope("moe.experts"):
        flat = chosen.reshape(-1)
        # stable: an expert's rows keep token order, so the grouping is
        # one deterministic function of the choice
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.bincount(flat, length=n_experts).astype(jnp.int32)
        rows = x.astype(dtype)[order // top_k]
        cast = lambda w: w.astype(dtype)
        hidden = (jax.nn.silu(jax.lax.ragged_dot(rows, cast(w1), sizes))
                  * jax.lax.ragged_dot(rows, cast(w3), sizes))
        out = jax.lax.ragged_dot(hidden, cast(w2), sizes)
        # back to (token, choice) order; the k parts add up in float32
        out = out[jnp.argsort(order)].reshape(t, top_k, d)
        y = (out.astype(jnp.float32) * weights[..., None]).sum(1)
    counted = jnp.ones(t, bool) if valid is None else valid
    load = jnp.zeros(n_experts, jnp.float32).at[flat].add(
        jnp.repeat(counted, top_k).astype(jnp.float32))
    return y.astype(dtype), load
