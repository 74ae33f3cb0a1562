"""A decoder whose layers are a per-layer list: short-convolution,
grouped-attention, block-sparse-attention and linear-attention mixers over
dense or routed-expert gated MLPs.

`HybridLM` is the registered architecture (`build_model("HybridLM", ...)`).
Its layers are the plain functions below, each `(params, x, ..., state
view) -> (y, new state)`; the flax module (for `init`, `Trainer`,
`TPUModel`) and the decode programs of `models/generate.py` (through
`HybridDecoding`) call THESE functions, so a layer is stated once.

    x0 = e * E[tokens]                            (no position table)
    x  = x + a * mixer_i(norm(x));  x = x + a * ffn_i(norm(x))   per layer
    logits = (l * norm(x_L)) @ E^T                (head tied to E, or its own)

with the multipliers e, a, l (`embed_scale`, `residual_scale`,
`logit_scale`) 1 unless the constructor says otherwise.  A LOOPED model
(`n_passes` R > 1) runs the same layers R times over the same weights:

    for t in 1..R:  x = stack(x; windows of pass t);  x = norm(x);
                    g_t = sigmoid(w_g . x + b_g)            (`exit_gate`)
    logits = x_R @ head

so the final norm closes EVERY pass and its output enters the next one, and
pass t of a layer attends to the keys and values that pass t of that layer
wrote: a row keeps R windows a layer, side by side on the head axis of the
layer's K and V leaves (pass t's heads at [t * n_kv_heads, (t + 1) *
n_kv_heads)), so rows stay on axis 0 and slots on axis 1.  The gates give
the exit distribution p_t = g_t prod_{j<t} (1 - g_j), p_R the rest; the
published `exit_threshold` of 1 leaves at the last pass, and nothing here
leaves earlier: the gates are counted (`loop_exit_expected`), not acted on.

  * `norm` is RMSNorm: x * rsqrt(mean(x^2) + eps) * g, in float32.
  * a `conv` mixer: [B, C, z] = W_in h; u = B * z; c_t = sum_j k_j
    u_{t-K+1+j} per channel (depthwise, causal, no activation);
    y = W_out (C * c).  A row carries u at its last K-1 positions.
  * a `full_attention` mixer: q, k, v projections with `n_kv_heads` <=
    `n_heads`; RMSNorm over each head of q and k (`qk_norm`; a model
    without it has no such gains), then rotary positions (half-split
    pairing) over the whole head; each KV head serves
    n_heads / n_kv_heads query heads; causal softmax.  A row carries a
    window of K and V.
  * a `minicpm4` mixer: q, k, v as above without rotary; the row keeps a
    window of K and V and, beside it, the indexer's cache of compressed
    keys, a sixteenth as wide; each query reads the blocks
    `ops/sparse_attention.read_blocks` selects; y = W_o (sigmoid(W_g h) *
    o).  K and V are written at the token's POSITION, not at the slot the
    engine names (the two differ once a right-padded prompt is decoded
    from), so that a block holds the same tokens wherever it is read.
  * a `lightning-attn` mixer: q, k, v of `n_heads` heads each; RMSNorm
    over each head of q and k, rotary, q / sqrt(D); the decayed linear
    recurrence of `ops/linear_attention`; RMSNorm over the concatenated
    heads; y = W_o (sigmoid(W_g h) * o).  A row carries S (H, D, D) in
    float32, taken at its true length.
  * the first `n_dense_layers` layers have a gated MLP W2 (silu(W1 h) *
    W3 h); every other layer has routed experts (`ops/moe.routed_experts`:
    sigmoid scores, a selection bias, top-k, renormalised, dropless).
  * `sandwich_norm`: a second RMSNorm AFTER each sub-layer, before the
    residual sum: x = x + a * norm'(mixer_i(norm(x))), and the same around
    the MLP (gains `op_post_norm`, `ffn_post_norm`).

No bias anywhere.  Parameters are float32, products run in `dtype`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from mmlspark_tpu.ops import sparse_attention as sparse
from mmlspark_tpu.ops.linear_attention import decay_slopes, linear_attention
from mmlspark_tpu.ops.moe import routed_experts

NEG_INF = -1e30
CONV, ATTENTION = "conv", "full_attention"
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
MIXERS = (CONV, ATTENTION, SPARSE, LIGHTNING)
WINDOW, FIXED = "window", "fixed"      # the two kinds of per-row state
# prompt length from which a whole-prompt prefill runs the pallas flash
# kernel instead of the masked dense matmul, for every architecture: a long
# prompt must not materialize the O(P^2) score tensor the flash path exists
# to avoid; short ones stay on the dense path, whose f32 softmax is
# bit-stable for the exact-parity tests
PREFILL_FLASH_MIN = 512

# what the decode programs count on the device, in this order (the keys
# `ServingEngine.stats()` gains)
COUNT_NAMES = ("moe_assignments", "moe_experts_touched", "moe_expert_slots",
               "moe_load_max", "moe_load_mean")
# and of a model with `minicpm4` or `lightning-attn` layers: keys a sparse
# layer's queries could read and did read (decode steps of live rows, one KV
# head's, per layer; `_prompt_`: the same of a prefill's true tokens), the
# steps answered on the `dense_len` path, and the tokens a linear state
# took in (a prompt's and a decoded one, per layer)
SPARSE_COUNT_NAMES = ("sparse_keys_visible", "sparse_keys_read",
                      "sparse_prompt_keys_visible", "sparse_prompt_keys_read",
                      "sparse_dense_steps", "linear_state_steps")
# and of a looped model (`n_passes` > 1): decode steps of live rows, the
# stack passes those steps ran, the sum over them of the pass at which the
# exit gates' own distribution would leave (sum_t t * p_t: a reading, nothing
# leaves early), and passes x true prompt tokens of prefills
LOOP_COUNT_NAMES = ("loop_tokens", "loop_passes", "loop_exit_expected",
                    "loop_prompt_passes")


@dataclasses.dataclass
class StateView:
    """How one call reads and writes the rows' state.

    `write_at`: a scalar (every row writes its segment's K/V from that
    slot on: a prefill or a prompt chunk) or a `(B,)` vector (row r
    writes at its own slot: a decode step).  `visible`: `(B, S, W)` bool,
    the slots each query may read, or None for "causal over the segment
    itself" (a whole prompt from slot 0).  `n_valid`: `(B,)`, how many of
    the segment's tokens are the row's own (the rest is bucket padding):
    the convolution state and the linear-attention state are taken
    there, at the row's true length.  `valid`: `(B, S)` bool, the tokens
    the counters count.  `counts`: what the mixers and the pass loop of
    this call counted ({name of SPARSE_COUNT_NAMES or LOOP_COUNT_NAMES: sum
    so far}), read once the layers ran."""

    write_at: Any
    visible: Optional[jax.Array]
    n_valid: jax.Array
    valid: Optional[jax.Array] = None
    counts: dict = dataclasses.field(default_factory=dict)

    def count(self, name: str, values: jax.Array) -> None:
        """Add `values` (B, S) at the counted tokens to `name`."""
        if self.valid is not None:
            self.counts[name] = self.counts.get(name, 0.0) + jnp.sum(
                jnp.where(self.valid, values.astype(jnp.float32), 0.0))


def rms_norm(x: jax.Array, scale: jax.Array, eps: float, dtype) -> jax.Array:
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale).astype(dtype)


def rotary(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary positions over the whole head, half-split pairing: x
    (B, S, H, D), positions (B, S)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


def _dot(x: jax.Array, kernel: jax.Array, dtype) -> jax.Array:
    return x.astype(dtype) @ kernel.astype(dtype)


def short_conv(p: dict, h: jax.Array, state, view: Optional[StateView],
               dtype):
    """The gated short convolution over normalized `h` (B, S, d); `state`
    is the row's last K-1 columns of u, (B, K-1, d), or None (a plain
    forward: nothing before the segment)."""
    with jax.named_scope("short_conv"):
        b, s, d = h.shape
        gate_b, gate_c, z = jnp.split(_dot(h, p["conv_in"], dtype), 3, -1)
        u = gate_b * z
        taps = p["conv_taps"].astype(jnp.float32)           # (K, d)
        k = taps.shape[0]
        before = (jnp.zeros((b, k - 1, d), dtype) if state is None
                  else state.astype(dtype))
        ext = jnp.concatenate([before, u], axis=1)          # (B, K-1+S, d)
        ext32 = ext.astype(jnp.float32)
        conv = sum(taps[j] * ext32[:, j:j + s] for j in range(k))
        y = _dot(gate_c * conv.astype(dtype), p["conv_out"], dtype)
        if state is None:
            return y, None
        # the state a row keeps is u at ITS last K-1 positions: n_valid
        # of this segment's tokens are its own, the rest pad the bucket
        kept = jax.vmap(lambda e, n: lax.dynamic_slice_in_dim(
            e, n, k - 1, axis=0))(ext, view.n_valid)
        return y, kept.astype(state.dtype)


def _row_write_is_flat(cache_rank: int, update_slots: int) -> bool:
    """Whether `_row_write` of `update_slots` slots a row into a leaf of
    rank `cache_rank` is ONE scatter on the TPU: a decode step's one slot
    into a rank-3 or rank-4 leaf.  Else it is `vmap` of
    `dynamic_update_slice`, which the TPU compiler expands into a `while`
    over the rows.  `Decoding.row_writes` counts by the same rule."""
    return update_slots == 1 and cache_rank in (3, 4)


def _row_write(cache: jax.Array, update: jax.Array, slots: jax.Array,
               lane=0):
    """Write `update` (B, S, ...) into `cache` (B, W, ...) from a PER-ROW
    start slot `slots` (B,) on, and from head `lane` on where the cache
    holds more heads than the update (a looped model's passes).  S is 1
    for a decode step, the verify segment's length under speculation.

    A decode step's write is one `lax.scatter` in which every leading
    dimension is named and the window is the trailing dimension alone:
    the row is a batching dimension, the indices name the slot of a
    rank-3 leaf and (slot, head) of a rank-4 one.  The TPU compiler keeps
    that as a single in-place op (it turns the batching dimension into an
    index of its own), and a mesh that shards the rows still sees them as
    parallel.  An update that carries a batched PARTIAL window (`vmap` of
    `dynamic_update_slice`: the slot's dimension stays in the window; kept
    for S > 1) it expands into a `while` over the rows: 34-44 us a leaf
    where the write moves 32 KB (PERF.md section 6, PR 35).  Both clamp a
    start past the window into it, as `dynamic_update_slice` does
    (`mode="clip"`): a frozen row writes into its own last slot."""
    if not _row_write_is_flat(cache.ndim, update.shape[1]):
        zeros = (0,) * (cache.ndim - 3)
        return jax.vmap(lambda c, u, s: lax.dynamic_update_slice(
            c, u, (s, lane) + zeros))(cache, update, slots)
    indices = slots.astype(jnp.int32)[:, None]                  # (B, 1)
    if cache.ndim == 4:
        heads = lane + jnp.arange(update.shape[2], dtype=jnp.int32)
        indices = jnp.stack(jnp.broadcast_arrays(
            indices, heads[None, :]), axis=-1)                  # (B, KV, 2)
    inner = tuple(range(1, cache.ndim - 1))
    # a row's heads in order, no two index rows alike: sorted and unique
    return lax.scatter(
        cache, indices, update[:, 0],
        lax.ScatterDimensionNumbers(
            update_window_dims=(cache.ndim - 2,),
            inserted_window_dims=inner,
            scatter_dims_to_operand_dims=inner,
            operand_batching_dims=(0,),
            scatter_indices_batching_dims=(0,)),
        indices_are_sorted=True, unique_indices=True, mode="clip")


def _grouped_attention(q, k, v, visible, scale: float):
    """q (B, S, H, D) against k, v (B, L, KV, D), H a multiple of KV; query
    head i reads KV head i // (H / KV).  `visible` (B, S, L) or (S, L).
    Scores and sums in float32."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    q = q.reshape(b, s, kv, h // kv, d).astype(jnp.float32)
    scores = jnp.einsum("bskgd,blkd->bkgsl", q,
                        k.astype(jnp.float32)) * scale
    mask = visible[:, None, None] if visible.ndim == 3 else visible
    probs = jax.nn.softmax(jnp.where(mask, scores, NEG_INF), axis=-1)
    out = jnp.einsum("bkgsl,blkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(b, s, h, d)


def _pass_heads(cache: jax.Array, lane, n_kv_heads: int) -> jax.Array:
    """The heads of a window leaf that one pass of a looped model keeps:
    `n_kv_heads` from head `lane` on (None: the leaf is one window)."""
    return cache if lane is None else lax.dynamic_slice_in_dim(
        cache, lane, n_kv_heads, axis=2)


def grouped_attention(p: dict, h: jax.Array, positions: jax.Array, state,
                      view: Optional[StateView], *, n_heads: int,
                      n_kv_heads: int, rope_theta: float, eps: float,
                      dtype, qk_norm: bool = True, lane=None):
    """Grouped-KV attention over normalized `h` (B, S, d); `state` is the
    row's (K, V) window, each (B, W, n_kv_heads, D), or None.  `lane`
    (a looped model's pass times `n_kv_heads`, traced): the windows hold
    every pass's heads side by side, and this call writes and reads the
    `n_kv_heads` from head `lane` on."""
    with jax.named_scope("attn"):
        b, s, d = h.shape
        dh = d // n_heads
        q = _dot(h, p["wq"], dtype).reshape(b, s, n_heads, dh)
        k = _dot(h, p["wk"], dtype).reshape(b, s, n_kv_heads, dh)
        v = _dot(h, p["wv"], dtype).reshape(b, s, n_kv_heads, dh)
        if qk_norm:
            q = rms_norm(q, p["q_norm"], eps, dtype)
        q = rotary(q, positions, rope_theta)
        if qk_norm:
            k = rms_norm(k, p["k_norm"], eps, dtype)
        k = rotary(k, positions, rope_theta)
        scale = dh ** -0.5
        causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        if state is None:
            o = _grouped_attention(q, k, v, causal, scale)
            return _dot(o.reshape(b, s, d), p["wo"], dtype), None
        k_cache, v_cache = state
        at = view.write_at
        head0 = 0 if lane is None else lane
        if jnp.ndim(at) == 0:
            k_cache = lax.dynamic_update_slice(
                k_cache, k.astype(k_cache.dtype), (0, at, head0, 0))
            v_cache = lax.dynamic_update_slice(
                v_cache, v.astype(v_cache.dtype), (0, at, head0, 0))
        else:
            k_cache = _row_write(k_cache, k.astype(k_cache.dtype), at, head0)
            v_cache = _row_write(v_cache, v.astype(v_cache.dtype), at, head0)
        if view.visible is not None:
            o = _grouped_attention(q, _pass_heads(k_cache, lane, n_kv_heads),
                                   _pass_heads(v_cache, lane, n_kv_heads),
                                   view.visible, scale)
        elif s >= PREFILL_FLASH_MIN:
            # a whole prompt from slot 0: causal attention over the
            # segment itself, so the flash kernel never builds (S, S)
            # scores.  It wants one KV head a query head: K and V are
            # widened for this one read (the cache keeps n_kv_heads)
            from mmlspark_tpu.ops.flash_attention import flash_attention
            wide = lambda t: jnp.repeat(t, n_heads // n_kv_heads, axis=2)
            o = flash_attention(q, wide(k), wide(v), causal=True)
        else:
            o = _grouped_attention(q, k, v, causal, scale)
        return (_dot(o.reshape(b, s, d), p["wo"], dtype),
                (k_cache, v_cache))


def _gated_out(p: dict, h: jax.Array, o: jax.Array, dtype) -> jax.Array:
    """y = W_o (sigmoid(W_g h) * o), o (B, S, d) float32: the output gate
    of the `minicpm4` and `lightning-attn` mixers."""
    with jax.named_scope("attn.gate"):
        gate = jax.nn.sigmoid(_dot(h, p["wg"], dtype).astype(jnp.float32))
        return _dot(gate * o, p["wo"], dtype)


def block_sparse_attention(p: dict, h: jax.Array, positions: jax.Array,
                           state, view: Optional[StateView], *, n_heads: int,
                           n_kv_heads: int, cfg: sparse.Sparse, eps: float,
                           dtype):
    """Block-sparse attention over normalized `h` (B, S, d); `state` is the
    row's K and V windows, (B, W, n_kv_heads, D) each, and its compressed
    keys, (B, W / stride, n_kv_heads, D) float32; or None."""
    b, s, d = h.shape
    dh = d // n_heads
    q = rms_norm(_dot(h, p["wq"], dtype).reshape(b, s, n_heads, dh),
                 p["q_norm"], eps, dtype)
    k = rms_norm(_dot(h, p["wk"], dtype).reshape(b, s, n_kv_heads, dh),
                 p["k_norm"], eps, dtype)
    v = _dot(h, p["wv"], dtype).reshape(b, s, n_kv_heads, dh)
    scale = dh ** -0.5
    if state is None:
        # a plain forward: a window of its own, in whole blocks
        w = -(-s // cfg.block) * cfg.block
        zeros = lambda width, of: jnp.zeros((b, width, n_kv_heads, dh), of)
        k_cache, v_cache = zeros(w, dtype), zeros(w, dtype)
        kc, at = zeros(w // cfg.stride, jnp.float32), 0
    else:
        (k_cache, v_cache, kc), at = state, view.write_at
    if jnp.ndim(at) == 0:
        # a prompt segment: every row from slot `at` on, its position
        k_cache = lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype), (0, at, 0, 0))
        v_cache = lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype), (0, at, 0, 0))
        kc = jax.vmap(lambda c, keys: sparse.compress_row(
            c, keys, at, s, cfg))(kc, k_cache)
        o, n_read = sparse.attend_masked(q, k_cache, v_cache, kc,
                                         positions[0], cfg, scale)
        names = ("sparse_prompt_keys_visible", "sparse_prompt_keys_read")
    else:
        # a decode step: row r writes at ITS position (the slot the engine
        # names lies past the bucket's padding)
        if s != 1:
            raise ValueError("a minicpm4 layer steps one token a row")
        at = positions[:, 0]
        k_cache = _row_write(k_cache, k.astype(k_cache.dtype), at)
        v_cache = _row_write(v_cache, v.astype(v_cache.dtype), at)
        kc = jax.vmap(lambda c, keys, a: sparse.compress_row(
            c, keys, a, 1, cfg))(kc, k_cache, at)
        o, n_read = sparse.attend_step(q, k_cache, v_cache, kc, positions,
                                       cfg, scale)
        names = ("sparse_keys_visible", "sparse_keys_read")
        view.count("sparse_dense_steps", positions + 1 <= cfg.dense_len)
    if view is not None:
        view.count(names[0], positions + 1)
        view.count(names[1], n_read)
    y = _gated_out(p, h, o.reshape(b, s, d), dtype)
    return y, None if state is None else (k_cache, v_cache, kc)


def lightning_attention(p: dict, h: jax.Array, positions: jax.Array, state,
                        view: Optional[StateView], *, n_heads: int,
                        rope_theta: float, eps: float, dtype):
    """Decayed linear attention over normalized `h` (B, S, d); `state` is
    the row's S (B, n_heads, D, D) float32, or None."""
    b, s, d = h.shape
    dh = d // n_heads
    heads = lambda name: _dot(h, p[name], dtype).reshape(b, s, n_heads, dh)
    q = rotary(rms_norm(heads("wq"), p["q_norm"], eps, dtype), positions,
               rope_theta)
    k = rotary(rms_norm(heads("wk"), p["k_norm"], eps, dtype), positions,
               rope_theta)
    q = (q.astype(jnp.float32) * dh ** -0.5).astype(dtype)
    o, carried = linear_attention(
        q, k, heads("wv"), decay_slopes(n_heads),
        jnp.zeros((b, n_heads, dh, dh), jnp.float32) if state is None
        else state,
        jnp.full((b,), s) if view is None else view.n_valid)
    if view is not None:
        view.count("linear_state_steps", jnp.ones((b, s)))
    o = rms_norm(o.reshape(b, s, d), p["o_norm"], eps, jnp.float32)
    return _gated_out(p, h, o, dtype), None if state is None else carried


def gated_mlp(p: dict, h: jax.Array, dtype) -> jax.Array:
    return _dot(jax.nn.silu(_dot(h, p["w1"], dtype))
                * _dot(h, p["w3"], dtype), p["w2"], dtype)


def expert_mlp(p: dict, h: jax.Array, valid, *, top_k: int, dtype):
    """Routed experts over (B, S, d): `(y, load (E,))`."""
    b, s, d = h.shape
    y, load = routed_experts(
        h.reshape(b * s, d), p["router"], p["expert_bias"], p["w1"],
        p["w3"], p["w2"], top_k=top_k, dtype=dtype,
        valid=None if valid is None else valid.reshape(b * s))
    return y.reshape(b, s, d), load


def layer_params_shapes(module, i: int) -> dict:
    """Names and shapes of layer i's parameters (all float32)."""
    d = module.d_model
    dh = d // module.n_heads
    shapes = {"op_norm": (d,), "ffn_norm": (d,)}
    if module.sandwich_norm:
        shapes.update(op_post_norm=(d,), ffn_post_norm=(d,))
    kind = module.layer_types[i]
    if kind == CONV:
        shapes.update(conv_in=(d, 3 * d), conv_taps=(module.conv_kernel, d),
                      conv_out=(d, d))
    else:
        kv = d if kind == LIGHTNING else module.n_kv_heads * dh
        shapes.update(wq=(d, d), wk=(d, kv), wv=(d, kv), wo=(d, d))
        if module.qk_norm:
            shapes.update(q_norm=(dh,), k_norm=(dh,))
        if kind != ATTENTION:
            shapes.update(wg=(d, d))
        if kind == LIGHTNING:
            shapes.update(o_norm=(d,))
    if i < module.n_dense_layers:
        w = module.mlp_width
        shapes.update(w1=(d, w), w3=(d, w), w2=(w, d))
    else:
        e, w = module.n_experts, module.expert_width
        shapes.update(router=(d, e), expert_bias=(e,), w1=(e, d, w),
                      w3=(e, d, w), w2=(e, w, d))
    return shapes


def _gated_mixer(module, i: int, p: dict, h: jax.Array, positions, state,
                 view: Optional[StateView]):
    """Layer i's `minicpm4` or `lightning-attn` mixer."""
    if module.layer_types[i] == SPARSE:
        return block_sparse_attention(
            p, h, positions, state, view, n_heads=module.n_heads,
            n_kv_heads=module.n_kv_heads, cfg=module.sparse_cfg,
            eps=module.norm_eps, dtype=module.dtype)
    y, state = lightning_attention(
        p, h, positions, None if state is None else state[0], view,
        n_heads=module.n_heads, rope_theta=module.rope_theta,
        eps=module.norm_eps, dtype=module.dtype)
    return y, None if state is None else (state,)


def apply_layer(module, i: int, p: dict, x: jax.Array, positions, state,
                view: Optional[StateView], lane=None):
    """Layer i over the residual stream x (B, S, d): `(x, new state,
    load)`; `load` is the experts' assignment count (E,), or None for a
    dense layer.  `lane`: where a looped model's pass keeps its heads in
    the layer's windows (`grouped_attention`)."""
    dtype, eps = module.dtype, module.norm_eps
    h = rms_norm(x, p["op_norm"], eps, dtype)
    if module.layer_types[i] == CONV:
        y, state = short_conv(p, h, None if state is None else state[0],
                              view, dtype)
        state = None if state is None else (state,)
    elif module.layer_types[i] == ATTENTION:
        y, state = grouped_attention(
            p, h, positions, state, view, n_heads=module.n_heads,
            n_kv_heads=module.n_kv_heads, rope_theta=module.rope_theta,
            eps=eps, dtype=dtype, qk_norm=module.qk_norm, lane=lane)
    else:
        y, state = _gated_mixer(module, i, p, h, positions, state, view)
    if module.sandwich_norm:
        y = rms_norm(y, p["op_post_norm"], eps, dtype)
    x = x + module.residual_scale * y
    h = rms_norm(x, p["ffn_norm"], eps, dtype)
    if i < module.n_dense_layers:
        y, load = gated_mlp(p, h, dtype), None
    else:
        y, load = expert_mlp(p, h, None if view is None else view.valid,
                             top_k=module.experts_per_token, dtype=dtype)
    if module.sandwich_norm:
        y = rms_norm(y, p["ffn_post_norm"], eps, dtype)
    return x + module.residual_scale * y, state, load


def exit_distribution(gates: jax.Array) -> jax.Array:
    """The passes' exit probabilities of their gates, both (R, ...): p_t =
    g_t prod_{j<t} (1 - g_j), and the last pass takes what is left."""
    stay = jnp.cumprod(1.0 - gates, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    return jnp.concatenate([(gates * before)[:-1], before[-1:]])


def close_pass(module, params: dict, x: jax.Array):
    """What ends every pass of a looped model: the final norm, whose
    output is the next pass's input (and the head's, after the last), and
    the exit gate read from it: `(x, gate (B, S) float32)`, zeros without
    `exit_gate`."""
    with jax.named_scope("loop.close"):
        x = rms_norm(x, params["out_norm"], module.norm_eps, module.dtype)
        if not module.exit_gate:
            return x, jnp.zeros(x.shape[:2], jnp.float32)
        return x, jax.nn.sigmoid(x.astype(jnp.float32) @ params["exit_w"]
                                 + params["exit_b"])


def looped_stack(module, params: dict, x: jax.Array, positions, state=None,
                 view: Optional[StateView] = None):
    """The layers `n_passes` times over the same weights, from the
    embedded tokens `x`: `(x of the last pass, normalized; new state; the
    passes' exit gates (R, B, S) float32, zeros without `exit_gate`)`.
    The program holds the stack ONCE: a `lax.scan` over the passes with
    the weights closed over; pass t's windows are the heads from t *
    `n_kv_heads` on of each layer's K and V leaves."""
    def one_pass(carry, t):
        x, state = carry
        with jax.named_scope("loop.pass"):
            new_state = []
            for i in range(module.n_layers):
                x, layer_state, _ = apply_layer(
                    module, i, params[f"layer{i}"], x, positions,
                    None if state is None else state[i], view,
                    lane=t * module.n_kv_heads)
                new_state.append(layer_state)
        x, gate = close_pass(module, params, x)
        return (x, None if state is None else new_state), gate

    (x, state), gates = lax.scan(one_pass, (x, state),
                                 jnp.arange(module.n_passes))
    if view is not None:
        ones = jnp.ones(x.shape[:2], jnp.float32)
        if jnp.ndim(view.write_at) == 0:        # a prompt segment
            view.count("loop_prompt_passes", module.n_passes * ones)
        else:
            view.count("loop_tokens", ones)
            view.count("loop_passes", module.n_passes * ones)
            view.count("loop_exit_expected", jnp.einsum(
                "t,tbs->bs", 1.0 + jnp.arange(module.n_passes),
                exit_distribution(gates)))
    return x, state, gates


def hidden_states(module, params: dict, tokens: jax.Array, positions,
                  state=None, view: Optional[StateView] = None):
    """The model up to its final norm: `(x (B, S, d), new state, loads)`;
    `state` is a list of per-layer tuples (or None: a plain forward) and
    `loads` the expert layers' assignment counts, stacked (n, E)."""
    x = module.embed_scale * params["embed"][tokens].astype(module.dtype)
    if module.n_passes > 1:
        x, state, _ = looped_stack(module, params, x, positions, state,
                                   view)
        return x, state, jnp.zeros((0, module.n_experts), jnp.float32)
    new_state, loads = [], []
    for i in range(module.n_layers):
        x, layer_state, load = apply_layer(
            module, i, params[f"layer{i}"], x, positions,
            None if state is None else state[i], view)
        new_state.append(layer_state)
        if load is not None:
            loads.append(load)
    x = rms_norm(x, params["out_norm"], module.norm_eps, module.dtype)
    loads = (jnp.stack(loads) if loads
             else jnp.zeros((0, module.n_experts), jnp.float32))
    return x, (None if state is None else new_state), loads


def head(module, params: dict, x: jax.Array) -> jax.Array:
    """Logits, float32, of normalized hidden states (..., d)."""
    kernel = (params["embed"].T if module.tie_embeddings
              else params["head"])
    return _dot(module.logit_scale * x, kernel,
                module.dtype).astype(jnp.float32)


def _fan_in(name: str, shape: tuple) -> int:
    """Fan-in of a leaf for `init` (0: a scale, initialised to one).  An
    expert stack's fan-in is one expert's; the selection bias starts at
    zero-mean noise of its own small scale."""
    if name.endswith("norm"):
        return 0
    if name == "expert_bias":
        return 10_000
    if name == "conv_taps":
        return shape[0]
    return shape[-2]


class _Leaves(nn.Module):
    """Declares a flat group of float32 parameters and returns them."""

    shapes: Any       # ((name, shape), ...)

    @nn.compact
    def __call__(self) -> dict:
        out = {}
        for name, shape in self.shapes:
            fan_in = _fan_in(name, shape)
            init = (nn.initializers.normal(fan_in ** -0.5) if fan_in
                    else nn.initializers.ones)
            out[name] = self.param(name, init, shape, jnp.float32)
        return out


class HybridLM(nn.Module):
    """The decoder of the module docstring.  `layer_types` lists each
    layer's mixer (`conv` | `full_attention` | `minicpm4` |
    `lightning-attn`); `max_len` caps the positions a decode may reach
    (rotary positions need no table).  The `sparse_*` numbers are those of
    `ops/sparse_attention.py`, in tokens.  `n_passes`, `exit_gate` and
    `exit_threshold` are the looped model's of the module docstring (full
    attention over dense MLPs only: no other mixer keeps a state a pass);
    `sandwich_norm` and `qk_norm` say which norms a layer has."""

    vocab_size: int = 256
    d_model: int = 128
    n_heads: int = 8
    n_kv_heads: int = 2
    layer_types: tuple = (CONV, ATTENTION)
    n_dense_layers: int = 1
    mlp_width: int = 512
    n_experts: int = 8
    experts_per_token: int = 2
    expert_width: int = 128
    conv_kernel: int = 3
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    tie_embeddings: bool = True
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    sparse_block: int = 64
    sparse_kernel: int = 32
    sparse_stride: int = 16
    sparse_window: int = 2048
    sparse_init_blocks: int = 1
    sparse_topk: int = 64
    sparse_dense_len: int = 8192
    n_passes: int = 1
    sandwich_norm: bool = False
    qk_norm: bool = True
    exit_gate: bool = False
    exit_threshold: float = 1.0
    max_len: int = 2048
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        unknown = set(self.layer_types) - set(MIXERS)
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)} "
                             f"({' | '.join(MIXERS)})")
        plain = (set(self.layer_types) == {ATTENTION}
                 and self.n_dense_layers >= len(self.layer_types))
        if self.n_passes < 1 or (self.n_passes > 1 and not plain):
            raise ValueError(
                f"n_passes {self.n_passes}: a looped model (n_passes > 1) "
                "takes full_attention layers over dense MLPs only")
        if self.exit_gate and self.n_passes == 1:
            raise ValueError("exit_gate needs n_passes > 1: one pass has "
                             "nothing to leave early from")
        if self.exit_threshold != 1.0:
            raise ValueError(
                f"exit_threshold {self.exit_threshold}: no program here "
                "exits early; only the published 1.0 (every token runs "
                "every pass) is served")
        if not self.qk_norm and {SPARSE, LIGHTNING} & set(self.layer_types):
            raise ValueError(
                "qk_norm=False is for full_attention layers: a minicpm4 "
                "or lightning-attn mixer always norms q and k")
        if SPARSE in self.layer_types:
            sparse.check(self.sparse_cfg)
        if self.d_model % self.n_heads or self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"d_model {self.d_model} / n_heads {self.n_heads} / "
                f"n_kv_heads {self.n_kv_heads} do not divide")
        super().__post_init__()

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def sparse_cfg(self) -> sparse.Sparse:
        return sparse.Sparse(self.sparse_block, self.sparse_kernel,
                             self.sparse_stride, self.sparse_window,
                             self.sparse_init_blocks, self.sparse_topk,
                             self.sparse_dense_len)

    @nn.compact
    def __call__(self, tokens):
        leaves = lambda shapes, name: _Leaves(tuple(shapes.items()),
                                              name=name)()
        top = {"embed": (self.vocab_size, self.d_model),
               "out_norm": (self.d_model,)}
        if not self.tie_embeddings:
            top["head"] = (self.d_model, self.vocab_size)
        if self.exit_gate:
            top.update(exit_w=(self.d_model,), exit_b=())
        params = {}
        for i in range(self.n_layers):
            params[f"layer{i}"] = leaves(layer_params_shapes(self, i),
                                         f"layer{i}")
        # the top-level leaves live beside the layers' groups
        for name, shape in top.items():
            init = (nn.initializers.ones if name == "out_norm"
                    else nn.initializers.zeros if name == "exit_b"
                    else nn.initializers.normal(
                        1.0 if name == "embed" else shape[0] ** -0.5))
            params[name] = self.param(name, init, shape, jnp.float32)
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        x, _, _ = hidden_states(self, params, tokens, positions)
        return head(self, params, x)


class Decoding:
    """What `DecodeEngine` asks of a model, once, when it is built
    (`generate._decoding_for`), with the answers of a model that counts
    nothing on the device and whose state has one layout.  `cache_dtype`
    is the layout segments carry the state in ('model' | 'int8'), `fused`
    whether steps may read through the Pallas kernel, `verifies` whether
    the engine speculates (its rounds read the state through
    `run_verify`), `hint` the sharding hint of a state leaf on the
    engine's mesh (None: no mesh).

    The state a finished prompt hands on (`close_prompt`) is RESIDENT: the
    one layout that `merge_cache_rows`, window growth, every segment,
    the prefix pool's slices and handoff pages carry, and that a segment
    steps on as it is."""

    count_names = ()
    # what a decoding may carry beyond the calls above; `DecodeEngine`
    # refuses by name what its model's decoding says it lacks: state
    # layouts other than the model dtype's, the speculative verify segment
    # (`run_verify`), and a window or weights sharded over a mesh's
    # 'model' / 'seq' axes (`run_prompt_seq`, `run_step_seq`, partition
    # rules)
    cache_dtypes = ("model",)
    speculates = False
    shards = False

    def __init__(self, module, *, cache_dtype: str = "model",
                 fused: bool = False, verifies: bool = False, hint=None):
        self.module = module
        self.cache_dtype, self.fused = cache_dtype, fused
        self.verifies = verifies
        self.hint = hint or (lambda c: c)

    def _same(self, state: list) -> list:
        return state

    # a prompt's state is resident as it is
    close_prompt = reopen_prompt = _same

    def relayout_bytes(self, program: str, state: list,
                       tokens: int = 0) -> int:
        """Bytes of `state` that ONE call of `program` re-tiles on the
        device, known from shapes alone: "prompt" (`run_prompt` over
        `tokens` a row from slot 0), "chunk" (a later prompt segment),
        "reopen" (`reopen_prompt` of donor rows), "step" (a segment).
        The serving engine sums them at each dispatch
        (`state_relayout_bytes`).  One layout: nothing, ever."""
        return 0

    def _step_writes(self, state: list) -> list:
        """`(leaf, times)` for every leaf of `state` that a decode step
        writes a slot a row into through `_row_write`, `times` a step."""
        return []

    def row_writes(self, program: str, state: list, steps: int = 1) -> tuple:
        """`(writes, looped)` of ONE call of `program` on `state`, known
        from shapes alone: the per-row writes into window leaves, and
        those of them that take `_row_write`'s looped form (a `while`
        over the rows on the TPU) and not its flat one.  "step" is a
        segment of `steps` decode steps, a slot a row at each; "verify"
        (`run_verify`) writes `steps` slots a row at once.  The serving
        engine sums them at each dispatch (`row_writes`,
        `row_writes_looped`)."""
        slots, calls = (steps, 1) if program == "verify" else (1, steps)
        writes = looped = 0
        for leaf, times in self._step_writes(state):
            writes += times * calls
            if not _row_write_is_flat(leaf.ndim, slots):
                looped += times * calls
        return writes, looped


class HybridDecoding(Decoding):
    """`Decoding` for a `HybridLM`: its layers' state kinds and shapes,
    and the calls its programs make.  Every call runs `hidden_states`
    above.  The state has one layout, the model dtype's, unhinted (the
    engine refuses int8 state, speculation and a sharded window or
    weights for every `HybridLM`, whatever its layers' state kinds)."""

    def __init__(self, module: HybridLM, **how):
        super().__init__(module, **how)
        kinds = module.layer_types
        self.state_kinds = tuple(
            FIXED if kind in (CONV, LIGHTNING) else WINDOW for kind in kinds)
        # the expert counts, and the sparse and linear layers' where the
        # model has such layers: a program counts what it can read
        self.count_names = COUNT_NAMES + (
            SPARSE_COUNT_NAMES if {SPARSE, LIGHTNING} & set(kinds) else ()
        ) + (LOOP_COUNT_NAMES if module.n_passes > 1 else ())

    def _step_writes(self, state: list) -> list:
        """K and V of every attention layer, once a pass; K and V of a
        `minicpm4` layer (its compressed keys take `compress_row`)."""
        m = self.module
        return [(leaf, m.n_passes if kind == ATTENTION else 1)
                for kind, layer in zip(m.layer_types, state)
                if kind in (ATTENTION, SPARSE) for leaf in layer[:2]]

    def empty_state(self, rows: int, window: int,
                    resident: bool = False) -> list:
        """Zero state for `rows` rows, a layer at a time: K and V, (rows,
        window, n_kv_heads, D) each (a looped model's `n_passes` windows
        side by side: n_passes * n_kv_heads heads), and under a `minicpm4`
        layer the compressed keys beside them, (rows, window / stride,
        n_kv_heads, D) float32; a convolution's last K-1 columns, (rows,
        K-1, d); a linear-attention layer's S, (rows, n_heads, D, D)
        float32 whatever the model's dtype is.  The same for a prompt and
        for a `resident` batch."""
        m = self.module
        dh = m.d_model // m.n_heads
        kv = (rows, window, m.n_passes * m.n_kv_heads, dh)
        if SPARSE in m.layer_types and window % m.sparse_block:
            raise ValueError(
                f"a window of {window} slots is not whole blocks of "
                f"{m.sparse_block}: the cache chunk must be a multiple")
        empty = {
            CONV: lambda: (jnp.zeros((rows, m.conv_kernel - 1, m.d_model),
                                     m.dtype),),
            ATTENTION: lambda: (jnp.zeros(kv, m.dtype),
                                jnp.zeros(kv, m.dtype)),
            SPARSE: lambda: (jnp.zeros(kv, m.dtype), jnp.zeros(kv, m.dtype),
                             jnp.zeros((rows, window // m.sparse_stride)
                                       + kv[2:], jnp.float32)),
            LIGHTNING: lambda: (jnp.zeros((rows, m.n_heads, dh, dh),
                                          jnp.float32),)}
        return [empty[kind]() for kind in m.layer_types]

    # a layer's leaves that `_dot` and `routed_experts` read, through a
    # cast to the compute dtype and in no other way
    _PRODUCT_LEAVES = frozenset({"conv_in", "conv_out", "wq", "wk", "wv",
                                 "wo", "wg", "w1", "w2", "w3"})

    def resident_params(self, params: dict, cast) -> dict:
        """`params` with `cast` over every leaf the programs read only
        through a cast to `module.dtype` (`generate.resident_variables`):
        the products' kernels above, the expert stacks among them, the
        embedding (gathered, then cast: the cast commutes with the
        gather; the tied head reads it through `_dot`) and an untied
        head.  The norms and gains, `conv_taps`, the router's kernel and
        the selection bias are read in float32 and stay (the decay slopes
        are no parameters)."""
        out = dict(params)
        for name in ("embed", "head"):
            if name in params:
                out[name] = cast(params[name])
        for i in range(self.module.n_layers):
            name = f"layer{i}"
            out[name] = {k: cast(v) if k in self._PRODUCT_LEAVES else v
                         for k, v in params[name].items()}
        return out

    def _counts(self, experts: list, view: StateView):
        """The count vector, `count_names` long: the experts' five, then
        what the sparse and linear layers added to `view.counts`."""
        return jnp.stack([jnp.asarray(c, jnp.float32) for c in experts] + [
            jnp.asarray(view.counts.get(name, 0.0), jnp.float32)
            for name in self.count_names[len(COUNT_NAMES):]])

    def _prompt_counts(self, loads, view: StateView):
        """A prefill's counts: assignments, and each expert layer's
        fullest expert beside the mean."""
        return self._counts([loads.sum(), 0.0, 0.0, loads.max(-1).sum(),
                             loads.mean(-1).sum()], view)

    def _step_counts(self, loads, view: StateView):
        """A decode step's counts: assignments, and the experts that got
        one beside all that a step could touch (none where no row is
        live)."""
        slots = loads.size * (loads.sum() > 0)
        return self._counts([loads.sum(), (loads > 0).sum(), slots, 0.0,
                             0.0], view)

    def run_prompt(self, params, tokens, state, start, true_len, live):
        """A prompt segment of right-padded rows from slot `start` on
        (0 for a whole prompt): `(normalized hidden states (B, S, d), new
        state, counts)`.  Positions are the slots; a row's tokens past
        its `true_len` are padding: they never enter its convolution
        state, and causality keeps them from its true tokens."""
        b, s = tokens.shape
        at = start + jnp.arange(s)
        whole = isinstance(start, int) and start == 0
        window = next((layer[0].shape[1] for layer, kind
                       in zip(state, self.state_kinds) if kind == WINDOW),
                      s)
        visible = None if whole else jnp.broadcast_to(
            jnp.arange(window)[None, :] <= at[:, None], (b, s, window))
        valid = (at[None, :] < true_len[:, None]) & live[:, None]
        view = StateView(write_at=start, visible=visible,
                         n_valid=jnp.clip(true_len - start, 0, s),
                         valid=valid)
        x, state, loads = hidden_states(
            self.module, params, tokens, jnp.broadcast_to(at, (b, s)),
            state, view)
        return x, state, (self._prompt_counts(loads, view),)

    def run_step_rows(self, params, tok, pos, slots, state, visible, live):
        """One decode token a row: per-row positions `pos`, write `slots`
        and visibility (B, W): `(logits (B, V), new state, counts)`."""
        b = tok.shape[0]
        slots = jnp.broadcast_to(slots, (b,))
        view = StateView(write_at=slots, visible=visible[:, None],
                         n_valid=jnp.ones(b, jnp.int32),
                         valid=live[:, None])
        x, state, loads = hidden_states(
            self.module, params, tok[:, None], pos[:, None], state, view)
        return (head(self.module, params, x[:, 0]), state,
                (self._step_counts(loads, view),))

    # the uniform-slot step is the per-row step with equal slots
    run_step = run_step_rows

    def head(self, params, x):
        return head(self.module, params, x)
