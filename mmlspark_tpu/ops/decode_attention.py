"""Pallas fused single-query attention: the decode-step cache read.

`ops/attention.single_query_attention` is the XLA-composed reference: one
einsum for QK^T, a masked softmax, a second einsum for PV, with the int8
dequant hoisted to the score row (k_scale) and the softmax weights
(v_scale).  XLA runs that as separate HBM round trips — the score row and
the softmax weights are materialized between the two einsums, and for an
int8 cache the dequant scales are re-read per einsum.  Steady-state decode
is bandwidth-bound (bench_lm_decode's roofline attribution), so those
round trips are the whole per-step budget.

This module fuses the read: one kernel streams K/V blocks of the cache
window through VMEM, dequantizes in-registers (k_scale multiplies the
score row AFTER QK^T, v_scale folds into the softmax weights BEFORE PV —
the same algebraic hoist as the reference, so the int8 bytes are the only
cache traffic), and folds blocks with the online-softmax accumulators of
`ops/flash_attention.py`.  Semantics match `single_query_attention`
exactly: float32 statistics, per-row visibility mask, (B, H, D) float32
out.

Layout: the cache stays (B, L, H, D).  Rather than transposing to the
flash kernel's (B*H, L, D) — a full relayout of the window per decode
step, the exact traffic the kernel exists to avoid — the head axis is
folded into the lane dimension: blocks are (block_k, H*D) slices of the
(B, L, H*D) view (contiguous in the array's order, but on the TPU tiled
otherwise than the 4-D window: XLA makes that view by copying the window,
so a caller that reads a window at every decode step hands it over folded
and keeps it so: `TransformerDecoding.folds`, the resident layout), per-head score
rows are produced by one MXU matmul against a constant head-selector
matrix (lane i of the cache belongs to head i // D), and the softmax
weights are expanded back through its transpose.  Scores and statistics
live in a 128-lane tile (one lane per head, padded with NEG_INF), so
H <= 128.

On the CPU, for windows with no sublane-aligned divisor, or inside a
shard_map manual region, the wrapper falls back to the reference — the
engine's CPU tier-1 path exercises exactly that path, while parity tests
drive the kernel itself (through the interpreter on the CPU, compiled on
a TPU).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mmlspark_tpu.ops.attention import (NEG_INF, single_query_attention,
                                        single_query_attention_stats)
from mmlspark_tpu.ops.flash_attention import (_auto_interpret,
                                              _in_manual_region)

# scores/statistics tile width: one lane per head (head h of the decode
# query scores in lane h), padded to the TPU lane count with NEG_INF
_STATS_LANES = 128

_warned_fallbacks: set = set()


def _warn_reference_fallback(reason: str, b: int, l: int, block_k: int,
                             interpret: bool) -> None:
    """The reference path re-materializes the score row and softmax
    weights in HBM — silently taking it on a real TPU decode loop gives up
    the fused read this kernel exists for, so it must be visible.  Deduped
    per reason (a serving process cycles through many window widths);
    interpreter contexts are test/CPU and stay quiet."""
    if interpret or reason in _warned_fallbacks:
        return
    _warned_fallbacks.add(reason)
    from mmlspark_tpu.observe import get_logger
    get_logger("ops.decode").warning(
        "fused_single_query_attention (first seen at B=%d, L=%d, "
        "block_k=%d): %s — falling back to the XLA-composed reference "
        "read; warned once per reason", b, l, block_k, reason)


def _head_selector(n_heads: int, head_dim: int):
    """(LANES, H*D) constant: T[h, i] = 1 where lane i belongs to head h.

    One matrix serves both directions: contracting the folded lane axis
    (dim 1) turns a (block_k, H*D) elementwise product into per-head score
    rows; contracting the stats-lane axis (dim 0) expands per-head weights
    back onto the folded lanes.  Rows h >= n_heads are all zero, so the
    NEG_INF padding lanes of the stats tile never leak into the output."""
    hd = n_heads * head_dim
    heads = jax.lax.broadcasted_iota(jnp.int32, (_STATS_LANES, hd), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (_STATS_LANES, hd), 1)
    return (heads == lanes // head_dim).astype(jnp.float32)


def _scale_pad(n_heads: int):
    """(H, LANES) constant placing a per-head dequant scale in its stats
    lane (pad lanes get 0 — harmless, their scores are NEG_INF-masked)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (n_heads, _STATS_LANES), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n_heads, _STATS_LANES), 1)
    return (rows == cols).astype(jnp.float32)


def _sqa_kernel(q_ref, k_ref, v_ref, vis_ref, ks_ref, vs_ref, out_refs,
                acc_ref, m_ref, l_ref, *, scale: float, n_heads: int,
                head_dim: int, block_k: int, emit_stats: bool = False):
    """One (batch row, k-block) grid step.

    The grid's inner dimension walks the window's K/V blocks; the
    online-softmax state (acc, running max m, normalizer l) persists in
    VMEM scratch across those steps (TPU grids execute minor-to-major on
    one core), so VMEM holds one K/V block at a time and the window is
    bounded by HBM, not VMEM.

    `out_refs` is `(o_ref,)` for the normalized read, or — with
    `emit_stats` — `(acc_out, m_out, l_out)`: the final block then writes
    the raw online-softmax statistics instead of dividing, for the
    seq-sharded decode's cross-chip merge
    (`ops/attention.merge_attention_stats`)."""
    j = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    sel = _head_selector(n_heads, head_dim)

    q = q_ref[0].astype(jnp.float32) * scale            # (1, H*D)
    kb = k_ref[0].astype(jnp.float32)                   # (block_k, H*D)
    vb = v_ref[0].astype(jnp.float32)
    # per-head scores: fold q into the lanes, reduce each head's D lanes
    # through the selector on the MXU -> one score lane per head
    s = jax.lax.dot_general(kb * q, sel, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if ks_ref is not None:
        # int8 dequant, k side: the per-(slot, head) scale multiplies the
        # score row AFTER QK^T — the dot streamed raw int8 bytes
        ks = ks_ref[0].astype(jnp.float32)              # (block_k, H)
        s = s * jax.lax.dot_general(ks, _scale_pad(n_heads),
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (block_k, _STATS_LANES), 1)
    s = jnp.where((vis_ref[0] > 0) & (lanes < n_heads), s, NEG_INF)

    m = m_ref[:][0:1]                                   # (1, LANES)
    l = l_ref[:][0:1]
    m_new = jnp.maximum(m, s.max(axis=0, keepdims=True))
    # fully-masked-lane guards (same algebra as the flash kernel's fold)
    safe_m = jnp.where(m_new == NEG_INF, 0.0, m_new)
    p = jnp.where(s == NEG_INF, 0.0, jnp.exp(s - safe_m))
    corr = jnp.where(m == NEG_INF, 0.0, jnp.exp(m - safe_m))
    l_new = l * corr + p.sum(axis=0, keepdims=True)
    w = p
    if vs_ref is not None:
        # int8 dequant, v side: fold the scale into the softmax weights
        # BEFORE PV, so that dot too streams raw int8 bytes
        vs = vs_ref[0].astype(jnp.float32)
        w = w * jax.lax.dot_general(vs, _scale_pad(n_heads),
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    # expand per-head weights back onto the folded lanes and accumulate
    w_exp = jax.lax.dot_general(w, sel, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    corr_exp = jax.lax.dot_general(corr, sel, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
    acc = acc_ref[:][0:1] * corr_exp + (w_exp * vb).sum(axis=0,
                                                        keepdims=True)
    # sublane-broadcast writes: scratch tiles are (8, lanes); every row
    # holds the same single-query state (sub-tile writes aren't supported)
    acc_ref[:] = jnp.broadcast_to(acc, acc_ref.shape)
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nk - 1)
    def _():
        if emit_stats:
            oa_ref, om_ref, ol_ref = out_refs
            oa_ref[0] = acc_ref[:][0:1].astype(oa_ref.dtype)
            om_ref[0] = m_ref[:][0:1].astype(om_ref.dtype)
            ol_ref[0] = l_ref[:][0:1].astype(ol_ref.dtype)
            return
        (o_ref,) = out_refs
        l_fin = l_ref[:][0:1]
        l_safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
        l_exp = jax.lax.dot_general(l_safe, _head_selector(n_heads,
                                                           head_dim),
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        # a fully-masked row (l == 0 in every lane) divides 0 by 1 -> 0;
        # l_exp of a pad lane is 0 only where acc is also 0
        l_exp = jnp.where(l_exp == 0.0, 1.0, l_exp)
        o_ref[0] = (acc_ref[:][0:1] / l_exp).astype(o_ref.dtype)


def _fused_forward(q, k_cache, v_cache, visible, scale, k_scale, v_scale,
                   block_k: int, interpret: bool,
                   emit_stats: bool = False):
    b, h, d = q.shape
    l = k_cache.shape[1]
    hd = h * d
    # head-fold views: free for a window handed over folded; of a
    # (B, L, H, D) window the TPU makes a re-tiled copy
    q3 = q.reshape(b, 1, hd)
    k3 = k_cache.reshape(b, l, hd)
    v3 = v_cache.reshape(b, l, hd)
    vis3 = visible.astype(jnp.int32).reshape(b, l, 1)
    quantized = k_scale is not None

    in_specs = [
        pl.BlockSpec((1, 1, hd), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((1, block_k, hd), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, block_k, hd), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, block_k, 1), lambda i, j: (i, j, 0)),
    ]
    args = [q3, k3, v3, vis3]
    if quantized:
        in_specs += [pl.BlockSpec((1, block_k, h), lambda i, j: (i, j, 0)),
                     pl.BlockSpec((1, block_k, h), lambda i, j: (i, j, 0))]
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]

    n_out = 3 if emit_stats else 1

    def kernel(q_ref, k_ref, v_ref, vis_ref, *rest):
        if quantized:
            ks_ref, vs_ref, rest = rest[0], rest[1], rest[2:]
        else:
            ks_ref, vs_ref = None, None
        out_refs, (acc_ref, m_ref, l_ref) = rest[:n_out], rest[n_out:]
        _sqa_kernel(q_ref, k_ref, v_ref, vis_ref, ks_ref, vs_ref, out_refs,
                    acc_ref, m_ref, l_ref, scale=scale, n_heads=h,
                    head_dim=d, block_k=block_k, emit_stats=emit_stats)

    if emit_stats:
        # raw statistics: acc on the folded lanes, m/l one lane per head
        out_specs = [pl.BlockSpec((1, 1, hd), lambda i, j: (i, 0, 0)),
                     pl.BlockSpec((1, 1, _STATS_LANES),
                                  lambda i, j: (i, 0, 0)),
                     pl.BlockSpec((1, 1, _STATS_LANES),
                                  lambda i, j: (i, 0, 0))]
        out_shape = [jax.ShapeDtypeStruct((b, 1, hd), jnp.float32),
                     jax.ShapeDtypeStruct((b, 1, _STATS_LANES),
                                          jnp.float32),
                     jax.ShapeDtypeStruct((b, 1, _STATS_LANES),
                                          jnp.float32)]
    else:
        out_specs = pl.BlockSpec((1, 1, hd), lambda i, j: (i, 0, 0))
        out_shape = jax.ShapeDtypeStruct((b, 1, hd), jnp.float32)

    out = pl.pallas_call(
        kernel,
        grid=(b, l // block_k),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((8, hd), jnp.float32),            # acc (folded lanes)
            pltpu.VMEM((8, _STATS_LANES), jnp.float32),  # running max / head
            pltpu.VMEM((8, _STATS_LANES), jnp.float32),  # normalizer / head
        ],
        interpret=interpret,
        name="decode_sqa",
    )(*args)
    if emit_stats:
        acc, m, lsum = out
        return (acc.reshape(b, h, d), m[:, 0, :h], lsum[:, 0, :h])
    return out.reshape(b, h, d)


# Default K/V block: the decode engine's cache-chunk granularity
# (models/generate.DEFAULT_CACHE_CHUNK), so every window the engine opens
# at its default chunk — odd multiples of 128 included (384, 640, 1152 …)
# — tiles with ONE block shape.  A fully-masked block is an exact no-op in
# the online-softmax fold, so with one block shape a row's result does
# not depend on how wide a window its batch neighbours forced.
DEFAULT_BLOCK_K = 128


def _fit_block_k(window: int, block_k: int, sublane: int) -> Optional[int]:
    """The largest K/V block <= `block_k` that tiles `window` and whose
    row count is a multiple of the cache dtype's sublane tile; None when
    the window has no such divisor."""
    for cand in range(min(block_k, window) // sublane * sublane, 0,
                      -sublane):
        if window % cand == 0:
            return cand
    return None


def _plan(q, k_cache, k_scale, v_scale, block_k: int, interpret: bool):
    """(block_k, None) when the fused kernel can read this window, else
    (None, reason) — the one fallback ladder both wrappers share."""
    h = q.shape[1]
    l = k_cache.shape[1]
    if _in_manual_region(q):
        return None, ("shard_map manual region (the partitioner owns "
                      "placement)")
    if (k_scale is None) != (v_scale is None):
        return None, "mixed quantization (k_scale xor v_scale)"
    if h > _STATS_LANES:
        return None, f"n_heads {h} exceeds the {_STATS_LANES}-lane stats tile"
    # mosaic sublane tiles: (8, 128) f32 / (16, 128) bf16 / (32, 128) int8
    # — the K/V block's sublane dim is block_k (the interpreter takes any)
    sub = 1 if interpret else {jnp.int8.dtype: 32, jnp.bfloat16.dtype: 16
                               }.get(k_cache.dtype, 8)
    fit = _fit_block_k(l, block_k, sub)
    if fit is None:
        return None, (f"window {l} has no divisor <= block_k {block_k} "
                      f"that is a multiple of the {k_cache.dtype} sublane "
                      f"tile ({sub}); round the window")
    return fit, None


def _read(q, k_cache, v_cache, visible, scale, k_scale, v_scale,
          block_k: int, interpret: Optional[bool], emit_stats: bool):
    """The dispatch both public wrappers share: the CPU's quiet reference
    path, the fallback ladder, else the kernel."""
    b, h, d = q.shape
    scale = scale if scale is not None else d ** -0.5

    def reference(q, k_cache, v_cache, *rest):
        # the references read (B, L, H, D); a head-folded window unfolds
        unfold = lambda c: c.reshape(c.shape[:2] + (h, d))
        return (single_query_attention_stats if emit_stats
                else single_query_attention)(q, unfold(k_cache),
                                             unfold(v_cache), *rest)
    if interpret is None:
        if _auto_interpret():
            # the CPU: the reference is the intended path (quiet)
            return reference(q, k_cache, v_cache, visible, scale, k_scale,
                             v_scale)
        interpret = False
    fit, reason = _plan(q, k_cache, k_scale, v_scale, block_k, interpret)
    if reason is not None:
        _warn_reference_fallback(reason, b, k_cache.shape[1], block_k,
                                 interpret)
        return reference(q, k_cache, v_cache, visible, scale, k_scale,
                         v_scale)
    return _fused_forward(q, k_cache, v_cache, visible, scale, k_scale,
                          v_scale, fit, interpret, emit_stats=emit_stats)


def fused_single_query_attention(q: jax.Array, k_cache: jax.Array,
                                 v_cache: jax.Array, visible: jax.Array,
                                 scale: Optional[float] = None,
                                 k_scale: Optional[jax.Array] = None,
                                 v_scale: Optional[jax.Array] = None,
                                 *, block_k: int = DEFAULT_BLOCK_K,
                                 interpret: Optional[bool] = None
                                 ) -> jax.Array:
    """`single_query_attention` with a fused Pallas cache read on TPU.

    Same contract as the reference (q (B, H, D); caches (B, L, H, D), or
    head-folded (B, L, H*D): the kernel's own layout, which on the TPU is
    another tiling than the 4-D one, so a caller that reads one window
    many times folds it once; per
    row visibility (B, L); optional per-(row, slot, head) int8 dequant
    scales (B, L, H); returns (B, H, D) float32) and the same float32
    statistics, so the two agree to rounding — tests/test_decode_attention
    pins the parity per dtype, and scripts/lint.py requires that registry
    entry for any `pallas_call` site in ops/.

    `block_k` is an upper bound: the kernel streams the window in the
    largest block under it that tiles the window (`_fit_block_k`).
    `interpret=None` resolves by platform: an accelerator compiles the
    kernel, the CPU takes the reference path (the interpreter inside a
    decode scan would be pure overhead — tier-1 CPU runs cover that
    path).  `interpret=True` forces the kernel through the Pallas
    interpreter — the parity tests' mode on the CPU.  Windows with no
    sublane-aligned divisor, H > 128 and shard_map manual regions fall
    back to the reference with a deduped warning, recorded in
    `_warned_fallbacks`.
    """
    return _read(q, k_cache, v_cache, visible, scale, k_scale, v_scale,
                 block_k, interpret, emit_stats=False)


def fused_single_query_attention_stats(q: jax.Array, k_cache: jax.Array,
                                       v_cache: jax.Array,
                                       visible: jax.Array,
                                       scale: Optional[float] = None,
                                       k_scale: Optional[jax.Array] = None,
                                       v_scale: Optional[jax.Array] = None,
                                       *, block_k: int = DEFAULT_BLOCK_K,
                                       interpret: Optional[bool] = None):
    """`single_query_attention_stats` with the fused cache read on TPU.

    Identical streaming to `fused_single_query_attention`, but the final
    block writes the raw online-softmax statistics instead of normalizing:
    returns float32 `(acc (B, H, D), m (B, H), l (B, H))` — the local-shard
    triple `ops/attention.merge_attention_stats` combines across a
    seq-sharded KV cache (running max via pmax, rescaled normalizer and
    accumulator via psum).  A fully-masked row reports m == NEG_INF and
    l == 0, the merge identity.  Fallback ladder matches the normalized
    wrapper exactly, landing on the XLA-composed reference stats.
    """
    return _read(q, k_cache, v_cache, visible, scale, k_scale, v_scale,
                 block_k, interpret, emit_stats=True)


__all__ = ["fused_single_query_attention",
           "fused_single_query_attention_stats"]
