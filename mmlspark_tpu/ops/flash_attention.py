"""Pallas flash attention: the fused single-device attention kernel.

The dense `attention` (ops/attention.py) materializes the (S, S) score
matrix in HBM — fine until S grows; flash attention streams K/V blocks
through VMEM with online-softmax accumulators so peak memory is
O(block_q x block_k) per core and the QK^T / PV matmuls run back-to-back on
the MXU without a round trip to HBM.  This is the single-chip hot op of the
long-context stack (across chips, `ring_attention` shards S over the mesh
and uses the same online-softmax algebra; the reference has no sequence
dimension at all — SURVEY §5).

Semantics match `attention(q, k, v, causal, scale)` exactly: inputs
(B, S, H, D), float32 softmax statistics, scale defaulting to D^-0.5.
Backward is a custom VJP over two blocked pallas kernels (dQ, and dK/dV)
that recompute the score blocks against the forward's saved log-sum-exp —
the standard flash backward: no O(S^2) matrix is ever materialized, P is
rebuilt one (block_q, block_k) tile at a time as exp(S - LSE), and
dS = P * (dP - delta) with delta = rowsum(dO * O) precomputed in XLA.
Shapes that don't tile the blocks fall back to the dense VJP.

On CPU (tests, virtual meshes) the kernel runs in interpreter mode
automatically; shapes that don't tile (S not divisible by the block sizes)
fall back to the dense path rather than padding silently.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mmlspark_tpu.ops.attention import NEG_INF, attention


def _flash_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_ref, acc_ref,
                  m_ref, l_ref, *, scale: float, causal: bool, block_q: int,
                  block_k: int, lse_ref=None):
    """One (batch*head, q-block, k-block) grid step.

    The grid's innermost dimension walks the K/V blocks; the online-softmax
    state (acc, running max m, normalizer l) lives in VMEM scratch that
    persists across those steps (TPU grids execute minor-to-major on one
    core), so VMEM holds only one K/V block at a time — sequence length is
    bounded by HBM, not by the 16 MB VMEM (a whole-K/V-in-VMEM layout tops
    out around S=16k at D=64)."""
    qi = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    q_off = qoff_ref[0]   # global position offsets (ring attention calls
    k_off = koff_ref[0]   # with rotating K/V shard origins; 0 standalone)

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    if causal:
        # K/V blocks entirely above the diagonal contribute nothing
        live = (k_off + j * block_k) <= (q_off + (qi + 1) * block_q - 1)
    else:
        live = j >= 0

    @pl.when(live)
    def _():
        q = q_ref[0].astype(jnp.float32) * scale          # (block_q, d)
        kb = k_ref[0].astype(jnp.float32)                 # (block_k, d)
        vb = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            rows = q_off + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = k_off + j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m = m_ref[:][:, :1]                               # (block_q, 1)
        l = l_ref[:][:, :1]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        # fully-masked-row guards (same algebra as ring_attention's fold)
        safe_m = jnp.where(m_new == NEG_INF, 0.0, m_new)
        p = jnp.where(s == NEG_INF, 0.0, jnp.exp(s - safe_m))
        corr = jnp.where(m == NEG_INF, 0.0, jnp.exp(m - safe_m))
        l_new = l * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # lane-broadcast the (block_q, 1) stats into the (block_q, 128)
        # scratch tiles (sub-lane scratch writes aren't supported)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nk - 1)
    def _():
        m = m_ref[:][:, :1]
        l = l_ref[:][:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        if lse_ref is not None:
            # log-sum-exp of the scaled scores: the residual that lets a
            # caller (ring attention) merge normalized partial outputs.
            # The block is (block_q, 1) — a rank-3 (bh, sq, 1) output
            # layout, because mosaic requires the last two block dims to
            # divide (8, 128) or equal the array dims, which a rank-2
            # (1, block_q) lse block cannot satisfy for b*h > 1
            lse_ref[0] = jnp.where(l == 0.0, NEG_INF, m + jnp.log(l_safe))


def _flash_forward(q, k, v, causal: bool, scale: float, block_q: int,
                   block_k: int, interpret: bool, with_lse: bool = False,
                   q_offset=0, k_offset=0):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    # (B, S, H, D) -> (B*H, S, D): one grid row per (batch, head)
    q3 = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    k3 = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    v3 = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    qoff = jnp.asarray(q_offset, jnp.int32).reshape(1)
    koff = jnp.asarray(k_offset, jnp.int32).reshape(1)

    # under shard_map (ring attention) outputs must declare which mesh axes
    # they vary over; inherit the query's varying-manual-axes type
    vma = _vma_of(q)
    sds = (functools.partial(jax.ShapeDtypeStruct, vma=vma)
           if vma else jax.ShapeDtypeStruct)
    out_shapes = [sds((b * h, sq, d), q.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, d), lambda bh, qi, j: (bh, qi, 0))]
    if with_lse:
        # rank-3 (bh, sq, 1) lse: blocks (1, block_q, 1) tile legally on
        # mosaic (block_q % 8 == 0); squeezed after the call
        out_shapes.append(sds((b * h, sq, 1), jnp.float32))
        out_specs.append(pl.BlockSpec((1, block_q, 1),
                                      lambda bh, qi, j: (bh, qi, 0)))

    def kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_ref, *rest):
        if with_lse:
            lse_ref, acc_ref, m_ref, l_ref = rest
        else:
            (acc_ref, m_ref, l_ref), lse_ref = rest, None
        _flash_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_ref,
                      acc_ref, m_ref, l_ref, scale=scale, causal=causal,
                      block_q=block_q, block_k=block_k, lse_ref=lse_ref)

    results = pl.pallas_call(
        kernel,
        grid=(b * h, sq // block_q, sk // block_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), lambda bh, qi, j: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, j: (bh, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, j: (bh, j, 0)),
        ],
        out_specs=out_specs if with_lse else out_specs[0],
        out_shape=out_shapes if with_lse else out_shapes[0],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),    # acc
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max (lane-bcast)
            pltpu.VMEM((block_q, 128), jnp.float32),  # normalizer (lane-bcast)
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qoff, koff, q3, k3, v3)
    if with_lse:
        out, lse = results
        return (out.reshape(b, h, sq, d).transpose(0, 2, 1, 3),
                lse.reshape(b, h, sq).transpose(0, 2, 1))  # drops the 1-lane
    return results.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


def _bwd_p_block(q_ref, k_ref, lse_ref, *, scale, causal, block_q, block_k,
                 qi, j, q_off, k_off):
    """Recompute one probability tile P = exp(S - LSE) from saved stats.

    Shared by both backward kernels.  Rows whose LSE is NEG_INF (fully
    masked) and masked score entries produce exact zeros, so padding /
    above-diagonal tiles contribute nothing."""
    q = q_ref[0].astype(jnp.float32) * scale              # (block_q, d)
    kb = k_ref[0].astype(jnp.float32)                     # (block_k, d)
    s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if causal:
        rows = q_off + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = k_off + j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(rows >= cols, s, NEG_INF)
    lse = lse_ref[0]                                      # (block_q, 1)
    p = jnp.exp(s - jnp.where(lse == NEG_INF, 0.0, lse))
    return jnp.where((s == NEG_INF) | (lse == NEG_INF), 0.0, p)


def _dq_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dq_ref, dq_acc, *, scale, causal, block_q,
               block_k):
    """dQ grid step: (batch*head, q-block, k-block), k innermost.

    dS = P * (dP - delta) with dP = dO V^T; dQ_i = scale * sum_j dS @ K_j
    accumulated in VMEM scratch across the innermost k walk."""
    qi, j = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    q_off, k_off = qoff_ref[0], koff_ref[0]

    @pl.when(j == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    if causal:
        live = (k_off + j * block_k) <= (q_off + (qi + 1) * block_q - 1)
    else:
        live = j >= 0

    @pl.when(live)
    def _():
        p = _bwd_p_block(q_ref, k_ref, lse_ref, scale=scale, causal=causal,
                         block_q=block_q, block_k=block_k, qi=qi, j=j,
                         q_off=q_off, k_off=k_off)
        do = do_ref[0].astype(jnp.float32)                # (block_q, d)
        vb = v_ref[0].astype(jnp.float32)                 # (block_k, d)
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])                      # delta: (block_q, 1)
        dq_acc[:] += scale * jax.lax.dot_general(
            ds, k_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                block_q, block_k):
    """dK/dV grid step: (batch*head, k-block, q-block), q innermost.

    dV_j = sum_i P^T dO_i; dK_j = scale * sum_i dS^T Q_i — one pass over
    the q blocks per k block, accumulators in VMEM scratch."""
    j, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)
    q_off, k_off = qoff_ref[0], koff_ref[0]

    @pl.when(qi == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if causal:
        live = (k_off + j * block_k) <= (q_off + (qi + 1) * block_q - 1)
    else:
        live = qi >= 0

    @pl.when(live)
    def _():
        p = _bwd_p_block(q_ref, k_ref, lse_ref, scale=scale, causal=causal,
                         block_q=block_q, block_k=block_k, qi=qi, j=j,
                         q_off=q_off, k_off=k_off)
        do = do_ref[0].astype(jnp.float32)                # (block_q, d)
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])                      # delta: (block_q, 1)
        dk_acc[:] += scale * jax.lax.dot_general(
            ds, q_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, do, lse, delta, causal, scale, block_q,
                    block_k, interpret, q_offset=0, k_offset=0):
    """Blocked backward from saved statistics: (dq, dk, dv).

    `lse`/`delta` are (B, Sq, H) float32 — the forward's log-sum-exp and
    rowsum(dO * O).  Two pallas launches (dQ walks k blocks; dK/dV walks q
    blocks) so each output has exactly one accumulating writer — no
    cross-grid-row races, no atomics (TPU grids are sequential per core,
    parallel across cores only over the batch*head dimension)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    q3 = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    k3 = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    v3 = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    do3 = do.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    # stats ride as rank-3 (bh, sq, 1): see the forward's lse layout note
    lse2 = lse.transpose(0, 2, 1).reshape(b * h, sq, 1)
    delta2 = delta.transpose(0, 2, 1).reshape(b * h, sq, 1)
    qoff = jnp.asarray(q_offset, jnp.int32).reshape(1)
    koff = jnp.asarray(k_offset, jnp.int32).reshape(1)

    vma = _vma_of(q)
    sds = (functools.partial(jax.ShapeDtypeStruct, vma=vma)
           if vma else jax.ShapeDtypeStruct)

    def in_specs(q_map, k_map):
        """q_map/k_map: grid-indices -> (bh, block-row) for q-side and
        k-side operands respectively (the two kernels transpose the grid)."""
        return [
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), lambda *g: (*q_map(*g), 0)),  # q
            pl.BlockSpec((1, block_k, d), lambda *g: (*k_map(*g), 0)),  # k
            pl.BlockSpec((1, block_k, d), lambda *g: (*k_map(*g), 0)),  # v
            pl.BlockSpec((1, block_q, d), lambda *g: (*q_map(*g), 0)),  # do
            pl.BlockSpec((1, block_q, 1), lambda *g: (*q_map(*g), 0)),  # lse
            pl.BlockSpec((1, block_q, 1), lambda *g: (*q_map(*g), 0)),  # delta
        ]

    dq3 = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(b * h, sq // block_q, sk // block_k),
        in_specs=in_specs(q_map=lambda bh, qi, j: (bh, qi),
                          k_map=lambda bh, qi, j: (bh, j)),
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, j: (bh, qi, 0)),
        out_shape=sds((b * h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(qoff, koff, q3, k3, v3, do3, lse2, delta2)

    dk3, dv3 = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(b * h, sk // block_k, sq // block_q),
        in_specs=in_specs(q_map=lambda bh, j, qi: (bh, qi),
                          k_map=lambda bh, j, qi: (bh, j)),
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, j, qi: (bh, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, j, qi: (bh, j, 0)),
        ],
        out_shape=[sds((b * h, sk, d), k.dtype),
                   sds((b * h, sk, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qoff, koff, q3, k3, v3, do3, lse2, delta2)

    unshape_q = lambda a: a.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    unshape_k = lambda a: a.reshape(b, h, sk, d).transpose(0, 2, 1, 3)
    return unshape_q(dq3), unshape_k(dk3), unshape_k(dv3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    return _flash_forward(q, k, v, causal, scale, block_q, block_k,
                          interpret)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    # the lse residual rides as rank-3 (bh, sq, 1) inside the kernels and
    # the backward's dK/dV output blocks are (1, block_k, d): real TPU
    # needs sublane-multiple block_q AND block_k there (interpret mode
    # does not); without them the backward will be the dense VJP, so
    # don't pay for lse in the forward
    if interpret or (block_q % 8 == 0 and block_k % 8 == 0):
        out, lse = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                                  interpret, with_lse=True)
        # named residuals: under jax.checkpoint with the 'save_attention'
        # policy (models/definitions.py) these are STORED, so the remat
        # backward reuses them instead of re-running the forward kernel
        from jax.ad_checkpoint import checkpoint_name
        out = checkpoint_name(out, "flash_out")
        lse = checkpoint_name(lse, "flash_lse")
        return out, (q, k, v, out, lse)
    _warn_dense_fallback(
        "flash_attention backward", q.shape[1], k.shape[1], block_q, block_k,
        interpret, "the lse residual / dK-dV blocks need sublane-multiple "
        "block_q and block_k (8) on TPU")
    out = _flash_forward(q, k, v, causal, scale, block_q, block_k, interpret)
    return out, (q, k, v, None, None)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    if lse is not None:
        delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
        return _flash_backward(q, k, v, g, lse, delta, causal, scale,
                               block_q, block_k, interpret)
    # non-sublane-multiple block_q on real TPU: the dense VJP of the
    # same function (dense and flash forwards agree to float32 rounding)
    _, vjp = jax.vjp(lambda q_, k_, v_: attention(q_, k_, v_, causal=causal,
                                                  scale=scale), q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


_warned_fallbacks: set = set()


def _warn_dense_fallback(fn_name: str, sq: int, sk: int, block_q: int,
                         block_k: int, interpret: bool,
                         reason: str) -> None:
    """The dense fallback is O(Sq x Sk) memory — silent on a long-context
    shard it is exactly the blow-up the flash path exists to avoid, so it
    must be visible.  Deduped per (fn, reason) with the first-seen shape in
    the message — a long-running scoring service cycling through many
    distinct sequence lengths must neither re-warn per shape nor grow the
    dedup set unboundedly; real-compute paths only (the interpreter already
    implies a test/CPU context)."""
    key = (fn_name, reason)
    if interpret or key in _warned_fallbacks:
        return
    _warned_fallbacks.add(key)
    from mmlspark_tpu.observe import get_logger
    get_logger("ops.flash").warning(
        "%s (first seen at Sq=%d, Sk=%d, blocks %d x %d): %s — falling "
        "back to DENSE attention (O(Sq*Sk) memory); warned once per reason",
        fn_name, sq, sk, block_q, block_k, reason)


def _vma_of(x):
    """The array type's varying-manual-axes (empty outside shard_map and
    under `check_vma=False`)."""
    return jax.typeof(x).vma


def _in_manual_region(x) -> bool:
    """True inside a shard_map manual region (the array type carries
    varying-manual-axes); the pallas interpreter cannot run there."""
    return bool(_vma_of(x))


def _auto_interpret() -> bool:
    # the Pallas interpreter is for the CPU (tests, virtual meshes) only;
    # every accelerator compiles the kernel or fails loudly doing so
    return jax.devices()[0].platform == "cpu"


def _dense_with_lse(q, k, v, causal, scale, q_offset, k_offset):
    """Reference-shape fallback: dense attention that also returns the
    scaled-score log-sum-exp per query (f32), with global-position causal
    masking."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        q_pos = q_offset + jnp.arange(s.shape[-2])
        k_pos = k_offset + jnp.arange(s.shape[-1])
        s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, NEG_INF)
    m = s.max(axis=-1)
    safe_m = jnp.where(m == NEG_INF, 0.0, m)
    p = jnp.where(s == NEG_INF, 0.0, jnp.exp(s - safe_m[..., None]))
    l = p.sum(axis=-1)
    lse = jnp.where(l == 0.0, NEG_INF, safe_m + jnp.log(jnp.maximum(l, 1e-30)))
    out = jnp.einsum("bhqk,bkhd->bqhd", p / jnp.maximum(l, 1e-30)[..., None],
                     v.astype(jnp.float32))
    return out.astype(q.dtype), lse.transpose(0, 2, 1)  # lse: (B, Sq, H)


def flash_attention_with_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                             causal: bool = False,
                             scale: Optional[float] = None,
                             q_offset=0, k_offset=0,
                             block_q: int = 1024, block_k: int = 1024,
                             interpret: Optional[bool] = None):
    """Flash attention that ALSO returns the log-sum-exp residual
    (B, Sq, H) — the merge key for combining normalized partial outputs
    across K/V shards (ring_flash_attention).  `q_offset`/`k_offset` shift
    global positions for causal masking when q / k are shards of a longer
    sequence.  Forward-only (no VJP): the scoring/inference path.

    On real TPU, block_q must be a sublane multiple (8) for the rank-3
    lse output; non-tiling shapes fall back to the dense computation."""
    d = q.shape[-1]
    scale_ = scale if scale is not None else d ** -0.5
    sq, sk = q.shape[1], k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if interpret is None:
        interpret = _auto_interpret()
    # inside shard_map (ring attention) the pallas INTERPRETER trips on
    # varying-manual-axes bookkeeping; the dense local op is equivalent
    # there (CPU test meshes) while real TPU compiles the kernel
    in_manual_region = _in_manual_region(q)
    if sq % block_q or sk % block_k:
        _warn_dense_fallback(
            "flash_attention_with_lse", sq, sk, block_q, block_k, interpret,
            "sequence lengths do not tile the blocks (pad the sequence or "
            "adjust block sizes)")
        return _dense_with_lse(q, k, v, causal, scale_, q_offset, k_offset)
    if not interpret and (block_q % 8 or block_k % 8):
        _warn_dense_fallback(
            "flash_attention_with_lse", sq, sk, block_q, block_k, interpret,
            "the lse output / (1, block_k, d) K-V blocks need "
            "sublane-multiple block_q and block_k (8) on TPU")
        return _dense_with_lse(q, k, v, causal, scale_, q_offset, k_offset)
    if interpret and in_manual_region:
        return _dense_with_lse(q, k, v, causal, scale_, q_offset, k_offset)
    return _flash_forward(q, k, v, causal, scale_, block_q, block_k,
                          interpret, with_lse=True,
                          q_offset=q_offset, k_offset=k_offset)


def _dense_block_grads(q, k, v, do, lse, delta, causal, scale,
                       q_offset, k_offset):
    """Dense equivalent of `flash_block_grads` (fallback path): the
    gradient CONTRIBUTION of one K/V block given the global softmax
    statistics — not the VJP of local attention, whose normalizer would be
    this block's alone."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        q_pos = q_offset + jnp.arange(s.shape[-2])
        k_pos = k_offset + jnp.arange(s.shape[-1])
        s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, NEG_INF)
    lse_b = lse.transpose(0, 2, 1)[..., None]             # (B,H,Sq,1)
    p = jnp.exp(s - jnp.where(lse_b == NEG_INF, 0.0, lse_b))
    p = jnp.where((s == NEG_INF) | (lse_b == NEG_INF), 0.0, p)
    do32 = do.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bqhd->bkhd", p, do32)
    dp = jnp.einsum("bqhd,bkhd->bhqk", do32, v.astype(jnp.float32))
    ds = p * (dp - delta.transpose(0, 2, 1)[..., None])
    dq = scale * jnp.einsum("bhqk,bkhd->bqhd", ds, k.astype(jnp.float32))
    dk = scale * jnp.einsum("bhqk,bqhd->bkhd", ds, q.astype(jnp.float32))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def flash_block_grads(q, k, v, do, lse, delta, causal: bool, scale: float,
                      q_offset=0, k_offset=0, block_q: int = 1024,
                      block_k: int = 1024,
                      interpret: Optional[bool] = None):
    """(dq, dk, dv) contribution of ONE K/V shard against global statistics.

    The building block of the ring backward (ops/attention.py
    `ring_flash_attention`): `lse` and `delta` are the FULL-sequence
    log-sum-exp and rowsum(dO * O), both (B, Sq, H) float32, so
    P = exp(S - LSE) is the true global probability of this block's keys
    and the per-block contributions simply sum around the ring.  Offsets
    place the shards in global positions for causal masking.  Falls back
    to the dense per-block computation for non-tiling shapes or inside a
    shard_map region on the interpreter (CPU test meshes)."""
    sq, sk = q.shape[1], k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if interpret is None:
        interpret = _auto_interpret()
    if sq % block_q or sk % block_k:
        _warn_dense_fallback(
            "flash_block_grads", sq, sk, block_q, block_k, interpret,
            "sequence lengths do not tile the blocks (pad the sequence or "
            "adjust block sizes)")
        return _dense_block_grads(q, k, v, do, lse, delta, causal, scale,
                                  q_offset, k_offset)
    if not interpret and (block_q % 8 or block_k % 8):
        _warn_dense_fallback(
            "flash_block_grads", sq, sk, block_q, block_k, interpret,
            "the lse/delta operands and (1, block_k, d) dK/dV blocks need "
            "sublane-multiple block_q and block_k (8) on TPU")
        return _dense_block_grads(q, k, v, do, lse, delta, causal, scale,
                                  q_offset, k_offset)
    if interpret and _in_manual_region(q):
        return _dense_block_grads(q, k, v, do, lse, delta, causal, scale,
                                  q_offset, k_offset)
    return _flash_backward(q, k, v, do, lse, delta, causal, scale,
                           block_q, block_k, interpret,
                           q_offset=q_offset, k_offset=k_offset)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, scale: Optional[float] = None,
                    block_q: int = 1024, block_k: int = 1024,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Fused blocked attention; drop-in for `attention(q, k, v, causal)`.

    q: (B, Sq, H, D), k/v: (B, Sk, H, D) -> (B, Sq, H, D).  Block sizes
    clamp to the sequence lengths; shapes that still don't tile evenly
    fall back to the dense path (correctness first — padding KV silently
    would corrupt the softmax normalizer).  Defaults measured best on v5e
    at D=64 (8k ctx: 2.1x over 512-blocks; much larger k blocks overflow
    the double-buffered VMEM pipeline).
    """
    d = q.shape[-1]
    scale_ = scale if scale is not None else d ** -0.5
    sq, sk = q.shape[1], k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if interpret is None:
        interpret = _auto_interpret()
    if sq % block_q or sk % block_k:
        _warn_dense_fallback(
            "flash_attention", sq, sk, block_q, block_k, interpret,
            "sequence lengths do not tile the blocks (pad the sequence or "
            "adjust block sizes)")
        return attention(q, k, v, causal=causal, scale=scale_)
    # same guard as flash_attention_with_lse: inside shard_map the pallas
    # INTERPRETER (CPU test meshes) trips on varying-manual-axes
    # bookkeeping; the dense local op is equivalent there
    if interpret and _in_manual_region(q):
        return attention(q, k, v, causal=causal, scale=scale_)
    return _over_mesh(
        lambda q_, k_, v_: _flash(q_, k_, v_, causal, scale_, block_q,
                                  block_k, interpret), q, k, v)


def _over_mesh(kernel_call, q, k, v):
    """Run a kernel call on the active mesh.

    GSPMD cannot partition a Mosaic kernel (jax refuses at lowering:
    "wrap the call in a shard_map"), so under a multi-device mesh the call
    runs in a shard_map manual region.  Attention is independent per
    (batch row, head): batch splits over 'data' and heads over 'model'
    (HEADS_SPEC, the layout the forward's own hints state), and a shape
    the mesh does not divide stays replicated — every device then computes
    the whole call, which is what the kernel costs without a rule.  No
    mesh, one device, or a region that is already manual (the ring path):
    the plain call."""
    from mmlspark_tpu.parallel.partition import (HEADS_SPEC, active_mesh,
                                                 compatible_spec)
    from mmlspark_tpu.parallel.ring import _shard_map
    mesh = active_mesh()
    if (mesh is None or mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return kernel_call(q, k, v)
    spec = compatible_spec(HEADS_SPEC, q.shape, mesh)
    return _shard_map(kernel_call, mesh=mesh, in_specs=(spec, spec, spec),
                      out_specs=spec)(q, k, v)
