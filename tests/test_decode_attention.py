"""Fused single-query attention vs the reference cache read
(ops/decode_attention.py vs ops/attention.single_query_attention).

Runs the kernel through the Pallas interpreter on the CPU; on a TPU
(`MMLSPARK_TPU_TEST_PLATFORM=tpu`) the same cases compile it with Mosaic
(`INTERPRET` below).  This file is the registered
parity suite for the module's `pallas_call` site (scripts/lint.py's
pallas-parity registry)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.ops.attention import single_query_attention
from mmlspark_tpu.ops.decode_attention import (_fit_block_k,
                                               fused_single_query_attention)
from mmlspark_tpu.quant.quantize import quantize_kv

ON_TPU = jax.devices()[0].platform == "tpu"
INTERPRET = not ON_TPU
TOL = dict(rtol=1e-2, atol=1e-2) if ON_TPU else dict(rtol=2e-5, atol=2e-5)


def _case(b=2, l=128, h=4, d=32, dtype=jnp.float32, seed=0, true_len=None,
          frontier=None):
    """A decode-step read: per-row prompt slots [0, true_len) plus decode
    slots [l // 2, frontier] visible — the engine's bucketed layout with a
    per-row pad hole between prompt and decode slots."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, l, h, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, l, h, d)), dtype)
    true_len = true_len if true_len is not None else \
        rng.integers(1, l // 2, size=b)
    frontier = frontier if frontier is not None else l // 2
    slots = np.arange(l)[None, :]
    visible = (slots < np.asarray(true_len)[:, None]) | \
        ((slots >= l // 2) & (slots <= frontier))
    return q, k, v, jnp.asarray(visible)


def _assert_parity(q, k, v, visible, k_scale=None, v_scale=None,
                   block_k=64, tol=TOL):
    ref = single_query_attention(q, k, v, visible, k_scale=k_scale,
                                 v_scale=v_scale)
    got = fused_single_query_attention(q, k, v, visible, k_scale=k_scale,
                                       v_scale=v_scale, block_k=block_k,
                                       interpret=INTERPRET)
    assert got.dtype == jnp.float32 and got.shape == ref.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **tol)


@pytest.mark.parametrize("block_k", [32, 64, 128])
def test_matches_reference_f32(block_k):
    _assert_parity(*_case(), block_k=block_k)


def test_matches_reference_bf16():
    q, k, v, visible = _case(dtype=jnp.bfloat16, seed=1)
    # both paths cast the bf16 cache to f32 before the dot, so they agree
    # to f32 rounding, not bf16 rounding
    _assert_parity(q, k, v, visible)


def test_matches_reference_int8_kv():
    """The in-kernel dequant (k_scale after QK^T, v_scale folded into the
    weights) against the reference's identical algebraic hoist."""
    q, k, v, visible = _case(seed=2)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    _assert_parity(q, kq, vq, visible, k_scale=ks, v_scale=vs)


def test_int8_zero_slots():
    """Never-written cache slots are int8 zeros with scale 0 — visible or
    not, both paths must treat them as exact-zero keys/values."""
    q, k, v, visible = _case(seed=3)
    k = k.at[:, 100:].set(0.0)
    v = v.at[:, 100:].set(0.0)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    assert float(jnp.abs(ks[:, 100:]).max()) == 0.0
    # make a zeroed slot visible on every row: scale-0 dequant must
    # reproduce exact zeros, not NaNs, in both implementations
    visible = visible.at[:, 100].set(True)
    _assert_parity(q, kq, vq, visible, k_scale=ks, v_scale=vs)


def test_window_edges():
    """Visibility frontiers on and off block boundaries, including a row
    whose only visible slot is the last of the window."""
    q, k, v, _ = _case(b=4, seed=4)
    slots = np.arange(128)[None, :]
    visible = np.stack([
        (slots[0] < 63),            # frontier one short of a block edge
        (slots[0] < 64),            # exactly a block edge
        (slots[0] < 65),            # one past a block edge
        (slots[0] == 127),          # single visible slot, last of window
    ])
    _assert_parity(q, k, v, jnp.asarray(visible))


def test_single_block_and_odd_batch():
    q, k, v, visible = _case(b=3, l=64, seed=5)
    _assert_parity(q, k, v, visible, block_k=64)


def test_scale_override():
    q, k, v, visible = _case(seed=6)
    ref = single_query_attention(q, k, v, visible, scale=0.25)
    got = fused_single_query_attention(q, k, v, visible, scale=0.25,
                                       interpret=INTERPRET, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)


def test_non_tiling_window_takes_a_smaller_block():
    """A window block_k does not divide is read in the largest block under
    block_k that does (96 = 2 x 48), not handed to the reference."""
    q, k, v, visible = _case(l=96, seed=7)
    _assert_parity(q, k, v, visible, block_k=64)


def test_fit_block_k():
    """Every window the decode engine opens at its default chunk tiles
    the default block, odd multiples of 128 included; a window with no
    sublane-aligned divisor has no fit (the wrapper then falls back,
    loudly)."""
    for window in (128, 256, 384, 640, 1152):
        assert _fit_block_k(window, 128, 16) == 128
        assert _fit_block_k(window, 128, 32) == 128
    assert _fit_block_k(384, 256, 32) == 192
    assert _fit_block_k(96, 64, 8) == 48
    assert _fit_block_k(48, 128, 16) == 48
    assert _fit_block_k(48, 128, 32) is None


def test_auto_interpret_on_cpu_is_reference():
    """interpret=None on the CPU resolves to the reference path — what
    the engine's decode step runs in tier-1."""
    if ON_TPU:
        pytest.skip("auto mode compiles the kernel on TPU")
    q, k, v, visible = _case(seed=8)
    ref = single_query_attention(q, k, v, visible)
    got = fused_single_query_attention(q, k, v, visible)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=0, atol=0)


@pytest.mark.parametrize("mode", ["kernel", "int8_kernel", "cpu_reference",
                                  "non_tiling_fallback"])
def test_head_folded_window_reads_the_same(mode):
    """A window handed over head-folded, (B, L, H*D) (the kernel's own
    layout: a decode segment folds its windows once, where the 4-D one
    would be re-tiled on the TPU for every read), gives bit for bit the
    4-D window's result: through the kernel, through the reference the
    CPU's auto mode takes, and through the fallback for a window the
    kernel cannot tile."""
    if mode == "cpu_reference" and ON_TPU:
        pytest.skip("auto mode compiles the kernel on TPU")
    kw = dict(interpret=None if mode == "cpu_reference" else INTERPRET)
    q, k, v, visible = _case(
        dtype=jnp.bfloat16, seed=9,
        l=100 if mode == "non_tiling_fallback" else 128)
    if mode == "non_tiling_fallback":
        # compiled, 100 has no divisor that is a multiple of bfloat16's
        # sublane tile: the wrapper hands over to the reference
        kw.update(interpret=False)
    if mode == "int8_kernel":
        (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
        kw.update(k_scale=ks, v_scale=vs)
    fold = lambda c: c.reshape(c.shape[:2] + (-1,))
    want = fused_single_query_attention(q, k, v, visible, **kw)
    got = fused_single_query_attention(q, fold(k), fold(v), visible, **kw)
    assert got.shape == want.shape == q.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---- softmax-stats variants (seq-sharded decode's merge epilogue) -------

def _merge_halves(fn, q, k, v, visible, **kw):
    """Run a stats attention `fn` over the two window halves separately
    and merge — exactly what the seq-sharded decode step does across
    chips, minus the collectives (axis_name=None exercises the identical
    merge algebra on stacked per-shard stats)."""
    l = k.shape[1]
    halves = [fn(q, k[:, :l // 2], v[:, :l // 2], visible[:, :l // 2],
                 **{n: (w[:, :l // 2] if w is not None else None)
                    for n, w in kw.items()}),
              fn(q, k[:, l // 2:], v[:, l // 2:], visible[:, l // 2:],
                 **{n: (w[:, l // 2:] if w is not None else None)
                    for n, w in kw.items()})]
    acc, m, lsum = (jnp.stack(ts) for ts in zip(*halves))
    return _merge_stacked(acc, m, lsum)


def _merge_stacked(acc, m, lsum):
    """The cross-chip merge, computed on a host-stacked leading axis:
    same max/rescale/sum algebra as `merge_attention_stats` under pmax/
    psum, so the parity it proves carries to the collective form."""
    m_g = jnp.max(m, axis=0)
    safe = jnp.where(m_g == -1e30, 0.0, m_g)
    corr = jnp.where(m == -1e30, 0.0, jnp.exp(m - safe[None]))
    l_g = jnp.sum(lsum * corr, axis=0)
    acc_g = jnp.sum(acc * corr[..., None], axis=0)
    return acc_g / jnp.where(l_g == 0.0, 1.0, l_g)[..., None]


def test_reference_stats_merge_matches_whole_window():
    """Two-shard stats + merge == the whole-window reference read — the
    numerical contract the seq-sharded decode engine stands on."""
    from mmlspark_tpu.ops.attention import single_query_attention_stats
    q, k, v, visible = _case(seed=9)
    ref = single_query_attention(q, k, v, visible)
    got = _merge_halves(single_query_attention_stats, q, k, v, visible)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_reference_stats_merge_int8_scales_compose():
    """Per-shard dequant happens inside the local stats pass, so the
    merged result equals the whole-window int8 read bit-for-tolerance."""
    from mmlspark_tpu.ops.attention import single_query_attention_stats
    q, k, v, visible = _case(seed=10)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    ref = single_query_attention(q, kq, vq, visible, k_scale=ks,
                                 v_scale=vs)
    got = _merge_halves(single_query_attention_stats, q, kq, vq, visible,
                        k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_fused_stats_merge_matches_whole_window():
    """The fused kernel's emit-stats mode (interpret on CPU): raw
    (acc, m, l) from two half-windows, merged, equals the normalized
    whole-window kernel output."""
    from mmlspark_tpu.ops.decode_attention import (
        fused_single_query_attention_stats)
    q, k, v, visible = _case(seed=11)
    ref = fused_single_query_attention(q, k, v, visible, block_k=64,
                                       interpret=INTERPRET)
    got = _merge_halves(
        lambda *a, **kw: fused_single_query_attention_stats(
            *a, block_k=32, interpret=INTERPRET, **kw),
        q, k, v, visible)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)


def test_fused_stats_fully_masked_shard_is_identity():
    """A shard whose visible slots are all False must contribute the
    merge identity (m=-inf, l=0, acc=0) — decode's early steps leave
    whole shards unwritten."""
    from mmlspark_tpu.ops.decode_attention import (
        fused_single_query_attention_stats)
    q, k, v, visible = _case(seed=12)
    masked = jnp.zeros_like(visible)
    acc, m, lsum = fused_single_query_attention_stats(
        q, k, v, masked, block_k=64, interpret=INTERPRET)
    assert float(jnp.max(jnp.abs(acc))) == 0.0
    assert float(jnp.max(lsum)) == 0.0
    assert bool(jnp.all(m <= -1e30))
