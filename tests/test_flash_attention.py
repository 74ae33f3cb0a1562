"""Pallas flash attention vs the dense reference (ops/flash_attention.py).

Runs in pallas interpreter mode on the CPU mesh; on a real TPU the same
tests compile the kernel (interpret resolves by platform)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.ops.attention import attention
from mmlspark_tpu.ops.flash_attention import flash_attention

# On real TPU the MXU's default-precision f32 matmul rounds differently in
# the blocked kernel vs the dense einsum (~1e-3 absolute); in interpreter
# mode (CPU suite) both paths are exact f32.
ON_TPU = jax.devices()[0].platform == "tpu"
TOL = dict(rtol=1e-2, atol=1e-2) if ON_TPU else dict(rtol=2e-5, atol=2e-5)


def _qkv(b=2, s=256, h=4, d=32, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_matches_dense(causal):
    q, k, v = _qkv()
    ref = attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)


def test_matches_dense_bf16():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    ref = attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_ragged_q_blocks():
    """block_q != block_k and q blocks that straddle the causal diagonal."""
    q, k, v = _qkv(s=192)
    ref = attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block_q=96, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)


def test_non_tiling_shapes_fall_back_to_dense():
    q, k, v = _qkv(s=100)  # 100 % 64 != 0 after clamping
    got = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_cross_attention_lengths():
    q, _, _ = _qkv(s=128)
    _, k, v = _qkv(s=256, seed=1)
    ref = attention(q, k, v, causal=False)
    got = flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)


def test_gradients_match_dense():
    q, k, v = _qkv(s=128, d=16)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       block_q=64, block_k=64) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        # the squared loss doubles the forward's MXU rounding in g=2*out
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b),
            **(dict(rtol=2e-2, atol=3e-2) if ON_TPU else
               dict(rtol=1e-4, atol=1e-5)))


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_dense_4k(causal):
    """The pallas backward at S=4096 (VERDICT round-3 done-criterion):
    blocked dQ/dK/dV from the saved LSE vs the dense VJP."""
    q, k, v = _qkv(b=1, s=4096, h=1, d=32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=1024, block_k=1024) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b),
            **(dict(rtol=2e-2, atol=3e-2) if ON_TPU else
               dict(rtol=1e-4, atol=1e-4)))


def test_gradients_bf16_and_cross_lengths():
    """bf16 grads keep the input dtype; Sq != Sk exercises the transposed
    dK/dV grid."""
    q, _, _ = _qkv(s=128, d=16, dtype=jnp.bfloat16)
    _, k, v = _qkv(s=256, d=16, seed=1, dtype=jnp.bfloat16)
    loss = lambda fn: lambda q_, k_, v_: jnp.sum(
        fn(q_, k_, v_).astype(jnp.float32) ** 2)
    gf = jax.grad(loss(lambda a, b, c: flash_attention(
        a, b, c, block_q=64, block_k=64)), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss(attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-1, atol=1e-1)


def test_with_lse_matches_dense_stats():
    """flash_attention_with_lse: output equals dense attention AND the lse
    residual equals the scaled-score logsumexp (the ring merge key)."""
    from mmlspark_tpu.ops.flash_attention import flash_attention_with_lse
    q, k, v = _qkv(s=256, d=32)
    out, lse = flash_attention_with_lse(q, k, v, causal=True,
                                        block_q=64, block_k=64)
    ref = attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * d ** -0.5
    mask = jnp.tril(jnp.ones((256, 256), bool))
    s = jnp.where(mask[None, None], s, -jnp.inf)
    ref_lse = jax.scipy.special.logsumexp(s, axis=-1).transpose(0, 2, 1)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(ref_lse),
        **(dict(rtol=1e-2, atol=1e-2) if ON_TPU else
           dict(rtol=1e-5, atol=1e-5)))


def test_with_lse_offsets_mask_globally():
    """q_offset/k_offset shift the causal mask by global positions: with
    the k shard entirely AFTER the q shard, everything is masked (zero
    output, -inf-class lse); entirely BEFORE, nothing is."""
    from mmlspark_tpu.ops.attention import NEG_INF
    from mmlspark_tpu.ops.flash_attention import flash_attention_with_lse
    q, k, v = _qkv(s=64, d=16)
    out, lse = flash_attention_with_lse(q, k, v, causal=True,
                                        q_offset=0, k_offset=64,
                                        block_q=64, block_k=64)
    assert np.allclose(np.asarray(out), 0.0)
    assert np.all(np.asarray(lse) <= NEG_INF / 2)
    out2, lse2 = flash_attention_with_lse(q, k, v, causal=True,
                                          q_offset=64, k_offset=0,
                                          block_q=64, block_k=64)
    ref2 = attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2), **TOL)
    assert np.all(np.isfinite(np.asarray(lse2)))


@pytest.mark.slow
def test_transformer_lm_flash_matches_dense():
    from mmlspark_tpu.models.definitions import build_model
    cfg = {"vocab_size": 64, "d_model": 64, "n_heads": 4, "n_layers": 2,
           "max_len": 128, "dtype": "float32"}
    dense_lm = build_model("TransformerLM", {**cfg, "attn_impl": "dense"})
    flash_lm = build_model("TransformerLM", {**cfg, "attn_impl": "flash"})
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 64, size=(2, 128)), jnp.int32)
    params = dense_lm.init(jax.random.key(0), tokens)
    ref = dense_lm.apply(params, tokens)
    got = flash_lm.apply(params, tokens)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref),
        **(dict(rtol=3e-2, atol=3e-2) if ON_TPU else
           dict(rtol=2e-4, atol=2e-4)))
