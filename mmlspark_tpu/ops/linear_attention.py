"""Linear attention with a per-head decay (Lightning Attention), as a
chunked scan.

    S_t = exp(-s_h) S_{t-1} + k_t^T v_t        per head h: a (D, D) matrix
    o_t = q_t S_t

A segment of S tokens runs in blocks of `BLOCK`: inside a block the decayed
scores (q_t . k_u) exp(-s_h (t - u)), u <= t, are one masked product; the
state before the block enters as exp(-s_h (t + 1)) q_t S; the block leaves
its own last state to the next.  One token (a decode step) is a block of
one, a prompt chunk starts from the state its predecessor left.

Rows are right-padded to their bucket, and padding must never enter a row's
state: `n_valid` says how many of the segment's tokens are the row's own,
and a token past it neither decays the state nor adds to it, so the state a
segment returns is the state at the row's TRUE length.  (What such a token
reads is of no interest to anyone.)

The state is float32 whatever the products' dtype is: a head whose decay is
exp(-1/256) sums hundreds of terms into it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

BLOCK = 128         # tokens a block of the scan holds


def decay_slopes(n_heads: int) -> jax.Array:
    """s_h = 2^(-8 (h + 1) / H): Lightning Attention-2's slopes, head 0 the
    quickest to forget."""
    h = jnp.arange(1, n_heads + 1, dtype=jnp.float32)
    return 2.0 ** (-8.0 * h / n_heads)


def _block(q, k, v, log_decay, state):
    """One block: q, k, v (B, C, H, D), `log_decay` (B, C, H) float32 (each
    token's own log decay: -s_h, or 0 for padding, whose k is 0 too),
    `state` (B, H, D, D) float32.  Returns (o (B, C, H, D) float32, the
    state after the block)."""
    c = q.shape[1]
    total = jnp.cumsum(log_decay, axis=1)                    # (B, C, H)
    since = total[:, :, None] - total[:, None, :]            # (B, t, u, H)
    causal = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    weight = jnp.exp(jnp.where(causal[None, :, :, None], since, -jnp.inf))
    scores = jnp.einsum("bthd,buhd->btuh", q, k,
                        preferred_element_type=jnp.float32) * weight
    o = jnp.einsum("btuh,buhd->bthd", scores.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    o = o + jnp.exp(total)[..., None] * jnp.einsum(
        "bthd,bhde->bthe", q.astype(jnp.float32), state)
    # what each token still weighs when the block ends
    left = jnp.exp(total[:, -1:] - total)[..., None]         # (B, C, H, 1)
    state = (jnp.exp(total[:, -1])[..., None, None] * state
             + jnp.einsum("buhd,buhe->bhde",
                          (k.astype(jnp.float32) * left).astype(k.dtype), v,
                          preferred_element_type=jnp.float32))
    return o, state


def linear_attention(q, k, v, slopes, state, n_valid, block: int = BLOCK):
    """q, k, v (B, S, H, D) in the products' dtype; `slopes` (H,) float32;
    `state` (B, H, D, D) float32, what the rows carried in (zeros: nothing
    before the segment); `n_valid` (B,) int, how many of the S tokens are
    each row's own.  Returns (o (B, S, H, D) float32, the state at each
    row's true length)."""
    with jax.named_scope("lightning.scan"):
        b, s, h, d = q.shape
        own = jnp.arange(s)[None, :] < n_valid[:, None]      # (B, S)
        k = jnp.where(own[..., None, None], k, jnp.zeros((), k.dtype))
        log_decay = -slopes * own[..., None].astype(jnp.float32)
        if s <= block:
            return _block(q, k, v, log_decay, state)
        pad = -s % block
        blocks = lambda t: jnp.pad(
            t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2)).reshape(
                (b, (s + pad) // block, block) + t.shape[2:]).swapaxes(0, 1)

        def step(state, xs):
            o, state = _block(*xs, state)
            return state, o

        # padded tokens of the last block are nobody's own: k = 0, decay 1
        state, o = lax.scan(step, state, (blocks(q), blocks(k), blocks(v),
                                          blocks(log_decay)))
        return o.swapaxes(0, 1).reshape(b, s + pad, h, d)[:, :s], state
