"""Block-sparse softmax attention whose blocks are chosen by an indexer
over compressed keys (InfLLM-v2, as MiniCPM4 runs it).

Keys live in a window (B, W, G, D) of G KV heads, each serving H / G query
heads.  Beside it a row keeps the INDEXER'S CACHE: compressed keys

    Kc_j = mean(K[stride * j : stride * j + kernel])      (no parameters)

one for every kernel window that is complete, appended when its last key
arrives and never recomputed from K (that would read the whole window at
every step).  For the query at position t and KV head g:

    p_j   = softmax_j(q . Kc_j * scale) per query head, over the j whose
            window is complete (stride * j + kernel <= t + 1), summed over
            the heads of g
    score of block b (tokens block * b .. block * b + block - 1)
          = max of p_j over the j whose window overlaps b
    read  = the first `init_blocks` blocks, the `window / block` blocks
            ending at the query's own, and of the rest the `topk` by score
            (the earlier block where two tie); every visible block while
            t + 1 <= `dense_len`

and attention is a causal softmax over the tokens of the read blocks.

`read_blocks` states the selection once.  A decode step (`attend_step`)
turns it into block indices and reads K and V by a gather
(`attend_gathered`); a prompt segment
computes attention under the block mask in chunks of queries and tiles of
keys with a running softmax (`attend_masked`): dense operations over every
key at or before the chunk, the right result, no block skipped yet.

Slots are positions here: a token's K and V are written at its position,
so that a block is the same 64 tokens in a prompt and in a decode step.
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30
Q_CHUNK = 128       # queries a chunk of `attend_masked` holds
K_TILE = 2048       # keys a tile of its running softmax holds

Sparse = collections.namedtuple(
    "Sparse", "block kernel stride window init_blocks topk dense_len")


def check(cfg: Sparse) -> None:
    if cfg.block % cfg.stride or cfg.window % cfg.block:
        raise ValueError(
            f"sparse attention: block {cfg.block} must be a multiple of "
            f"stride {cfg.stride}, and window {cfg.window} of the block")
    if cfg.kernel < cfg.stride or cfg.topk < 1 or cfg.init_blocks < 0:
        raise ValueError(f"sparse attention: {cfg} leaves keys uncovered")


def capacity(cfg: Sparse, n_blocks: int) -> int:
    """Blocks a decode step's gather holds: all a query reads."""
    sparse = cfg.init_blocks + cfg.window // cfg.block + cfg.topk
    dense = -(-cfg.dense_len // cfg.block)
    return min(n_blocks, max(sparse, dense))


def compress_row(kc, k_cache, start, n_new: int, cfg: Sparse):
    """One row's compressed keys after `n_new` keys were written into
    `k_cache` (W, G, D) from slot `start` on: every kernel window that ends
    inside the `n_new` is averaged from the cache (float32) and written to
    `kc` (W / stride, G, D); a window that is not complete yet stays as it
    was."""
    n = -(-n_new // cfg.stride)
    first = -(-(start - cfg.kernel + 1) // cfg.stride)   # ends at >= start
    first = jnp.clip(first, 0, kc.shape[0] - n)
    j = first + jnp.arange(n)
    complete = cfg.stride * j + cfg.kernel <= start + n_new
    windows = jax.vmap(lambda at: lax.dynamic_slice_in_dim(
        k_cache, at, cfg.kernel, axis=0))(cfg.stride * j)
    mean = windows.astype(jnp.float32).mean(axis=1)
    old = lax.dynamic_slice_in_dim(kc, first, n, axis=0)
    new = jnp.where(complete[:, None, None], mean.astype(kc.dtype), old)
    return lax.dynamic_update_slice_in_dim(kc, new, first, axis=0)


def block_scores(q, kc, q_pos, cfg: Sparse, scale: float):
    """q (B, S, H, D), kc (B, Wc, G, D), q_pos (B, S): each block's score,
    (B, G, S, W / block) float32, 0 where no complete window overlaps it.
    float32 at `highest`: the 64th and 65th block of 500 lie a thousandth
    apart, and this product is a sixteenth of a key read."""
    b, s, h, d = q.shape
    wc, g = kc.shape[1], kc.shape[2]
    qg = q.reshape(b, s, g, h // g, d).astype(jnp.float32)
    logits = jnp.einsum("bsgkd,bjgd->bgksj", qg, kc.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST) * scale
    complete = (cfg.stride * jnp.arange(wc) + cfg.kernel
                <= q_pos[..., None] + 1)                     # (B, S, Wc)
    logits = jnp.where(complete[:, None, None], logits, NEG_INF)
    p = jnp.exp(logits - logits.max(-1, keepdims=True))
    p = jnp.where(complete[:, None, None], p, 0.0)
    p = (p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)).sum(2)
    # windows r * b + o overlap block b for o in lo..hi
    r = cfg.block // cfg.stride
    lo = -((cfg.kernel - 1) // cfg.stride)
    hi = (cfg.block - 1) // cfg.stride
    n_blocks = wc // r
    padded = jnp.pad(p, [(0, 0)] * 3 + [(-lo, hi)])
    return jnp.stack(
        [padded[..., o - lo:o - lo + r * n_blocks:r]
         for o in range(lo, hi + 1)]).max(0)


def read_blocks(scores, q_pos, cfg: Sparse):
    """Which blocks each query reads: (B, G, S, Wb) bool, from their scores
    (the same shape) and the queries' positions (B, S)."""
    n_blocks = scores.shape[-1]
    blocks = jnp.arange(n_blocks)
    own = (q_pos // cfg.block)[:, None, :, None]             # (B, 1, S, 1)
    visible = blocks <= own
    forced = visible & ((blocks < cfg.init_blocks)
                        | (blocks > own - cfg.window // cfg.block))
    rest = visible & ~forced
    ranked = jnp.where(rest, scores, -1.0)
    k = min(cfg.topk, n_blocks)
    kth = lax.top_k(ranked, k)[0][..., -1:]
    # neighbours often tie exactly (one window overlaps both and is the
    # largest of each): of the blocks that tie at the k-th score the
    # earliest are read, as a stable sort would have it
    above = rest & (ranked > kth)
    tied = rest & (ranked == kth)
    room = k - above.sum(-1, keepdims=True)
    chosen = above | (tied & (jnp.cumsum(tied, axis=-1) <= room))
    dense = (q_pos + 1 <= cfg.dense_len)[:, None, :, None]
    return forced | chosen | (visible & dense)


def keys_read(read, q_pos, cfg: Sparse):
    """Keys the queries read, of one KV head: (B, S) float32; the query's
    own block counts up to the query."""
    n_blocks = read.shape[-1]
    own = (q_pos // cfg.block)[:, None, :, None]
    full = (read & (jnp.arange(n_blocks) < own)).sum(-1) * cfg.block
    return (full + (q_pos % cfg.block + 1)[:, None]).astype(
        jnp.float32).mean(1)


def attend_gathered(q, k_cache, v_cache, read, q_pos, cfg: Sparse,
                    scale: float):
    """A decode step: q (B, 1, H, D) reads the blocks `read` (B, G, 1, Wb)
    names out of the windows (B, W, G, D) by a gather of `capacity` blocks.
    Returns (B, 1, H, D) float32."""
    b, _, h, d = q.shape
    w, g = k_cache.shape[1], k_cache.shape[2]
    n_blocks = w // cfg.block
    n = capacity(cfg, n_blocks)
    taken, idx = lax.top_k(read[:, :, 0].astype(jnp.float32), n)  # (B, G, n)
    rows, heads = jnp.arange(b)[:, None, None], jnp.arange(g)[None, :, None]
    take = lambda cache: cache.reshape(b, n_blocks, cfg.block, g, d)[
        rows, idx, :, heads]                                 # (B, G, n, blk, D)
    kg, vg = take(k_cache), take(v_cache)
    at = idx[..., None] * cfg.block + jnp.arange(cfg.block)  # (B, G, n, blk)
    seen = (taken[..., None] > 0) & (at <= q_pos[:, :, None, None])
    qg = q.reshape(b, g, h // g, d)
    scores = jnp.einsum("bgkd,bgntd->bgknt", qg, kg,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(seen[:, :, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores.reshape(b, g, h // g, -1), axis=-1)
    out = jnp.einsum("bgknt,bgntd->bgkd",
                     probs.reshape(scores.shape).astype(vg.dtype), vg,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, d)


def attend_step(q, k_cache, v_cache, kc, q_pos, cfg: Sparse, scale: float):
    """A decode step of q (B, 1, H, D) at positions `q_pos` (B, 1): select,
    then gather.  Returns (out (B, 1, H, D) float32, keys read (B, 1))."""
    with jax.named_scope("sparse.select"):
        read = read_blocks(block_scores(q, kc, q_pos, cfg, scale), q_pos,
                           cfg)
    with jax.named_scope("sparse.attend"):
        out = attend_gathered(q, k_cache, v_cache, read, q_pos, cfg, scale)
    return out, keys_read(read, q_pos, cfg)


def attend_masked(q, k_cache, v_cache, kc, q_pos, cfg: Sparse, scale: float,
                  q_chunk: int = None, k_tile: int = None):
    """A prompt segment: q (B, S, H, D) at positions `q_pos` (S,), the same
    for every row, against the windows (B, W, G, D) and the compressed keys
    (B, Wc, G, D).  Chunks of queries one after another: each selects its
    blocks, then meets the keys a tile at a time, up to its last query's
    own tile and no further.  Returns (out (B, S, H, D) float32, keys read
    (B, S) float32 of one KV head, averaged over them)."""
    b, s, h, d = q.shape
    w, g = k_cache.shape[1], k_cache.shape[2]
    qc = min(q_chunk or Q_CHUNK, s)
    tile = min(k_tile or K_TILE, w) // cfg.block * cfg.block
    pad = -s % qc
    # padded queries repeat the last position: they read what it reads
    q = jnp.pad(q, [(0, 0), (0, pad), (0, 0), (0, 0)])
    q_pos = jnp.pad(q_pos, (0, pad), mode="edge")
    chunks = (s + pad) // qc

    def chunk(args):
        qi, pos = args                      # (B, qc, H, D), (qc,)
        rows = jnp.broadcast_to(pos, (b, qc))
        with jax.named_scope("sparse.select"):
            read = read_blocks(block_scores(qi, kc, rows, cfg, scale), rows,
                               cfg)                          # (B, G, qc, Wb)
        qg = qi.reshape(b, qc, g, h // g, d)

        def one_tile(i, carry):
            top, total, acc = carry
            # the last tile is pulled back inside the window: the keys it
            # shares with the tile before it are not read twice
            at = jnp.minimum(i * tile, w - tile)
            kt = lax.dynamic_slice_in_dim(k_cache, at, tile, axis=1)
            vt = lax.dynamic_slice_in_dim(v_cache, at, tile, axis=1)
            rt = lax.dynamic_slice_in_dim(read, at // cfg.block,
                                          tile // cfg.block, axis=3)
            slot = at + jnp.arange(tile)
            seen = (jnp.repeat(rt, cfg.block, axis=3)
                    & (slot <= pos[:, None]) & (slot >= i * tile))
            scores = jnp.einsum("bsgkd,btgd->bgkst", qg, kt,
                                preferred_element_type=jnp.float32) * scale
            scores = jnp.where(seen[:, :, None], scores, NEG_INF)
            new_top = jnp.maximum(top, scores.max(-1))
            # a query that has seen nothing yet keeps weights of zero
            probs = jnp.where(seen[:, :, None],
                              jnp.exp(scores - new_top[..., None]), 0.0)
            keep = jnp.exp(top - new_top)
            acc = acc * keep[..., None] + jnp.einsum(
                "bgkst,btgd->bgksd", probs.astype(vt.dtype), vt,
                preferred_element_type=jnp.float32)
            return new_top, total * keep + probs.sum(-1), acc

        shape = (b, g, h // g, qc)
        init = (jnp.full(shape, NEG_INF, jnp.float32),
                jnp.zeros(shape, jnp.float32),
                jnp.zeros(shape + (d,), jnp.float32))
        with jax.named_scope("sparse.attend"):
            _, total, acc = lax.fori_loop(0, pos[-1] // tile + 1, one_tile,
                                          init)
            out = acc / jnp.maximum(total, 1e-30)[..., None]
        return (out.transpose(0, 3, 1, 2, 4).reshape(b, qc, h, d),
                keys_read(read, rows, cfg))

    split = lambda t, axis: jnp.moveaxis(
        t.reshape(t.shape[:axis] + (chunks, qc) + t.shape[axis + 1:]),
        axis, 0)
    out, n_read = lax.map(chunk, (split(q, 1), split(q_pos, 0)))
    merge = lambda t: jnp.moveaxis(t, 0, 1).reshape(
        (b, s + pad) + t.shape[3:])[:, :s]
    return merge(out), merge(n_read)
