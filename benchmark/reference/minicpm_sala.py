"""Plain reference of the MiniCPM-SALA decoder (`model_type: minicpm_sala`,
https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json): a
per-layer list (`mixer_types`) of `minicpm4` layers (block-sparse softmax
attention, InfLLM-v2 as MiniCPM4 runs it) and `lightning-attn` layers
(linear attention with a per-head decay) over gated MLPs, with MiniCPM's
three multipliers.  float32 `jax.numpy`, every matrix product through
`precision.einsum` (float32 at `highest`, or the float8 control); no
kernels, no cache, no state: the recurrence is a scan over positions, the
compressed keys are averaged from all of K, the blocks are chosen per token
by rank.  Imports nothing of the program.

The equations (`config.json` keys in brackets); d = `hidden_size`, H =
`num_attention_heads`, G = `num_key_value_heads`, D = d / H, n(x) = x *
rsqrt(mean(x^2) + `rms_norm_eps`) * g, no bias anywhere:

  x0 = `scale_emb` * E[tokens]
  x  = x + a * mixer_i(n(x));  x = x + a * W2(silu(W1 n(x)) * W3 n(x))
       a = `scale_depth` / sqrt(`num_hidden_layers`), the PUBLISHED depth
  logits = head(n(x_L) / (d / `dim_model_base`))             untied head

  `lightning-attn`: q, k, v = Wq h, Wk h, Wv h, H heads each; n over each
    head of q and of k (learned gains); rotary (theta `rope_theta`,
    half-split pairing, whole head) on q and k; q / sqrt(D);
    S_t = exp(-s_j) S_{t-1} + k_t^T v_t for head j, s_j = 2^(-8 (j + 1) / H);
    o_t = q_t S_t; o <- n(o) over the concatenated d (`use_output_norm`);
    y = Wo (sigmoid(Wg h) * o) (`use_output_gate`).
  `minicpm4`: q = Wq h (H heads), k, v = Wk h, Wv h (G heads); n over each
    head of q and k; no rotary (`attn_use_rope` false); scale 1 / sqrt(D).
    Kc_j = mean(K[stride j : stride j + kernel]) for every j with stride j
    + kernel <= t + 1.  For the query at t and KV head g: p = softmax_j(q
    Kc_j / sqrt(D)) per query head over those j, summed over the H / G
    heads of g; block b (tokens block b .. block b + block - 1) scores the
    max of p_j over the j whose window overlaps it.  Read: the first
    `init_blocks` blocks, the `window_size` / block blocks ending at the
    query's own, and of the rest the `topk` by score (the earlier block
    where two tie: neighbours share a window, and tie exactly when it is
    the largest of both); every visible block while t + 1 <= `dense_len`.  Causal softmax over the tokens of the read
    blocks; y = Wo (sigmoid(Wg h) * o) (`attn_use_output_gate`).

What the catalog's config lacks (the `sparse_config` numbers, the slopes,
the output norm's extent, the depth under `a`) is listed with its origin
under `assumed` in the configuration file.  The recurrence's two products
a position (k^T v, q S) are float32 in both precisions, like the running
sums of a scan: the control rounds the operands of the weight products and
of the softmax attention.

A row of 33,280 tokens is computed in blocks of `Spec.positions` positions
wherever a temporary would otherwise hold positions x 16,384 or positions x
keys; `embed`, `layer` and `head` are the pieces `forward` is made of, so
that a caller short of memory may hold the weights on the host and bring
them up a layer at a time.

`mode` beside the two precisions: `swap` reads, of the ranked rest, the
(topk + 1)-th block in place of the topk-th (what a near-tie costs), and
`dense` reads every visible block (a model without its selection).
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import precision

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"

Spec = collections.namedtuple(
    "Spec", "layer_types n_heads n_kv_heads eps theta embed_scale "
    "residual_scale logit_scale block kernel stride window init_blocks topk "
    "dense_len positions")


def spec_for(constructor: dict, positions: int = 128) -> Spec:
    """What `forward` needs beside the weights, hashable."""
    c = constructor
    return Spec(tuple(c["layer_types"]), c["n_heads"], c["n_kv_heads"],
                c["norm_eps"], float(c["rope_theta"]), c["embed_scale"],
                c["residual_scale"], c["logit_scale"], c["sparse_block"],
                c["sparse_kernel"], c["sparse_stride"], c["sparse_window"],
                c["sparse_init_blocks"], c["sparse_topk"],
                c["sparse_dense_len"], positions)


def shapes_for(constructor: dict) -> dict:
    """Names and shapes of the model's variables, as the weights' rule
    wants them; the harness holds the program's own tree against this."""
    from jax import ShapeDtypeStruct
    c = constructor
    S = lambda *shape: ShapeDtypeStruct(shape, jnp.float32)
    d, w = c["d_model"], c["mlp_width"]
    dh = d // c["n_heads"]
    params = {"embed": S(c["vocab_size"], d), "out_norm": S(d),
              "head": S(d, c["vocab_size"])}
    for i, kind in enumerate(c["layer_types"]):
        kv = d if kind == LIGHTNING else c["n_kv_heads"] * dh
        layer = {"op_norm": S(d), "ffn_norm": S(d), "wq": S(d, d),
                 "wk": S(d, kv), "wv": S(d, kv), "wo": S(d, d),
                 "wg": S(d, d), "q_norm": S(dh), "k_norm": S(dh),
                 "w1": S(d, w), "w3": S(d, w), "w2": S(w, d)}
        if kind == LIGHTNING:
            layer["o_norm"] = S(d)
        params[f"layer{i}"] = layer
    return {"params": params}


def _einsum(spec: str, a, b, mode: str):
    return precision.einsum(spec, a, b, "fp8" if mode == "fp8" else "f32")


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """x (B, S, H, D) at positions 0..S-1, half-split pairing."""
    s, half = x.shape[1], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def by_positions(fn, arrays: tuple, n: int):
    """`fn` over blocks of `n` positions (axis 1 of every array), one after
    another; a last block is padded with zeros and cut again."""
    s = arrays[0].shape[1]
    pad = -s % n
    cut = lambda t: jnp.moveaxis(jnp.pad(
        t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2)).reshape(
            (t.shape[0], (s + pad) // n, n) + t.shape[2:]), 1, 0)
    out = jax.lax.map(lambda xs: fn(*xs), tuple(cut(t) for t in arrays))
    join = lambda t: jnp.moveaxis(t, 0, 1).reshape(
        (t.shape[1], s + pad) + t.shape[3:])[:, :s]
    return jax.tree_util.tree_map(join, out)


def project(h, w, heads: int, mode: str, n: int):
    """W h, split into `heads` heads: (B, S, heads, D)."""
    y = by_positions(lambda t: _einsum("bsd,de->bse", t, w, mode), (h,), n)
    return y.reshape(h.shape[:2] + (heads, -1))


def gated_out(p, h, o, mode: str, n: int):
    """Wo (sigmoid(Wg h) * o), o (B, S, d)."""
    return by_positions(
        lambda ht, ot: _einsum(
            "bsd,de->bse",
            jax.nn.sigmoid(_einsum("bsd,de->bse", ht, p["wg"], mode)) * ot,
            p["wo"], mode), (h, o), n)


def lightning_mixer(p, h, spec: Spec, mode: str):
    b, s, d = h.shape
    heads, n = spec.n_heads, spec.positions
    dh = d // heads
    q = rotary(rms_norm(project(h, p["wq"], heads, mode, n), p["q_norm"],
                        spec.eps), spec.theta) * dh ** -0.5
    k = rotary(rms_norm(project(h, p["wk"], heads, mode, n), p["k_norm"],
                        spec.eps), spec.theta)
    v = project(h, p["wv"], heads, mode, n)
    slopes = 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32)
                     / heads)
    decay = jnp.exp(-slopes)[None, :, None, None]

    def position(state, qkv):
        qt, kt, vt = qkv                                     # (B, H, D)
        state = decay * state + precision.einsum("bhd,bhe->bhde", kt, vt,
                                                 "f32")
        return state, precision.einsum("bhd,bhde->bhe", qt, state, "f32")

    _, o = jax.lax.scan(position, jnp.zeros((b, heads, dh, dh), jnp.float32),
                        tuple(t.swapaxes(0, 1) for t in (q, k, v)))
    o = rms_norm(o.swapaxes(0, 1).reshape(b, s, d), p["o_norm"], spec.eps)
    return gated_out(p, h, o, mode, n)


def _overlaps(spec: Spec, n_windows: int, n_blocks: int) -> np.ndarray:
    """For each block the compressed keys whose window overlaps it, as
    indices (n_blocks, most a block has), -1 where it has fewer."""
    j, b = np.arange(n_windows)[:, None], np.arange(n_blocks)[None, :]
    over = ((spec.stride * j + spec.kernel - 1 >= spec.block * b)
            & (spec.stride * j <= spec.block * b + spec.block - 1))
    most = max(int(over.sum(0).max()), 1) if n_windows else 1
    out = np.full((n_blocks, most), -1, np.int64)
    for blk in range(n_blocks):
        found = np.nonzero(over[:, blk])[0]
        out[blk, :len(found)] = found
    return out


def sparse_mixer(p, h, spec: Spec, mode: str):
    b, s, d = h.shape
    heads, groups, n = spec.n_heads, spec.n_kv_heads, spec.positions
    dh, per = d // heads, heads // groups
    q = rms_norm(project(h, p["wq"], heads, mode, n), p["q_norm"], spec.eps)
    k = rms_norm(project(h, p["wk"], groups, mode, n), p["k_norm"], spec.eps)
    v = project(h, p["wv"], groups, mode, n)
    n_blocks = -(-s // spec.block)
    n_windows = max((s - spec.kernel) // spec.stride + 1, 0)
    at = spec.stride * np.arange(n_windows)[:, None] + np.arange(spec.kernel)
    kc = k[:, at].mean(2)                                    # (B, J, G, D)
    overlaps = _overlaps(spec, n_windows, n_blocks)
    blocks = jnp.arange(n_blocks)
    key_at = jnp.arange(s)

    def chunk(qt, t):
        """qt (B, n, H, D) at positions t (B, n) (all rows alike)."""
        t = t[0]
        qg = qt.reshape(b, n, groups, per, dh)
        complete = (spec.stride * jnp.arange(n_windows) + spec.kernel
                    <= t[:, None] + 1)                       # (n, J)
        logits = _einsum("bsgkd,bjgd->bgksj", qg, kc, mode) * dh ** -0.5
        logits = jnp.where(complete, logits, -jnp.inf)
        p_j = jnp.where(complete, jnp.exp(
            logits - jnp.max(logits, -1, keepdims=True, initial=-1e30)), 0.0)
        p_j = (p_j / jnp.maximum(p_j.sum(-1, keepdims=True), 1e-30)).sum(2)
        padded = jnp.pad(p_j, [(0, 0)] * 3 + [(0, 1)])       # index -1 -> 0
        score = padded[..., overlaps].max(-1)                # (B, G, n, Wb)
        own = (t // spec.block)[:, None]
        visible = blocks <= own
        forced = visible & ((blocks < spec.init_blocks)
                            | (own - blocks < spec.window // spec.block))
        rest = visible & ~forced
        masked = jnp.where(rest, score, -1.0)
        rank = jnp.argsort(jnp.argsort(-masked, axis=-1, stable=True),
                           axis=-1, stable=True)
        if mode == "swap":
            chosen = rest & ((rank < spec.topk - 1) | (rank == spec.topk))
        else:
            chosen = rest & (rank < spec.topk)
        read = forced | chosen | (visible & (t + 1 <= spec.dense_len)[:, None])
        if mode == "dense":
            read = jnp.broadcast_to(visible, read.shape)
        seen = (jnp.repeat(read, spec.block, axis=-1)[..., :s]
                & (key_at <= t[:, None]))                    # (B, G, n, S)
        scores = _einsum("bsgkd,btgd->bgkst", qg, k, mode) * dh ** -0.5
        probs = jax.nn.softmax(jnp.where(seen[:, :, None], scores, -1e30), -1)
        o = _einsum("bgkst,btgd->bsgkd", probs, v, mode)
        return o.reshape(b, n, d)

    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    return gated_out(p, h, by_positions(chunk, (q, positions), n), mode, n)


def mlp(p, h, mode: str, n: int):
    def block(t):
        up = (jax.nn.silu(_einsum("bsd,dw->bsw", t, p["w1"], mode))
              * _einsum("bsd,dw->bsw", t, p["w3"], mode))
        return _einsum("bsw,wd->bsd", up, p["w2"], mode)
    return by_positions(block, (h,), n)


def embed(params, tokens, spec: Spec):
    return spec.embed_scale * params["embed"][tokens]


def layer(p, x, spec: Spec, kind: str, mode: str = "f32"):
    """One layer over the residual stream x (B, S, d)."""
    h = rms_norm(x, p["op_norm"], spec.eps)
    mixer = sparse_mixer if kind == SPARSE else lightning_mixer
    x = x + spec.residual_scale * mixer(p, h, spec, mode)
    h = rms_norm(x, p["ffn_norm"], spec.eps)
    return x + spec.residual_scale * mlp(p, h, mode, spec.positions)


def head(params, x, spec: Spec, mode: str = "f32"):
    """Logits of the hidden states x (B, S, d) before the last norm."""
    h = rms_norm(x, params["out_norm"], spec.eps) * spec.logit_scale
    return by_positions(
        lambda t: _einsum("bsd,dv->bsv", t, params["head"], mode), (h,),
        spec.positions)


def forward(params, tokens, spec: Spec, mode: str = "f32"):
    """tokens (B, S) int32 -> `(logits (B, S, vocab) float32, margins (0,
    B, S))`: no margins, see `reach`."""
    x = embed(params, tokens, spec)
    for i, kind in enumerate(spec.layer_types):
        x = layer(params[f"layer{i}"], x, spec, kind, mode)
    return (head(params, x, spec, mode),
            jnp.ones((0,) + tokens.shape, jnp.float32))


def reach(constructor: dict) -> list:
    """No layer reports margins.  The 64th and 65th block of some 500 lie
    a thousandth of their score apart, so bfloat16 reads another block
    than this reference at most positions, and a changed selection reaches
    every later position: margins would leave nothing to compare.  What
    such a block moves is inside the comparison's limit instead (`swap`
    measures it; PERF.md section 2)."""
    return []


def keys_read(constructor: dict, t: np.ndarray) -> np.ndarray:
    """Keys the query at position t reads in a `minicpm4` layer (one KV
    head's): all t + 1 up to `dense_len`, then the initial blocks, the
    local window and `topk` blocks, as far as the row has them."""
    c = constructor
    block, local = c["sparse_block"], c["sparse_window"] // c["sparse_block"]
    own = t // block
    init = np.clip(own - local + 1, 0, c["sparse_init_blocks"])
    rest = np.maximum(own - local + 1 - init, 0)
    sparse = (block * (init + np.minimum(own, local - 1)
                       + np.minimum(rest, c["sparse_topk"]))
              + t % block + 1)
    return np.where(t + 1 <= c["sparse_dense_len"], t + 1, sparse)


def forward_flops(constructor: dict, first: int, last: int) -> int:
    """Forward operations the tokens at positions first..last-1 of one
    sequence require (a multiply-add is two): the weights a token
    multiplies and the head; in a `lightning-attn` layer the state's update
    and its read; in a `minicpm4` layer the complete compressed keys (the
    indexer's product) and the keys the token READS, not those it could
    see."""
    c = constructor
    n = last - first
    if n <= 0:
        return 0
    d = c["d_model"]
    dh = d // c["n_heads"]
    kv = c["n_kv_heads"] * dh
    kinds = list(c["layer_types"])
    n_sparse = kinds.count(SPARSE)
    n_linear = kinds.count(LIGHTNING)
    weights = (d * c["vocab_size"] + len(kinds) * 3 * d * c["mlp_width"]
               + n_linear * 5 * d * d + n_sparse * (3 * d * d + 2 * d * kv))
    t = np.arange(first, last, dtype=np.int64)
    windows = np.maximum((t + 1 - c["sparse_kernel"])
                         // c["sparse_stride"] + 1, 0)
    sparse = int((4 * d * keys_read(c, t) + 2 * d * windows).sum())
    return int(2 * n * weights + n * n_linear * 4 * d * dh
               + n_sparse * sparse)
