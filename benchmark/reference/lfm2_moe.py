"""Plain reference of the LFM2-MoE decoder (`model_type: lfm2_moe`,
https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json): a
per-layer list of short-convolution and grouped-attention mixers over a
dense gated MLP (the first `num_dense_layers` layers) or dropless
sigmoid-routed experts (every other layer).  float32 `jax.numpy`, every
matrix product through `precision.einsum` (float32 at `highest`, or the
float8 control), no kernels, no cache, no grouping: every expert is applied
to every token and weighted by a gate that is 0 where it was not chosen.
Imports nothing of the program.

The equations (`config.json` keys in brackets), d = `hidden_size`:

  n(x) = x * rsqrt(mean(x^2) + `norm_eps`) * g          RMSNorm, no bias
  x0 = E[tokens]                                        no position table
  x  = x + mixer_i(n_op(x));  x = x + ffn_i(n_ffn(x))   each layer
  logits = n_out(x_L) @ E^T

  `conv` layer [`layer_types`, `conv_L_cache` = K]: [B, C, z] = W_in h, split
    in that order; u = B * z; c_t = sum_{j<K} k_j u_{t-K+1+j} per channel
    (depthwise, causal, u_t = 0 for t < 0, no activation); y = W_out (C * c).
  `full_attention` layer: q, k, v = W_q h, W_k h, W_v h with
    `num_attention_heads` query and `num_key_value_heads` KV heads of d /
    heads each; RMSNorm over each head of q and of k (own scales) BEFORE
    rotary; rotary over the whole head, half-split pairing, `rope_theta`;
    KV head j serves query heads j*G .. j*G+G-1; causal softmax at
    head^-1/2; y = W_o o.
  dense MLP: W_2 (silu(W_1 h) * W_3 h), width `intermediate_size`.
  expert layer: s = sigmoid(W_r h), `num_experts` scores; chosen = top
    `num_experts_per_tok` of s + b (`use_expert_bias`: b enters the choice
    only); weights = s at the chosen over their sum + 1e-6
    (`norm_topk_prob`), times `routed_scaling_factor` = 1; y = sum_chosen
    w_e W2_e (silu(W1_e h) * W3_e h), width `moe_intermediate_size`.  No
    shared expert, no token dropped.

Departures and assumptions, each also in the configuration file: the head
is tied to E (the key is absent from the catalog's config: the LFM2
family's convention); the head size is d / heads (`head_dim` is not
given); the depthwise convolution's three multiply-adds a channel are
elementwise float32 in both precisions (the control rounds the operands of
matrix products).

`forward` also returns each (expert layer, position)'s margin: the k-th
selection score less the (k+1)-th.  Where it is small the program's
bfloat16 may choose another expert with no fault, and the driver leaves
the positions such a choice can reach out of the comparison (`reach`).
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from benchmark.reference.precision import einsum

CONV, ATTENTION = "conv", "full_attention"

Spec = collections.namedtuple(
    "Spec", "layer_types n_heads n_kv_heads n_dense top_k eps theta")


def spec_for(constructor: dict) -> Spec:
    """What `forward` needs beside the weights, hashable."""
    c = constructor
    return Spec(tuple(c["layer_types"]), c["n_heads"], c["n_kv_heads"],
                c["n_dense_layers"], c["experts_per_token"], c["norm_eps"],
                float(c["rope_theta"]))


def shapes_for(constructor: dict) -> dict:
    """Names and shapes of the model's variables, as the weights' rule
    wants them; the harness holds the program's own tree against this."""
    from jax import ShapeDtypeStruct
    c = constructor
    S = lambda *shape: ShapeDtypeStruct(shape, jnp.float32)
    d, heads = c["d_model"], c["n_heads"]
    dh = d // heads
    kv = c["n_kv_heads"] * dh
    params = {"embed": S(c["vocab_size"], d), "out_norm": S(d)}
    if not c.get("tie_embeddings", True):
        params["head"] = S(d, c["vocab_size"])
    for i, kind in enumerate(c["layer_types"]):
        layer = {"op_norm": S(d), "ffn_norm": S(d)}
        if kind == CONV:
            layer.update(conv_in=S(d, 3 * d),
                         conv_taps=S(c.get("conv_kernel", 3), d),
                         conv_out=S(d, d))
        else:
            layer.update(wq=S(d, d), wk=S(d, kv), wv=S(d, kv), wo=S(d, d),
                         q_norm=S(dh), k_norm=S(dh))
        if i < c["n_dense_layers"]:
            w = c["mlp_width"]
            layer.update(w1=S(d, w), w3=S(d, w), w2=S(w, d))
        else:
            e, w = c["n_experts"], c["expert_width"]
            layer.update(router=S(d, e), expert_bias=S(e), w1=S(e, d, w),
                         w3=S(e, d, w), w2=S(e, w, d))
        params[f"layer{i}"] = layer
    return {"params": params}


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


def conv_mixer(p, h, mode):
    bcz = einsum("bsd,de->bse", h, p["conv_in"], mode)
    gate_b, gate_c, z = jnp.split(bcz, 3, -1)
    u = gate_b * z
    taps = p["conv_taps"]
    k, s = taps.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(taps[j] * padded[:, j:j + s] for j in range(k))
    return einsum("bsd,de->bse", gate_c * conv, p["conv_out"], mode)


def attention_mixer(p, h, spec: Spec, mode):
    b, s, d = h.shape
    dh = d // spec.n_heads
    group = spec.n_heads // spec.n_kv_heads
    proj = lambda w, n: einsum("bsd,de->bse", h, w, mode).reshape(b, s, n, dh)
    q = proj(p["wq"], spec.n_heads)
    k = proj(p["wk"], spec.n_kv_heads)
    v = proj(p["wv"], spec.n_kv_heads)
    q = rotary(rms_norm(q, p["q_norm"], spec.eps), spec.theta)
    k = rotary(rms_norm(k, p["k_norm"], spec.eps), spec.theta)
    # KV head j serves the query heads j*group .. j*group + group - 1
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = einsum("bqhd,bkhd->bhqk", q, k, mode) * dh ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    o = einsum("bhqk,bkhd->bqhd", probs, v, mode).reshape(b, s, d)
    return einsum("bsd,de->bse", o, p["wo"], mode)


def dense_mlp(p, h, mode):
    up = (jax.nn.silu(einsum("bsd,dw->bsw", h, p["w1"], mode))
          * einsum("bsd,dw->bsw", h, p["w3"], mode))
    return einsum("bsw,wd->bsd", up, p["w2"], mode)


def expert_mlp(p, h, spec: Spec, mode):
    """Every expert on every token, weighted by a gate that is 0 where the
    expert was not chosen: `(y, margin (B, S))`."""
    scores = jax.nn.sigmoid(einsum("bsd,de->bse", h, p["router"], mode))
    select = scores + p["expert_bias"]
    ranked = jnp.sort(select, axis=-1)[..., ::-1]
    chosen = select >= ranked[..., spec.top_k - 1:spec.top_k]
    gate = jnp.where(chosen, scores, 0.0)
    gate = gate / (gate.sum(-1, keepdims=True) + 1e-6)
    up = (jax.nn.silu(einsum("bsd,edw->bsew", h, p["w1"], mode))
          * einsum("bsd,edw->bsew", h, p["w3"], mode))
    y = einsum("bsew,ewd->bsed", up, p["w2"], mode)
    margin = ranked[..., spec.top_k - 1] - ranked[..., spec.top_k]
    return (y * gate[..., None]).sum(-2), margin


def forward(params, tokens, spec: Spec, mode: str = "f32"):
    """tokens (B, S) int32 -> `(logits (B, S, vocab) float32, margins
    (expert layers, B, S))`."""
    x = params["embed"][tokens]
    margins = []
    for i, kind in enumerate(spec.layer_types):
        p = params[f"layer{i}"]
        h = rms_norm(x, p["op_norm"], spec.eps)
        x = x + (conv_mixer(p, h, mode) if kind == CONV
                 else attention_mixer(p, h, spec, mode))
        h = rms_norm(x, p["ffn_norm"], spec.eps)
        if i < spec.n_dense:
            x = x + dense_mlp(p, h, mode)
        else:
            y, margin = expert_mlp(p, h, spec, mode)
            x = x + y
            margins.append(margin)
    x = rms_norm(x, params["out_norm"], spec.eps)
    head = params["head"] if "head" in params else params["embed"].T
    return einsum("bsd,dv->bsv", x, head, mode), jnp.stack(margins)


def reach(constructor: dict) -> list:
    """For each expert layer, how many positions past its own a changed
    choice at a position can reach: K-1 a later convolution layer, and
    None (every later position) where an attention layer follows."""
    kinds = constructor["layer_types"]
    k = constructor.get("conv_kernel", 3)
    out = []
    for i in range(constructor["n_dense_layers"], len(kinds)):
        later = kinds[i + 1:]
        out.append(None if ATTENTION in later else (k - 1) * len(later))
    return out


def forward_flops(constructor: dict, first: int, last: int) -> int:
    """Forward operations the tokens at positions first..last-1 of one
    sequence require (a multiply-add is two): the weights a token
    multiplies (its `experts_per_token` experts, not all), the head, and
    in each attention layer the t+1 visible keys and values of the token
    at position t."""
    c = constructor
    n = last - first
    if n <= 0:
        return 0
    d = c["d_model"]
    kv = c["n_kv_heads"] * (d // c["n_heads"])
    weights = d * c["vocab_size"]
    attention_layers = 0
    for i, kind in enumerate(c["layer_types"]):
        if kind == CONV:
            weights += 4 * d * d + c.get("conv_kernel", 3) * d
        else:
            weights += 2 * d * d + 2 * d * kv
            attention_layers += 1
        if i < c["n_dense_layers"]:
            weights += 3 * d * c["mlp_width"]
        else:
            weights += (d * c["n_experts"]
                        + c["experts_per_token"] * 3 * d * c["expert_width"])
    keys = (first + 1 + last) * n // 2                  # sum of t+1
    return 2 * n * weights + 4 * attention_layers * d * keys
