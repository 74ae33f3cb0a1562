"""Plain reference of the Ouro looped decoder (`model_type: ouro`,
https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json; the
LoopLM paper, arXiv:2510.25741, and the model's `modeling_ouro.py` for what
the config does not say): ONE stack of layers applied `total_ut_steps`
times over the same weights, a norm before and after every sub-layer, the
final norm closing every pass, an exit gate after it.  float32 `jax.numpy`,
every matrix product through `precision.einsum` (float32 at `highest`, or
the float8 control), no kernels, no cache, the loop written out.  Imports
nothing of the program.

The equations (`config.json` keys in brackets), d = `hidden_size`, H =
`num_attention_heads` heads of `head_dim`, one KV head a query head
(`num_key_value_heads` = H), R = `total_ut_steps`, n layers held:

  N(x) = x * rsqrt(mean(x^2) + `rms_norm_eps`) * g        RMSNorm, no bias
  x = E[tokens]                          no multiplier, no position table
  for t = 1..R:                          the SAME n layers every pass
    for i = 0..n-1:
      x = x + N2_i(Attn_i(N1_i(x)))      `input_layernorm` (N1) before,
                                         `input_layernorm_2` (N2) after
      x = x + N4_i(W2_i (silu(W1_i h) * W3_i h)),  h = N3_i(x)
                                         `post_attention_layernorm` (N3),
                                         `post_attention_layernorm_2` (N4);
                                         width `intermediate_size`, silu
    x = N_out(x);  h_t = x               the final norm closes EVERY pass,
                                         and its output enters the next
    g_t = sigmoid(w_g . h_t + b_g)       the exit gate, a Linear(d, 1)
  p_t = g_t prod_{j<t} (1 - g_j) for t < R;  p_R = prod_{j<R} (1 - g_j)
  exit at the first t with p_1 + .. + p_t >= `early_exit_threshold` (= 1:
  the last pass, at every token);  logits = h_R @ W_head  (untied:
  `tie_word_embeddings` false)

  Attn_i: q, k, v = W_q h, W_k h, W_v h, no bias, NO norm over q or k; rotary
    over the whole head, half-split pairing, `rope_theta`, at the token's
    position, the same in every pass; causal softmax at head_dim^-1/2;
    y = W_o o.  Without a cache, "pass t of layer i attends to what pass t
    of layer i wrote" is simply causal attention inside pass t.

The program's names for the gains: `op_norm` (N1), `op_post_norm` (N2),
`ffn_norm` (N3), `ffn_post_norm` (N4), `out_norm`, `exit_w`, `exit_b`.

`forward` returns `(logits, margins)` with margins of ZERO layers (no
router: every served position is compared), and with `gates=True` the
gates g_t (R, B, S) as a third value, for the tests.
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from benchmark.reference.precision import einsum

Spec = collections.namedtuple("Spec", "n_layers n_heads passes eps theta")


def spec_for(constructor: dict) -> Spec:
    """What `forward` needs beside the weights, hashable."""
    c = constructor
    return Spec(len(c["layer_types"]), c["n_heads"], c["n_passes"],
                c["norm_eps"], float(c["rope_theta"]))


def shapes_for(constructor: dict) -> dict:
    """Names and shapes of the model's variables, as the weights' rule
    wants them; the harness holds the program's own tree against this."""
    from jax import ShapeDtypeStruct
    c = constructor
    S = lambda *shape: ShapeDtypeStruct(shape, jnp.float32)
    d, w, v = c["d_model"], c["mlp_width"], c["vocab_size"]
    params = {"embed": S(v, d), "head": S(d, v), "out_norm": S(d),
              "exit_w": S(d), "exit_b": S()}
    for i in range(len(c["layer_types"])):
        params[f"layer{i}"] = dict(
            op_norm=S(d), op_post_norm=S(d), ffn_norm=S(d),
            ffn_post_norm=S(d), wq=S(d, d), wk=S(d, d), wv=S(d, d),
            wo=S(d, d), w1=S(d, w), w3=S(d, w), w2=S(w, d))
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


def attention(p, h, spec: Spec, mode):
    b, s, d = h.shape
    dh = d // spec.n_heads
    heads = lambda w: einsum("bsd,de->bse", h, w, mode).reshape(
        b, s, spec.n_heads, dh)
    q, k = rotary(heads(p["wq"]), spec.theta), rotary(heads(p["wk"]),
                                                      spec.theta)
    scores = einsum("bqhd,bkhd->bhqk", q, k, mode) * dh ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    o = einsum("bhqk,bkhd->bqhd", probs, heads(p["wv"]), mode)
    return einsum("bsd,de->bse", o.reshape(b, s, d), p["wo"], mode)


def mlp(p, h, mode):
    up = (jax.nn.silu(einsum("bsd,dw->bsw", h, p["w1"], mode))
          * einsum("bsd,dw->bsw", h, p["w3"], mode))
    return einsum("bsw,wd->bsd", up, p["w2"], mode)


def forward(params, tokens, spec: Spec, mode: str = "f32",
            gates: bool = False):
    """tokens (B, S) int32 -> `(logits (B, S, vocab) float32, margins (0,
    B, S))`, and the passes' gates (R, B, S) where `gates`."""
    x = params["embed"][tokens]
    seen = []
    for _ in range(spec.passes):
        for i in range(spec.n_layers):
            p = params[f"layer{i}"]
            y = attention(p, rms_norm(x, p["op_norm"], spec.eps), spec, mode)
            x = x + rms_norm(y, p["op_post_norm"], spec.eps)
            y = mlp(p, rms_norm(x, p["ffn_norm"], spec.eps), mode)
            x = x + rms_norm(y, p["ffn_post_norm"], spec.eps)
        x = rms_norm(x, params["out_norm"], spec.eps)
        seen.append(jax.nn.sigmoid(
            einsum("bsd,d->bs", x, params["exit_w"], mode)
            + params["exit_b"]))
    # `early_exit_threshold` 1: the exit distribution's sum reaches 1 at
    # the last pass only, so every token leaves there
    logits = einsum("bsd,dv->bsv", x, params["head"], mode)
    margins = jnp.zeros((0,) + tokens.shape, jnp.float32)
    return (logits, margins, jnp.stack(seen)) if gates else (logits, margins)


def exit_expected(gates):
    """sum_t t * p_t of gates (R, ...): the pass at which the gates' own
    distribution leaves, on average (between 1 and R)."""
    r = gates.shape[0]
    left, out = jnp.ones_like(gates[0]), 0.0
    for t in range(r - 1):
        out = out + (t + 1) * gates[t] * left
        left = left * (1.0 - gates[t])
    return out + r * left


def reach(constructor: dict) -> list:
    """No router, no near-tie: nothing is left out of the comparison."""
    return []


def forward_flops(constructor: dict, first: int, last: int) -> int:
    """Forward operations the tokens at positions first..last-1 of one
    sequence require (a multiply-add is two): `n_passes` times the layers'
    products, the gate's, and each layer's attention over the t+1 visible
    keys and values of the token at position t; the head once."""
    c = constructor
    n = last - first
    if n <= 0:
        return 0
    d, layers, passes = c["d_model"], len(c["layer_types"]), c["n_passes"]
    a_pass = layers * (4 * d * d + 3 * d * c["mlp_width"]) + d
    keys = (first + 1 + last) * n // 2                  # sum of t+1
    return (2 * n * (passes * a_pass + d * c["vocab_size"])
            + 4 * passes * layers * d * keys)
