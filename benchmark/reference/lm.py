"""Plain reference of the GPT-2-shaped decoder the LM cells run
(Cerebras-GPT, arXiv:2304.03208): learned positions, pre-norm blocks
(LayerNorm, fused QKV, causal softmax attention, projection; LayerNorm,
MLP x4 with tanh-GELU), final LayerNorm, an untied head with a bias (the
departure the configuration file lists).  float32 `jax.numpy`, no
kernels, no cache, no batching tricks; imports nothing of the program.

`params` is the tree `weights.make_variables` made, under the names the
program's checkpoint format gives its leaves.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.precision import einsum

LN_EPS = 1e-6


def variable_shapes(vocab_size: int, d_model: int, n_layers: int,
                    max_len: int, mlp_ratio: int = 4) -> dict:
    """Names and shapes of the model's variables, as `make_variables`
    wants them; the harness holds the program's own tree against this."""
    from jax import ShapeDtypeStruct as S
    f32 = jnp.float32
    d, h = d_model, mlp_ratio * d_model

    def dense(i, o):
        return {"kernel": S((i, o), f32), "bias": S((o,), f32)}

    def norm():
        return {"scale": S((d,), f32), "bias": S((d,), f32)}

    params = {"tok_embed": {"embedding": S((vocab_size, d), f32)},
              "pos_embed": {"embedding": S((max_len, d), f32)},
              "final_norm_w": norm(), "lm_head": dense(d, vocab_size)}
    for i in range(n_layers):
        params[f"block{i}_w"] = {
            "LayerNorm_0": norm(), "qkv": dense(d, 3 * d),
            "proj": dense(d, d), "LayerNorm_1": norm(),
            "mlp_up": dense(d, h), "mlp_down": dense(h, d)}
    return {"params": params}


def shapes_for(constructor: dict) -> dict:
    """`variable_shapes` from a configuration file's `constructor`."""
    c = constructor
    return variable_shapes(c["vocab_size"], c["d_model"], c["n_layers"],
                           c["max_len"], c.get("mlp_ratio", 4))


def layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def dense(x, p, mode):
    return einsum("...i,io->...o", x, p["kernel"], mode) + p["bias"]


def block(p, x, n_heads: int, mode: str):
    b, s, d = x.shape
    dh = d // n_heads
    qkv = dense(layer_norm(x, p["LayerNorm_0"]), p["qkv"], mode)
    q, k, v = (t.reshape(b, s, n_heads, dh) for t in jnp.split(qkv, 3, -1))
    scores = einsum("bqhd,bkhd->bhqk", q, k, mode) * dh ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    o = einsum("bhqk,bkhd->bqhd", probs, v, mode).reshape(b, s, d)
    x = x + dense(o, p["proj"], mode)
    h = dense(layer_norm(x, p["LayerNorm_1"]), p["mlp_up"], mode)
    return x + dense(jax.nn.gelu(h, approximate=True), p["mlp_down"], mode)


def forward(params, tokens, n_heads: int, mode: str = "f32",
            remat: bool = False):
    """tokens (B, S) int32 -> logits (B, S, vocab) float32."""
    s = tokens.shape[1]
    x = params["tok_embed"]["embedding"][tokens] \
        + params["pos_embed"]["embedding"][:s][None]
    n_layers = sum(k.startswith("block") for k in params)
    step = jax.checkpoint(block, static_argnums=(2, 3)) if remat else block
    for i in range(n_layers):
        x = step(params[f"block{i}_w"], x, n_heads, mode)
    x = layer_norm(x, params["final_norm_w"])
    return dense(x, params["lm_head"], mode)


def loss(params, tokens, targets, n_heads: int, mode: str = "f32"):
    """Mean softmax cross-entropy over every token of the batch."""
    logits = forward(params, tokens, n_heads, mode, remat=True)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return (logz - picked).mean()
