"""Plain reference of ResNet-50 at inference (He et al., arXiv:1512.03385,
Table 1, 50-layer column; v1.5 placement of the stride on the 3x3 conv, as
the program has it): 7x7/2 stem, BatchNorm, ReLU, 3x3/2 max pool, four
stages of (3, 4, 6, 3) bottleneck blocks, global average pool, a dense
head.  float32 `jax.numpy` and `lax` convolutions at `highest`; BatchNorm
uses its stored mean and variance.  Imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.precision import rounded

BN_EPS = 1e-5
STAGES = (3, 4, 6, 3)
WIDTHS = (64, 128, 256, 512)


def variable_shapes(num_classes: int = 1000, stage_sizes=STAGES,
                    widths=WIDTHS) -> dict:
    """Names and shapes of the model's variables, as `make_variables`
    wants them; the harness holds the program's own tree against this."""
    from jax import ShapeDtypeStruct as S
    f32 = jnp.float32
    params, stats = {}, {}

    def conv_bn(where_p, where_s, i, k, cin, cout):
        where_p[f"Conv_{i}"] = {"kernel": S((k, k, cin, cout), f32)}
        where_p[f"BatchNorm_{i}"] = {"scale": S((cout,), f32),
                                     "bias": S((cout,), f32)}
        where_s[f"BatchNorm_{i}"] = {"mean": S((cout,), f32),
                                     "var": S((cout,), f32)}

    stem_p, stem_s = {}, {}
    conv_bn(stem_p, stem_s, 0, 7, 3, 64)
    params["stem_conv"] = stem_p.pop("Conv_0")
    params.update(stem_p)
    stats.update(stem_s)
    cin, n = 64, 0
    for blocks, w in zip(stage_sizes, widths):
        for b in range(blocks):
            p, s = {}, {}
            conv_bn(p, s, 0, 1, cin, w)
            conv_bn(p, s, 1, 3, w, w)
            conv_bn(p, s, 2, 1, w, 4 * w)
            if b == 0:
                conv_bn(p, s, 3, 1, cin, 4 * w)
            params[f"ResNetBottleneckBlock_{n}"] = p
            stats[f"ResNetBottleneckBlock_{n}"] = s
            cin, n = 4 * w, n + 1
    params["out"] = {"kernel": S((cin, num_classes), f32),
                     "bias": S((num_classes,), f32)}
    return {"params": params, "batch_stats": stats}


def conv(x, kernel, stride: int, mode: str):
    return jax.lax.conv_general_dilated(
        rounded(x, mode), rounded(kernel, mode), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def batch_norm(x, p, stats):
    inv = jax.lax.rsqrt(stats["var"] + BN_EPS) * p["scale"]
    return (x - stats["mean"]) * inv + p["bias"]


def bottleneck(p, stats, x, stride: int, mode: str):
    def cbn(i, t, s):
        return batch_norm(conv(t, p[f"Conv_{i}"]["kernel"], s, mode),
                          p[f"BatchNorm_{i}"], stats[f"BatchNorm_{i}"])
    y = jax.nn.relu(cbn(0, x, 1))
    y = jax.nn.relu(cbn(1, y, stride))
    y = cbn(2, y, 1)
    if "Conv_3" in p:
        x = cbn(3, x, stride)
    return jax.nn.relu(y + x)


def forward(variables, images, mode: str = "f32", stage_sizes=STAGES):
    """images (N, 224, 224, 3), any real dtype -> logits (N, 1000)."""
    p, stats = variables["params"], variables["batch_stats"]
    x = conv(images.astype(jnp.float32), p["stem_conv"]["kernel"], 2, mode)
    x = jax.nn.relu(batch_norm(x, p["BatchNorm_0"], stats["BatchNorm_0"]))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    n = 0
    for stage, blocks in enumerate(stage_sizes):
        for b in range(blocks):
            name = f"ResNetBottleneckBlock_{n}"
            stride = 2 if b == 0 and stage > 0 else 1
            x = bottleneck(p[name], stats[name], x, stride, mode)
            n += 1
    x = x.mean(axis=(1, 2))
    out = p["out"]
    return jnp.einsum("ni,io->no", rounded(x, mode),
                      rounded(out["kernel"], mode),
                      precision=jax.lax.Precision.HIGHEST) + out["bias"]
