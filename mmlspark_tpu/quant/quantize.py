"""Post-training quantization of model bundles.

The serving-stack answer to "every inference path computes in float32":
weights are quantized ONCE, offline, and the compiled forward consumes them
directly — int8 weights stay int8 in HBM (and over the host->HBM link, a
4x byte reduction), and the dequantization is part of the jitted program,
fused by XLA into the weight read / matmul epilogue rather than ever
materializing a float copy in HBM.

Two modes (`quantize_bundle`):

  * ``bf16`` — cast the whole variable tree to bfloat16 and set the
    module's compute dtype to bfloat16: half the weight bytes, full MXU
    bf16 rates, no extra machinery.  The standard TPU serving dtype.
  * ``int8`` — per-output-channel symmetric int8 for every dense/conv
    ``kernel`` leaf (GPTQ-class weight-only PTQ): the int8 tensor replaces
    the kernel and a float32 ``kernel_scale`` vector (one scale per output
    channel) is stored alongside; norms, biases, embeddings, and MoE
    expert kernels stay bfloat16.  The forward runs int8 weights x bf16
    activations with the per-channel rescale applied AFTER the matmul
    (quant/modules.py) — int8 -> bf16 conversion is exact (|q| <= 127 fits
    bf16's mantissa), so the fused form loses nothing over
    dequantize-then-matmul and skips the float weight copy entirely.

Layout contract (what tests/test_quant.py pins byte-exactly through
save_bundle/load_bundle):

    {"kernel": int8 (..., out), "kernel_scale": float32 (out,), ...}

Tensor parallelism composes with this layout through the partition-rule
registry (parallel/partition.py): a ``*_scale`` leaf follows its kernel's
OUTPUT-channel spec — a column-parallel kernel (P(None, 'model')) shards
its (out,) scales over 'model' alongside it, a row-parallel kernel
(P('model', None)) replicates them — so an int8 bundle scores at mp >= 2
with no quant-specific placement code.

A leaf is quantized iff it is named ``kernel``, is floating, and has rank
2 (Dense) or 4 (2-D Conv); everything else floating becomes bfloat16.
The whole ``moe`` subtree (expert stacks AND router, ops/moe.py)
deliberately does NOT int8-quantize — decode re-applies the real MoEMLP
module against the raw tree (models/transformer_decoding.py::_mlp) and
must keep seeing plain float kernels.

KV-cache quantization (`quantize_kv`) is the activation-side counterpart:
per-head symmetric int8, quantize-on-write inside the decode step, dequant
on read — on a single TPU device inside the fused Pallas kernel
(`ops/decode_attention.fused_single_query_attention`: k_scale applied
after QK^T, v_scale folded into the softmax weights, so the cache
streams as 1 byte/element with no dequantized copy ever materialized),
elsewhere inside the reference `ops/attention.single_query_attention`
with the identical algebraic hoist — models/generate.py wires it behind
`TextGenerator.kvCacheDtype`.
"""

from __future__ import annotations

import jax.numpy as jnp
import ml_dtypes
import numpy as np

from mmlspark_tpu.models.bundle import ModelBundle

INT8_MAX = 127.0

# per-channel clip search: candidate fractions of the channel's |w| max
# tried as the clipping range, best (minimum squared error) kept — the
# standard PTQ refinement (GPTQ/AWQ-family "clip search").  Shrinking the
# range below the outlier trades a large clip error on one weight for a
# finer step on all the others; on the trained cifar10 ConvNet this is
# the difference between an accuracy delta of -0.0056 and -0.0028.
_CLIP_FRACTIONS = (1.0, 0.975, 0.95, 0.925, 0.9, 0.85, 0.8)


def quantize_array_int8(w: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Symmetric per-output-channel int8 quantization of a kernel.

    The output channel is the LAST axis (flax Dense (in, out) and Conv
    HWIO both put it there).  Each channel's scale is chosen by an MSE
    clip search over `_CLIP_FRACTIONS` of the channel's |w| max; weights
    beyond the chosen range clip to +-127.  Returns (q int8, scale
    float32 (out,)) with w ~= q * scale and, per channel,
    |w - q*scale| <= max(scale/2, amax - 127*scale) (round-to-nearest
    inside the range, clip distance outside — test-pinned); all-zero
    channels get scale 0 (dequant reproduces the zeros exactly).
    """
    w = np.asarray(w, np.float32)
    red = tuple(range(w.ndim - 1))
    amax = np.abs(w).max(axis=red)
    best_scale = None
    best_err = None
    for frac in _CLIP_FRACTIONS:
        scale = amax * (frac / INT8_MAX)
        inv = np.where(scale > 0, 1.0 / np.where(scale > 0, scale, 1.0), 0.0)
        q = np.clip(np.round(w * inv), -INT8_MAX, INT8_MAX)
        err = ((w - q * scale) ** 2).sum(axis=red)
        if best_err is None:
            best_scale, best_err = scale, err
        else:
            better = err < best_err
            best_err = np.where(better, err, best_err)
            best_scale = np.where(better, scale, best_scale)
    scale = best_scale.astype(np.float32)
    inv = np.where(scale > 0, 1.0 / np.where(scale > 0, scale, 1.0), 0.0)
    q = np.clip(np.round(w * inv), -INT8_MAX, INT8_MAX).astype(np.int8)
    return q, scale


def dequantize_array(q: np.ndarray, scale: np.ndarray) -> np.ndarray:
    """The float32 weights an int8 (q, scale) pair represents."""
    return np.asarray(q, np.float32) * np.asarray(scale, np.float32)


def _should_quantize(key: str, arr: np.ndarray) -> bool:
    return (key == "kernel" and arr.ndim in (2, 4)
            and np.issubdtype(arr.dtype, np.floating))


def _quantize_tree(tree: dict, mode: str, stats: dict,
                   int8_ok: bool = True) -> dict:
    out: dict = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            # the whole `moe` subtree stays float: decode re-applies the
            # real MoEMLP module against these params
            # (transformer_decoding.py::_mlp), which must keep seeing
            # plain kernels (router included)
            out[k] = _quantize_tree(v, mode, stats,
                                    int8_ok and k != "moe")
            continue
        arr = np.asarray(v)
        if mode == "int8" and int8_ok and _should_quantize(k, arr):
            q, s = quantize_array_int8(arr)
            out[k] = q
            out[k + "_scale"] = s
            stats["int8_kernels"] += 1
        elif np.issubdtype(arr.dtype, np.floating):
            out[k] = arr.astype(ml_dtypes.bfloat16)
        else:
            out[k] = arr
    return out


def _dequantize_tree(tree: dict, dtype=np.float32) -> dict:
    out: dict = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out[k] = _dequantize_tree(v, dtype)
        elif k.endswith("_scale") and k[:-len("_scale")] in tree:
            continue
        elif k + "_scale" in tree:
            out[k] = dequantize_array(v, tree[k + "_scale"]).astype(dtype)
        elif np.issubdtype(np.asarray(v).dtype, np.floating):
            out[k] = np.asarray(v, dtype)
        else:
            out[k] = np.asarray(v)
    return out


def quantize_bundle(bundle: ModelBundle, mode: str = "bf16") -> ModelBundle:
    """A new ModelBundle with quantized variables (the input is untouched).

    The architecture name is unchanged — quantization is a storage/compute
    property recorded in ``metadata["quantization"]``, not a different
    model — and the config's compute dtype becomes bfloat16 (int8 weights
    score against bf16 activations; bf16 weights compute natively).
    save_bundle/load_bundle round-trip the quantized tree byte-exactly
    (dtypes and scale arrays persist through msgpack; test-pinned).
    """
    if mode not in ("bf16", "int8"):
        raise ValueError(f"unknown quantization mode '{mode}' (bf16 | int8)")
    import jax
    host_vars = jax.device_get(bundle.variables)
    stats = {"int8_kernels": 0}
    variables = _quantize_tree(host_vars, mode, stats)
    config = dict(bundle.config)
    module = bundle.module()
    if "dtype" in getattr(module, "__dataclass_fields__", {}):
        config["dtype"] = "bfloat16"
    metadata = dict(bundle.metadata or {})
    metadata["quantization"] = {
        "mode": mode, "compute_dtype": "bfloat16",
        "int8_kernels": stats["int8_kernels"],
    }
    return ModelBundle(bundle.architecture, config, variables, metadata)


def dequantize_bundle(bundle: ModelBundle, dtype=np.float32) -> ModelBundle:
    """Expand a quantized bundle back to plain float weights (diagnostics /
    error measurement — never the serving path)."""
    variables = _dequantize_tree(bundle.variables, dtype)
    config = dict(bundle.config)
    metadata = dict(bundle.metadata or {})
    metadata.pop("quantization", None)
    return ModelBundle(bundle.architecture, config, variables, metadata)


def quantization_mode(bundle: ModelBundle) -> str | None:
    """'bf16' / 'int8' for a quantized bundle, None otherwise."""
    return ((bundle.metadata or {}).get("quantization") or {}).get("mode")


# --------------------------------------------------------------------------
# KV-cache quantization (jnp: runs inside the jitted decode programs)
# --------------------------------------------------------------------------

def quantize_kv(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-head symmetric int8 of a K/V slab (..., H, D) -> (q, scale).

    scale has shape (..., H): one scale per (row, slot, head) — the
    granularity the decode write produces (one new token's K/V per step)
    and the read dequantizes at zero extra bandwidth cost (the scale array
    is 1/D the payload).  All-zero vectors (never-written cache slots) get
    scale 0, so dequant reproduces exact zeros.
    """
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1)
    scale = amax / INT8_MAX
    inv = jnp.where(amax > 0, INT8_MAX / jnp.where(amax > 0, amax, 1.0), 0.0)
    q = jnp.clip(jnp.round(x32 * inv[..., None]), -INT8_MAX, INT8_MAX)
    return q.astype(jnp.int8), scale
