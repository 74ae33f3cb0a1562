"""Operations and bytes that the work of a cell requires, from shapes
alone.  A multiply-add is two operations.  Recomputed work (remat, the
flash backward's second pass over the scores) is never counted.
"""

from __future__ import annotations

import math

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
               "u32": 4, "f32": 4}


# -- ResNet -----------------------------------------------------------------

def _same(n: int, stride: int) -> int:
    return -(-n // stride)


def resnet_forward_flops(image_hw: int, stage_sizes=(3, 4, 6, 3),
                         widths=(64, 128, 256, 512),
                         num_classes: int = 1000) -> int:
    """Forward operations of one image through a bottleneck ResNet (the
    convolutions and the head; BatchNorm, ReLU and pooling are not
    counted).  ResNet-50 at 224: 2 x 4.09e9 multiply-adds."""
    hw = _same(image_hw, 2)
    macs = hw * hw * 7 * 7 * 3 * 64
    hw = _same(hw, 2)                                   # the max pool
    cin = 64
    for stage, (blocks, w) in enumerate(zip(stage_sizes, widths)):
        for b in range(blocks):
            stride = 2 if b == 0 and stage > 0 else 1
            out_hw = _same(hw, stride)
            macs += hw * hw * cin * w                   # 1x1 reduce
            macs += out_hw * out_hw * 9 * w * w         # 3x3, strided
            macs += out_hw * out_hw * w * 4 * w         # 1x1 expand
            if b == 0:
                macs += out_hw * out_hw * cin * 4 * w   # projection
            hw, cin = out_hw, 4 * w
    macs += cin * num_classes
    return 2 * macs


# -- GPT-2-shaped decoder ---------------------------------------------------

def lm_linear_params(d_model: int, n_layers: int, vocab_size: int,
                     mlp_ratio: int = 4) -> int:
    """Weights that every token multiplies: QKV, projection, the MLP pair
    of each layer, and the head (embeddings are looked up, not
    multiplied)."""
    return (n_layers * (4 + 2 * mlp_ratio) * d_model * d_model
            + d_model * vocab_size)


def lm_forward_flops(first: int, last: int, d_model: int, n_layers: int,
                     vocab_size: int, mlp_ratio: int = 4) -> int:
    """Forward operations of the tokens at positions first..last-1 of one
    sequence: the dense layers, and causal attention in which the token
    at position t reads t+1 keys and values."""
    n = last - first
    if n <= 0:
        return 0
    dense = 2 * n * lm_linear_params(d_model, n_layers, vocab_size,
                                     mlp_ratio)
    keys = (first + 1 + last) * n // 2                  # sum of t+1
    return dense + 4 * n_layers * d_model * keys


def lm_train_flops(batch: int, seq: int, d_model: int, n_layers: int,
                   vocab_size: int, mlp_ratio: int = 4) -> dict:
    """Operations one optimizer step requires (forward and backward; the
    causal half of attention only; no recomputation): `dense` is 6 x
    tokens x linear weights, `attn` the six attention products (QK^T, PV,
    dV, dP, dQ, dK) over the lower triangle.  (Copied from
    `mmlspark_tpu/utils/perf.py` `lm_train_flops`.)"""
    dense = 6 * batch * seq * lm_linear_params(d_model, n_layers,
                                               vocab_size, mlp_ratio)
    attn = 6 * 2 * n_layers * batch * seq * seq * d_model // 2
    return {"dense": dense, "attn": attn, "total": dense + attn}


# -- kernels, from the shapes a trace event's name carries ------------------

def _size(shape: tuple) -> int:
    dtype, dims = shape
    return DTYPE_BYTES[dtype] * math.prod(dims)


def kernel_bytes(operands: list, results: list) -> int:
    """Every operand read once and every result written once."""
    return sum(map(_size, operands)) + sum(map(_size, results))


def flash_pair_causal(operands: list, results: list) -> int:
    """Two causal attention products over (BH, S, d) blocks: what one
    call of the flash forward (QK^T, PV), of dQ (dP, dQ) or of dK/dV (dV,
    dK) requires; the scores a backward kernel rebuilds are not counted."""
    bh, s, d = next(dims for _, dims in operands if len(dims) == 3
                    and dims[-1] > 1)
    return 2 * (2 * bh * s * s * d) // 2
