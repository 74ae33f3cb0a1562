"""The arithmetic a plain reference computes its products in.

`f32`   float32 at `highest`: the reference proper.
`fp8`   operands rounded to float8 e4m3 (each tensor scaled to its largest
        element), float32 accumulation: the control, the precision below
        the stated one.  Rounding passes gradients straight through, so a
        training control differs in its forward values only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

MODES = ("f32", "fp8")
_E4M3_MAX = 448.0


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def rounded(x, mode: str):
    """`x` (float32) as the operand of a product in `mode`."""
    if mode == "f32":
        return x
    if mode == "fp8":
        return _fp8(x)
    raise ValueError(f"precision {mode!r} is not one of {MODES}")


def einsum(spec: str, a, b, mode: str):
    return jnp.einsum(spec, rounded(a, mode), rounded(b, mode),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
