"""The control's lower precision: the configuration serves bfloat16, and
the nearest precision below it is 8-bit floating point."""
from __future__ import annotations

import jax.numpy as jnp

FP8_MAX = 448.0         # largest finite float8_e4m3fn


def fp8(w):
    """Weights as float8_e4m3fn would hold them, with one scale per
    tensor, returned in float32."""
    w = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30) / FP8_MAX
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
