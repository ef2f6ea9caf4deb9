"""The control's precision: operands of every product rounded to a lower
number format before an exact float32 product.

``operand_rounding("float8_e4m3fn")`` scales each operand tensor so its
largest magnitude sits at the format's largest finite value (per-tensor
scaling, as fp8 training does), rounds, and scales back; a format with
float32's range (bfloat16) is rounded unscaled. The rounding is
straight-through: the backward pass multiplies the rounded operands by
exact cotangents.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def operand_rounding(dtype_name: str | None):
    if dtype_name is None:
        return None
    dt = jnp.dtype(dtype_name)
    top = float(jnp.finfo(dt).max)

    def rnd(x):
        if top > 1e30:
            q = x.astype(dt).astype(x.dtype)
        else:
            scale = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top)
            q = (x / scale).astype(dt).astype(x.dtype) * scale
        return x + jax.lax.stop_gradient(q - x)

    return rnd
