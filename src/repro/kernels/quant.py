"""Pallas kernels: symmetric int8 block quantization of update deltas.

The transport stage of the quant8 aggregation mode: each BLOCK-element tile
is scaled by max|x|/127 and rounded on the VPU; dequant is the inverse.
Block size doubles as the scale granularity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ops

BLOCK = 1024


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q_ref[...] = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    s_ref[0] = scale


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[0]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def quantize(x: jax.Array, *, interpret: bool | None = None, block: int = BLOCK):
    """x (N,) -> (q int8 (N,), scales f32 (ceil(N/block),)). Pads with 0."""
    N = x.shape[0]
    pad = (-N) % block
    if pad:
        x = jnp.pad(x, (0, pad))
    nb = (N + pad) // block
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((1,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N + pad,), jnp.int8),
            jax.ShapeDtypeStruct((nb,), jnp.float32),
        ],
        interpret=ops.interpret_mode(interpret),
    )(x)
    return q[:N], s


@functools.partial(jax.jit, static_argnames=("interpret", "block", "dtype"))
def dequantize(q: jax.Array, scales: jax.Array, *, dtype=jnp.float32, interpret: bool | None = None, block: int = BLOCK) -> jax.Array:
    N = q.shape[0]
    pad = (-N) % block
    if pad:
        q = jnp.pad(q, (0, pad))
    out = pl.pallas_call(
        _dequant_kernel,
        grid=((N + pad) // block,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((1,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((N + pad,), dtype),
        interpret=ops.interpret_mode(interpret),
    )(q, scales)
    return out[:N]
