"""Pallas kernel: fused FedAvg (Eq. 5) + Eq. 6 mask for one layer tensor.

The aggregation server's hot loop: out[n] = sum_c w_c m_c x[c,n] / den.
Tiled over N so the (C, BLOCK_N) window sits in VMEM; the weighted mask is
precomputed into a (C,) vector and the reduction runs on the VPU with an
f32 accumulator. 8-bit/bf16 inputs upcast in-register.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ops

BLOCK_N = 1024


def _kernel(x_ref, wm_ref, den_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)  # (C, BN)
    wm = wm_ref[...].astype(jnp.float32)  # (C, 1)
    num = jnp.sum(x * wm, axis=0)  # (BN,)
    o_ref[...] = (num / den_ref[0]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_n"))
def fedavg_masked_mean(stacked: jax.Array, weights: jax.Array, mask: jax.Array, *, interpret: bool | None = None, block_n: int = BLOCK_N) -> jax.Array:
    """stacked (C, N) -> (N,). N padded to block_n internally."""
    C, N = stacked.shape
    pad = (-N) % block_n
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
    npad = N + pad
    wm = (weights * mask).astype(jnp.float32)[:, None]  # (C,1)
    den = jnp.maximum(jnp.sum(wm), 1e-12).reshape(1)
    out = pl.pallas_call(
        _kernel,
        grid=(npad // block_n,),
        in_specs=[
            pl.BlockSpec((C, block_n), lambda i: (0, i)),
            pl.BlockSpec((C, 1), lambda i: (0, 0)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((npad,), stacked.dtype),
        interpret=ops.interpret_mode(interpret),
    )(stacked, wm, den)
    return out[:N]
