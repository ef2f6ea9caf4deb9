"""Fused 4-bit transport kernel (DESIGN.md §15).

`quant4_reduce` is `kernels.pack.quant8_reduce`'s 4-bit sibling: per-block
symmetric quantization to the [-7, 7] nibble range, dequant, and the
weighted client sum in ONE launch on the same 2-D (N-block x client-block)
accumulating grid. The stochastic-rounding bits come from a counter-based
PRNG (murmur3 fmix32 over the GLOBAL (client, element) index — derived
in-kernel from program_id + iota, so every grid decomposition produces the
same stream) keyed by a TRACED uint32 scalar: the per-round key changes
every round without retracing, and `kernels.ref.quant4_reduce_np` /
`packing.quant4_mean_ref` generate the exact same bits host-side/traced.

The wire payload this models packs two nibbles per byte (codec.py); here —
as in quant8 — the nibble values live in f32 lanes (|q| <= 7 is exact) and
the payload never materializes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ops
from repro.kernels.pack import BLOCK_C, BLOCK_N, _pad_rows, _quant_grid

_C1 = 0x85EBCA6B
_C2 = 0xC2B2AE35
_IDX_C = 0x9E3779B1
_IDX_N = 0x85EBCA77


def _fmix32(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(_C1)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(_C2)
    h = h ^ (h >> 16)
    return h


def _quant4_reduce_kernel(x_ref, w_ref, key_ref, num_ref, *, block, mode):
    j = pl.program_id(0)
    ci = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)  # (BC, BN) delta window
    w = w_ref[...].astype(jnp.float32)  # (BC, 1)
    bc, bn = x.shape
    xb = x.reshape(bc, bn // block, block)
    amax = jnp.max(jnp.abs(xb), axis=-1)
    scale = jnp.maximum(amax, 1e-12) / 7.0
    v = xb / scale[..., None]
    if mode == "nearest":
        q = jnp.clip(jnp.round(v), -7, 7)
    else:
        # global (client, element) indices: the counter stream is identical
        # for every grid decomposition; zero padding floors to exactly 0
        cg = ci * bc + jax.lax.broadcasted_iota(jnp.int32, (bc, bn), 0)
        ng = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bc, bn), 1)
        bits = _fmix32(
            key_ref[0]
            + cg.astype(jnp.uint32) * jnp.uint32(_IDX_C)
            + ng.astype(jnp.uint32) * jnp.uint32(_IDX_N)
        )
        # via int32: the TPU converts no uint32 to f32 (bits >> 8 < 2^24, exact)
        u = jax.lax.bitcast_convert_type(bits >> 8, jnp.int32).astype(jnp.float32) * jnp.float32(2.0**-24)
        # clip AFTER the floor: 7 + u can round to 8.0 in f32
        q = jnp.clip(jnp.floor(v + u.reshape(bc, bn // block, block)), -7, 7)
    d = (q * scale[..., None]).reshape(bc, bn)
    partial = jnp.sum(d * w, axis=0)

    @pl.when(ci == 0)
    def _():
        num_ref[...] = partial

    @pl.when(ci > 0)
    def _():
        num_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("interpret", "block", "mode", "block_n", "block_c"))
def quant4_reduce(
    delta: jax.Array, weights: jax.Array, key: jax.Array | int = 0, *,
    mode: str = "nearest", interpret: bool | None = None,
    block: int = BLOCK_N, block_n: int = 4 * BLOCK_N, block_c: int = BLOCK_C,
) -> jax.Array:
    """Fused 4-bit transport: delta (C, N) + weights (C,) [+ uint32 round
    key] -> (N,) f32 weighted sum of dequant(quant4(delta)) in ONE launch.
    ``mode`` is "nearest" (half-step error bound) or "stochastic"
    (counter-PRNG rounding, mean-unbiased); the key is a traced operand so
    per-round keys never retrace. Weights are used as-is; fold the
    participation mask in before calling. Matches `packing.quant4_mean_ref`
    bit-for-bit on the q values (the reduction differs only in
    accumulation order)."""
    C, N = delta.shape
    bn, pad, bc = _quant_grid(C, N, block, block_n, block_c)
    if pad:
        delta = jnp.pad(delta, ((0, 0), (0, pad)))
    delta = _pad_rows(delta, bc)
    cpad = delta.shape[0]
    wp = jnp.pad(weights.astype(jnp.float32).reshape(C, 1), ((0, cpad - C), (0, 0)))
    kv = jnp.asarray(key).astype(jnp.uint32).reshape(1)
    num = pl.pallas_call(
        functools.partial(_quant4_reduce_kernel, block=block, mode=mode),
        grid=((N + pad) // bn, cpad // bc),
        in_specs=[
            pl.BlockSpec((bc, bn), lambda j, ci: (ci, j)),
            pl.BlockSpec((bc, 1), lambda j, ci: (ci, 0)),
            pl.BlockSpec((1,), lambda j, ci: (0,)),
        ],
        out_specs=pl.BlockSpec((bn,), lambda j, ci: (j,)),
        out_shape=jax.ShapeDtypeStruct((N + pad,), jnp.float32),
        interpret=ops.interpret_mode(interpret),
    )(delta, wp, kv)
    return num[:N]
