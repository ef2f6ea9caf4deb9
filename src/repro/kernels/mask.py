"""Secure-aggregation integer reduce kernel (DESIGN.md §15).

`masked_u32_sum` is the server side of the packed Bonawitz transport: the
participation-gated uint32 sum of the masked client rows, on the same 2-D
(N-block x client-block) accumulating grid as `kernels.pack`. All
arithmetic is mod-2^32, which IS the masking ring — the pairwise masks
cancel bit-exactly in this sum, not to float tolerance. The kernel adds the
rows' bits as int32 (the TPU reduces no unsigned type); two's-complement
addition wraps identically mod 2^32, so the uint32 result is unchanged.
Mask construction itself stays in `packing.secure_client_masks` (shared by
the ref and kernel paths); only the hot gated reduction lives here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ops
from repro.kernels.pack import BLOCK_N, _pad_rows, client_block


def _masked_sum_kernel(x_ref, pm_ref, out_ref):
    ci = pl.program_id(1)
    x = x_ref[...]  # (BC, BN) masked rows, uint32 bits viewed as int32
    pm = pm_ref[...].astype(jnp.float32)  # (BC, 1) participation
    partial = jnp.sum(jnp.where(pm > 0, x, 0), axis=0, dtype=jnp.int32)

    @pl.when(ci == 0)
    def _():
        out_ref[...] = partial

    @pl.when(ci > 0)
    def _():
        out_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("interpret", "block_n", "block_c"))
def masked_u32_sum(
    rows: jax.Array, participation: jax.Array, *, interpret: bool | None = None,
    block_n: int = BLOCK_N, block_c: int | None = None,
) -> jax.Array:
    """rows (C, N) uint32 + participation (C,) -> (N,) uint32 modular sum
    of the participating rows, one accumulating launch. Padding rows carry
    participation 0, so the modular total is exact."""
    C, N = rows.shape
    pad = (-N) % block_n
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad)))
    bc = min(client_block(C) if block_c is None else block_c, C)
    rows = _pad_rows(rows, bc)
    cpad = rows.shape[0]
    pmp = jnp.pad(
        participation.astype(jnp.float32).reshape(C, 1), ((0, cpad - C), (0, 0))
    )
    out = pl.pallas_call(
        _masked_sum_kernel,
        grid=((N + pad) // block_n, cpad // bc),
        in_specs=[
            pl.BlockSpec((bc, block_n), lambda j, ci: (ci, j)),
            pl.BlockSpec((bc, 1), lambda j, ci: (ci, 0)),
        ],
        out_specs=pl.BlockSpec((block_n,), lambda j, ci: (j,)),
        out_shape=jax.ShapeDtypeStruct((N + pad,), jnp.int32),
        interpret=ops.interpret_mode(interpret),
    )(jax.lax.bitcast_convert_type(rows, jnp.int32), pmp)
    return jax.lax.bitcast_convert_type(out[:N], jnp.uint32)
