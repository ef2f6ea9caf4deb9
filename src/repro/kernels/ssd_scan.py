"""Pallas TPU kernel: Mamba2 SSD intra-chunk block.

One grid cell = one (batch, head, chunk): computes the quadratic intra-chunk
output Y_diag, the chunk's state contribution, the chunk decay, and exp(cum)
(needed by the host-side inter-chunk pass). The (Q x Q) decay matrix L lives
entirely in VMEM; Q = ssm_chunk (128 default) keeps it MXU-aligned. The
inter-chunk recurrence stays a lax.scan in ops.py (O(1) state, 500k-ready).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ops


def _kernel(xdt_ref, dA_ref, b_ref, c_ref, y_ref, st_ref, dec_ref, cum_ref):
    xdt = xdt_ref[0, :, 0, :].astype(jnp.float32)  # (Q, P)
    dA = dA_ref[0, :, 0].astype(jnp.float32)  # (Q,)
    Bm = b_ref[0].astype(jnp.float32)  # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)  # (Q, N)
    Q = xdt.shape[0]
    cum = jnp.cumsum(dA)  # (Q,)
    diff = cum[:, None] - cum[None, :]  # (Q, Q)
    tri = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= jax.lax.broadcasted_iota(
        jnp.int32, (Q, Q), 1
    )
    L = jnp.where(tri, jnp.exp(diff), 0.0)
    scores = jnp.dot(Cm, Bm.T, preferred_element_type=jnp.float32)  # (Q, Q)
    y = jnp.dot(scores * L, xdt, preferred_element_type=jnp.float32)  # (Q, P)
    decay_states = jnp.exp(cum[-1] - cum)  # (Q,)
    st = jnp.dot((Bm * decay_states[:, None]).T, xdt, preferred_element_type=jnp.float32)  # (N, P)
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)
    st_ref[0, 0, 0] = st.T  # (P, N)
    dec_ref[0, 0, 0] = jnp.exp(cum[-1])
    cum_ref[0, :, 0] = jnp.exp(cum)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_scan(xdt: jax.Array, dA: jax.Array, Bm: jax.Array, Cm: jax.Array, *, chunk: int = 128, interpret: bool | None = None):
    """Intra-chunk pass. xdt (B,S,H,P); dA (B,S,H); Bm/Cm (B,S,N).

    Returns (y_diag (B,S,H,P) f32, states (B,nc,H,P,N) f32,
    chunk_decay (B,nc,H) f32, exp_cum (B,S,H) f32). S % chunk == 0.
    """
    B, S, H, P = xdt.shape
    N = Bm.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    out = pl.pallas_call(
        _kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, 1, P, N), lambda b, h, c: (b, c, h, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, P), jnp.float32),
            jax.ShapeDtypeStruct((B, nc, H, P, N), jnp.float32),
            jax.ShapeDtypeStruct((B, nc, H), jnp.float32),
            jax.ShapeDtypeStruct((B, S, H), jnp.float32),
        ],
        interpret=ops.interpret_mode(interpret),
    )(xdt, dA, Bm, Cm)
    return out
