"""Pallas TPU kernel: blockwise online-softmax attention (flash) forward.

Causal/windowed GQA attention with MXU-aligned (BLOCK_Q x BLOCK_K) tiles.
Grid (B, H, nq, nk) with the K dimension innermost & sequential; running
max/sum and the f32 accumulator live in VMEM scratch. Blocks fully outside
the causal/window band are skipped with pl.when, which is what realizes the
~2x causal saving the jnp reference (repro.kernels.ref.flash_attention)
cannot express.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ops

BLOCK_Q = 128
BLOCK_K = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale, causal, window, bq, bk, nk):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * bq
    k_start = ik * bk
    # skip blocks fully outside the causal/window band
    relevant = True
    if causal:
        relevant = k_start <= q_start + bq - 1
    if window:
        relevant = relevant & (k_start + bk - 1 >= q_start - window + 1)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= qpos >= kpos
        if window:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = BLOCK_Q,
    block_k: int = BLOCK_K,
    interpret: bool | None = None,
) -> jax.Array:
    """q (B,H,S,hd); k/v (B,Hkv,S,hd) -> (B,H,S,hd). S % blocks == 0."""
    B, H, S, hd = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    nq, nk = S // block_q, S // block_k
    scale = 1.0 / (hd ** 0.5)
    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, bq=block_q, bk=block_k, nk=nk
    )
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=ops.interpret_mode(interpret),
    )(q, k, v)
