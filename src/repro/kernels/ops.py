"""Jit'd public wrappers around the Pallas kernels, and the one switch
between compiled and interpreted kernels.

Every kernel takes ``interpret: bool | None = None`` and passes it through
:func:`interpret_mode` when it is traced: None means "decide from the
backend" — interpreted on ``cpu`` (tests), compiled on ``tpu``, an error
anywhere else. No path falls back to the interpreter or to the jnp
reference on the chip. The wrappers compose kernels into the shapes the
rest of the framework uses (pytree-wide aggregation, full SSD with the
inter-chunk recurrence, etc.).

The kernel modules import this one while it imports them; that cycle is
safe because they read `interpret_mode` only at trace time and
`repro.kernels` imports `ops` first.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import detect as _detect
from repro.kernels import fedavg as _fedavg
from repro.kernels import flash_attention as _flash
from repro.kernels import pack as _pack
from repro.kernels import quant as _quant
from repro.kernels import ref
from repro.kernels import ssd_scan as _ssd

PyTree = Any

# backend -> interpret mode for kernels called with interpret=None
_INTERPRET_ON = {"cpu": True, "tpu": False}


def interpret_mode(interpret: bool | None = None) -> bool:
    """Resolve a kernel's ``interpret`` flag. An explicit bool wins (tests
    pin interpret mode, compile checks force compiled kernels); None reads
    ``jax.default_backend()``. Called while a kernel is traced, never at
    import, so importing a kernel initializes no backend."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend not in _INTERPRET_ON:
        raise RuntimeError(
            f"no Pallas kernel mode for backend {backend!r}: kernels compile "
            "on tpu and run interpreted on cpu"
        )
    return _INTERPRET_ON[backend]

fedavg_masked_mean = _fedavg.fedavg_masked_mean
pairwise_iou = _detect.pairwise_iou
nms = _detect.nms
packed_bucket_reduce = _pack.packed_bucket_reduce
quantize_rows = _pack.quantize_rows
dequantize_rows = _pack.dequantize_rows
quantize = _quant.quantize
dequantize = _quant.dequantize
flash_attention = _flash.flash_attention
ssd_chunk_scan = _ssd.ssd_chunk_scan


def flash_attention_trainable(q, k, v, *, causal: bool = True, window: int = 0, interpret: bool | None = None):
    """Flash-kernel forward with the jnp-reference VJP (training-safe).

    The Pallas kernel implements only the forward pass; custom_vjp pairs it
    with gradients derived from the numerically-equivalent reference, so
    models can select `attention_impl="pallas"` for both train and serve.
    Layout: (B, H, S, hd) like kernels.ref.flash_attention.
    """

    @jax.custom_vjp
    def fa(q, k, v):
        return _flash.flash_attention(q, k, v, causal=causal, window=window, interpret=interpret)

    def fwd(q, k, v):
        return fa(q, k, v), (q, k, v)

    def bwd(res, g):
        q, k, v = res
        _, vjp = jax.vjp(lambda a, b, c: ref.flash_attention(a, b, c, causal=causal, window=window), q, k, v)
        return vjp(g)

    fa.defvjp(fwd, bwd)
    return fa(q, k, v)


def ssd_full_trainable(xdt, dA, Bm, Cm, *, chunk: int = 128, interpret: bool | None = None):
    """ssd_full forward (Pallas intra-chunk) with the jnp-reference VJP."""
    from repro.models.mamba2 import ssd_chunked

    @jax.custom_vjp
    def ssd(xdt, dA, Bm, Cm):
        return ssd_full(xdt, dA, Bm, Cm, chunk=chunk, interpret=interpret)

    def fwd(xdt, dA, Bm, Cm):
        return ssd(xdt, dA, Bm, Cm), (xdt, dA, Bm, Cm)

    def bwd(res, g):
        _, vjp = jax.vjp(lambda a, b, c, d: ssd_chunked(a, b, c, d, chunk), *res)
        return vjp(g)

    ssd.defvjp(fwd, bwd)
    return ssd(xdt, dA, Bm, Cm)


def fedavg_tree(stacked: PyTree, weights: jax.Array, mask_per_leaf: PyTree, *, interpret: bool | None = None) -> PyTree:
    """Kernel-backed Eq.5+Eq.6 over a client-stacked pytree.

    mask_per_leaf: (C,) upload mask per leaf (from Eq. 6 layer scores).
    Each leaf is flattened to (C, N) and aggregated by the fedavg kernel.
    """

    def agg(x, m):
        C = x.shape[0]
        flat = x.reshape(C, -1)
        out = _fedavg.fedavg_masked_mean(flat, weights, m, interpret=interpret)
        return out.reshape(x.shape[1:])

    return jax.tree.map(agg, stacked, mask_per_leaf)


def quantize_tree(tree: PyTree, *, interpret: bool | None = None) -> PyTree:
    """Per-leaf int8 block quantization -> {"q", "scales"} leaves."""
    return jax.tree.map(
        lambda x: dict(zip(("q", "scales"), _quant.quantize(x.reshape(-1), interpret=interpret))),
        tree,
    )


def dequantize_tree(qtree: PyTree, like: PyTree, *, interpret: bool | None = None) -> PyTree:
    return jax.tree.map(
        lambda qt, x: _quant.dequantize(qt["q"], qt["scales"], dtype=x.dtype, interpret=interpret).reshape(x.shape),
        qtree,
        like,
        is_leaf=lambda t: isinstance(t, dict) and "q" in t,
    )


def ssd_full(xdt: jax.Array, dA: jax.Array, Bm: jax.Array, Cm: jax.Array, *, chunk: int = 128, interpret: bool | None = None, init_state: jax.Array | None = None):
    """Full SSD = Pallas intra-chunk kernel + lax.scan inter-chunk pass.

    Same contract as models.mamba2.ssd_chunked: returns (y (B,S,H,P),
    final_state (B,H,P,N)).
    """
    B, S, H, P = xdt.shape
    N = Bm.shape[-1]
    y_diag, states, chunk_decay, exp_cum = _ssd.ssd_chunk_scan(
        xdt, dA, Bm, Cm, chunk=chunk, interpret=interpret
    )
    nc = S // chunk

    def scan_fn(carry, inp):
        st, cd = inp  # (B,H,P,N), (B,H)
        new = carry * cd[:, :, None, None] + st
        return new, carry

    init = jnp.zeros((B, H, P, N), jnp.float32) if init_state is None else init_state
    final_state, prev = jax.lax.scan(
        scan_fn, init, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0))
    )
    prev = jnp.moveaxis(prev, 0, 1)  # (B,nc,H,P,N)
    Cc = Cm.reshape(B, nc, chunk, N)
    ec = exp_cum.reshape(B, nc, chunk, H)
    y_off = jnp.einsum("bcqn,bchpn,bcqh->bcqhp", Cc.astype(jnp.float32), prev, ec)
    y = y_diag + y_off.reshape(B, S, H, P)
    return y.astype(xdt.dtype), final_state
