"""Pallas kernels over the packed (C, N_total) aggregation buffer.

The reduction kernels run on a 2-D ``(N-block x client-block)`` grid
(DESIGN.md §11): the N axis is the outer grid dim, clients the inner, and
partial sums accumulate into the revisited output block across consecutive
client steps. Each grid step therefore loads only a ``(BLOCK_C, BLOCK_N)``
window — the old single-axis grid reloaded *all* C rows per N-block, which
is exactly why the monolithic launches lost to the per-leaf tree path once
C x BLOCK_N outgrew VMEM.

`packed_bucket_reduce` additionally tiles the bucket -> weight recovery:
per N-block the one-hot matmul runs over a lane-aligned window of the
(C, B) weight-mask wide enough for ``bucket_tile`` buckets (a block of a
sorted-id buffer touches few buckets; `packing.bucket_tile_bound` gives the
static bound), not all B columns.

`quant8_reduce` fuses the int8 transport into the reduction — encode
(per-block amax scale, round, clip), decode, and the weighted client sum in
ONE launch, versus the old encode -> decode -> reduce triple pass.
`quantize_rows` survives for the sharded transport, where the int8 payload
must materialize for the all_gather (the gathered decode+reduce then runs
fused via `packing.dequant_reduce_ref`); `dequantize_rows` is its
standalone inverse, used by tests/tooling rather than the round path.

`grouped_reduce` is the hierarchical inner reduce (DESIGN.md §13): a 3-D
``(N-block x group x member-block)`` grid turns every edge group's
renormalized weighted mean into one accumulating launch, so the two-level
`hier` aggregator costs one launch for all C/G groups plus the registered
outer reduce over (C/G, N) rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ops

BLOCK_N = 1024
BLOCK_C = 8
LANES = 128  # TPU vector lane width: dynamic lane offsets must be multiples


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def client_block(C: int) -> int:
    """Client-block width for a C-row launch. BLOCK_C=8 was tuned at the
    C=8 federation; at C=256/1024 an 8-row block revisits every output
    N-block C/8 times, and the revisit overhead (output reload + grid-step
    bookkeeping) dominates. Wider client blocks amortize the revisits while
    a (32, BLOCK_N) f32 window still sits far under VMEM."""
    if C <= 64:
        return BLOCK_C
    return 32


def _pad_rows(x: jax.Array, block_c: int) -> jax.Array:
    pad = (-x.shape[0]) % block_c
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) if pad else x


def _reduce_kernel(x_ref, wm_ref, pm_ref, bid_ref, b0_ref, num_ref, den_ref, *, window):
    ci = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)  # (BC, BN)
    pm = pm_ref[...].astype(jnp.float32)  # (BC, 1) participation mask
    # first weight column of this N-block's window (SMEM, LANES-aligned)
    b0 = pl.multiple_of(b0_ref[pl.program_id(0)], LANES)
    bn = x.shape[1]
    # bucket-tiled weight recovery: load the window of the zero-padded
    # weights, then one-hot matmul on the MXU over its columns instead of
    # all B. Padding positions carry bucket id B, a zero column.
    wt = wm_ref[:, pl.ds(b0, window)].astype(jnp.float32) * pm
    local = bid_ref[...] - b0  # (BN,) in [0, window)
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, (window, bn), 0) == local[None, :]
    ).astype(jnp.float32)
    # exact: one nonzero product per output; HIGHEST keeps the f32 weights
    # from being rounded to bf16 by a single MXU pass on the TPU
    w = jnp.dot(wt, onehot, preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)  # (BC, BN)
    pnum = jnp.sum(x * w, axis=0)
    pden = jnp.sum(w, axis=0)

    @pl.when(ci == 0)
    def _():
        num_ref[...] = pnum
        den_ref[...] = pden

    @pl.when(ci > 0)
    def _():
        num_ref[...] += pnum
        den_ref[...] += pden


@functools.partial(jax.jit, static_argnames=("interpret", "block_n", "block_c", "bucket_tile"))
def packed_bucket_reduce(
    packed: jax.Array,
    wmask: jax.Array,
    bucket_ids: jax.Array,
    mask: jax.Array | None = None,
    *,
    interpret: bool | None = None,
    block_n: int = BLOCK_N,
    block_c: int | None = None,
    bucket_tile: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """packed (C, N), wmask (C, B), bucket_ids (N,), mask (C,) or None
    -> (num (N,), den (N,)).

    num[n] = sum_c mask[c] wmask[c, bucket_ids[n]] * packed[c, n];
    den[n] = sum_c mask[c] wmask[c, bucket_ids[n]]. `mask` is the 0/1
    participation vector from the scheduler (None -> all participate); it is
    a traced operand, so per-round selection changes never retrace. N pads
    to block_n (padding gets bucket id B, whose weight column is zero) and C
    pads to block_c with zero-weight rows (block_c None -> `client_block(C)`:
    wider client blocks at C > 64). `bucket_tile` bounds how many buckets
    one N-block spans (packing.bucket_tile_bound for a real spec);
    None means B — always safe, e.g. for unsorted id vectors.
    """
    C, N = packed.shape
    B = wmask.shape[1]
    if mask is None:
        mask = jnp.ones((C,), jnp.float32)
    tb = B if bucket_tile is None else min(bucket_tile, B)
    pad = (-N) % block_n
    if pad:
        packed = jnp.pad(packed, ((0, 0), (0, pad)))
        bucket_ids = jnp.pad(bucket_ids, (0, pad), constant_values=B)
    npad = N + pad
    bc = min(client_block(C) if block_c is None else block_c, C)
    packed = _pad_rows(packed, bc)
    cpad = packed.shape[0]
    # Ids span [0, B] (B = padding), so W zero-padded weight columns hold
    # them all. A block's window starts at its smallest id rounded down to
    # the lane width and spans tb buckets past it; it slides back where it
    # would run off the end (a window of all W columns starts at 0).
    W = _round_up(B + 1, LANES)
    window = min(_round_up(tb + LANES - 1, LANES), W)
    wmp = jnp.pad(wmask.astype(jnp.float32), ((0, cpad - C), (0, W - B)))
    pmp = jnp.pad(mask.astype(jnp.float32).reshape(C, 1), ((0, cpad - C), (0, 0)))
    ids = bucket_ids.astype(jnp.int32)
    first = jnp.min(ids.reshape(npad // block_n, block_n), axis=1)  # (nblocks,)
    b0 = jnp.minimum(first // LANES * LANES, W - window)
    num, den = pl.pallas_call(
        functools.partial(_reduce_kernel, window=window),
        grid=(npad // block_n, cpad // bc),
        in_specs=[
            pl.BlockSpec((bc, block_n), lambda j, ci: (ci, j)),
            pl.BlockSpec((bc, W), lambda j, ci: (ci, 0)),
            pl.BlockSpec((bc, 1), lambda j, ci: (ci, 0)),
            pl.BlockSpec((block_n,), lambda j, ci: (j,)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_n,), lambda j, ci: (j,)),
            pl.BlockSpec((block_n,), lambda j, ci: (j,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((npad,), jnp.float32),
            jax.ShapeDtypeStruct((npad,), jnp.float32),
        ],
        interpret=ops.interpret_mode(interpret),
    )(packed, wmp, pmp, ids, b0)
    return num[:N], den[:N]


def _rowquant_kernel(x_ref, q_ref, s_ref, *, block):
    x = x_ref[...].astype(jnp.float32)  # (BC, BN)
    bc, bn = x.shape
    xb = x.reshape(bc, bn // block, block)
    amax = jnp.max(jnp.abs(xb), axis=-1)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(xb / scale[..., None]), -127, 127)
    q_ref[...] = q.reshape(bc, bn).astype(jnp.int8)
    s_ref[0] = scale


def _rowdequant_kernel(q_ref, s_ref, o_ref, *, block):
    q = q_ref[...].astype(jnp.float32)
    bc, bn = q.shape
    d = q.reshape(bc, bn // block, block) * s_ref[0][..., None]
    o_ref[...] = d.reshape(bc, bn).astype(o_ref.dtype)


def _quant_grid(C, N, block, block_n, block_c):
    bn = max(block_n, block)
    bn -= bn % block
    pad = (-N) % bn
    bc = min(block_c, C)
    return bn, pad, bc


# The scale sideband of `quantize_rows`/`dequantize_rows` is (C, nb) to the
# caller but (N-steps, C, bn/block) to the kernel: each grid step's scales
# are then a block whose last dim is the array's own, as the TPU requires.
def _scales_to_steps(s, bn, block):
    C, nb = s.shape
    return s.reshape(C, nb * block // bn, bn // block).transpose(1, 0, 2)


def _scales_from_steps(s):
    steps, C, per = s.shape
    return s.transpose(1, 0, 2).reshape(C, steps * per)


@functools.partial(jax.jit, static_argnames=("interpret", "block", "block_n", "block_c"))
def quantize_rows(
    x: jax.Array, *, interpret: bool | None = None, block: int = BLOCK_N,
    block_n: int = 4 * BLOCK_N, block_c: int = BLOCK_C,
):
    """x (C, N) -> (q int8 (C, N), scales f32 (C, ceil(N/block))).

    Scale granularity is one f32 per `block` elements per client row; each
    grid step quantizes a (block_c, block_n) window (block_n a multiple of
    block), so the whole packed buffer is one launch.
    """
    C, N = x.shape
    bn, pad, bc = _quant_grid(C, N, block, block_n, block_c)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    x = _pad_rows(x, bc)
    cpad = x.shape[0]
    nb_real = -(-N // block)  # ceil: the scale sideband's real width
    q, s = pl.pallas_call(
        functools.partial(_rowquant_kernel, block=block),
        grid=((N + pad) // bn, cpad // bc),
        in_specs=[pl.BlockSpec((bc, bn), lambda j, ci: (ci, j))],
        out_specs=[
            pl.BlockSpec((bc, bn), lambda j, ci: (ci, j)),
            pl.BlockSpec((1, bc, bn // block), lambda j, ci: (j, ci, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((cpad, N + pad), jnp.int8),
            jax.ShapeDtypeStruct(((N + pad) // bn, cpad, bn // block), jnp.float32),
        ],
        interpret=ops.interpret_mode(interpret),
    )(x)
    return q[:C, :N], _scales_from_steps(s)[:C, :nb_real]


@functools.partial(jax.jit, static_argnames=("interpret", "block", "dtype", "block_n", "block_c"))
def dequantize_rows(
    q: jax.Array, scales: jax.Array, *, dtype=jnp.float32, interpret: bool | None = None,
    block: int = BLOCK_N, block_n: int = 4 * BLOCK_N, block_c: int = BLOCK_C,
) -> jax.Array:
    C, N = q.shape
    bn, pad, bc = _quant_grid(C, N, block, block_n, block_c)
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad)))
    q = _pad_rows(q, bc)
    cpad = q.shape[0]
    nb = (N + pad) // block
    s = jnp.pad(scales, ((0, 0), (0, nb - scales.shape[1])))
    s = _scales_to_steps(_pad_rows(s, bc), bn, block)
    out = pl.pallas_call(
        functools.partial(_rowdequant_kernel, block=block),
        grid=((N + pad) // bn, cpad // bc),
        in_specs=[
            pl.BlockSpec((bc, bn), lambda j, ci: (ci, j)),
            pl.BlockSpec((1, bc, bn // block), lambda j, ci: (j, ci, 0)),
        ],
        out_specs=pl.BlockSpec((bc, bn), lambda j, ci: (ci, j)),
        out_shape=jax.ShapeDtypeStruct((cpad, N + pad), dtype),
        interpret=ops.interpret_mode(interpret),
    )(q, s)
    return out[:C, :N]


def _quant_reduce_kernel(x_ref, w_ref, num_ref, *, block):
    ci = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)  # (BC, BN) delta window
    w = w_ref[...].astype(jnp.float32)  # (BC, 1)
    bc, bn = x.shape
    xb = x.reshape(bc, bn // block, block)
    amax = jnp.max(jnp.abs(xb), axis=-1)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(xb / scale[..., None]), -127, 127)  # int8 values, f32 lanes
    d = (q * scale[..., None]).reshape(bc, bn)
    partial = jnp.sum(d * w, axis=0)

    @pl.when(ci == 0)
    def _():
        num_ref[...] = partial

    @pl.when(ci > 0)
    def _():
        num_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("interpret", "block", "block_n", "block_c"))
def quant8_reduce(
    delta: jax.Array, weights: jax.Array, *, interpret: bool | None = None,
    block: int = BLOCK_N, block_n: int = 4 * BLOCK_N, block_c: int = BLOCK_C,
) -> jax.Array:
    """Fused int8 transport: delta (C, N) + weights (C,) -> (N,) f32
    weighted sum of dequant(quant(delta)) in ONE launch (encode, decode and
    client reduction never leave the grid step). Matches
    `packing.quant8_mean_ref` — clip(round(x/s)) in f32 lanes is exactly the
    int8 value. Weights are used as-is; fold the participation mask in
    before calling. Zero-padding is exact: pad blocks quantize to 0.
    """
    C, N = delta.shape
    bn, pad, bc = _quant_grid(C, N, block, block_n, block_c)
    if pad:
        delta = jnp.pad(delta, ((0, 0), (0, pad)))
    delta = _pad_rows(delta, bc)
    cpad = delta.shape[0]
    wp = jnp.pad(weights.astype(jnp.float32).reshape(C, 1), ((0, cpad - C), (0, 0)))
    num = pl.pallas_call(
        functools.partial(_quant_reduce_kernel, block=block),
        grid=((N + pad) // bn, cpad // bc),
        in_specs=[
            pl.BlockSpec((bc, bn), lambda j, ci: (ci, j)),
            pl.BlockSpec((bc, 1), lambda j, ci: (ci, 0)),
        ],
        out_specs=pl.BlockSpec((bn,), lambda j, ci: (j,)),
        out_shape=jax.ShapeDtypeStruct((N + pad,), jnp.float32),
        interpret=ops.interpret_mode(interpret),
    )(delta, wp)
    return num[:N]


def _grouped_kernel(x_ref, w_ref, out_ref):
    ci = pl.program_id(2)
    x = x_ref[0].astype(jnp.float32)  # (BC, BN) member window of one group
    w = w_ref[0].astype(jnp.float32)  # (BC, 1) pre-normalized weights
    partial = jnp.sum(x * w, axis=0, keepdims=True)  # (1, BN)

    @pl.when(ci == 0)
    def _():
        out_ref[0] = partial

    @pl.when(ci > 0)
    def _():
        out_ref[0] += partial


@functools.partial(jax.jit, static_argnames=("interpret", "block_n", "block_c"))
def grouped_reduce(
    packed: jax.Array, wn: jax.Array, *, interpret: bool | None = None,
    block_n: int = BLOCK_N, block_c: int | None = None,
) -> jax.Array:
    """Hierarchical inner reduce: packed (C, N) + wn (C/G, G) pre-normalized
    per-group weights -> (C/G, N) f32 group rows, ONE launch for all groups.

    ``out[g] = sum_i wn[g, i] * packed[g*G + i]``. The grid is 3-D
    (N-block x group x member-block): each step loads one group's
    (block_c, block_n) member window and accumulates into the revisited
    group-row output block — the same client-step accumulation as
    `packed_bucket_reduce`, batched over groups. Callers fold the 1/den
    group renormalization into ``wn`` (`packing.grouped_weighted_mean`);
    zero-weight padding rows keep the sums exact."""
    C, N = packed.shape
    ngroups, G = wn.shape
    assert ngroups * G == C, (wn.shape, packed.shape)
    bc = min(client_block(G) if block_c is None else block_c, G)
    gpad = (-G) % bc
    pad = (-N) % block_n
    if pad:
        packed = jnp.pad(packed, ((0, 0), (0, pad)))
    xg = packed.reshape(ngroups, G, N + pad)
    if gpad:
        xg = jnp.pad(xg, ((0, 0), (0, gpad), (0, 0)))
        wn = jnp.pad(wn, ((0, 0), (0, gpad)))
    npad, Gp = N + pad, G + gpad
    # weights as (groups, Gp, 1) and rows as (groups, 1, N): every block's
    # last two dims are then aligned or the array's own, as the TPU requires
    out = pl.pallas_call(
        _grouped_kernel,
        grid=(npad // block_n, ngroups, Gp // bc),
        in_specs=[
            pl.BlockSpec((1, bc, block_n), lambda j, g, ci: (g, ci, j)),
            pl.BlockSpec((1, bc, 1), lambda j, g, ci: (g, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_n), lambda j, g, ci: (g, 0, j)),
        out_shape=jax.ShapeDtypeStruct((ngroups, 1, npad), jnp.float32),
        interpret=ops.interpret_mode(interpret),
    )(xg, wn[..., None])
    return out[:, 0, :N]
