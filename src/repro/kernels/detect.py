"""Pallas detection kernels: pairwise IoU/GIoU matrix + mask-based NMS.

The federated eval engine (core.detection, DESIGN.md §10) replaces the
seed's O(pairs) per-pair Python IoU with two launches per eval batch:

``pairwise_iou`` — a tiled (batch, N-tile, M-tile) grid over center-format
box arrays; each grid step loads one (BN, 4) / (BM, 4) pair of box tiles
and emits the (BN, BM) IoU (or GIoU) block on the VPU. Boxes are tiny on
the lane axis (4 coordinates), so tiles block only the pair dims.

``nms`` — fixed-size, score-sorted, mask-based non-maximum suppression
with jit-stable shapes: the wrapper sorts by score (stable, so score ties
break by original index) and the kernel runs one grid step per image: it
writes the (N, N) IoU matrix of the sorted boxes to VMEM, then walks the
rows with a `fori_loop` that zeroes later boxes overlapping a still-kept
earlier box. The output is a 0/1 keep mask in the
*original* box order, never a dynamic-length index list — the whole eval
stays one compiled program.

Every op in both kernel bodies is plain IEEE add/sub/mul/div/min/max, so
the NumPy oracles in `kernels.ref` (`pairwise_iou_np`, `nms_np`) match
bit-for-bit in interpret mode (pinned by tests/test_detect.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ops

BLOCK_BOXES = 128
IOU_EPS = 1e-9


def _area(p):
    """Clamp a geometric product to >= 0 (areas/intersections are
    non-negative; negative-w/h degenerate boxes collapse to zero area).

    Doubles as the bit-for-bit guard: LLVM contracts `a - x*y` into an FMA
    (one rounding where NumPy rounds twice, a 1-ulp drift vs kernels.ref) —
    `jax.lax.optimization_barrier` does NOT stop that backend contraction.
    Routing every product through `max(., 0)` breaks the fsub(., fmul)
    pattern, so kernel and NumPy oracle round identically.
    (`w * 0.5` is exact — power-of-two scale — so corners need no guard.)
    """
    return jnp.maximum(p, 0.0)


def _corners(boxes):
    """(..., 4) center-format (x, y, w, h) -> x1, y1, x2, y2, area."""
    x1 = boxes[..., 0] - boxes[..., 2] * 0.5
    y1 = boxes[..., 1] - boxes[..., 3] * 0.5
    x2 = boxes[..., 0] + boxes[..., 2] * 0.5
    y2 = boxes[..., 1] + boxes[..., 3] * 0.5
    return x1, y1, x2, y2, _area((x2 - x1) * (y2 - y1))


def _iou_tile(a, b, giou: bool):
    """(BN, 4) x (BM, 4) -> (BN, BM) IoU (or GIoU) block.

    Shared between the kernel body and the jnp fallback; zero-area boxes
    get IoU 0 against everything (the eps floor, never NaN).
    """
    ax1, ay1, ax2, ay2, aa = _corners(a)
    bx1, by1, bx2, by2, ba = _corners(b)
    ix = jnp.maximum(jnp.minimum(ax2[:, None], bx2[None, :]) - jnp.maximum(ax1[:, None], bx1[None, :]), 0.0)
    iy = jnp.maximum(jnp.minimum(ay2[:, None], by2[None, :]) - jnp.maximum(ay1[:, None], by1[None, :]), 0.0)
    inter = _area(ix * iy)
    union = aa[:, None] + ba[None, :] - inter
    iou = inter / jnp.maximum(union, IOU_EPS)
    if not giou:
        return iou
    cx = jnp.maximum(ax2[:, None], bx2[None, :]) - jnp.minimum(ax1[:, None], bx1[None, :])
    cy = jnp.maximum(ay2[:, None], by2[None, :]) - jnp.minimum(ay1[:, None], by1[None, :])
    carea = _area(cx * cy)
    return iou - (carea - union) / jnp.maximum(carea, IOU_EPS)


def _iou_kernel(a_ref, b_ref, o_ref, *, giou):
    o_ref[0] = _iou_tile(a_ref[0].astype(jnp.float32), b_ref[0].astype(jnp.float32), giou)


@functools.partial(jax.jit, static_argnames=("giou", "interpret", "block_n", "block_m"))
def pairwise_iou(
    boxes_a: jax.Array,
    boxes_b: jax.Array,
    *,
    giou: bool = False,
    interpret: bool | None = None,
    block_n: int = BLOCK_BOXES,
    block_m: int = BLOCK_BOXES,
) -> jax.Array:
    """boxes_a (B?, N, 4), boxes_b (B?, M, 4) center-format -> (B?, N, M).

    One launch over a (B, ceil(N/bn), ceil(M/bm)) grid; a leading batch dim
    is optional and becomes the outer grid axis (no vmap of the kernel).
    N/M are padded to the tile sizes internally with zero-area boxes, whose
    IoU against anything is 0 — the padding is sliced off before returning.
    """
    squeeze = boxes_a.ndim == 2
    if squeeze:
        boxes_a, boxes_b = boxes_a[None], boxes_b[None]
    B, N, _ = boxes_a.shape
    M = boxes_b.shape[1]
    bn, bm = min(block_n, max(N, 1)), min(block_m, max(M, 1))
    pad_n, pad_m = (-N) % bn, (-M) % bm
    if pad_n:
        boxes_a = jnp.pad(boxes_a, ((0, 0), (0, pad_n), (0, 0)))
    if pad_m:
        boxes_b = jnp.pad(boxes_b, ((0, 0), (0, pad_m), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_iou_kernel, giou=giou),
        grid=(B, (N + pad_n) // bn, (M + pad_m) // bm),
        in_specs=[
            pl.BlockSpec((1, bn, 4), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bm, 4), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bn, bm), lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, N + pad_n, M + pad_m), jnp.float32),
        interpret=ops.interpret_mode(interpret),
    )(boxes_a.astype(jnp.float32), boxes_b.astype(jnp.float32))
    out = out[:, :N, :M]
    return out[0] if squeeze else out


def _nms_kernel(boxes_ref, valid_ref, keep_ref, iou_ref, *, iou_thresh):
    boxes = boxes_ref[0].astype(jnp.float32)  # (N, 4) score-sorted desc
    n = boxes.shape[0]
    # all pairs at once (the same IEEE ops as pairwise_iou), then the
    # sequential walk reads row i from VMEM: no vector is ever indexed by
    # the traced loop counter, which the TPU lowering cannot do
    iou_ref[...] = _iou_tile(boxes, boxes, giou=False)
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)

    def body(i, keep):  # keep: (1, N) 0/1
        kept_i = jnp.max(jnp.where(pos == i, keep, 0.0))
        # a box only suppresses *later* boxes, and only while itself kept —
        # suppressed boxes never cascade (sequential NMS semantics)
        suppress = (pos > i) & (iou_ref[pl.ds(i, 1), :] > iou_thresh) & (kept_i > 0)
        return jnp.where(suppress, 0.0, keep)

    keep_ref[0] = jax.lax.fori_loop(0, n, body, valid_ref[0])


@functools.partial(jax.jit, static_argnames=("iou_thresh", "score_thresh", "max_keep", "interpret"))
def nms(
    boxes: jax.Array,
    scores: jax.Array,
    *,
    iou_thresh: float = 0.5,
    score_thresh: float = 0.0,
    max_keep: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    """boxes (B?, N, 4), scores (B?, N) -> keep mask (B?, N) f32, original order.

    Score-sorted sequential NMS with fixed shapes: boxes are stably sorted
    by descending score (ties keep original order), the kernel walks the
    sorted list once per image (grid step = image), and the keep mask is
    scattered back to the caller's order. ``score_thresh`` pre-drops boxes
    below it; ``max_keep > 0`` caps the survivors to the top max_keep by
    score (the fixed-size output contract — extra survivors are masked, not
    sliced, so shapes never depend on data).
    """
    squeeze = boxes.ndim == 2
    if squeeze:
        boxes, scores = boxes[None], scores[None]
    scores = scores.astype(jnp.float32)
    order = jnp.argsort(-scores, axis=-1, stable=True)
    boxes_s = jnp.take_along_axis(boxes.astype(jnp.float32), order[..., None], axis=1)
    valid_s = (jnp.take_along_axis(scores, order, axis=1) > score_thresh).astype(jnp.float32)
    B, N = valid_s.shape
    # valid/keep ride as (B, 1, N): every block's last two dims then equal
    # the array's own, which the TPU lowering requires for N off the lanes
    keep_s = pl.pallas_call(
        functools.partial(_nms_kernel, iou_thresh=iou_thresh),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, N, 4), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, N), lambda b: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, N), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=ops.interpret_mode(interpret),
    )(boxes_s, valid_s[:, None, :])[:, 0, :]
    if max_keep:
        rank = jnp.cumsum(keep_s, axis=-1)  # survivor rank in score order
        keep_s = keep_s * (rank <= max_keep).astype(jnp.float32)
    inv = jnp.argsort(order, axis=-1, stable=True)
    keep = jnp.take_along_axis(keep_s, inv, axis=1)
    return keep[0] if squeeze else keep
