"""Federated detection evaluation engine (DESIGN.md §10).

The paper trains *and serves* object detectors federatedly, so the platform
needs a detection metric in the round loop, not just scalar losses. This
module is that metric path, end to end and fully jit-stable:

  raw heads -> :func:`decode_predictions` (yolov3.decode_boxes + top-K +
  Pallas NMS) -> :func:`match_detections` (one tiled pairwise-IoU launch +
  greedy score-ordered matching) -> :func:`average_precision` (vectorized
  VOC all-point AP@0.5) -> :func:`build_evaluator` (per-client AND pooled
  global mAP from ONE jitted call over the (C, ...) client axis).

Every shape is fixed at trace time — detections are a constant
``max_detections`` slots with a 0/1 validity mask, ground truth is padded
with a mask — so per-round evaluation never retraces, mirroring how the
participation engine feeds the round (DESIGN.md §8). The per-client mAP
vector is what `server.evaluate_round` feeds into the Task Scheduler's
quality EMA (today loss-only), closing the paper's quality-aware selection
loop with an actual detection-quality signal.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models import yolov3

Batch = Any

# NMS pre-suppression score floor: conf * class-prob below this is noise
SCORE_THRESH = 0.05


def detection_candidates(
    cfg,
    params,
    images: jax.Array,
    *,
    max_detections: int = 64,
) -> dict[str, jax.Array]:
    """images (B, H, W, 3) -> the K = max_detections top-scoring decoded
    boxes per image, before NMS.

    Returns {"boxes" (B, K, 4) center-format, "scores" (B, K) descending,
    "cls" (B, K) int32, "shifted" (B, K, 4)}: all three scales are decoded,
    flattened and top-K'd by conf * max class prob. ``shifted`` is what
    class-aware NMS runs on (the coordinate-offset trick): each class's
    boxes are x-shifted by a stride wider than any box extent in the image
    (decoded w/h can blow past [0, 1] — up to anchor * e^6 — so the stride
    is computed from the boxes, not assumed from normalized coordinates).
    """
    outs = yolov3.forward(params, images, cfg)
    boxes, scores, labels = [], [], []
    for raw, anchors in zip(outs, yolov3.ANCHORS):
        b, conf, cls = yolov3.decode_boxes(raw.astype(jnp.float32), anchors)
        B = b.shape[0]
        boxes.append(b.reshape(B, -1, 4))
        scores.append((conf * jnp.max(cls, axis=-1)).reshape(B, -1))
        labels.append(jnp.argmax(cls, axis=-1).reshape(B, -1).astype(jnp.int32))
    boxes = jnp.concatenate(boxes, axis=1)
    scores = jnp.concatenate(scores, axis=1)
    labels = jnp.concatenate(labels, axis=1)
    k = min(max_detections, scores.shape[1])
    top_scores, top_idx = jax.lax.top_k(scores, k)
    top_boxes = jnp.take_along_axis(boxes, top_idx[..., None], axis=1)
    top_labels = jnp.take_along_axis(labels, top_idx, axis=1)
    if k < max_detections:  # static pad up to the fixed K slots
        pad = max_detections - k
        top_boxes = jnp.pad(top_boxes, ((0, 0), (0, pad), (0, 0)))
        top_scores = jnp.pad(top_scores, ((0, 0), (0, pad)), constant_values=-1.0)
        top_labels = jnp.pad(top_labels, ((0, 0), (0, pad)))
    # |x1-x2| + (w1+w2)/2 <= 3 * max|coord|, so this stride strictly
    # separates classes for any decoded box. Per IMAGE, not per batch: with
    # a batch-wide max, image i's NMS arithmetic would depend on the other
    # images in the batch, and the serving plane's padded-batch pin (a
    # request's detections are bit-identical at any batch occupancy,
    # DESIGN.md §17) needs every slot's decode to be a function of that
    # slot alone.
    stride = 1.0 + 3.0 * jnp.max(jnp.abs(top_boxes), axis=(1, 2))
    shifted = top_boxes.at[..., 0].add(
        top_labels.astype(jnp.float32) * stride[:, None]
    )
    return {"boxes": top_boxes, "scores": top_scores, "cls": top_labels, "shifted": shifted}


def decode_predictions(
    cfg,
    params,
    images: jax.Array,
    *,
    max_detections: int = 64,
    score_thresh: float = SCORE_THRESH,
    nms_iou: float = 0.5,
) -> dict[str, jax.Array]:
    """images (B, H, W, 3) -> fixed-size detections per image.

    Returns {"boxes" (B, K, 4) center-format, "scores" (B, K) descending,
    "cls" (B, K) int32, "valid" (B, K) 0/1 f32} with K = max_detections:
    the :func:`detection_candidates` suppressed by ONE batched, class-aware
    Pallas NMS launch.
    """
    cand = detection_candidates(cfg, params, images, max_detections=max_detections)
    keep = ops.nms(cand.pop("shifted"), cand["scores"], iou_thresh=nms_iou, score_thresh=score_thresh)
    return {**cand, "valid": keep}


def match_detections(
    pred: dict[str, jax.Array],
    gt_boxes: jax.Array,
    gt_cls: jax.Array,
    gt_valid: jax.Array,
    *,
    iou_thresh: float = 0.5,
) -> jax.Array:
    """Greedy score-ordered matching -> per-detection TP flags (B, K) f32.

    pred: decode_predictions output (scores already descending per image);
    gt_boxes (B, G, 4), gt_cls (B, G) int32, gt_valid (B, G) 0/1. One tiled
    Pallas pairwise-IoU launch covers the whole batch; the greedy pass is a
    lax.scan over the K score-ranked slots: a detection is a true positive
    iff its best same-class, still-unmatched, valid GT reaches iou_thresh
    (each GT matches at most one detection — COCO/VOC greedy semantics).
    """
    iou = ops.pairwise_iou(pred["boxes"], gt_boxes)  # (B, K, G)

    def per_image(iou_i, pcls_i, pvalid_i, gcls_i, gvalid_i):
        def step(matched, k):
            cand = (
                (iou_i[k] >= iou_thresh)
                & (gcls_i == pcls_i[k])
                & (gvalid_i > 0)
                & ~matched
            )
            j = jnp.argmax(jnp.where(cand, iou_i[k], -1.0))
            hit = cand[j] & (pvalid_i[k] > 0)
            return matched.at[j].set(matched[j] | hit), hit.astype(jnp.float32)

        matched0 = jnp.zeros(gcls_i.shape, bool)
        _, tp = jax.lax.scan(step, matched0, jnp.arange(iou_i.shape[0]))
        return tp

    return jax.vmap(per_image)(iou, pred["cls"], pred["valid"], gt_cls, gt_valid)


def average_precision(
    scores: jax.Array,
    tp: jax.Array,
    valid: jax.Array,
    cls: jax.Array,
    n_gt_per_class: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Vectorized VOC all-point AP over one detection pool.

    scores/tp/valid/cls: flat (D,) over every detection slot in the pool;
    n_gt_per_class: (n_classes,) GT counts. Returns (ap (n_classes,), mAP
    scalar) where mAP averages over classes with at least one GT (classes
    absent from the pool contribute nothing rather than a fake 0 or 1).
    """
    n_classes = n_gt_per_class.shape[0]

    def ap_for(c):
        m = (valid > 0) & (cls == c)
        order = jnp.argsort(-jnp.where(m, scores, -jnp.inf), stable=True)
        mf = m.astype(jnp.float32)
        tp_s = jnp.take(tp * mf, order)
        fp_s = jnp.take((1.0 - tp) * mf, order)
        ctp, cfp = jnp.cumsum(tp_s), jnp.cumsum(fp_s)
        recall = ctp / jnp.maximum(n_gt_per_class[c].astype(jnp.float32), 1.0)
        precision = ctp / jnp.maximum(ctp + cfp, 1e-9)
        env = jax.lax.cummax(precision[::-1])[::-1]  # precision envelope
        dr = jnp.diff(recall, prepend=0.0)
        return jnp.sum(env * dr)

    ap = jax.vmap(ap_for)(jnp.arange(n_classes))
    present = (n_gt_per_class > 0).astype(jnp.float32)
    map50 = jnp.sum(ap * present) / jnp.maximum(jnp.sum(present), 1.0)
    return ap, map50


def evaluate_detections(
    pred: dict[str, jax.Array],
    gt_boxes: jax.Array,
    gt_cls: jax.Array,
    gt_valid: jax.Array,
    n_classes: int,
    *,
    iou_thresh: float = 0.5,
) -> dict[str, jax.Array]:
    """One population's detection quality: {"ap" (n_classes,), "map" ()}.

    Leading dim of every array is the image axis; matching runs once, AP
    pools every image's detections (mAP@iou_thresh, default 0.5).
    """
    tp = match_detections(pred, gt_boxes, gt_cls, gt_valid, iou_thresh=iou_thresh)
    n_gt = jnp.sum(
        jax.nn.one_hot(gt_cls, n_classes, dtype=jnp.float32) * gt_valid[..., None],
        axis=(0, 1),
    )
    ap, map50 = average_precision(
        pred["scores"].reshape(-1), tp.reshape(-1), pred["valid"].reshape(-1),
        pred["cls"].reshape(-1), n_gt,
    )
    return {"ap": ap, "map": map50}


def build_evaluator(
    cfg,
    *,
    max_detections: int = 64,
    score_thresh: float = SCORE_THRESH,
    nms_iou: float = 0.5,
    match_iou: float = 0.5,
):
    """Jitted federated evaluator: (params, eval_batch) -> mAP tree.

    eval_batch: {"images" (C, B, H, W, 3), "gt_boxes" (C, B, G, 4),
    "gt_cls" (C, B, G) int32, "gt_valid" (C, B, G) 0/1}. Returns
    {"map": pooled global mAP@0.5, "per_client_map": (C,),
    "per_client_ap": (C, n_classes)} — per-client and global come out of
    the SAME call: decode/NMS/IoU run once over the flattened (C*B) image
    axis (one launch each), only the pure-jnp AP pooling differs.
    """
    n_classes = cfg.vocab_size

    @jax.jit
    def evaluate(params, batch):
        images = batch["images"]
        C, B = images.shape[:2]
        flat = lambda x: x.reshape((C * B,) + x.shape[2:])
        pred = decode_predictions(
            cfg, params, flat(images),
            max_detections=max_detections, score_thresh=score_thresh, nms_iou=nms_iou,
        )
        gt_boxes = flat(batch["gt_boxes"]).astype(jnp.float32)
        gt_cls = flat(batch["gt_cls"]).astype(jnp.int32)
        gt_valid = flat(batch["gt_valid"]).astype(jnp.float32)
        tp = match_detections(pred, gt_boxes, gt_cls, gt_valid, iou_thresh=match_iou)
        gt_hist = jax.nn.one_hot(gt_cls, n_classes, dtype=jnp.float32) * gt_valid[..., None]

        def client_ap(scores, tps, valids, clss, n_gt):
            return average_precision(scores, tps, valids, clss, n_gt)

        per = lambda x: x.reshape(C, -1)
        ap_c, map_c = jax.vmap(client_ap)(
            per(pred["scores"]), per(tp), per(pred["valid"]),
            per(pred["cls"]), gt_hist.reshape(C, -1, n_classes).sum(axis=1),
        )
        _, map_g = average_precision(
            pred["scores"].reshape(-1), tp.reshape(-1), pred["valid"].reshape(-1),
            pred["cls"].reshape(-1), gt_hist.sum(axis=(0, 1)),
        )
        return {"map": map_g, "per_client_map": map_c, "per_client_ap": ap_c}

    return evaluate
