"""Plain float32 reference of the federated detector (YOLOv3-lite, FedVision).

Written from the model's description, independent of the program: a
darknet-style backbone of stages (stride-2 3x3 conv, then a residual
1x1 -> 3x3 pair, leaky ReLU 0.1), 1x1 heads on the last three stages
(strides 8/16/32 at five stages), YOLOv3 box decoding with per-scale
anchor priors, and the FedVision paper's Eqs. 2-4 loss (squared-error
class and box terms on object cells, confidence target p(obj) * IoU,
lambda_coord 5 and lambda_noobj 0.5). Every convolution runs at HIGHEST
precision; ``rnd``, where given, rounds both of its operands first (the
control's lower precision, see ``bench/reference/rounding.py``).

The parameter tree uses the layout the model description names (``stem``,
``stages[i].down/res1/res2``, ``heads[i]``), so one tree made by the
benchmark can be handed to the program and to this reference.
"""
from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

LAMBDA_COORD = 5.0
LAMBDA_NOOBJ = 0.5
LEAK = 0.1
ANCHORS = (
    ((0.05, 0.06), (0.10, 0.12), (0.16, 0.20)),  # stride 8
    ((0.22, 0.28), (0.35, 0.40), (0.45, 0.55)),  # stride 16
    ((0.55, 0.70), (0.75, 0.85), (0.90, 0.95)),  # stride 32
)


STACKED: tuple[str, ...] = ()  # no parameter is stacked on a layer axis


def n_layers(conf: dict) -> int:
    return conf["stages"]


def shapes(conf: dict) -> dict:
    """Parameter shapes (HWIO) from the configuration file's sizes."""
    c, n = conf["stem_filters"], max(conf["stages"], 3)
    out_ch = conf["anchors_per_scale"] * (5 + conf["classes"])
    widths = [c * 2 ** min(i + 1, 5) for i in range(n)]
    stages, cin = [], c
    for w in widths:
        stages.append({"down": (3, 3, cin, w), "res1": (1, 1, w, w // 2), "res2": (3, 3, w // 2, w)})
        cin = w
    return {
        "stem": (3, 3, 3, c),
        "stages": tuple(stages),
        "heads": tuple((1, 1, widths[-3 + i], out_ch) for i in range(3)),
    }


def init(conf: dict, key) -> dict:
    """Fan-in normal weights for every convolution (heads included, so the
    served scores spread over (0, 1) instead of sitting on one value)."""
    tree = shapes(conf)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], int))
    keys = jax.random.split(key, len(leaves))
    made = [jax.random.normal(k, s, jnp.float32) / math.sqrt(s[0] * s[1] * s[2]) for k, s in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, made)


def grid_sizes(conf: dict, img: int) -> list[int]:
    n = max(conf["stages"], 3)
    return [img // (1 << (n - 2)), img // (1 << (n - 1)), img // (1 << n)]


def _conv(x, w, stride, rnd):
    if rnd is not None:
        x, w = rnd(x), rnd(w)
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def _lrelu(x):
    return jnp.where(x >= 0, x, LEAK * x)


def forward(conf, params, images, rnd=None):
    """images (B, H, W, 3) -> three raw head outputs (B, S, S, A, 5 + C)."""
    A, C = conf["anchors_per_scale"], conf["classes"]
    x = _lrelu(_conv(images, params["stem"], 1, rnd))
    feats = []
    for st in params["stages"]:
        x = _lrelu(_conv(x, st["down"], 2, rnd))
        h = _lrelu(_conv(x, st["res1"], 1, rnd))
        x = x + _lrelu(_conv(h, st["res2"], 1, rnd))
        feats.append(x)
    outs = []
    for f, head in zip(feats[-3:], params["heads"]):
        o = _conv(f, head, 1, rnd)
        B, S1, S2, _ = o.shape
        outs.append(o.reshape(B, S1, S2, A, 5 + C))
    return outs


def decode(raw, anchors):
    """raw (B, S, S, A, 5 + C) -> boxes (x, y, w, h), confidence, class probs."""
    S = raw.shape[1]
    raw = raw.astype(jnp.float32)
    gy, gx = jnp.meshgrid(jnp.arange(S), jnp.arange(S), indexing="ij")
    grid = jnp.stack([gx, gy], -1)[:, :, None, :].astype(jnp.float32)
    xy = (jax.nn.sigmoid(raw[..., 0:2]) + grid) / S
    wh = jnp.asarray(anchors, jnp.float32) * jnp.exp(jnp.clip(raw[..., 2:4], -6, 6))
    return jnp.concatenate([xy, wh], -1), jax.nn.sigmoid(raw[..., 4]), jax.nn.sigmoid(raw[..., 5:])


def iou(a, b):
    """IoU of center-format boxes (..., 4), broadcasting."""
    ax1, ay1, ax2, ay2 = a[..., 0] - a[..., 2] / 2, a[..., 1] - a[..., 3] / 2, a[..., 0] + a[..., 2] / 2, a[..., 1] + a[..., 3] / 2
    bx1, by1, bx2, by2 = b[..., 0] - b[..., 2] / 2, b[..., 1] - b[..., 3] / 2, b[..., 0] + b[..., 2] / 2, b[..., 1] + b[..., 3] / 2
    inter = jnp.maximum(jnp.minimum(ax2, bx2) - jnp.maximum(ax1, bx1), 0) * jnp.maximum(jnp.minimum(ay2, by2) - jnp.maximum(ay1, by1), 0)
    union = jnp.maximum((ax2 - ax1) * (ay2 - ay1), 0) + jnp.maximum((bx2 - bx1) * (by2 - by1), 0) - inter
    return inter / jnp.maximum(union, 1e-9)


def loss(conf, params, batch, rnd=None):
    """FedVision Eqs. 2-4, summed over scales, averaged over images."""
    outs = forward(conf, params, batch["images"], rnd)
    total = jnp.float32(0)
    for raw, anchors, tgt in zip(outs, ANCHORS, batch["targets"]):
        obj = tgt["obj"].astype(jnp.float32)
        boxes, conf_p, cls = decode(raw, anchors)
        l_cls = jnp.sum(obj[..., None] * (tgt["cls"] - cls) ** 2)
        d = (tgt["box"] - boxes) ** 2
        l_box = LAMBDA_COORD * jnp.sum(obj * (d[..., 0] + d[..., 1] + d[..., 2] + d[..., 3]))
        theta = obj * jax.lax.stop_gradient(iou(boxes, tgt["box"]))
        l_conf = jnp.sum(obj * (theta - conf_p) ** 2) + LAMBDA_NOOBJ * jnp.sum((1 - obj) * (theta - conf_p) ** 2)
        total = total + l_cls + l_box + l_conf
    return total / batch["images"].shape[0]


# -- serving: per-anchor candidates and the NMS the service promises -----------

def candidates(conf, params, images, rnd=None):
    """Every anchor of every scale, flattened scale by scale in (row, column,
    anchor) order: boxes (B, N, 4), scores (B, N) = confidence x best class
    probability, classes (B, N), class probabilities (B, N, C)."""
    boxes, scores, labels, probs = [], [], [], []
    for raw, anchors in zip(forward(conf, params, images, rnd), ANCHORS):
        b, c, p = decode(raw, anchors)
        B = b.shape[0]
        boxes.append(b.reshape(B, -1, 4))
        scores.append((c * jnp.max(p, -1)).reshape(B, -1))
        labels.append(jnp.argmax(p, -1).reshape(B, -1))
        probs.append(p.reshape(B, -1, p.shape[-1]))
    cat = lambda xs: jnp.concatenate(xs, 1)
    return cat(boxes), cat(scores), cat(labels).astype(jnp.int32), cat(probs)


def anchor_index(conf: dict, img: int) -> list[tuple[int, int, int]]:
    """(offset, grid S, anchors A) of each scale in the flattened anchor axis."""
    out, off = [], 0
    for S in grid_sizes(conf, img):
        out.append((off, S, conf["anchors_per_scale"]))
        off += S * S * conf["anchors_per_scale"]
    return out


def iou_np(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """NumPy IoU of center-format boxes, broadcasting."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    ix = np.maximum(np.minimum(a[..., 0] + a[..., 2] / 2, b[..., 0] + b[..., 2] / 2)
                    - np.maximum(a[..., 0] - a[..., 2] / 2, b[..., 0] - b[..., 2] / 2), 0)
    iy = np.maximum(np.minimum(a[..., 1] + a[..., 3] / 2, b[..., 1] + b[..., 3] / 2)
                    - np.maximum(a[..., 1] - a[..., 3] / 2, b[..., 1] - b[..., 3] / 2), 0)
    inter = ix * iy
    union = np.maximum(a[..., 2] * a[..., 3], 0) + np.maximum(b[..., 2] * b[..., 3], 0) - inter
    return inter / np.maximum(union, 1e-9)


def select(boxes: np.ndarray, scores: np.ndarray, labels: np.ndarray, k: int,
           score_thresh: float, iou_thresh: float) -> list[int]:
    """Top-k by score, then greedy class-aware NMS: the kept anchor indices
    of one image, score-descending."""
    top = np.argsort(-scores, kind="stable")[:k]
    kept: list[int] = []
    for i in top:
        if scores[i] <= score_thresh:
            continue
        same = [j for j in kept if labels[j] == labels[i]]
        if same and np.max(iou_np(boxes[same], boxes[i])) > iou_thresh:
            continue
        kept.append(int(i))
    return kept
