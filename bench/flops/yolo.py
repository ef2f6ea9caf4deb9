"""Operation counts of the YOLOv3-lite detector, from its shapes.

Multiply-adds of every convolution, two operations each, over the taps that
fall inside the image ("SAME" padding adds zeros that need no work);
elementwise work is left out.
"""
from __future__ import annotations


def _taps(n: int, k: int, stride: int) -> tuple[int, int]:
    """(output size, kernel taps inside the input summed over the outputs)
    of one axis of a "SAME" convolution."""
    m = -(-n // stride)
    lo = max((m - 1) * stride + k - n, 0) // 2
    return m, sum(sum(0 <= o * stride - lo + t < n for t in range(k)) for o in range(m))


def conv(hw: int, k: int, stride: int, cin: int, cout: int) -> tuple[int, float]:
    """(output side, operations) of a square "SAME" convolution."""
    m, taps = _taps(hw, k, stride)
    return m, 2.0 * taps * taps * cin * cout


def forward_per_image(conf: dict, img: int) -> float:
    c, n = conf["stem_filters"], max(conf["stages"], 3)
    out_ch = conf["anchors_per_scale"] * (5 + conf["classes"])
    hw, total = conv(img, 3, 1, 3, c)
    cin, feats = c, []
    for i in range(n):
        w = c * 2 ** min(i + 1, 5)
        hw, f1 = conv(hw, 3, 2, cin, w)
        _, f2 = conv(hw, 1, 1, w, w // 2)
        _, f3 = conv(hw, 3, 1, w // 2, w)
        total += f1 + f2 + f3
        feats.append((hw, w))
        cin = w
    for hw, w in feats[-3:]:
        total += conv(hw, 1, 1, w, out_ch)[1]
    return total


def train_per_round(conf: dict, cell: dict) -> float:
    """Forward and backward (three forwards) of every image every client
    trains in one round."""
    images = cell["clients"] * cell["local_steps"] * cell["local_batch"]
    return 3.0 * images * forward_per_image(conf, conf["img_size"])
