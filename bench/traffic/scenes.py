"""Detection traffic: procedural camera scenes with ground truth, from a seed.

The scene model of the program's synthetic data (background noise of
std 0.05, one to three class-coloured rectangles of 15-50% of the image
side per scene), made in bulk with NumPy; the training targets are the
YOLO grid targets (each box on the cell holding its centre at every scale,
on the anchor closest to its shape in log space).
"""
from __future__ import annotations

import numpy as np


def scenes(rng: np.random.Generator, n: int, size: int, n_classes: int,
           max_boxes: int = 3) -> tuple[np.ndarray, list[list[tuple]]]:
    """-> images (n, size, size, 3) f32, boxes [[(label, x, y, w, h), ...], ...]."""
    images = rng.standard_normal((n, size, size, 3), dtype=np.float32)
    images *= np.float32(0.05)
    boxes: list[list[tuple]] = []
    for b in range(n):
        mine = []
        for _ in range(int(rng.integers(1, max_boxes + 1))):
            w, h = rng.uniform(0.15, 0.5, 2)
            x, y = rng.uniform(w / 2, 1 - w / 2), rng.uniform(h / 2, 1 - h / 2)
            label = int(rng.integers(0, n_classes))
            x0, y0 = int((x - w / 2) * size), int((y - h / 2) * size)
            x1, y1 = int((x + w / 2) * size), int((y + h / 2) * size)
            images[b, y0:y1, x0:x1, label % 3] += np.float32(1.0)
            mine.append((label, float(x), float(y), float(w), float(h)))
        boxes.append(mine)
    return images, boxes


def targets(boxes: list[list[tuple]], grids: list[int], anchors, n_classes: int) -> list[dict]:
    """Grid targets per scale: {"obj" (B,S,S,A), "box" (B,S,S,A,4), "cls" (B,S,S,A,C)}."""
    B, A = len(boxes), len(anchors[0])
    out = []
    for s, S in enumerate(grids):
        obj = np.zeros((B, S, S, A), np.float32)
        box = np.zeros((B, S, S, A, 4), np.float32)
        cls = np.zeros((B, S, S, A, n_classes), np.float32)
        log_anc = np.log(np.asarray(anchors[s], np.float64))
        for b, mine in enumerate(boxes):
            for label, x, y, w, h in mine:
                gx, gy = min(int(x * S), S - 1), min(int(y * S), S - 1)
                a = int(np.argmin(np.sum((log_anc - np.log([w, h])) ** 2, axis=1)))
                obj[b, gy, gx, a] = 1.0
                box[b, gy, gx, a] = (x, y, w, h)
                cls[b, gy, gx, a, label % n_classes] = 1.0
        out.append({"obj": obj, "box": box, "cls": cls})
    return out
