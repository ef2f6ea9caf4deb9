"""Round batches of the detector's federated training cells, from a seed.

A pool of ``pool_rounds`` distinct rounds; each round holds, for each
client, ``local_steps`` steps of ``local_batch`` scenes with their grid
targets, laid out as the round program takes them: (C, E, b, ...).
"""
from __future__ import annotations

import numpy as np

import jax

from bench.reference import yolo as ref
from bench.traffic import scenes


def round_pool(conf: dict, cell: dict, seed: int) -> list:
    rng = np.random.default_rng(seed)
    C, E, b, img = cell["clients"], cell["local_steps"], cell["local_batch"], conf["img_size"]
    grids = ref.grid_sizes(conf, img)
    pool = []
    for _ in range(cell["pool_rounds"]):
        images, boxes = scenes.scenes(rng, C * E * b, img, conf["classes"])
        tgt = scenes.targets(boxes, grids, ref.ANCHORS, conf["classes"])
        lead = lambda x: x.reshape((C, E, b) + x.shape[1:])
        pool.append(jax.device_put({"images": lead(images),
                                    "targets": [{k: lead(v) for k, v in t.items()} for t in tgt]}))
    return pool

