"""serve_open_loop: open-loop detection requests to ``InferenceService``
over its socket.

Set-up makes the detector's weights on the device from the seed (the
reference's own initialiser), publishes them once through a ``ModelSlot``,
starts the service, and starts the load generator in a child process that
never imports JAX (``bench/traffic/openloop.py``). The generator opens the
cell's camera connections and warms the service with closed-loop rounds
(the first compiles the service program). The window then starts: the
generator sends the seed's fixed schedule at the cell's rate, whether or not
earlier requests were answered, and keeps collecting answers for up to
``drain_s`` after the last one was due.

``serve_p50_ms``/``serve_p95_ms`` are over every request due in the window,
timed from when it was due to when its RESULT arrived; an ERROR or no answer
counts as infinite. ``served_rps`` is the RESULTs received inside the
window over its seconds. The service's counters are read at the window's
edges for ``batch_occupancy``.

Once the generator is done, peak memory read and the service stopped, every
answer is checked against the plain float32 reference at HIGHEST precision
over its image (forward, decode, top-K, greedy class-aware NMS). Each
served detection is matched to the reference anchor it came from (the
anchors of the cells around its centre, nearest box), and these gaps are
taken, the widest over all answers; the cell file's ``limits`` say which of
them are checks (the others are printed for the record):

- ``score_gap``: |served score - the reference's score of that anchor|;
- ``box_gap``: largest coordinate gap, relative where a coordinate exceeds 1;
- ``rank_gap``: how far that anchor's reference score lies below the
  reference's K-th best (0 when it is in the reference's top K);
- ``label_gap``: how far the reference's probability of the served class
  lies below its best class's;
- ``overlap_gap``: how far the IoU of two served detections of one class
  lies above the NMS threshold (suppression left out or too weak);
- ``missed_gap``: for a detection the reference keeps and the answer lacks,
  how far the largest IoU between it and a served detection of its class
  lies below the NMS threshold, i.e. below what would have suppressed it
  (suppression too strong, or detections dropped). A detection whose
  reference score lies within ``2 * score_gap``'s limit of the K-th best
  or of the score threshold, or whose best two classes lie that close, may
  fairly be missing at the program's precision and is not counted.
"""
from __future__ import annotations

import math
import multiprocessing
import os
import time

import numpy as np

import jax

from bench import harness
from bench.reference import rounding
from bench.traffic import openloop, scenes

WARMUP_ROUNDS = 2
INF_MS = 1e9  # what an infinite latency prints as


def _stats(svc) -> tuple[int, int]:
    """(batches launched, real images in them) so far."""
    return svc.stats.batches, svc.stats.occupancy_sum


def start_generator(svc, cell: dict, conf: dict, sched: dict, seed: int):
    ctx = multiprocessing.get_context("spawn")
    parent, child = ctx.Pipe()
    proc = ctx.Process(target=openloop.generator, daemon=True, args=(
        child, svc.host, svc.port, sched, cell["connections"], seed, cell["pool_images"],
        conf["img_size"], conf["classes"], WARMUP_ROUNDS, cell["drain_s"]))
    old = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"  # the child never touches the chip
    try:
        proc.start()
    finally:
        if old is None:
            os.environ.pop("JAX_PLATFORMS")
        else:
            os.environ["JAX_PLATFORMS"] = old
    child.close()
    return proc, parent


def reference_candidates(conf: dict, seed: int, n: int, *, operands: str | None = None,
                         block: int = 8) -> list[np.ndarray]:
    """Reference candidates of the seed's image pool, a block of images at a
    time. The control passes ``operands``: the number format every
    product's operands are rounded to (``bench/reference/rounding.py``)."""
    ref = harness.reference(conf)
    images, _ = scenes.scenes(np.random.default_rng(seed), n, conf["img_size"], conf["classes"])
    w = harness.make_weights(conf, seed)
    rnd = rounding.operand_rounding(operands)
    fn = jax.jit(lambda p, x: ref.candidates(conf, p, x, rnd))
    parts = [jax.tree.map(np.asarray, fn(w, images[i:i + block])) for i in range(0, n, block)]
    return [np.concatenate([p[k] for p in parts]) for k in range(4)]


def detections_of(boxes, scores, labels, k: int, conf: dict, cell: dict) -> list[tuple]:
    """One image's detections as the service defines them, from candidates."""
    ref = harness.reference(conf)
    kept = ref.select(boxes, scores, labels, k, cell["score_thresh"], cell["nms_iou"])
    return [(int(labels[i]), float(scores[i]), tuple(float(v) for v in boxes[i])) for i in kept]


def _anchors_near(conf: dict, x: float, y: float) -> list[int]:
    ref = harness.reference(conf)
    out = []
    for off, S, A in ref.anchor_index(conf, conf["img_size"]):
        cx, cy = int(np.floor(x * S)), int(np.floor(y * S))
        for gy in range(max(cy - 1, 0), min(cy + 2, S)):
            for gx in range(max(cx - 1, 0), min(cx + 2, S)):
                out.extend(off + (gy * S + gx) * A + a for a in range(A))
    return out


def image_reference(cand: list[np.ndarray], conf: dict, cell: dict) -> dict:
    """What every answer for one image is held to: its candidates, the K-th
    best reference score and the detections the reference keeps."""
    boxes, scores, labels, probs = cand
    k = cell["max_detections"]
    ref = harness.reference(conf)
    return {"boxes": boxes, "scores": scores, "labels": labels, "probs": probs,
            "kth": float(np.partition(scores, -k)[-k]),
            "kept": ref.select(boxes, scores, labels, k, cell["score_thresh"], cell["nms_iou"])}


GAPS = ("score_gap", "box_gap", "rank_gap", "label_gap", "overlap_gap", "missed_gap")


def answer_gaps(dets: list, img: dict, conf: dict, cell: dict) -> dict:
    """Gaps of one answer against the reference of its image."""
    ref = harness.reference(conf)
    boxes, scores, probs = img["boxes"], img["scores"], img["probs"]
    thr, margin = cell["nms_iou"], 2.0 * cell["limits"]["score_gap"]
    g = dict.fromkeys(GAPS, 0.0)
    served = []  # (reference anchor, label, box) of each served detection
    for label, score, box in dets:
        near = _anchors_near(conf, box[0], box[1])
        box = np.asarray(box, np.float64)
        rel = np.max(np.abs(boxes[near] - box) / np.maximum(1.0, np.abs(boxes[near])), axis=1)
        a = near[int(np.argmin(rel))]
        served.append((a, int(label) % probs.shape[1], box))
        g["box_gap"] = max(g["box_gap"], float(np.min(rel)))
        g["score_gap"] = max(g["score_gap"], abs(score - float(scores[a])))
        g["rank_gap"] = max(g["rank_gap"], max(0.0, img["kth"] - float(scores[a])))
        g["label_gap"] = max(g["label_gap"], float(probs[a].max() - probs[a][int(label) % probs.shape[1]]))
    for i, (_, li, bi) in enumerate(served):
        for _, lj, bj in served[i + 1:]:
            if li == lj:
                g["overlap_gap"] = max(g["overlap_gap"], float(ref.iou_np(bi, bj)) - thr)
    anchors = {a for a, _, _ in served}
    for r in img["kept"]:
        top2 = np.sort(probs[r])[-2:]
        if (r in anchors or scores[r] < img["kth"] + margin or scores[r] < cell["score_thresh"] + margin
                or top2[1] - top2[0] < margin):
            continue
        same = [b for _, lb, b in served if lb == int(img["labels"][r])]
        best = max((float(ref.iou_np(boxes[r], b)) for b in same), default=0.0)
        g["missed_gap"] = max(g["missed_gap"], thr - best)
    return g


def check_answers(answers: dict, images: np.ndarray, cand: list[np.ndarray], conf: dict,
                  cell: dict) -> dict:
    """Widest gap of each kind over all answers (request -> (version, dets));
    ``images`` gives each request's pool image."""
    refs = [image_reference([c[i] for c in cand], conf, cell) for i in range(len(cand[0]))]
    worst = dict.fromkeys(GAPS, 0.0)
    done = set()  # an image answered alike many times is checked once
    for i, (_version, dets) in answers.items():
        key = (int(images[i]), tuple((d[0], d[1], tuple(d[2])) for d in dets))
        if key in done:
            continue
        done.add(key)
        for name, v in answer_gaps(dets, refs[key[0]], conf, cell).items():
            worst[name] = max(worst[name], v)
    return worst


def drive(run: harness.Run) -> harness.Outcome:
    from repro.core import serving
    from repro.core.rounds import FedConfig

    cell, conf = run.cell, run.conf
    dev = jax.devices()[0]
    since = lambda: time.perf_counter() - run.t_start
    t_dev = since()
    fed = FedConfig(n_clients=1, serve_batch=cell["serve_batch"], serve_max_wait_s=cell["linger_s"],
                    serve_max_detections=cell["max_detections"])
    slot = serving.ModelSlot()
    slot.publish(0, harness.make_weights(conf, run.seed))
    sched = openloop.schedule(cell["rate_rps"], run.seconds, cell["connections"], cell["pool_images"],
                              run.seed)
    t_weights = since()
    svc = serving.InferenceService(harness.arch(conf), fed, slot, img_size=conf["img_size"]).start()
    t_service = since()
    proc, pipe = None, None
    try:
        proc, pipe = start_generator(svc, cell, conf, sched, run.seed)
        if not pipe.poll(900.0):
            raise RuntimeError("the load generator did not warm the service up")
        warm = pipe.recv()
        run.log(f"set-up: device ready {t_dev:.3f} s, weights {t_weights:.3f} s, service {t_service:.3f} s, "
                f"generator up {since() - t_service - warm['pool_s'] - warm['warmup_s']:.3f} s, "
                f"its pool {warm['pool_s']:.3f} s, warm-up {warm['warmup_s']:.3f} s")
        t0 = time.monotonic() + 0.5
        pipe.send(t0)
        setup_s = (t0 - time.monotonic()) + (time.perf_counter() - run.t_start)
        run.tracer.start(host_spans=False)
        time.sleep(max(0.0, t0 - time.monotonic()))
        s0, c0 = _stats(svc), run.compile_stats.compiles
        with run.tracer.span("bench.window"):
            time.sleep(max(0.0, t0 + run.seconds - time.monotonic()))
        s1, c1 = _stats(svc), run.compile_stats.compiles
        run.tracer.stop()
        memory = harness.peak_memory(dev)
        if not pipe.poll(run.seconds + cell["drain_s"] + 120.0):
            raise RuntimeError("the load generator sent no record")
        rec = pipe.recv()
        proc.join(timeout=30.0)
    finally:
        if proc is not None and proc.is_alive():
            proc.terminate()
            proc.join(timeout=10.0)
        svc.stop()

    due = t0 + sched["due"]
    lat_ms = openloop.latencies(due, rec["recv"], rec["ok"]) * 1e3
    in_window = rec["ok"] & (rec["recv"] >= t0) & (rec["recv"] < t0 + run.seconds)
    late_ms = (rec["sent"] - due) * 1e3
    n, failed = len(due), int(np.sum(~rec["ok"]))
    run.log(f"serve_open_loop: {n} requests due at {cell['rate_rps']} req/s, {int(rec['ok'].sum())} answered, "
            f"{failed} failed or unanswered; generator late p50 {np.percentile(late_ms, 50):.3f} ms "
            f"p99 {np.percentile(late_ms, 99):.3f} ms max {np.max(late_ms):.3f} ms")
    p = {q: openloop.percentile(lat_ms, q) for q in (50, 95, 99)}
    run.log(f"serve_open_loop: latency p50 {p[50]!r} p95 {p[95]!r} p99 {p[99]!r} ms; "
            f"batches {s1[0] - s0[0]} occupancy {s1[1] - s0[1]}")
    problems = []
    versions = {v for v, _ in rec["answers"].values()}
    if versions - {0}:
        problems.append(f"answers carry versions {sorted(versions)}, published 0")
    t_ref = time.perf_counter()
    cand = reference_candidates(conf, run.seed, cell["pool_images"])
    gaps = check_answers(rec["answers"], sched["image"], cand, conf, cell)
    run.log(f"serve_open_loop: reference and check took {time.perf_counter() - t_ref:.3f} s")
    checks = harness.checks(gaps, cell["limits"], run.log)
    finite = lambda v: v if math.isfinite(v) else INF_MS
    return harness.Outcome(
        metrics={"setup_s": setup_s, "serve_p50_ms": finite(p[50]), "serve_p95_ms": finite(p[95]),
                 "served_rps": float(np.sum(in_window)) / run.seconds},
        record={"window_s": run.seconds, "batches": s1[0] - s0[0], "images_served": s1[1] - s0[1],
                "compiles_in_window": c1 - c0, "gaps": gaps},
        checks=checks, attempted=n, failed=failed, memory_peak_bytes=memory, problems=problems)
