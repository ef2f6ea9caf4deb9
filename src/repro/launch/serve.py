"""Serving launcher: the online detection service + batched LLM decode.

CPU-runnable at reduced size; the production-mesh serve plans (32k decode,
500k long-context) are exercised via launch.dryrun.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --new-tokens 16
  PYTHONPATH=src python -m repro.launch.serve --arch fedyolov3 --store /tmp/cos
  PYTHONPATH=src python -m repro.launch.serve --arch fedyolov3 --one-shot

yolo-family archs serve *detections* — the paper's "model dispatch to
visual serving" leg. The default mode stands up the real serving plane
(DESIGN.md §17): `core.serving.InferenceService` listening on a socket,
batching INFER frames into one jitted decode+NMS program, then drives
``--requests`` synthetic requests through an `InferenceClient` and prints
the QPS/latency/freshness summary. ``--store``/``--task-id`` restore the
federated global model from the COS object store that `launch.train` /
`examples/fed_yolo.py` checkpointed into, published at the stored round
version (so RESULT frames carry the training round they came from).
``--one-shot`` keeps the old decode-one-batch-and-exit behavior.
"""
from __future__ import annotations

import argparse
import functools
import json
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.checkpoint import ObjectStore
from repro.configs import get_arch
from repro.launch.cache import enable_compile_cache
from repro.models import params as P
from repro.models import serving as S
from repro.models import transformer as T
from repro.models import yolov3


@functools.lru_cache(maxsize=8)
def decode_programs(cfg, max_len: int):
    """Cached jitted (prefill, decode_step) per (cfg, max_len).

    Built once and reused across `generate` calls — previously each call
    re-wrapped `jax.jit` around fresh lambdas, so every request paid a
    full retrace of both programs. `cfg` is a frozen dataclass, hence a
    valid cache key; `tests/test_serving.py` pins the cache hit."""
    prefill = jax.jit(lambda p, b: S.prefill(cfg, p, b, max_len=max_len))
    step = jax.jit(lambda p, c, t, pos: S.decode_step(cfg, p, c, t, pos))
    return prefill, step


def generate(cfg, params, prompts: jax.Array, new_tokens: int, images=None, temperature: float = 0.0, seed: int = 0):
    B, Sq = prompts.shape
    ni = cfg.n_image_tokens if cfg.modality == "vlm" else 0
    batch = {"tokens": prompts}
    if ni:
        batch["images"] = images
    prefill, step = decode_programs(cfg, ni + Sq + new_tokens)
    logits, cache = prefill(params, batch)
    out = []
    key = jax.random.key(seed)
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    for i in range(new_tokens):
        out.append(tok)
        logits, cache = step(params, cache, tok, jnp.int32(ni + Sq + i))
        if temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, logits[:, -1] / temperature)[:, None].astype(jnp.int32)
        else:
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    return jnp.concatenate(out, axis=1)


def restore_params(cfg, args):
    """COS restore -> (params, round version). The published version is the
    stored round index, so served RESULT frames carry the actual training
    round — not a fake 0 — after a restore."""
    params = P.init_params(yolov3.template(cfg), jax.random.key(0), jnp.float32)
    version = 0
    if args.store:
        store = ObjectStore(args.store)
        version = max(store.rounds(args.task_id))
        params = store.restore_into(args.task_id, params)
    return params, version


def serve_detection(cfg, args) -> None:
    """--one-shot: decode one synthetic batch -> box list JSON, exit."""
    from repro.core import detection
    from repro.data import synthetic

    params, _ = restore_params(cfg, args)
    rng = np.random.default_rng(7)
    imgs, _ = synthetic.scene_images(rng, args.batch, args.img_size, cfg.vocab_size)
    t0 = time.time()
    pred = detection.decode_predictions(
        cfg, params, jnp.asarray(imgs), max_detections=args.max_detections
    )
    jax.block_until_ready(pred)
    dt = time.time() - t0
    valid, cls, scores, boxes = (np.asarray(pred[k]) for k in ("valid", "cls", "scores", "boxes"))
    detections = [
        [
            {
                "label": int(cls[b, k]),
                "score": round(float(scores[b, k]), 4),
                "box": [round(float(v), 4) for v in boxes[b, k]],
            }
            for k in np.nonzero(valid[b])[0]
        ]
        for b in range(args.batch)
    ]
    print(json.dumps({
        "arch": cfg.name,
        "restored": bool(args.store),
        "detections": detections,
        "images_per_s": round(args.batch / dt, 2),
    }))


def serve_service(cfg, args) -> None:
    """The serving plane (DESIGN.md §17): stand up the socket service,
    drive --requests synthetic requests, print the operational summary."""
    from repro.core import rounds as R
    from repro.core import serving
    from repro.data import synthetic

    fed = R.FedConfig(
        n_clients=1,
        serve_batch=args.serve_batch,
        serve_max_detections=args.max_detections,
    )
    params, version = restore_params(cfg, args)
    slot = serving.ModelSlot()
    slot.publish(version, params)
    svc = serving.InferenceService(
        cfg, fed, slot, img_size=args.img_size, port=args.port
    ).start()
    rng = np.random.default_rng(7)
    imgs, _ = synthetic.scene_images(rng, args.requests, args.img_size, cfg.vocab_size)
    # warm the jitted program so compile time doesn't pollute the latencies
    with serving.InferenceClient(svc.host, svc.port) as warm:
        warm.infer(imgs[0])
    lat = []
    t0 = time.perf_counter()
    with serving.InferenceClient(svc.host, svc.port) as client:
        for i in range(args.requests):
            t1 = time.perf_counter()
            res = client.infer(imgs[i])
            lat.append(time.perf_counter() - t1)
        total = time.perf_counter() - t0
        status = client.status()
    svc.stop()
    lat.sort()
    print(json.dumps({
        "arch": cfg.name,
        "restored": bool(args.store),
        "version": status["version"],
        "tier": status["tier"],
        "requests": args.requests,
        "dropped": status["in_flight"],
        "qps": round(args.requests / total, 2),
        "p50_ms": round(lat[len(lat) // 2] * 1e3, 3),
        "p99_ms": round(lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3, 3),
        "avg_occupancy": status["avg_occupancy"],
        "last_detections": len(res.detections),
    }))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--img-size", type=int, default=64, help="yolo: served image size")
    ap.add_argument("--max-detections", type=int, default=16, help="yolo: NMS output slots")
    ap.add_argument("--store", default="", help="COS dir to restore the federated model from")
    ap.add_argument("--task-id", default="fedyolo", help="COS task id (with --store)")
    ap.add_argument("--one-shot", action="store_true",
                    help="yolo: decode one synthetic batch and exit (pre-§17 behavior)")
    ap.add_argument("--port", type=int, default=0, help="service port (0 = ephemeral)")
    ap.add_argument("--requests", type=int, default=8,
                    help="service: synthetic requests to drive through the socket")
    ap.add_argument("--serve-batch", type=int, default=8,
                    help="service: batch slots of the jitted decode+NMS program")
    ap.add_argument("--full-size", action="store_true",
                    help="use the full config (must match how the stored model was trained)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if not args.full_size:
        cfg = cfg.reduced()
    if cfg.family == "yolo":
        if args.one_shot:
            serve_detection(cfg, args)
        else:
            serve_service(cfg, args)
        return
    if not cfg.has_decode:
        raise SystemExit(f"{args.arch} is encoder-only: no decode step (DESIGN.md)")
    params = P.init_params(T.template(cfg), jax.random.key(0), jnp.float32)
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32)
    images = (
        jnp.asarray(rng.normal(size=(args.batch, cfg.n_image_tokens, cfg.d_model)) * 0.1, jnp.float32)
        if cfg.modality == "vlm"
        else None
    )
    t0 = time.time()
    toks = generate(cfg, params, prompts, args.new_tokens, images, args.temperature)
    dt = time.time() - t0
    print(json.dumps({
        "arch": cfg.name,
        "generated": np.asarray(toks[0]).tolist(),
        "tokens_per_s": round(args.batch * args.new_tokens / dt, 2),
    }))


if __name__ == "__main__":
    main()
