"""Bring-up smoke run of the FedVision main path on a TPU.

    python chip_smoke.py             # one chip: train, eval, serve
    python chip_smoke.py --chips 4   # four chips: sharded hier round vs one chip

One process, full-width `fedyolov3` (5 darknet stages, base width 32,
~13M parameters, random weights from a seed) on 416 px images, YOLOv3's
published input size. Every phase prints what it found on its own lines and
raises on anything wrong; the last line of a run that passed is one JSON
object naming the device. There is no CPU fallback: without a TPU the
script exits non-zero before any phase runs, and it sets no
``JAX_PLATFORMS``. It starts no child process.

Phases (one chip):
  device  the first device must be a TPU;
  train   FLServer sync rounds, C=4 clients, batch 4, the default (eq6)
          aggregator, built as `launch/train.py --task detection
          --full-size --img-size 416` builds it; the loss must be finite and
          fall; prints peak device bytes;
  eval    `FLServer.evaluate_round` (compiled Pallas pairwise IoU + NMS);
  serve   `InferenceService` (serve_batch 8, 16 detections) answers INFER
          requests over its socket; every result must equal the decode
          with the NumPy reference NMS, the compiled NMS keep mask must
          equal that reference on the same inputs, and the service program
          must hold a compiled Pallas kernel (`tpu_custom_call`).

Phase (four chips): `hier` (group_size 2, dense base) with C=8 clients
sharded over a (4, 1) mesh, against the same two rounds on one of those
chips. TPU f32 convolutions and matmuls run at default precision (bf16
passes, f32 accumulation) and the two programs tile their per-device work
differently (2 clients per chip against 8), so the comparison allows
HIER_RTOL relative to the largest parameter, and the same for the loss.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import telemetry  # noqa: E402  (its listener counts every compile)

IMG = 416  # YOLOv3's published input size: 52/26/13 grids
CLIENTS = 4
BATCH = 4
ROUNDS = 5
SERVE_BATCH = 8
SERVE_DETECTIONS = 16
SERVE_REQUESTS = 24
HIER_CLIENTS = 8
HIER_BATCH = 2
HIER_ROUNDS = 2
HIER_RTOL = 1e-4


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def compile_line() -> str:
    """Backend compile seconds and persistent-cache hits so far, from
    `telemetry`'s compile counters (a cache hit still records its retrieval
    time)."""
    c = telemetry.counters()
    return (f"backend compile {c.get('jax.compile_ns', 0) / 1e9:.3f} s, persistent cache "
            f"hits {c.get('jax.cache_hits', 0)} misses {c.get('jax.cache_misses', 0)}")


def check_device(n_chips: int):
    devs = jax.devices()
    d = devs[0]
    log("device", f"platform {d.platform} kind {d.device_kind!r} count {len(devs)} jax {jax.__version__}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, found {d.platform!r}; no CPU fallback")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: --chips {n_chips} needs {n_chips} devices, found {len(devs)}")
    return d


def one_chip_mesh(devices=None):
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2, devices=devices)


# -- train --------------------------------------------------------------------

def phase_train(cfg, img: int):
    """FLServer sync rounds built as `launch/train.py --task detection`
    builds them. Returns (server, eval_batch)."""
    from repro.core.rounds import FedConfig
    from repro.core.scheduler import SchedulerConfig, TaskScheduler
    from repro.core.server import FLServer
    from repro.data.pipeline import detection_suite
    from repro.launch import specs
    from repro.optim import adamw

    budget = max(2, CLIENTS // 2)
    fed = FedConfig(n_clients=CLIENTS, local_steps=1, topn=specs.default_topn(cfg),
                    client_axis="data", data_axis=None)
    mesh = one_chip_mesh()
    with jax.set_mesh(mesh):
        server = FLServer(
            cfg, fed, adamw(3e-3), mesh=mesh, task_id=cfg.name,
            scheduler=TaskScheduler(CLIENTS, SchedulerConfig(max_participants=budget, fairness_rounds=4)),
        )
        gen, eval_batch, _ = detection_suite(cfg, fed, batch=BATCH, img_size=img, scenario="iid")
        log("train", f"{cfg.name} params {server.aggregator.ctx.spec.n_total} "
                     f"clients {CLIENTS} batch {BATCH} img {img} aggregation {fed.aggregation}")
        for _ in range(ROUNDS):
            rec = server.run_round(jax.tree.map(jnp.asarray, next(gen)))
            log("train", f"round {rec.round_idx} loss {rec.loss!r} seconds {rec.seconds:.3f} "
                         f"participants {rec.participants}")
    losses = [r.loss for r in server.history]
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    log("train", f"loss fell {losses[0]!r} -> {losses[-1]!r}")
    return server, eval_batch


# -- eval ---------------------------------------------------------------------

def phase_eval(server, eval_batch) -> None:
    ev = server.evaluate_round(eval_batch)
    if not (0.0 <= ev.map50 <= 1.0):
        raise AssertionError(f"mAP@0.5 out of range: {ev.map50}")
    # a path check, not a result: random-init weights after a few rounds
    log("eval", f"mAP@0.5 {ev.map50!r} per-client {ev.per_client_map}")


# -- serve --------------------------------------------------------------------

def reference_detections(cfg, params, images, max_detections: int):
    """Per-image detection lists from `detection_candidates` + the NumPy
    NMS oracle, and the compiled NMS keep mask on the same inputs."""
    from repro.core import detection, serving
    from repro.kernels import ops, ref

    cand = jax.jit(lambda p, x: detection.detection_candidates(
        cfg, p, x, max_detections=max_detections))(params, jnp.asarray(images))
    keep_dev = np.asarray(ops.nms(cand["shifted"], cand["scores"],
                                  score_thresh=detection.SCORE_THRESH))
    host = jax.tree.map(np.asarray, cand)
    keep_ref = ref.nms_np(host["shifted"], host["scores"], score_thresh=detection.SCORE_THRESH)
    if not np.array_equal(keep_dev, keep_ref):
        raise AssertionError(f"compiled NMS keep mask != reference:\n{keep_dev}\n{keep_ref}")
    pred = {**host, "valid": keep_ref}
    return [serving.decode_result(pred, i) for i in range(len(images))], int(keep_ref.sum())


def phase_serve(cfg, params, version: int, img: int, *, fed=None, require_kernel: bool = True) -> None:
    from repro.core import serving
    from repro.core.rounds import FedConfig
    from repro.data import synthetic

    fed = dataclasses.replace(fed or FedConfig(n_clients=1), serve_batch=SERVE_BATCH,
                              serve_max_detections=SERVE_DETECTIONS)
    slot = serving.ModelSlot()
    slot.publish(version, params)
    images, _ = synthetic.scene_images(np.random.default_rng(7), SERVE_REQUESTS, img, cfg.vocab_size)
    svc = serving.InferenceService(cfg, fed, slot, img_size=img).start()
    try:
        with serving.InferenceClient(svc.host, svc.port, timeout=600.0) as client:
            t0 = time.perf_counter()
            first = client.infer(images[0])  # compiles the service program
            log("serve", f"first request (compile included) {time.perf_counter() - t0:.3f} s")
            results = {0: first}
            lock = threading.Lock()

            def pipelined(lo, hi):
                with serving.InferenceClient(svc.host, svc.port, timeout=600.0) as c:
                    rids = {c.send_infer(images[i]): i for i in range(lo, hi)}
                    for _ in rids:
                        res = c.recv_result()
                        with lock:
                            results[rids[res.request_id]] = res

            half = 1 + (SERVE_REQUESTS - 1) // 2
            workers = [threading.Thread(target=pipelined, args=(1, half)),
                       threading.Thread(target=pipelined, args=(half, SERVE_REQUESTS))]
            for w in workers:
                w.start()
            for w in workers:
                w.join()
        st = svc.stats
        log("serve", f"requests {st.requests} results {st.results} batches {st.batches} "
                     f"avg occupancy {st.avg_occupancy:.3f} in flight {st.in_flight} failed {st.failed}")
        if len(results) != SERVE_REQUESTS or st.in_flight != 0 or st.failed:
            raise AssertionError(f"served {len(results)}/{SERVE_REQUESTS}, stats {st.as_dict()}")
    finally:
        svc.stop()  # re-raises a batch failure with the compiler's own message

    refs, kept = [], 0
    for lo in range(0, SERVE_REQUESTS, SERVE_BATCH):
        r, k = reference_detections(cfg, params, images[lo:lo + SERVE_BATCH], SERVE_DETECTIONS)
        refs += r
        kept += k
    for i in range(SERVE_REQUESTS):
        if results[i].detections != refs[i]:
            raise AssertionError(f"request {i}: served {results[i].detections} != reference {refs[i]}")
        if results[i].version != version:
            raise AssertionError(f"request {i}: version {results[i].version} != {version}")
    log("serve", f"{SERVE_REQUESTS} results equal the reference-NMS decode ({kept} detections kept); "
                 "compiled NMS keep masks equal the NumPy oracle")

    prog = serving.detection_program(cfg, SERVE_DETECTIONS)
    text = prog.lower(params, jnp.zeros((SERVE_BATCH, img, img, 3), jnp.float32)).compile().as_text()
    n_kernels = text.count("tpu_custom_call")
    log("serve", f"service program holds tpu_custom_call x{n_kernels}")
    if require_kernel and not n_kernels:
        raise AssertionError("service program has no compiled Pallas kernel (tpu_custom_call)")


# -- four chips: sharded hier -------------------------------------------------

def hier_round(cfg, mesh, batch, weights):
    """`hier` (group_size 2, dense base) rounds under ``mesh`` -> (params, loss)."""
    from repro.core import rounds as R
    from repro.core.rounds import FedConfig
    from repro.launch import specs
    from repro.optim import sgd

    fed = FedConfig(n_clients=len(weights), local_steps=1, aggregation="hier", group_size=2,
                    hier_base="dense", topn=specs.default_topn(cfg),
                    client_axis="data", data_axis=None)
    opt = sgd(1e-3)
    with jax.set_mesh(mesh):
        state = R.make_state(cfg, fed, opt, jax.random.key(0))
        fr = jax.jit(R.build_fed_round(cfg, fed, opt, mesh))
        for _ in range(HIER_ROUNDS):
            state, m = fr(state, batch, weights)
        params = state["params"]
        devices = sorted({s.device.id for s in params.addressable_shards})
        return np.asarray(jax.device_get(params), np.float64), float(m["loss"]), devices


def phase_hier(cfg, img: int, n_shards: int):
    from repro.core.rounds import FedConfig
    from repro.data.pipeline import detection_suite

    fed = FedConfig(n_clients=HIER_CLIENTS, local_steps=1)
    gen, _, _ = detection_suite(cfg, fed, batch=HIER_BATCH, img_size=img, scenario="iid")
    data = jax.tree.map(jnp.asarray, next(gen))
    w = jnp.asarray(np.random.default_rng(3).uniform(0.5, 1.5, HIER_CLIENTS), jnp.float32)
    w = w / jnp.sum(w)
    devs = jax.devices()
    sharded = jax.make_mesh((n_shards, 1), ("data", "model"),
                            axis_types=(jax.sharding.AxisType.Auto,) * 2, devices=devs[:n_shards])
    p_s, l_s, d_s = hier_round(cfg, sharded, data, w)
    log("hier", f"sharded over {n_shards}: loss {l_s!r}, params on devices {d_s}")
    p_1, l_1, d_1 = hier_round(cfg, one_chip_mesh(devs[:1]), data, w)
    log("hier", f"one chip: loss {l_1!r}, params on devices {d_1}")
    if len(d_s) != n_shards:
        raise AssertionError(f"sharded params live on {d_s}, expected {n_shards} devices")
    scale = max(float(np.max(np.abs(p_1))), 1e-9)
    dp = float(np.max(np.abs(p_s - p_1))) / scale
    dl = abs(l_s - l_1) / max(abs(l_1), 1e-9)
    log("hier", f"max |param diff| / max |param| {dp!r}, |loss diff| / |loss| {dl!r}, "
                f"tolerance {HIER_RTOL}")
    if not (np.isfinite(l_s) and dp <= HIER_RTOL and dl <= HIER_RTOL):
        raise AssertionError(f"sharded hier round differs from one chip: {dp}, {dl}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded hier phase against one chip")
    args = ap.parse_args(argv)

    dev = check_device(args.chips)
    from repro.configs import get_arch
    from repro.launch.cache import enable_compile_cache

    log("cache", f"persistent compilation cache at {enable_compile_cache()}")
    cfg = get_arch("fedyolov3")  # full width, unreduced
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_hier(cfg, IMG, 4)
    else:
        server, eval_batch = phase_train(cfg, IMG)
        log("train", f"peak device bytes {dev.memory_stats()['peak_bytes_in_use']}")
        phase_eval(server, eval_batch)
        phase_serve(cfg, server.global_params(), len(server.history), IMG, fed=server.fed)
        log("serve", f"peak device bytes {dev.memory_stats()['peak_bytes_in_use']}")
    log("cache", f"{compile_line()}; wall {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
