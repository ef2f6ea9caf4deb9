"""Serving-plane suite (DESIGN.md §17): wire frames, freshness tiers,
the ModelSlot swap protocol, the padded-batch bitwise pin, the socket
service end to end, and the version contracts against the async engine:

  - INFER/RESULT/STATUS frame round-trips survive adversarial chunking;
    corruption is withheld by the CRC firewall, never parsed.
  - freshness boundaries: exactly-at-threshold is the lower tier; the
    fresh -> soft_stale -> hard_stale transitions run on a controlled
    SimClock along BOTH axes (rounds-behind and seconds-behind).
  - ModelSlot publish is atomic and version-monotonic under concurrent
    publishers; an out-of-order (older) publish is refused.
  - THE padding pin: a request's detections are bit-identical whether it
    shares the fixed-slot batch with 7 other images or rides alone with 7
    zero-padded slots — per-slot decode is a function of that slot alone,
    and the socket path returns exactly the direct program's bits.
  - frames cross to the device as they arrive: a batch of occupancy k
    stages k frames and none for its padding; frames past the stage-ahead
    bound wait on the host and cross at close, with the same bits; a
    staging failure is answered with an ERROR frame and re-raised by stop().
  - hot swap under load drops zero requests and post-swap responses carry
    the new round version.
  - the served version ALWAYS equals the engine's landed round version:
    `publish_from_engine` reads the engine's own global snapshot, never a
    buffer row that mid-window holds a client's next in-flight update —
    and the COS restore round-trip (train -> checkpoint -> serve) is
    bit-identical to that same landed global.
"""
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.core import monitor, serving, telemetry
from repro.core import rounds as R
from repro.core.simclock import SimClock
from repro.core.transport import harness, replay, wire
from repro.data import synthetic
from repro.models import params as P
from repro.models import yolov3

IMG = 32


def tiny_cfg():
    return get_arch("fedyolov3").reduced()


def tiny_fed(**kw):
    return R.FedConfig(n_clients=2, serve_batch=kw.pop("serve_batch", 4), **kw)


def tiny_params(cfg, seed=0):
    return P.init_params(yolov3.template(cfg), jax.random.key(seed), jnp.float32)


def scenes(n, seed=0, size=IMG, n_classes=3):
    rng = np.random.default_rng(seed)
    imgs, _ = synthetic.scene_images(rng, n, size, n_classes)
    return imgs


# --------------------------- wire frames -------------------------------------

def test_infer_frame_roundtrip_chunked():
    img = np.random.default_rng(0).normal(size=(7, 5, 3)).astype(np.float32)
    frame = wire.pack_infer(42, img)
    parser = wire.FrameParser()
    frames = []
    for i in range(0, len(frame), 3):  # adversarial chunking: 3-byte feeds
        frames.extend(parser.feed(frame[i : i + 3]))
    assert len(frames) == 1 and frames[0][0] == wire.INFER
    rid, out = wire.parse_infer(frames[0][1])
    assert rid == 42
    assert out.dtype == np.float32 and out.shape == (7, 5, 3)
    np.testing.assert_array_equal(out, img)


def test_infer_frame_rejects_bad_shapes():
    with pytest.raises(ValueError, match=r"\(H, W, 3\)"):
        wire.pack_infer(0, np.zeros((4, 4), np.float32))
    rid_hw = wire._INFER.pack(1, 4, 4)
    with pytest.raises(ValueError, match="INFER body"):
        wire.parse_infer(rid_hw + b"\0" * 7)  # truncated image bytes


def test_result_frame_roundtrip():
    dets = [
        (2, np.float32(0.75), (np.float32(0.1), np.float32(0.2),
                               np.float32(0.3), np.float32(0.4))),
        (-1, np.float32(0.5), (np.float32(1.5),) * 4),
    ]
    frame = wire.pack_result(7, 12345, serving.TIER_CODES[serving.SOFT_STALE], dets)
    parser = wire.FrameParser()
    (ftype, payload), = parser.feed(frame)
    assert ftype == wire.RESULT
    rid, version, tier, out = wire.parse_result(payload)
    assert (rid, version, tier) == (7, 12345, 1)
    assert out == [(l, float(s), tuple(float(v) for v in b)) for l, s, b in dets]


def test_status_frame_roundtrip():
    (ftype, payload), = wire.FrameParser().feed(wire.pack_status_request())
    assert ftype == wire.STATUS and wire.parse_status(payload) is None
    status = {"version": 3, "tier": "fresh", "rounds_behind": 0}
    (_, payload), = wire.FrameParser().feed(wire.pack_status(status))
    assert wire.parse_status(payload) == status


def test_corrupted_serving_frame_is_withheld():
    frame = bytearray(wire.pack_infer(1, np.ones((2, 2, 3), np.float32)))
    frame[wire.HEADER_BYTES + 10] ^= 0xFF  # flip one body byte
    parser = wire.FrameParser()
    assert parser.feed(bytes(frame)) == []
    assert parser.crc_errors == 1  # detected, counted, never delivered


# --------------------------- freshness tiers ---------------------------------

def test_freshness_boundaries_rounds_axis():
    fed = tiny_fed()  # soft at >2 rounds, hard at >8
    assert serving.freshness_tier(0, 0.0, fed) == serving.FRESH
    assert serving.freshness_tier(fed.serve_soft_stale_rounds, 0.0, fed) == serving.FRESH
    assert serving.freshness_tier(fed.serve_soft_stale_rounds + 1, 0.0, fed) == serving.SOFT_STALE
    assert serving.freshness_tier(fed.serve_hard_stale_rounds, 0.0, fed) == serving.SOFT_STALE
    assert serving.freshness_tier(fed.serve_hard_stale_rounds + 1, 0.0, fed) == serving.HARD_STALE


def test_freshness_boundaries_seconds_axis():
    fed = tiny_fed()
    assert serving.freshness_tier(0, fed.serve_soft_stale_s, fed) == serving.FRESH
    assert serving.freshness_tier(0, fed.serve_soft_stale_s + 1e-3, fed) == serving.SOFT_STALE
    assert serving.freshness_tier(0, fed.serve_hard_stale_s, fed) == serving.SOFT_STALE
    assert serving.freshness_tier(0, fed.serve_hard_stale_s + 1e-3, fed) == serving.HARD_STALE


def test_freshness_transitions_on_simclock():
    """fresh -> soft -> hard driven by a controlled clock, then by landed
    rounds — the two staleness axes degrade independently."""
    fed = tiny_fed()
    clock = SimClock()
    slot = serving.ModelSlot(clock=clock)
    slot.publish(5, {"w": np.zeros(1)})
    latest = 5

    def tier():
        return serving.model_status(slot, latest, clock.now(), fed)["tier"]

    assert tier() == serving.FRESH
    clock.advance(fed.serve_soft_stale_s + 1.0)
    assert tier() == serving.SOFT_STALE
    clock.advance(fed.serve_hard_stale_s - fed.serve_soft_stale_s)
    assert tier() == serving.HARD_STALE
    # a fresh publish resets the wall axis...
    slot.publish(5, {"w": np.zeros(1)})
    assert tier() == serving.FRESH
    # ...and the rounds axis degrades on its own, clock untouched
    latest = 5 + fed.serve_soft_stale_rounds + 1
    assert tier() == serving.SOFT_STALE
    latest = 5 + fed.serve_hard_stale_rounds + 1
    status = serving.model_status(slot, latest, clock.now(), fed)
    assert status["tier"] == serving.HARD_STALE and status["degraded"]
    assert status["rounds_behind"] == fed.serve_hard_stale_rounds + 1


def test_tier_codes_are_a_bijection():
    assert sorted(serving.TIER_CODES.values()) == [0, 1, 2]
    for name, code in serving.TIER_CODES.items():
        assert serving.TIER_NAMES[code] == name


# --------------------------- ModelSlot ---------------------------------------

def test_modelslot_refuses_version_regression():
    slot = serving.ModelSlot()
    assert slot.publish(3, "v3")
    assert not slot.publish(2, "v2-late")  # an out-of-order publisher
    assert slot.snapshot().version == 3 and slot.snapshot().params == "v3"
    assert slot.stale_publishes == 1 and slot.swaps == 1
    assert slot.publish(3, "v3-again")  # same-version republish is allowed


def test_modelslot_empty_raises_and_service_refuses_start():
    slot = serving.ModelSlot()
    with pytest.raises(RuntimeError, match="empty"):
        slot.snapshot()
    svc = serving.InferenceService(tiny_cfg(), tiny_fed(), slot, img_size=IMG)
    with pytest.raises(RuntimeError, match="publish"):
        svc.start()
    svc.stop()


def test_modelslot_concurrent_publishers_end_at_max_version():
    slot = serving.ModelSlot()
    versions = list(range(1, 33))
    rng = np.random.default_rng(0)
    rng.shuffle(versions)

    def pub(v):
        slot.publish(v, f"params-{v}")

    threads = [threading.Thread(target=pub, args=(v,)) for v in versions]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    final = slot.snapshot()
    assert final.version == 32 and final.params == "params-32"
    assert slot.swaps + slot.stale_publishes == 32


# --------------------------- the padding pin ---------------------------------

def test_padded_batch_is_bit_identical_to_lone_request():
    """THE acceptance pin: slot i's detections depend on slot i alone.

    The same image rides (a) alone with 7 zero-padded slots and (b) in a
    full batch of 8 distinct scenes, through the SAME fixed-slot program —
    every output array for its slot must match bit for bit."""
    cfg, fed = tiny_cfg(), tiny_fed(serve_batch=8)
    params = tiny_params(cfg)
    prog = serving.detection_program(cfg, fed.serve_max_detections)
    imgs = scenes(8, seed=3)
    lone = np.zeros_like(imgs)
    lone[0] = imgs[0]
    full = jax.tree.map(np.asarray, prog(params, jnp.asarray(imgs)))
    alone = jax.tree.map(np.asarray, prog(params, jnp.asarray(lone)))
    for key in ("boxes", "scores", "cls", "valid"):
        np.testing.assert_array_equal(full[key][0], alone[key][0], err_msg=key)
    # and the decoded RESULT payload (the wire's view) agrees too
    assert serving.decode_result(full, 0) == serving.decode_result(alone, 0)
    assert sum(len(serving.decode_result(full, i)) for i in range(8)) > 0


def test_detection_program_is_cached():
    cfg = tiny_cfg()
    assert serving.detection_program(cfg, 16) is serving.detection_program(cfg, 16)
    assert serving.detection_program(cfg, 16) is not serving.detection_program(cfg, 8)


# --------------------------- socket service ----------------------------------

def serve_ctx(fed=None, *, seed=0, version=1, slot=None):
    cfg = tiny_cfg()
    fed = fed or tiny_fed()
    if slot is None:
        slot = serving.ModelSlot()
        slot.publish(version, tiny_params(cfg, seed))
    svc = serving.InferenceService(cfg, fed, slot, img_size=IMG).start()
    return cfg, fed, slot, svc


def test_served_request_matches_direct_program_bitwise():
    cfg, fed, slot, svc = serve_ctx()
    try:
        img = scenes(1, seed=5)[0]
        with serving.InferenceClient(svc.host, svc.port) as client:
            res = client.infer(img)
        pad = np.zeros((fed.serve_batch, IMG, IMG, 3), np.float32)
        pad[0] = img
        prog = serving.detection_program(cfg, fed.serve_max_detections)
        pred = jax.tree.map(np.asarray, prog(slot.snapshot().params, jnp.asarray(pad)))
        assert res.detections == serving.decode_result(pred, 0)
        assert res.version == 1 and res.tier == serving.FRESH
    finally:
        svc.stop()


def test_concurrent_requests_batch_into_shared_launches():
    cfg, fed, slot, svc = serve_ctx(tiny_fed(serve_batch=4))
    try:
        imgs = scenes(8, seed=6)
        with serving.InferenceClient(svc.host, svc.port) as warm:
            warm.infer(imgs[0])  # compile outside the batching window
        with serving.InferenceClient(svc.host, svc.port) as client:
            rids = [client.send_infer(imgs[i]) for i in range(8)]
            results = {client.recv_result().request_id for _ in rids}
        assert results == set(rids)  # every request answered exactly once
        assert svc.stats.in_flight == 0
        # 8 pipelined requests through 4 slots must have shared launches
        assert svc.stats.batches < 1 + 8
        assert svc.stats.avg_occupancy > 1.0
    finally:
        svc.stop()


def test_status_frame_equals_host_evaluator():
    """One evaluator, two callers: the STATUS frame a consumer reads is the
    same `model_status` dict the host/monitor sees (SimClock pins the
    seconds axis so the two calls can be compared exactly)."""
    clock = SimClock()
    slot = serving.ModelSlot(clock=clock)
    cfg, fed = tiny_cfg(), tiny_fed()
    slot.publish(4, tiny_params(cfg))
    svc = serving.InferenceService(cfg, fed, slot, img_size=IMG,
                                   latest_version=lambda: 7).start()
    try:
        with serving.InferenceClient(svc.host, svc.port) as client:
            over_wire = client.status()
        host = svc.status()
        host["status_requests"] = over_wire["status_requests"]  # the frame itself counted
        assert over_wire == host
        assert over_wire["version"] == 4 and over_wire["latest_version"] == 7
        assert over_wire["rounds_behind"] == 3
        assert over_wire["tier"] == serving.SOFT_STALE
    finally:
        svc.stop()


def test_wrong_size_image_is_a_protocol_error():
    _, _, _, svc = serve_ctx()
    try:
        client = serving.InferenceClient(svc.host, svc.port)
        client.send_infer(np.zeros((IMG + 1, IMG + 1, 3), np.float32))
        with pytest.raises(ConnectionError):
            client.recv_result()  # the service dropped the connection
        client.close()
        for _ in range(200):  # reader thread counts it asynchronously
            if svc.stats.protocol_errors:
                break
            time.sleep(0.005)
        assert svc.stats.protocol_errors == 1
        assert svc.stats.requests == 0  # never reached the batcher
    finally:
        svc.stop()


def test_error_frame_roundtrip():
    frames = wire.FrameParser().feed(wire.pack_error(9, "RuntimeError: refusé"))
    assert frames[0][0] == wire.ERROR
    assert wire.parse_error(frames[0][1]) == (9, "RuntimeError: refusé")


def test_failing_program_surfaces_its_error():
    """A batch whose program raises (a compile refusal, say) fails its
    requests with the server's own message: the client raises it at once,
    not after a socket timeout, later requests fail the same way, and
    stop() re-raises the exception."""
    _, _, _, svc = serve_ctx()

    def refused(params, images):
        raise RuntimeError("Mosaic failed to compile TPU kernel: injected")

    svc._program = refused
    try:
        with serving.InferenceClient(svc.host, svc.port, timeout=10.0) as client:
            with pytest.raises(serving.ServiceError, match="Mosaic failed to compile"):
                client.infer(scenes(1)[0])
            with pytest.raises(serving.ServiceError, match="injected"):
                client.infer(scenes(1, seed=1)[0])
        assert svc.stats.failed == 2 and svc.stats.in_flight == 0
    finally:
        with pytest.raises(RuntimeError, match="injected"):
            svc.stop()


def _warm(svc, img) -> None:
    with serving.InferenceClient(svc.host, svc.port) as warm:
        warm.infer(img)  # compiles outside the batch under test


def _one_batch(svc, imgs) -> list:
    """Send ``imgs`` in one write, so that they share one batch under a long
    linger; their results in order."""
    with serving.InferenceClient(svc.host, svc.port) as client:
        client.sock.sendall(b"".join(wire.pack_infer(i, img) for i, img in enumerate(imgs)))
        got = sorted((client.recv_result() for _ in imgs), key=lambda r: r.request_id)
    return got


def test_batch_stages_its_frames_and_none_for_padding(monkeypatch):
    cfg, fed, slot, svc = serve_ctx(tiny_fed(serve_batch=4, serve_max_wait_s=1.0))
    puts = []
    put = jax.device_put
    monkeypatch.setattr(jax, "device_put", lambda x, *a, **kw: puts.append(np.shape(x)) or put(x, *a, **kw))
    try:
        imgs = scenes(3, seed=11)
        _warm(svc, imgs[0])
        puts.clear()
        before, mark = telemetry.counters(), telemetry.reserve()
        _one_batch(svc, imgs)
    finally:
        svc.stop()
    assert puts == [(IMG, IMG, 3)] * 3  # one put a request, none for the padding slot
    # the ring is per process: other tests' 3-request batches sit before ``mark``
    (batch,) = [b for b in telemetry.spans("serve.batch") if b.seq > mark]
    assert {s.id for s in telemetry.spans("serve.stage") if s.seq > mark} == set(batch.ids)
    after = telemetry.counters()
    assert after["serve.frames"] - before.get("serve.frames", 0) == 3
    assert 0 <= after["serve.frames_ready"] - before.get("serve.frames_ready", 0) <= 3
    assert svc._staged == 0  # every staged frame was taken by its batch


@pytest.mark.parametrize("stage_ahead", [0, serving.STAGE_AHEAD_BATCHES])
def test_staged_and_host_frames_serve_the_zero_padded_program_bitwise(monkeypatch, stage_ahead):
    """With the stage-ahead bound at 0 every frame crosses when its batch
    closes; either way a batch of 3 in 4 slots answers with the bits of the
    direct program on a zero-padded host batch."""
    monkeypatch.setattr(serving, "STAGE_AHEAD_BATCHES", stage_ahead)
    cfg, fed, slot, svc = serve_ctx(tiny_fed(serve_batch=4, serve_max_wait_s=1.0))
    telemetry.reset()
    try:
        imgs = scenes(3, seed=12)
        _warm(svc, imgs[0])
        got = _one_batch(svc, imgs)
    finally:
        svc.stop()
    pad = np.zeros((fed.serve_batch, IMG, IMG, 3), np.float32)
    pad[:3] = imgs
    prog = serving.detection_program(cfg, fed.serve_max_detections)
    pred = jax.tree.map(np.asarray, prog(slot.snapshot().params, jnp.asarray(pad)))
    assert [r.detections for r in got] == [serving.decode_result(pred, i) for i in range(3)]
    assert len(telemetry.spans("serve.stage")) == (4 if stage_ahead else 0)  # the warm request too
    if not stage_ahead:
        assert [s.ids for s in telemetry.spans("serve.staged")] == [(), ()]


def test_stage_ahead_bound_keeps_later_frames_on_the_host():
    cfg, fed = tiny_cfg(), tiny_fed(serve_batch=2)
    slot = serving.ModelSlot()
    slot.publish(1, tiny_params(cfg))
    svc = serving.InferenceService(cfg, fed, slot, img_size=IMG)  # never started: no batcher takes them
    try:
        limit = serving.STAGE_AHEAD_BATCHES * fed.serve_batch
        frames = [svc._stage(img) for img in scenes(limit + 3, seed=13)]
        assert [isinstance(f, jax.Array) for f in frames] == [True] * limit + [False] * 3
        assert svc._staged == limit
        items = [serving._Pending(None, i, f, i, 0, 0, 0) for i, f in enumerate(frames[:2])]
        assert len(svc._take_frames(items)) == 2 and all(it.frame is None for it in items)
        assert svc._staged == limit - 2
        assert isinstance(svc._stage(scenes(1, seed=14)[0]), jax.Array)
    finally:
        svc.stop()


def test_concurrent_readers_never_stage_past_the_bound():
    """16 reader threads staging at once, switching every microsecond: the
    bound holds exactly, and every frame past it stays on the host."""
    cfg, fed = tiny_cfg(), tiny_fed(serve_batch=2)
    slot = serving.ModelSlot()
    slot.publish(1, tiny_params(cfg))
    svc = serving.InferenceService(cfg, fed, slot, img_size=IMG)
    img = scenes(1, seed=15)[0]
    got, lock = [], threading.Lock()

    def reader():
        for _ in range(8):
            frame = svc._stage(img)
            with lock:
                got.append(isinstance(frame, jax.Array))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=reader) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
        svc.stop()
    limit = serving.STAGE_AHEAD_BATCHES * fed.serve_batch
    assert len(got) == 128 and sum(got) == limit == svc._staged


def test_failing_stage_answers_with_an_error_frame(monkeypatch):
    """A put that raises in the reader fails its request at once with the
    server's message, later requests fail the same way, and stop()
    re-raises; nothing reaches the batcher and nothing hangs."""
    _, _, _, svc = serve_ctx()

    def refused(x, *a, **kw):
        raise RuntimeError("device_put refused: injected")

    monkeypatch.setattr(jax, "device_put", refused)
    try:
        with serving.InferenceClient(svc.host, svc.port, timeout=10.0) as client:
            with pytest.raises(serving.ServiceError, match="device_put refused"):
                client.infer(scenes(1)[0])
            with pytest.raises(serving.ServiceError, match="injected"):
                client.infer(scenes(1, seed=1)[0])
        assert svc.stats.failed == 2 and svc.stats.in_flight == 0 and svc.stats.batches == 0
        assert svc._staged == 0
    finally:
        with pytest.raises(RuntimeError, match="injected"):
            svc.stop()


def test_hot_swap_under_load_drops_nothing():
    cfg, fed, slot, svc = serve_ctx()
    try:
        imgs = scenes(4, seed=8)
        with serving.InferenceClient(svc.host, svc.port) as warm:
            warm.infer(imgs[0])
        versions = []
        with serving.InferenceClient(svc.host, svc.port) as client:
            for i in range(6):
                if i == 3:  # swap with requests still streaming
                    assert slot.publish(2, tiny_params(cfg, seed=9))
                versions.append(client.infer(imgs[i % 4]).version)
        assert svc.stats.in_flight == 0  # every INFER answered
        assert versions[0] == 1 and versions[-1] == 2  # post-swap = new round
        assert sorted(set(versions)) == [1, 2]
        assert slot.swaps == 2
    finally:
        svc.stop()


# --------------------- version contract vs the engine ------------------------

def engine_with_landed_round():
    """An arrival engine driven one flush in, plus one MID-WINDOW landing:
    the buffer row indexed by `global_row` now holds client 0's next
    trained update, while the landed global lives only in the engine's own
    snapshot — the exact hazard the serving plane must never serve."""
    meta = harness.make_meta(overrides=dict(harness.TINY_OVERRIDES),
                             n_clients=2, buffer_size=2)
    eng = replay.make_engine(meta)
    rng = np.random.default_rng(0)
    n = eng.state["params"].shape[1]
    for c in (0, 1):  # one full window -> flush -> version 1
        eng.land(c, eng.dispatch_row(c) + rng.normal(size=n).astype(np.float32) * 1e-3)
    assert eng.version == 1
    eng.dispatch(0)
    eng.land(0, eng.dispatch_row(0) + rng.normal(size=n).astype(np.float32) * 1e-3)
    assert eng.staged() == (0,) and eng.global_row == 0  # the hazard is live
    return meta, eng


def test_publish_from_engine_serves_the_landed_global_not_inflight():
    meta, eng = engine_with_landed_round()
    cfg = replay.build_cfg(meta)
    hazard_row = np.asarray(eng.state["params"][eng.global_row])
    landed = np.asarray(eng.global_packed_row())
    assert not np.array_equal(hazard_row, landed)  # mid-window rows differ
    slot = serving.ModelSlot()
    assert serving.publish_from_engine(slot, eng, cfg)
    pub = slot.snapshot()
    assert pub.version == eng.version == 1
    want = serving.unpack_global(cfg, eng.fed, landed)
    got_flat = np.concatenate([np.ravel(x) for x in jax.tree.leaves(pub.params)])
    want_flat = np.concatenate([np.ravel(x) for x in jax.tree.leaves(want)])
    np.testing.assert_array_equal(got_flat, want_flat)
    hazard = serving.unpack_global(cfg, eng.fed, hazard_row)
    hz_flat = np.concatenate([np.ravel(x) for x in jax.tree.leaves(hazard)])
    assert not np.array_equal(got_flat, hz_flat)


def test_restore_roundtrip_is_bit_identical_to_landed_global(tmp_path):
    """train -> COS checkpoint -> serve-side restore: the restored params
    repack to EXACTLY the engine's landed global row, not the stale
    in-flight buffer row (satellite acceptance)."""
    from repro.checkpoint import ObjectStore
    from repro.core import packing

    meta, eng = engine_with_landed_round()
    cfg = replay.build_cfg(meta)
    landed_tree = serving.unpack_global(cfg, eng.fed, eng.global_packed_row())
    store = ObjectStore(tmp_path)
    store.put_model("served", eng.version, landed_tree)
    # the serve side rebuilds the template from cfg alone, then restores
    from repro.models import transformer as T

    template = P.init_params(T.template(cfg), jax.random.key(99), jnp.float32)
    restored = store.restore_into("served", template, round_idx=eng.version)
    spec = packing.build_pack_spec(cfg, T.template(cfg))
    repacked = packing.pack(spec, jax.tree.map(lambda x: x[None], restored), jnp.float32)[0]
    np.testing.assert_array_equal(
        np.asarray(repacked), np.asarray(eng.global_packed_row())
    )
    assert not np.array_equal(
        np.asarray(repacked), np.asarray(eng.state["params"][eng.global_row])
    )
    assert max(store.rounds("served")) == eng.version  # the served version


# --------------------------- monitor -----------------------------------------

def test_render_serving_reports_tier_and_traffic():
    clock = SimClock()
    slot = serving.ModelSlot(clock=clock)
    fed = tiny_fed()
    slot.publish(6, "params")
    stats = serving.ServeStats(requests=10, results=10, batches=3, occupancy_sum=10)
    out = monitor.render_serving(
        "fedyolo", serving.model_status(slot, 6, clock.now(), fed, stats)
    )
    assert "serving round v6" in out and "fresh" in out
    assert "occupancy 3.33" in out and "in flight 0" in out
    clock.advance(fed.serve_hard_stale_s + 1)
    out = monitor.render_serving(
        "fedyolo", serving.model_status(slot, 6, clock.now(), fed)
    )
    assert "hard_stale" in out and "DEGRADED" in out
    assert "traffic" not in out  # no stats given -> no traffic line


def test_render_serving_json_roundtrip_of_status():
    # the STATUS payload is JSON all the way: what the wire carries renders
    clock = SimClock()
    slot = serving.ModelSlot(clock=clock)
    slot.publish(2, None)
    status = serving.model_status(slot, 3, clock.now(), tiny_fed())
    assert monitor.render_serving("t", json.loads(json.dumps(status))).startswith("[t]")


# --------------------------- launcher ----------------------------------------

def test_decode_programs_cache_hits_across_generate_calls():
    from repro.launch import serve as serve_mod
    from repro.models import transformer as T

    cfg = get_arch("qwen3-1.7b").reduced()
    serve_mod.decode_programs.cache_clear()
    a = serve_mod.decode_programs(cfg, 24)
    assert serve_mod.decode_programs(cfg, 24) is a  # no per-call re-jit
    params = P.init_params(T.template(cfg), jax.random.key(0), jnp.float32)
    prompts = jnp.zeros((1, 8), jnp.int32)
    t1 = serve_mod.generate(cfg, params, prompts, 4)
    hits_before = serve_mod.decode_programs.cache_info().hits
    t2 = serve_mod.generate(cfg, params, prompts, 4)
    assert serve_mod.decode_programs.cache_info().hits > hits_before
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))


ROOT = Path(__file__).resolve().parents[1]
CLI_ENV = {**os.environ, "PYTHONPATH": str(ROOT / "src")}


def _run_cli(args, timeout=420):
    return subprocess.run(
        [sys.executable, "-m", *args], env=CLI_ENV, cwd=ROOT,
        capture_output=True, text=True, timeout=timeout,
    )


def test_serve_cli_runs_the_service():
    r = _run_cli(["repro.launch.serve", "--arch", "fedyolov3", "--img-size", "32",
                  "--requests", "4", "--serve-batch", "2"])
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["requests"] == 4 and out["dropped"] == 0
    assert out["tier"] == "fresh" and out["qps"] > 0
    assert out["version"] == 0  # no --store: an un-trained v0 model


def test_serve_cli_one_shot_still_decodes():
    r = _run_cli(["repro.launch.serve", "--arch", "fedyolov3", "--img-size", "32",
                  "--batch", "2", "--one-shot"])
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert len(out["detections"]) == 2 and out["images_per_s"] > 0
