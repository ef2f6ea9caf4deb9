"""Open-loop arithmetic: the schedule and the latencies."""
import math

import numpy as np

from bench.traffic import openloop


def test_every_seed_gets_the_same_gaps_in_another_order():
    a = openloop.schedule(50.0, 10.0, 4, 8, seed=1)
    b = openloop.schedule(50.0, 10.0, 4, 8, seed=2**33 + 5)
    assert len(a["due"]) == len(b["due"]) == 500
    gaps = lambda s: np.sort(np.diff(np.append(s["due"], 10.0)))
    np.testing.assert_allclose(gaps(a), gaps(b), rtol=1e-9)
    assert not np.allclose(a["due"], b["due"])
    for s in (a, b):
        assert s["due"][0] == 0.0 and s["due"][-1] < 10.0 and np.all(np.diff(s["due"]) > 0)
        assert set(np.unique(s["conn"])) <= set(range(4)) and s["image"].max() < 8


def test_latency_is_timed_from_the_due_time_and_failures_are_infinite():
    due = np.array([0.0, 1.0, 2.0, 3.0])
    recv = np.array([0.5, 4.0, np.nan, 3.1])  # request 1 waited behind a stall; 2 never came
    ok = np.array([True, True, False, True])
    lat = openloop.latencies(due, recv, ok)
    np.testing.assert_allclose(lat[[0, 1, 3]], [0.5, 3.0, 0.1])
    assert math.isinf(lat[2])
    # an ERROR answer has a receive time but no result: still a miss
    assert math.isinf(openloop.latencies(due, recv, np.array([True, True, True, False]))[3])


def test_percentile_is_nearest_rank_and_sees_the_misses():
    lat = np.array([1.0, 2.0, 3.0, 4.0, math.inf] + [0.5] * 15)  # 20 requests, one missed
    assert openloop.percentile(lat, 50) == 0.5
    assert openloop.percentile(lat, 95) == 4.0
    assert math.isinf(openloop.percentile(lat, 99))
    assert math.isinf(openloop.percentile(np.array([]), 95))


def test_the_generator_frames_an_infer_as_the_program_does():
    from repro.core.transport import wire

    img = np.random.default_rng(3).random((8, 8, 3), np.float32)
    bufs = openloop.infer_frame(77, img.astype("<f4").tobytes(), 8)
    assert b"".join(bufs) == wire.pack_infer(77, img)
    frames = wire.FrameParser().feed(b"".join(bufs))
    rid, back = wire.parse_infer(frames[0][1])
    assert frames[0][0] == wire.INFER and rid == 77 and np.array_equal(back, img)
