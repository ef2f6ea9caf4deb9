"""The six readers of the program's span ring on a ring built here: each
reads the last N batches or rounds of the window, returns None when the
ring holds fewer, and None on a program without the ring."""
import sys

import pytest

from bench import harness
from repro.core import telemetry

MS = 1_000_000


def _round(t, host_ms, wait_ms, rid):
    """One fl.round starting at ``t`` ns: host_ms around a wait_ms read-back."""
    seq = telemetry.reserve()
    telemetry.add("fl.schedule", t, t + host_ms * MS // 2, id=rid, parent=seq)
    w0 = t + host_ms * MS // 2
    telemetry.add("fl.device_wait", w0, w0 + wait_ms * MS, id=rid, parent=seq)
    end = t + (host_ms + wait_ms) * MS
    telemetry.add("fl.round", t, end, id=rid, seq=seq)
    return end


def _batch(t, bid, sids, *, recv_ms, queue_ms, host_ms, wait_ms):
    """One serve.batch of requests ``sids``, opening at ``t`` ns."""
    bseq = telemetry.reserve()
    close = t + host_ms * MS // 2
    for sid in sids:
        rseq = telemetry.reserve()
        first = close - (recv_ms + queue_ms) * MS
        telemetry.add("serve.recv", first, first + recv_ms * MS, id=sid, parent=rseq)
        telemetry.add("serve.queue", first + recv_ms * MS, close, id=sid, parent=rseq)
    telemetry.add("serve.linger", t, close, id=bid, parent=bseq)
    telemetry.add("serve.device_wait", close, close + wait_ms * MS, id=bid, parent=bseq)
    end = t + (host_ms + wait_ms) * MS
    for sid in sids:
        telemetry.add("serve.request", close - (recv_ms + queue_ms) * MS, end, id=sid)
    telemetry.add("serve.batch", t, end, id=bid, ids=sids, seq=bseq)
    return end


@pytest.fixture
def ring():
    """Batches 0-1 slow (the warm-up), 2-4 the window; rounds likewise."""
    telemetry.reset()
    t = 0
    for bid, v in enumerate((50, 60, 2, 3, 4)):
        sids = (2 * bid, 2 * bid + 1)
        t = _batch(t, bid, sids, recv_ms=v, queue_ms=2 * v, host_ms=4 * v, wait_ms=v + 1) + MS
    for rid, v in enumerate((40, 30, 2, 4, 3)):
        t = _round(t, v, 100, rid) + MS
    yield
    telemetry.reset()


@pytest.mark.parametrize("name,record,want", [
    ("recv_ms", {"batches": 3}, 3.0),
    ("queue_wait_ms", {"batches": 3}, 6.0),
    ("batch_host_ms", {"batches": 3}, 12.0),
    ("device_wait_ms", {"batches": 3}, 4.0),
    ("service_ms", {"batches": 3}, 3.0 + 6.0 + 6.0 + 4.0),
    ("round_host_ms", {"rounds": 3}, 3.0),
])
def test_readers_take_the_last_n_of_the_window(ring, name, record, want):
    assert harness.metric_reader(name)(record, None, None) == pytest.approx(want)


@pytest.mark.parametrize("name,record", [
    ("recv_ms", {"batches": 6}), ("queue_wait_ms", {"batches": 6}), ("batch_host_ms", {"batches": 6}),
    ("device_wait_ms", {"batches": 6}), ("service_ms", {"batches": 0}), ("round_host_ms", {"rounds": 6}),
    ("round_host_ms", {}),
])
def test_readers_return_none_when_the_ring_holds_too_few(ring, name, record):
    assert harness.metric_reader(name)(record, None, None) is None


@pytest.mark.parametrize("name", ["recv_ms", "queue_wait_ms", "batch_host_ms", "device_wait_ms",
                                  "service_ms", "round_host_ms"])
def test_readers_return_none_on_a_program_without_the_ring(ring, name, monkeypatch):
    import repro.core

    monkeypatch.delattr(repro.core, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)  # the import raises ImportError
    assert harness.metric_reader(name)({"batches": 3, "rounds": 3}, None, None) is None
