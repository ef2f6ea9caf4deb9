"""frame_ready_share on a ring built here: the share of the last N batches'
requests named by each batch's serve.staged mark; None when the ring holds
fewer batches, when a batch has no mark, and on a program without the ring."""
import sys

import pytest

from bench import harness
from repro.core import telemetry

MS = 1_000_000


def _batch(t, bid, sids, ready=None):
    """One 2 ms serve.batch of requests ``sids``, opening at ``t`` ns; with
    ``ready``, its serve.staged mark names the first ``ready`` of them."""
    bseq = telemetry.reserve()
    close = t + MS
    for sid in sids:
        rseq = telemetry.reserve()
        telemetry.add("serve.recv", t, t + MS // 2, id=sid, parent=rseq)
        telemetry.add("serve.queue", t + MS // 2, close, id=sid, parent=rseq)
    telemetry.add("serve.linger", t, close, id=bid, parent=bseq)
    if ready is not None:
        telemetry.add("serve.staged", close, close, id=bid, parent=bseq, ids=sids[:ready])
    end = t + 2 * MS
    for sid in sids:
        telemetry.add("serve.request", t, end, id=sid)
    telemetry.add("serve.batch", t, end, id=bid, ids=sids, seq=bseq)
    return end + MS


@pytest.fixture
def ring():
    """Batches 0-1 unready (the warm-up), 2-4 the window."""
    telemetry.reset()
    t = 0
    for bid, ready in enumerate((0, 0, 2, 1, 1)):
        t = _batch(t, bid, (2 * bid, 2 * bid + 1), ready)
    yield
    telemetry.reset()


def _read(record):
    return harness.metric_reader("frame_ready_share")(record, None, None)


@pytest.mark.parametrize("batches,want", [(3, 4 / 6), (1, 1 / 2), (5, 4 / 10)])
def test_frame_ready_share_takes_the_last_n_of_the_window(ring, batches, want):
    assert _read({"batches": batches}) == pytest.approx(want)


@pytest.mark.parametrize("record", [{"batches": 6}, {}])
def test_frame_ready_share_is_none_when_the_ring_holds_too_few(ring, record):
    assert _read(record) is None


def test_frame_ready_share_is_none_on_a_program_without_the_ring(ring, monkeypatch):
    import repro.core

    monkeypatch.delattr(repro.core, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)  # the import raises ImportError
    assert _read({"batches": 3}) is None


def test_frame_ready_share_is_none_on_a_program_that_does_not_stage():
    """A service without the serve.staged mark (one that pads its batch on
    the host) reads None, and so does a window with one unmarked batch."""
    telemetry.reset()
    t = 0
    try:
        for bid in range(3):
            t = _batch(t, bid, (2 * bid, 2 * bid + 1))
        assert _read({"batches": 3}) is None
        _batch(t, 3, (6, 7), ready=2)
        assert _read({"batches": 1}) == 1.0
        assert _read({"batches": 2}) is None
    finally:
        telemetry.reset()
