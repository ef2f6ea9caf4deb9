"""Faults planted under the timed path, to show that ``correct`` catches them.

Each is a context manager that patches the program while it is built and
run; ``bench/calibrate.py`` reads them on the chip and the tests under
``bench/tests`` see ``correct`` come out false with each one.

- ``frozen``: a round that returns its state unchanged;
- ``half_batch``: each local step's loss over the first half of its batch;
- ``no_aggregation``: the Eq. 6 exchange left out, every client keeps its
  own trained row;
- ``altered_answer``: the first detection of every served answer gets its
  score raised by 0.05 where the RESULT is made;
- ``altered_box``: the first detection of every served answer has its box
  moved right by 0.05 of the image where the RESULT is made;
- ``no_suppression``: the serving program's NMS keeps every candidate above
  the score threshold;
- ``over_suppression``: the serving program's NMS suppresses at 0.6 of the
  IoU threshold it is given.
"""
from __future__ import annotations

import contextlib
from unittest import mock

import jax


@contextlib.contextmanager
def frozen():
    from repro.core import rounds

    def jit_frozen(fn):
        return jax.jit(lambda state, batch, part: (state, fn(state, batch, part)[1]))

    with mock.patch.object(rounds, "jit_fed_round", jit_frozen):
        yield


@contextlib.contextmanager
def half_batch():
    from repro.core import rounds

    orig = rounds.loss_for

    def loss_for(cfg):
        loss = orig(cfg)
        return lambda params, batch: loss(params, jax.tree.map(lambda x: x[: x.shape[0] // 2], batch))

    with mock.patch.object(rounds, "loss_for", loss_for):
        yield


@contextlib.contextmanager
def no_aggregation():
    from repro.core import packing
    from repro.core.aggregators import eq6

    def aggregate(self, packed, weights, agg_state, mask=None):
        return packed, {"prev_sums": packing.bucket_sums(self.ctx.spec, packed)}

    with mock.patch.object(eq6.Eq6, "aggregate", aggregate):
        yield


def _alter_first(change):
    from repro.core import serving

    orig = serving.decode_result

    def decode_result(pred, i):
        dets = orig(pred, i)
        if dets:
            dets[0] = change(*dets[0])
        return dets

    return mock.patch.object(serving, "decode_result", decode_result)


@contextlib.contextmanager
def altered_answer():
    with _alter_first(lambda label, score, box: (label, score + 0.05, box)):
        yield


@contextlib.contextmanager
def altered_box():
    with _alter_first(lambda label, score, box: (label, score, (box[0] + 0.05,) + tuple(box[1:]))):
        yield


@contextlib.contextmanager
def _nms_replaced(nms):
    """The serving program built with ``nms`` in place of the kernel (the
    program cache is emptied on the way in and out)."""
    from repro.core import serving
    from repro.kernels import ops

    serving.detection_program.cache_clear()
    try:
        with mock.patch.object(ops, "nms", nms):
            yield
    finally:
        serving.detection_program.cache_clear()


@contextlib.contextmanager
def no_suppression():
    def nms(boxes, scores, *, iou_thresh=0.5, score_thresh=0.0, **_):
        return (scores.astype(jax.numpy.float32) > score_thresh).astype(jax.numpy.float32)

    with _nms_replaced(nms):
        yield


@contextlib.contextmanager
def over_suppression():
    from repro.kernels import ops

    orig = ops.nms

    def nms(boxes, scores, *, iou_thresh=0.5, **kw):
        return orig(boxes, scores, iou_thresh=0.6 * iou_thresh, **kw)

    with _nms_replaced(nms):
        yield


FAULTS = {"frozen": frozen, "half_batch": half_batch, "no_aggregation": no_aggregation,
          "altered_answer": altered_answer, "altered_box": altered_box,
          "no_suppression": no_suppression, "over_suppression": over_suppression}
