"""The comparison that decides ``correct``: sound runs pass it; the control
(the reference with float8 operands) and every planted fault fail it, at the limits
of the benchmark's own cells."""
import numpy as np
import pytest

from bench import calibrate, faults
from bench.tests import helpers


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("faults")
    helpers.write_tree(root)
    return root


@pytest.mark.parametrize("fault", ["frozen", "half_batch", "no_aggregation"])
def test_a_broken_round_is_not_correct(tree, fault):
    with faults.FAULTS[fault]():
        out = helpers.run(tree, "yolo-tiny.sync")
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("fault", ["altered_answer", "altered_box", "no_suppression", "over_suppression"])
def test_a_broken_answer_is_not_correct(tree, fault):
    with faults.FAULTS[fault]():
        out = helpers.run(tree, "yolo-tiny.serve", seconds=2.0)
    assert out["correct"] is False, out["checks"]


def test_the_training_control_fails_a_limit(tree):
    run = helpers.make_run(tree, "fedyolov3-416.small_sync")
    got = calibrate.fed_readings(run, ["control"])["control"]
    assert any(got[k] > lim for k, lim in run.cell["limits"].items()), got


def test_the_serving_control_fails_a_limit(tree):
    run = helpers.make_run(tree, "fedyolov3-416.small_serve")
    got = calibrate.serve_readings(run, ["control"])["control"]
    assert any(got[k] > lim for k, lim in run.cell["limits"].items()), got


def test_the_reference_nms_is_greedy_and_class_aware():
    from bench.reference import yolo

    boxes = np.array([[0.5, 0.5, 0.2, 0.2], [0.51, 0.5, 0.2, 0.2], [0.51, 0.5, 0.2, 0.2], [0.9, 0.9, 0.1, 0.1]])
    scores = np.array([0.9, 0.8, 0.7, 0.01])
    labels = np.array([0, 0, 1, 0])
    assert yolo.select(boxes, scores, labels, k=16, score_thresh=0.05, iou_thresh=0.5) == [0, 2]
    assert yolo.select(boxes, scores, labels, k=1, score_thresh=0.05, iou_thresh=0.5) == [0]
