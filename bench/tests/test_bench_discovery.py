"""Cells are found by name from their own files: a cell written into any
directory laid out like bench/ runs with no code change."""
import json

from bench import harness
from bench.tests import helpers


def test_a_new_cell_file_is_found_and_runs(tmp_path):
    data = helpers.write_tree(tmp_path)
    cell, conf = harness.cell_files("yolo-tiny.sync", data)
    assert conf["name"] == "yolo-tiny" and cell["driver"] == "fed_sync"
    out = helpers.run(tmp_path, "yolo-tiny.sync")
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"setup_s", "round_ms"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] >= 1


def test_the_serving_driver_runs_open_loop(tmp_path):
    helpers.write_tree(tmp_path)
    out = helpers.run(tmp_path, "yolo-tiny.serve", seconds=2.0)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] == 80 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "serve_p95_ms"}


def test_metrics_of_a_cell_follow_benchmark_json():
    spec = harness.benchmark()
    for w in spec["workloads"]:
        names = [m["name"] for m in harness.metrics_of(spec, w["name"], "end_to_end")]
        assert "setup_s" in names and len(names) >= 2
        assert harness.metrics_of(spec, w["name"], "per_layer")
        for m in harness.metrics_of(spec, w["name"], "per_layer"):
            assert callable(harness.metric_reader(m["name"]))
        cell, conf = harness.cell_files(w["name"])
        assert cell["config"] == w["config"]
        assert harness.driver(cell["driver"]).drive


def test_every_file_of_the_benchmark_is_json_or_python():
    for path in harness.BENCH.rglob("*"):
        if path.is_file() and "__pycache__" not in path.parts:
            assert path.suffix in {".py", ".json", ".md"}, path
            if path.suffix == ".json":
                json.loads(path.read_text())
