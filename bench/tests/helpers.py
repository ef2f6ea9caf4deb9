"""Small cells for the CPU tests, written as files into a directory laid out
like ``bench/`` (the harness finds them by name, like any cell)."""
from __future__ import annotations

import json
import time
from pathlib import Path

from bench import harness

YOLO = {"name": "yolo-tiny", "arch": "fedyolov3", "family": "yolo", "stem_filters": 8, "stages": 3,
        "anchors_per_scale": 3, "classes": 3, "img_size": 32,
        "arch_keys": {"d_model": "stem_filters", "n_layers": "stages", "n_heads": "anchors_per_scale",
                      "n_kv_heads": "anchors_per_scale", "vocab_size": "classes"}}
OPT = {"lr": 0.0001, "b1": 0.9, "b2": 0.95, "eps": 1e-08, "weight_decay": 0.0}


def limits(cell: str) -> dict:
    """The limits of one of the benchmark's own cells."""
    return harness.cell_files(cell)[0]["limits"]


def cells() -> dict:
    sync = limits("fedyolov3-416.sync")
    return {
        "yolo-tiny.sync": ("yolo-tiny", "sync", {
            "config": "yolo-tiny", "driver": "fed_sync", "clients": 2, "local_batch": 2, "local_steps": 1,
            "pool_rounds": 4, "aggregation": "eq6", "topn": 8, "optimizer": OPT, "control": "float8_e4m3fn",
            "limits": sync}),
        "yolo-tiny.serve": ("yolo-tiny", "serve", {
            "config": "yolo-tiny", "driver": "serve_open_loop", "rate_rps": 40, "connections": 4,
            "pool_images": 8, "serve_batch": 4, "linger_s": 0.004, "max_detections": 16,
            "score_thresh": 0.05, "nms_iou": 0.5, "drain_s": 30, "control": "float8_e4m3fn",
            "limits": limits("fedyolov3-416.serve_steady")}),
    }


def full_width() -> dict:
    """Cells of the benchmark's own detector configuration (full width, 416
    px) with a few clients and images, for what only shows at full size."""
    sync = harness.cell_files("fedyolov3-416.sync")[0]
    serve = harness.cell_files("fedyolov3-416.serve_steady")[0]
    return {
        "fedyolov3-416.small_sync": ("fedyolov3-416", "small_sync",
                                     dict(sync, clients=2, local_batch=2, microbatches=1)),
        "fedyolov3-416.small_serve": ("fedyolov3-416", "small_serve",
                                      dict(serve, rate_rps=4, connections=2, pool_images=4, serve_batch=2)),
    }


def write_tree(root: Path) -> Path:
    """BENCHMARK.json and bench/{configs,workloads} of the small cells."""
    data = root / "bench"
    (data / "configs").mkdir(parents=True, exist_ok=True)
    (data / "workloads").mkdir(parents=True, exist_ok=True)
    (data / "configs" / "fedyolov3-416.json").write_text(
        (harness.BENCH / "configs" / "fedyolov3-416.json").read_text())
    (data / "configs" / "yolo-tiny.json").write_text(json.dumps(YOLO))
    spec = {"workloads": [], "end_to_end": [
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25, "source": "host_clock"},
        {"name": "round_ms", "unit": "ms", "better": "lower", "bound": 0.05, "source": "host_clock",
         "workloads": ["yolo-tiny.sync"]},
        {"name": "serve_p95_ms", "unit": "ms", "better": "lower", "bound": 0.1, "source": "host_clock",
         "workloads": ["yolo-tiny.serve"]}], "per_layer": []}
    for name, (conf, traffic, cell) in {**cells(), **full_width()}.items():
        (data / "workloads" / f"{name}.json").write_text(json.dumps(cell))
        spec["workloads"].append({"name": name, "config": conf, "traffic": traffic, "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return data


def run(root: Path, workload: str, seed: int = 20261018, seconds: float = 1.0) -> dict:
    """One run of a small cell, everything after the look for a chip."""
    return harness.run_cell(workload, seed, seconds, False, t_start=time.perf_counter(), root=root,
                            data=root / "bench", log=lambda m: None)


def make_run(root: Path, workload: str, seed: int = 20261018, seconds: float = 1.0) -> harness.Run:
    cell, conf = harness.cell_files(workload, root / "bench")
    return harness.Run(workload, cell, conf, seed, seconds, harness.Tracer(False, ""), time.perf_counter(),
                       lambda m: None, harness.CompileStats())
