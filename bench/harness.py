"""The benchmark harness: finds a cell's files by name, runs its driver once,
reduces the trace, reads the per-layer metrics and prints the result line.

Everything that belongs to one configuration, cell, traffic kind or
per-layer metric lives in a file of its own, found by name:

- ``bench/configs/<config>.json``     a model configuration;
- ``bench/workloads/<cell>.json``     a cell: its configuration, its driver,
                                      its traffic parameters and its limits;
- ``bench/drivers/<driver>.py``       a traffic kind: ``drive(run) -> Outcome``;
- ``bench/metrics/<metric>.py``       a per-layer metric: ``read(record, trace, peak)``;
- ``bench/flops/<family>.py``         operation counts of a model family.

``BENCHMARK.json`` says which metrics each cell reports.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
from pathlib import Path
from typing import Callable

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import one file by path (names may hold dots, e.g. idle_share.train)."""
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(f"bench_file_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell_files(workload: str, data: Path = BENCH) -> tuple[dict, dict]:
    """(cell, configuration) of a workload name, from their own files under
    ``data`` (``bench/`` itself, or any directory laid out like it)."""
    cell = load_json(data / "workloads" / f"{workload}.json")
    conf = load_json(data / "configs" / f"{cell['config']}.json")
    return cell, conf


def _module(kind: str, name: str):
    if not (BENCH / kind / f"{name}.py").is_file():
        raise FileNotFoundError(f"no bench/{kind}/{name}.py")
    return importlib.import_module(f"bench.{kind}.{name}")


def driver(name: str):
    """bench/drivers/<name>.py: one traffic kind."""
    return _module("drivers", name)


def flops(conf: dict):
    """bench/flops/<family>.py: operation counts of the configuration's family."""
    return _module("flops", conf["family"])


def reference(conf: dict):
    """bench/reference/<family>.py: the family's plain reference."""
    return _module("reference", conf["family"])


def traffic(conf: dict):
    """bench/traffic/<family>.py: the family's inputs from a seed."""
    return _module("traffic", conf["family"])


def metric_reader(name: str) -> Callable:
    return load_module(BENCH / "metrics" / f"{name}.py").read


def metrics_of(spec: dict, workload: str, kind: str) -> list[dict]:
    """The metrics of ``kind`` ("end_to_end" | "per_layer") that a cell reports."""
    return [m for m in spec[kind] if workload in m.get("workloads", [workload])]


def arch(conf: dict):
    """The program's ArchConfig for a configuration file: the registry entry
    ``arch`` with each field named in ``arch_keys`` taken from the file."""
    from repro.configs import get_arch

    base = get_arch(conf["arch"])
    return dataclasses.replace(base, **{field: conf[key] for field, key in conf["arch_keys"].items()})


def configure_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``, a fixed
    path inside the checkout (whatever the environment says), every program
    cached however fast it compiled. Call before JAX compiles anything."""
    import jax

    path = str(root / ".jax_cache")
    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)  # no eviction, no access-time files
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileStats:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit still records its retrieval time)."""

    def __init__(self):
        import jax

        self.compile_s, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        self.slowest: list[tuple[float, str]] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, fun_name: str = "?", **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1
            self.slowest = sorted(self.slowest + [(duration, fun_name)], reverse=True)[:5]

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def make_weights(conf: dict, seed: int):
    """The configuration's initial weights, on the device, from the seed: one
    jitted call of the family reference's initialiser."""
    import jax

    ref = reference(conf)
    return jax.jit(lambda k: ref.init(conf, k))(seed_key(seed))


def seed_key(seed: int):
    """A JAX key for any whole-number seed, 32 bits or more."""
    import jax
    import numpy as np

    return jax.random.key(int(np.random.default_rng(seed).integers(0, 2**31 - 1)))


@dataclasses.dataclass
class Tracer:
    """Profiler trace of the measured window (``--trace 1``) and the
    harness's host spans around its calls into each layer."""

    on: bool
    log_dir: str

    def span(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def start(self, host_spans: bool = True) -> None:
        """Start tracing (``--trace 1`` only). ``host_spans=False`` records
        the device alone: the serving path stalls under host events."""
        if self.on:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1 if host_spans else 0
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)

    def stop(self) -> None:
        if self.on:
            import jax

            jax.profiler.stop_trace()


@dataclasses.dataclass
class Run:
    """What a driver gets: the cell, its configuration, and the run's knobs."""

    workload: str
    cell: dict
    conf: dict
    seed: int
    seconds: float
    tracer: Tracer
    t_start: float  # perf_counter at process start
    log: Callable[[str], None]
    compile_stats: CompileStats


@dataclasses.dataclass
class Outcome:
    """What a driver returns. ``metrics``: end-to-end values by name;
    ``record``: what the per-layer readers read; ``checks``: name ->
    (value, limit), correct when every value is within its limit."""

    metrics: dict
    record: dict
    checks: dict
    attempted: int
    failed: int
    memory_peak_bytes: int | None
    problems: list = dataclasses.field(default_factory=list)

    @property
    def correct(self) -> bool:
        ok = all(math.isfinite(v) and v <= lim for v, lim in self.checks.values())
        return ok and not self.problems and self.failed == 0


def checks(values: dict, limits: dict, log: Callable[[str], None]) -> dict:
    """name -> (value, limit) for the numbers the cell's file sets a limit on;
    the others are printed for the record and decide nothing."""
    for name, v in values.items():
        if name not in limits:
            log(f"reading {name}: {v!r} (no limit: compared by no check)")
    return {name: (values[name], lim) for name, lim in limits.items()}


def peak_memory(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else int(stats.get("peak_bytes_in_use", 0))


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *, t_start: float,
             root: Path = ROOT, data: Path = BENCH, spec: dict | None = None,
             log: Callable[[str], None] | None = None) -> dict:
    """Everything after the look for a chip: drive the cell once and return
    the result object (the last line's content)."""
    import jax

    log = log or (lambda m: print(m, file=sys.stderr, flush=True))
    spec = spec or benchmark(root)
    entry = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"workload {workload!r} is not in BENCHMARK.json")
    cell, conf = cell_files(workload, data)
    dev = jax.devices()[0]
    stats = CompileStats()
    tracer = Tracer(trace, str(root / ".bench_traces" / f"{workload}.{seed}"))
    run = Run(workload, cell, conf, seed, seconds, tracer, t_start, log, stats)
    out: Outcome = driver(cell["driver"]).drive(run)
    log(f"compile: {stats.compiles} backend compiles, {stats.compile_s:.3f} s, "
        f"persistent cache hits {stats.hits} misses {stats.misses}; slowest "
        + ", ".join(f"{name} {sec:.3f} s" for sec, name in stats.slowest))
    log(f"compiles inside the window: {out.record.get('compiles_in_window', 0)}")

    names = metrics_of(spec, workload, "per_layer" if trace else "end_to_end")
    metrics, breakdown = {}, None
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": out.memory_peak_bytes}
    if trace:
        from bench import trace as trace_mod

        red = trace_mod.read(trace_mod.find_xplane(tracer.log_dir), dev.platform, out.record.get("window_s"))
        shutil.rmtree(tracer.log_dir, ignore_errors=True)
        peak = load_json(BENCH / "peaks.json")[dev.device_kind]
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        breakdown = {"device_ops": red.top_ops, "idle_gaps": red.idle_gaps}
        for m in names:
            v = metric_reader(m["name"])(dict(out.record, conf=conf, cell=cell), red, peak)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in names:
            if m["name"] in out.metrics:
                metrics[m["name"]] = {"value": out.metrics[m["name"]], "unit": m["unit"]}
    for name, (v, lim) in out.checks.items():
        log(f"check {name}: {v!r} limit {lim!r}")
    for p in out.problems:
        log(f"problem: {p}")
    result = {"correct": out.correct, "attempted": out.attempted, "failed": out.failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in out.checks.items()}
    return result
