"""Read the numbers a cell compares, on many seeds in one process: for the
program as it stands, for the control, and for each planted fault.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --modes program,control,half_batch,no_aggregation [--seconds 5]

One JSON line per seed and mode: ``{"seed", "mode", "checks": {name: value}}``.
The limits of a cell's file are set from these readings (see PERF.md).

- ``program``: the timed path, sound, as a run drives it (training cells:
  the three checked rounds; serving cells: a window of ``--seconds``).
- ``control``: the reference put in the program's place with every
  product's operands rounded to the cell file's ``control`` format, against
  the float32 HIGHEST reference.
- any name in ``bench/faults.py``: the program with that fault planted.

Like ``run.py`` it needs a TPU; the benchmark's own runs never run it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def fed_readings(run, modes: list[str]) -> dict:
    import jax

    from bench import faults, harness
    from bench.drivers import fed_sync as drv

    cell, conf = run.cell, run.conf
    pool = harness.traffic(conf).round_pool(conf, cell, run.seed)[: drv.ROUNDS_CHECKED]
    ref = drv.reference_readings(conf, cell, run.seed, pool)
    out = {}
    for mode in modes:
        if mode == "control":
            out[mode] = drv.gaps(drv.reference_readings(conf, cell, run.seed, pool, operands=cell["control"]), ref)
        else:
            with faults.FAULTS[mode]() if mode != "program" else contextlib.nullcontext():
                server, mesh = drv.build_server(run, harness.make_weights(conf, run.seed))
                with jax.set_mesh(mesh):
                    prog = drv.program_readings(run, server, pool)
                server.state = None
                del server
                gc.collect()
            out[mode] = drv.gaps(prog, ref)
    return out


def serve_readings(run, modes: list[str]) -> dict:
    import numpy as np

    from bench import faults
    from bench.drivers import serve_open_loop as drv

    cell, conf = run.cell, run.conf
    out = {}
    for mode in modes:
        if mode == "control":
            n = cell["pool_images"]
            ref = drv.reference_candidates(conf, run.seed, n)
            ctl = drv.reference_candidates(conf, run.seed, n, operands=cell["control"])
            answers = {i: (0, drv.detections_of(ctl[0][i], ctl[1][i], ctl[2][i], cell["max_detections"],
                                                 conf, cell)) for i in range(n)}
            out[mode] = drv.check_answers(answers, np.arange(n), ref, conf, cell)
        else:
            with faults.FAULTS[mode]() if mode != "program" else contextlib.nullcontext():
                res = drv.drive(run)
            out[mode] = dict(res.record["gaps"], serve_p95_ms=res.metrics["serve_p95_ms"], failed=res.failed)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--modes", default="program,control")
    ap.add_argument("--seconds", type=float, default=5.0, help="serving cells: window per seed")
    args = ap.parse_args(argv)

    from bench import harness

    harness.configure_compile_cache(ROOT)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: needs a TPU", file=sys.stderr)
        return 3
    cell, conf = harness.cell_files(args.workload)
    stats = harness.CompileStats()
    log = lambda m: print(m, file=sys.stderr, flush=True)
    readings = {"fed_sync": fed_readings, "serve_open_loop": serve_readings}[cell["driver"]]
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        run = harness.Run(args.workload, cell, conf, seed, args.seconds,
                          harness.Tracer(False, ""), time.perf_counter(), log, stats)
        for mode, checks in readings(run, args.modes.split(",")).items():
            print(json.dumps({"seed": seed, "mode": mode, "checks": checks,
                              "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
