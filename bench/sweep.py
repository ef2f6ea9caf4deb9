"""Find a serving cell's knee: step the offered rate in one process.

    python3 bench/sweep.py --workload <serving cell> --rates 40,80,120 --seconds 10 --seed 7

For each rate, one window of the cell's driver with only ``rate_rps``
changed; prints one JSON line per rate with the latency percentiles, the
rate served, the failures and the batch occupancy. The knee is the highest
rate at which, and at every lower rate of the sweep, ``serve_p95_ms`` stays
within the limit (``--p95-limit-ms``, 100: one frame interval of a 10 fps
camera) while the rate served keeps up with the rate offered (no growing
backlog). The service is bistable near capacity: sweep two seeds or more
and take the lower knee, then check the cell's own rate over full sets of
runs. Needs a TPU; the benchmark's own runs never run it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s, ascending")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--p95-limit-ms", type=float, default=100.0)
    args = ap.parse_args(argv)

    from bench import harness

    harness.configure_compile_cache(ROOT)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep.py: needs a TPU", file=sys.stderr)
        return 3
    cell, conf = harness.cell_files(args.workload)
    stats = harness.CompileStats()
    log = lambda m: print(m, file=sys.stderr, flush=True)
    drv = harness.driver(cell["driver"])
    knee, held = None, True
    for rate in (float(r) for r in args.rates.split(",")):
        run = harness.Run(args.workload, dict(cell, rate_rps=rate), conf, args.seed, args.seconds,
                          harness.Tracer(False, ""), time.perf_counter(), log, stats)
        out = drv.drive(run)
        served = out.metrics["served_rps"]
        ok = out.metrics["serve_p95_ms"] <= args.p95_limit_ms and served >= 0.97 * rate and not out.failed
        held = held and ok
        knee = rate if held else knee
        occ = out.record["images_served"] / max(out.record["batches"], 1)
        print(json.dumps({"rate_rps": rate, **out.metrics, "failed": out.failed, "occupancy": occ,
                          "within_limit": ok}), flush=True)
    print(json.dumps({"knee_rps": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
