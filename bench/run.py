"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. Needs a TPU with as many chips as the cell
asks for: on any other platform it exits non-zero and prints no result.
With ``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, the device's busy and window seconds
and the trace's breakdown. The numbers compared against the reference are
printed beside their limits as the last lines on standard error and under
``checks``, the line's last key.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    spec = harness.benchmark(ROOT)
    entry = next((w for w in spec["workloads"] if w["name"] == args.workload), None)
    if entry is None:
        print(f"run.py: no workload {args.workload!r} in BENCHMARK.json", file=sys.stderr)
        return 2
    harness.configure_compile_cache(ROOT)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < entry["chips"]:
        print(f"run.py: needs {entry['chips']} TPU chip(s), found {len(devs)} "
              f"{devs[0].platform!r} device(s); no fallback", file=sys.stderr)
        return 3
    if devs[0].device_kind not in harness.load_json(harness.BENCH / "peaks.json"):
        print(f"run.py: device kind {devs[0].device_kind!r} is not in bench/peaks.json", file=sys.stderr)
        return 3
    result = harness.run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                              t_start=T_START, root=ROOT, spec=spec)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
