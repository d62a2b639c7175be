"""Run one benchmark cell once, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell, its configuration, traffic and
metrics are found by name through ``BENCHMARK.json``. The first line says
which devices JAX found; without a TPU (or with fewer chips than the cell
asks for, or a device kind with no peaks in ``bench/peaks.json``) the
command exits non-zero and prints no result. Otherwise the last line of
standard output is the result object, and the last lines of standard error
give each number the check compared beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    try:
        cell = harness.load_cell(args.workload)
        devices, peaks = harness.check_devices(cell.chips)
        print(f"compile cache: {harness.use_checkout_compile_cache()}",
              flush=True)
        log = harness.CompileLog()
        result = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                                  trace=bool(args.trace), t_start=T_START,
                                  devices=devices, peaks=peaks, log=log)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["check"].items():
        if isinstance(c, dict):
            print(f"check {name}: {c['value']:.6g} (limit {c['limit']})",
                  file=sys.stderr)
        else:
            print(f"check {name}: {c}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
