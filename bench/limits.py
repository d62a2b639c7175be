"""Readings that the check's limits are set from.

    python3 bench/limits.py --workload mistral-7b-v0.1.rollout \\
        --seeds 11,12,13 --seconds 10

Not part of a benchmark run. For each seed, in one process, it runs the
cell as ``bench/run.py`` does (set-up, a window of ``--seconds`` at the
cell's own load, the sample the check takes) and reads, over the same
served tokens, both the program's numbers and the control's: the reference
computed with every matmul in float8 e4m3, one precision below the
configuration's bfloat16, judged by the same comparison in the program's
place. One JSON line per seed; the exit code is 1 if the control ever came
out correct.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def program_and_control(config, seed, sample):
    """One reference pass over the sample, judged twice by the comparison
    that decides ``correct``: once for the program, once with the fp8
    control in its place."""
    from bench.check import judge, numbers, readings
    r = readings(config, seed, sample, control=True)
    verdicts = {name: judge(numbers(r, config["vocab_size"], control),
                            config["check"])
                for name, control in (("program", False), ("control", True))}
    return {"correct": verdicts["program"]["correct"], "compared": verdicts}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    cell = harness.load_cell(args.workload)
    devices, peaks = harness.check_devices(cell.chips)
    harness.use_checkout_compile_cache()
    log = harness.CompileLog()
    control_passed = False
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        result = harness.run_cell(cell, seed=seed, seconds=args.seconds,
                                  trace=False, t_start=t, devices=devices,
                                  peaks=peaks, log=log,
                                  checker=program_and_control)
        print(json.dumps({"seed": seed, **result["check"],
                          "seconds": time.perf_counter() - t}), flush=True)
        control_passed |= result["check"]["control"]["correct"]
    if control_passed:
        print("limits: the control came out correct", file=sys.stderr)
    return int(control_passed)


if __name__ == "__main__":
    sys.exit(main())
