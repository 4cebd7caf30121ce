#!/usr/bin/env python3
"""Readings that set a cell's correctness limits: the program's and the
bfloat16 control's, on several seeds, in one process.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 51 \
        [--out gaps.jsonl]

For each seed it runs the cell as a benchmark run does (weights from the
seed, warm-up, ramp, a window at the cell's load), draws the same
sample of finished requests, and prints one JSON line: the program's
``gap_max`` / ``gap_mean`` and the control's on the same prompts and served
tokens.  The limit of each number lies between the largest program reading
and the smallest control reading (PERF.md gives both).  ``--out`` also
writes each seed's per-token gaps, program and control, one JSON line per
seed.  Needs a TPU.
"""
from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import numpy as np
    from chipbench import harness, judge
    from chipbench.spec import load_cell
    cell = load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        try:
            out, params, _ = harness.measure(cell, seed, args.seconds, False,
                                             t0)
        except harness.NoChip as e:
            print(f"calibrate: {e}", file=sys.stderr)
            return 3
        g = harness.sample_gaps(cell, out, params, seed, control=True)
        limits = cell.config["check"]["limits"]
        row = {"seed": seed, "requests": len(g["sample"]),
               "tokens": sum(len(x) for x in g["program"]),
               "program": judge.numbers(g["program"]),
               "control": judge.numbers(g["control"]),
               "program_correct": judge.judge(
                   judge.numbers(g["program"]), limits)[0],
               "control_correct": judge.judge(
                   judge.numbers(g["control"]), limits)[0],
               "driver_errors": out.driver_errors,
               "seconds": time.monotonic() - t0}
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps({
                    "seed": seed,
                    "program": [np.asarray(x).tolist() for x in g["program"]],
                    "control": [np.asarray(x).tolist()
                                for x in g["control"]]}) + "\n")
        del out, params, g
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
